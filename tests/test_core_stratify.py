import dataclasses

import numpy as np
import pytest

from repro.core.stratify import (
    auto_num_strata,
    collect_top,
    stratify_dense,
    stratify_streaming,
    sweep_pass,
    threshold_for_top_m,
    weight_histogram,
)
from repro.core.similarity import normalize, pair_weights
from repro.core.types import BASConfig

CFG = BASConfig()


def test_auto_num_strata_clamps():
    assert auto_num_strata(0.2, 1000, CFG) == 5        # min K
    assert auto_num_strata(0.2, 100_000, CFG) == 20    # alpha*b/1000
    assert auto_num_strata(0.2, 10_000_000, CFG) == 64  # max K


def test_stratify_dense_invariants():
    rng = np.random.default_rng(0)
    w = rng.random(10_000)
    strat = stratify_dense(w, alpha=0.2, budget=5000, cfg=CFG)
    m = strat.blocking_regime_size()
    assert m == 1000  # alpha * budget
    # order is sorted descending
    ow = w[strat.order]
    assert np.all(np.diff(ow) <= 1e-12)
    # order really is the global top-m
    thresh = np.sort(w)[::-1][m - 1]
    assert ow.min() >= thresh - 1e-12
    # strata partition the blocking regime into equal (±1) sizes
    sizes = strat.stratum_sizes()
    assert sizes[1:].sum() == m
    assert sizes[0] == 10_000 - m
    assert sizes[1:].max() - sizes[1:].min() <= 1
    # strata are similarity-ordered: min weight of stratum i >= max of i+1
    for i in range(1, strat.num_strata):
        a = w[strat.stratum_indices(i)]
        b = w[strat.stratum_indices(i + 1)]
        assert a.min() >= b.max() - 1e-12


def test_stratify_dense_small_space():
    w = np.array([0.9, 0.1, 0.5])
    strat = stratify_dense(w, alpha=0.5, budget=100, cfg=CFG)
    assert strat.blocking_regime_size() == 3  # capped at |D|
    assert strat.stratum_sizes().sum() == 3


def test_histogram_threshold_matches_exact():
    rng = np.random.default_rng(1)
    e1 = normalize(rng.standard_normal((200, 16)))
    e2 = normalize(rng.standard_normal((150, 16)))
    w = pair_weights(e1, e2).reshape(-1)
    counts, edges = weight_histogram(e1, e2, n_bins=512)
    assert counts.sum() == len(w)
    m = 500
    thr = threshold_for_top_m(counts, edges, m)
    n_above = int((w >= thr).sum())
    assert n_above >= m  # threshold is conservative (collects at least m)
    top = collect_top(e1, e2, thr, m)
    exact_top = np.argsort(w)[::-1][:m]
    # identical up to bin-boundary ties: overlap must be near-total
    overlap = len(set(top.tolist()) & set(exact_top.tolist())) / m
    assert overlap > 0.98


def test_stratify_streaming_close_to_dense():
    rng = np.random.default_rng(2)
    e1 = normalize(rng.standard_normal((100, 16)))
    e2 = normalize(rng.standard_normal((100, 16)))
    w = pair_weights(e1, e2).reshape(-1)
    dense = stratify_dense(w, alpha=0.2, budget=2000, cfg=CFG)
    stream = stratify_streaming(e1, e2, alpha=0.2, budget=2000, cfg=CFG)
    assert stream.blocking_regime_size() == dense.blocking_regime_size()
    overlap = len(
        set(stream.order.tolist()) & set(dense.order.tolist())
    ) / dense.blocking_regime_size()
    assert overlap > 0.98


# ----------------------------------------------------------------------------
# threshold_for_top_m edge cases
# ----------------------------------------------------------------------------

def test_threshold_edge_cases():
    counts = np.array([5, 0, 3, 2], np.int64)
    edges = np.linspace(0.0, 1.0, 5)
    # m = 0: top edge — nothing needs collecting
    assert threshold_for_top_m(counts, edges, 0) == edges[-1]
    # m == total mass: bottom edge — collect everything
    assert threshold_for_top_m(counts, edges, 10) == edges[0]
    # m beyond total mass: still the bottom edge
    assert threshold_for_top_m(counts, edges, 10_000) == edges[0]
    # empty histogram: bottom edge
    assert threshold_for_top_m(np.zeros(4, np.int64), edges, 1) == edges[0]
    # all mass in one bin: that bin's lower edge, for any m <= mass
    one = np.array([0, 0, 7, 0], np.int64)
    assert threshold_for_top_m(one, edges, 1) == edges[2]
    assert threshold_for_top_m(one, edges, 7) == edges[2]
    # m exactly the top-bin mass: top bin's lower edge
    assert threshold_for_top_m(counts, edges, 2) == edges[3]


def test_threshold_collects_at_least_m():
    rng = np.random.default_rng(7)
    w = rng.random(5000)
    edges = np.linspace(0.0, 1.0, 257)
    counts, _ = np.histogram(w, bins=edges)
    for m in (1, 7, 100, 2500, 5000):
        thr = threshold_for_top_m(counts.astype(np.int64), edges, m)
        assert int((w >= thr).sum()) >= m


# ----------------------------------------------------------------------------
# single-sweep path (sweep_pass + sweep-aware collection)
# ----------------------------------------------------------------------------

def _strata_identical(a, b):
    return (
        np.array_equal(a.order, b.order)
        and np.array_equal(a.bounds, b.bounds)
        and a.n_total == b.n_total
    )


@pytest.mark.parametrize("use_kernel", [False, True])
def test_sweep_bit_identical_to_two_pass(use_kernel):
    """The fused single-sweep stratification must produce *bit-identical*
    strata to the retired two-pass (histogram then collect) schedule, on
    both the numpy sweep and the Pallas kernel path."""
    rng = np.random.default_rng(21)
    e1 = normalize(rng.standard_normal((130, 16)))
    e2 = normalize(rng.standard_normal((90, 16)))
    one = stratify_streaming(e1, e2, 0.2, 2500, CFG, use_kernel=use_kernel,
                             use_sweep=True)
    two = stratify_streaming(e1, e2, 0.2, 2500, CFG, use_kernel=use_kernel,
                             use_sweep=False)
    assert _strata_identical(one, two)
    assert one.sweep is not None and two.sweep is None
    assert one.sweep.kernel == use_kernel
    assert one.order_weights is not None and len(one.order_weights) == len(one.order)
    # collected weights really are the strata weights, sorted descending
    assert np.all(np.diff(one.order_weights) <= 1e-12)


def test_sweep_fallback_hist_matches_two_pass_hist():
    rng = np.random.default_rng(22)
    e1 = normalize(rng.standard_normal((300, 8)))
    e2 = normalize(rng.standard_normal((50, 8)))
    sw = sweep_pass(e1, e2, n_bins=512, block=128)
    counts, edges = weight_histogram(e1, e2, n_bins=512, block=128)
    np.testing.assert_array_equal(sw.counts, counts)
    np.testing.assert_array_equal(sw.block_counts.sum(axis=0), counts)
    assert sw.block_counts.shape == (3, 512) and sw.block_rows == 128


def test_sweep_block_skipping_is_conservative():
    """Dense collection guided by the count tiles must return exactly the
    full-scan result — skipped blocks are *proven* empty."""
    rng = np.random.default_rng(23)
    # two clusters: rows 0-63 near e2's cluster, rows 64-255 far away
    base = normalize(rng.standard_normal((1, 16)))
    near = normalize(base + 0.05 * rng.standard_normal((64, 16)))
    far = normalize(rng.standard_normal((192, 16)))
    e1 = np.concatenate([near, far])
    e2 = normalize(base + 0.05 * rng.standard_normal((40, 16)))
    sw = sweep_pass(e1, e2, n_bins=512, block=64)
    thr = threshold_for_top_m(sw.counts, sw.edges, 200)
    got = collect_top(e1, e2, thr, 200, sweep=sw)
    want = collect_top(e1, e2, thr, 200)
    np.testing.assert_array_equal(got, want)
    assert sw.stats["blocks_rescanned"] < sw.stats["blocks_total"]


@pytest.mark.parametrize("use_sweep", [False, True])
def test_collect_top_beyond_candidate_cap(use_sweep):
    """Regression for the hard k=64 top-k candidate cap: a few hot left
    rows with > 64 qualifying right rows each (amid cold rows, so the
    top-k path engages) — the raised-k retry / targeted rescan must still
    collect exactly the dense-scan result instead of silently dropping
    the pairs beyond the cap."""
    rng = np.random.default_rng(24)
    base = normalize(rng.standard_normal((1, 16)))
    hot = normalize(base + 0.01 * rng.standard_normal((4, 16)))
    cold = normalize(rng.standard_normal((60, 16)))
    e1 = np.concatenate([hot, cold])
    e2 = normalize(base + 0.01 * rng.standard_normal((200, 16)))
    w = pair_weights(e1, e2)
    ws = np.sort(w.reshape(-1))
    m_cap = 400
    thr = float((ws[-m_cap] + ws[-m_cap - 1]) / 2)  # off any exact weight
    assert (w[:4] >= thr).sum(axis=1).min() > 64  # hot rows exceed the cap
    assert m_cap < 16 * e1.shape[0]  # the top-k collection path engages
    sw = sweep_pass(e1, e2, n_bins=512, use_kernel=True) if use_sweep else None
    got = collect_top(e1, e2, thr, m_cap, use_kernel=True, sweep=sw)
    want = collect_top(e1, e2, thr, m_cap, use_kernel=False)
    assert set(got.tolist()) == set(want.tolist())
    assert len(got) == m_cap
    if use_sweep:
        assert sw.stats.get("topk_retry_rows", 0) > 0


# ----------------------------------------------------------------------------
# end-to-end: sweep vs two-pass estimates, low-precision opt-in
# ----------------------------------------------------------------------------

def _small_query(budget=900):
    from repro.core import Agg, Query
    from repro.data import make_clustered_tables

    ds = make_clustered_tables(150, 150, n_entities=80, noise=0.4, seed=5)
    return Query(spec=ds.spec(), agg=Agg.COUNT, oracle=ds.oracle(),
                 budget=budget)


def test_bas_streaming_sweep_estimates_match_two_pass():
    """The fused sweep path draws the same samples as the two-pass schedule
    (identical strata and oracle calls); its walk setup reads the sweep's
    compensated f32 row sums instead of recomputing them in f64, so the
    estimate agrees to the compensated-accumulation contract (~1 f32 ulp),
    not bit-exactly (see kernels/sim_sweep: one-pass chain statistics)."""
    from repro.core.bas_streaming import run_bas_streaming

    r1 = run_bas_streaming(_small_query(), seed=0, use_sweep=True)
    r2 = run_bas_streaming(_small_query(), seed=0, use_sweep=False)
    assert r1.estimate == pytest.approx(r2.estimate, rel=1e-7)
    assert r1.ci.lo == pytest.approx(r2.ci.lo, rel=1e-6)
    assert r1.ci.hi == pytest.approx(r2.ci.hi, rel=1e-6)
    assert r1.oracle_calls == r2.oracle_calls
    assert r1.detail["stratify"]["path"] == "sweep"
    assert r1.detail["stratify"]["walk_setup"] == "fused"
    assert "stratify" not in r2.detail or r2.detail["stratify"]["path"] == "two-pass"


@pytest.mark.parametrize("precision", ["bf16", "int8"])
def test_bas_streaming_low_precision_within_tolerance(precision):
    """The opt-in bf16/int8 sweep must (a) report its CDF deviation, (b)
    stay within the documented per-precision tolerance, and (c) land the
    estimate within a few percent of the fp32 run (same seed)."""
    from repro.configs.joinml_embedder import EMBEDDING_PRECISIONS
    from repro.core.bas_streaming import run_bas_streaming

    ref = run_bas_streaming(_small_query(), seed=0)
    low = run_bas_streaming(_small_query(), seed=0, precision=precision)
    st = low.detail["stratify"]
    assert st["precision"] == precision
    assert st["lowp_cdf_dev"] <= EMBEDDING_PRECISIONS[precision].max_cdf_shift
    assert abs(low.estimate - ref.estimate) <= 0.05 * max(abs(ref.estimate), 1.0)


def test_low_precision_tolerance_fallback():
    """A sweep whose low-precision CDF drifts past the tolerance must fall
    back to fp32 (and say so in its stats)."""
    rng = np.random.default_rng(25)
    e1 = normalize(rng.standard_normal((64, 16)))
    e2 = normalize(rng.standard_normal((64, 16)))
    with pytest.warns(UserWarning, match="falling back to fp32"):
        sw = sweep_pass(e1, e2, n_bins=256, use_kernel=True,
                        precision="bf16", tolerance=0.0)
    assert sw.precision == "fp32"
    assert "lowp_fallback" in sw.stats
    ref = sweep_pass(e1, e2, n_bins=256, use_kernel=True)
    np.testing.assert_array_equal(sw.counts, ref.counts)


def test_unknown_precision_rejected():
    rng = np.random.default_rng(26)
    e1 = normalize(rng.standard_normal((32, 8)))
    e2 = normalize(rng.standard_normal((32, 8)))
    for use_kernel in (True, False):  # validated on the fallback path too
        with pytest.raises(ValueError, match="unknown sweep precision"):
            sweep_pass(e1, e2, use_kernel=use_kernel, precision="fp4")


def test_low_precision_warns_when_kernel_unavailable():
    """A bf16/int8 opt-in on the numpy sweep (use_kernel=False) must say so
    instead of silently computing fp32."""
    rng = np.random.default_rng(27)
    e1 = normalize(rng.standard_normal((32, 8)))
    e2 = normalize(rng.standard_normal((32, 8)))
    with pytest.warns(UserWarning, match="numpy sweep computes fp32"):
        sw = sweep_pass(e1, e2, use_kernel=False, precision="int8")
    assert sw.precision == "fp32" and not sw.kernel


def test_sweep_config_is_plumbed_through_dispatch():
    from repro.core import dispatch

    q = _small_query()
    cfg = dataclasses.replace(BASConfig(), max_dense_weight_bytes=0)
    res = dispatch.run_auto(q, cfg, seed=0)
    assert res.detail["dispatch"]["path"] == "streaming"
    assert res.detail["dispatch"]["sweep"] is True
    assert res.detail["dispatch"]["sweep_precision"] == "fp32"
    assert res.detail["stratify"]["path"] == "sweep"
