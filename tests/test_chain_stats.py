"""One-pass chain statistics: the fused sweep's compensated walk sums.

Contract under test (kernels/sim_sweep, "one-pass chain statistics"): the
single blocked sweep additionally emits per-row walk sums and the chain
total weight, accumulated in compensated (two-float) f32 — and those agree
with the f64 numpy reference to 1e-6 relative even on adversarial magnitude
spreads, for both the Pallas kernel (interpret on CPU) and the blocked
numpy sweep.  Downstream: walk setup consumes the fused statistics, so a
warm-index (or cold fused-sweep) streaming query launches ZERO standalone
passes over the cross product — asserted via the pass-launch counters in
``repro.core.similarity.PASS_COUNTS``.

The property sweep runs over a deterministic seeded grid always; when
``hypothesis`` is installed the same check also runs under ``@given`` draws
(exponent/floor/shape/spread), widening coverage without adding a
dependency.
"""
import numpy as np
import pytest

from repro.core import similarity
from repro.core.similarity import (
    chain_total_weight,
    edge_row_sums_raw,
    pair_weights,
)
from repro.core.stratify import sweep_pass, sweep_pass_chain
from repro.kernels.sim_sweep.ops import sim_sweep

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # property sweep falls back to the seeded grid only
    HAVE_HYPOTHESIS = False

REL_TOL = 1e-6


def _unit_rows(rng, n, d):
    e = rng.normal(size=(n, d)).astype(np.float32)
    return e / np.linalg.norm(e, axis=1, keepdims=True)


def _spread_v(rng, n, decades=4.0):
    """A backward vector spanning ~10**(2*decades) in magnitude — the
    adversarial summand spread naive f32 accumulation cannot absorb."""
    return (10.0 ** rng.uniform(-decades, decades, n)).astype(np.float32)


def _kernel_f32_weights(e1, e2, exponent, floor, block):
    """The clipped f32 scores and f32 weights the sweep kernel computes for
    ``sim_sweep(..., block=block)``: the same dot on the same (bm, d) x
    (bn, d) blocks — the Pallas interpreter runs that very XLA op — and the
    same f32 transform."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.padding import col_block, pad_rows, row_block
    from repro.kernels.sim_topk.kernel import F32_DOT

    dot = jax.jit(lambda a, b: jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=F32_DOT))
    n1, n2 = len(e1), len(e2)
    bm, bn = row_block(block, n1), col_block(block, n2)
    a, _ = pad_rows(e1, bm)
    b, _ = pad_rows(e2, bn)
    s = np.concatenate([
        np.concatenate([np.asarray(dot(a[i:i + bm], b[j:j + bn]))
                        for j in range(0, len(b), bn)], axis=1)
        for i in range(0, len(a), bm)
    ])[:n1, :n2]
    s = jnp.clip(jnp.asarray(s), 0.0, 1.0)
    base = jnp.maximum(s, floor)
    w = base if exponent == 1.0 else base**exponent
    return np.asarray(s), np.asarray(w)


def _check_fused_pair_sums(seed, n1, n2, d, exponent, floor, decades):
    """Fused kernel sums vs the f64 sum of the kernel's own f32 weights.

    The kernel scores and transforms in f32; at exponent p a score's
    rounding moves its weight by p times as much, so an f64 reference would
    charge the accumulation for the matmul's and the power's rounding.  The
    reference therefore recomputes the kernel's f32 weights
    (:func:`_kernel_f32_weights`; the sweep's top-k values must equal its
    scores exactly, checked) and sums them in f64: what is left to differ
    is the compensated accumulation, held to 1e-6 relative."""
    rng = np.random.default_rng(seed)
    e1, e2 = _unit_rows(rng, n1, d), _unit_rows(rng, n2, d)
    v = _spread_v(rng, n2, decades)
    out = sim_sweep(e1, e2, n_bins=64, exponent=exponent, floor=floor,
                    block=64, back_v=v)
    s32, w32 = _kernel_f32_weights(e1, e2, exponent, floor, block=64)
    rows = np.nonzero(out.valid)[0]
    np.testing.assert_array_equal(out.vals[out.valid],
                                  s32[rows, out.idx[out.valid]])
    ref = (w32.astype(np.float64) * v.astype(np.float64)).sum(axis=1)
    np.testing.assert_allclose(out.row_sums, ref, rtol=REL_TOL)


PAIR_GRID = [
    (0, 50, 70, 16, 1.0, 1e-3, 0.0),
    (1, 33, 190, 32, 2.5, 1e-2, 2.0),
    (2, 130, 65, 48, 4.0, 1e-4, 4.0),
    (3, 7, 260, 8, 3.0, 1e-3, 4.0),
    (4, 64, 64, 24, 1.5, 1e-2, 3.0),
]


@pytest.mark.parametrize("case", PAIR_GRID, ids=lambda c: f"seed{c[0]}")
def test_fused_kernel_sums_match_f64_seeded(case):
    _check_fused_pair_sums(*case)


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        n1=st.integers(3, 140),
        n2=st.integers(3, 270),
        d=st.integers(4, 48),
        exponent=st.floats(0.5, 4.0),
        floor=st.floats(1e-4, 1e-1),
        decades=st.floats(0.0, 4.0),
    )
    def test_fused_kernel_sums_match_f64_property(seed, n1, n2, d, exponent,
                                                  floor, decades):
        _check_fused_pair_sums(seed, n1, n2, d, exponent, floor, decades)


def _check_fallback_pair_sums(seed, n1, n2, d, exponent, floor):
    """Numpy-fallback sweep_pass emits the same statistics contract."""
    rng = np.random.default_rng(seed)
    e1, e2 = _unit_rows(rng, n1, d), _unit_rows(rng, n2, d)
    info = sweep_pass(e1, e2, n_bins=64, exponent=exponent, floor=floor,
                      block=64, use_kernel=False)
    ref = pair_weights(e1, e2, exponent, floor).sum(axis=1)
    np.testing.assert_allclose(info.row_sums[0], ref, rtol=REL_TOL)
    assert info.total_weight == pytest.approx(float(ref.sum()), rel=REL_TOL)


@pytest.mark.parametrize("case", PAIR_GRID, ids=lambda c: f"seed{c[0]}")
def test_fallback_sums_match_f64_seeded(case):
    _check_fallback_pair_sums(*case[:6])


@pytest.mark.parametrize("use_kernel", [True, False])
@pytest.mark.parametrize("k", [2, 3])
def test_chain_sweep_sums_match_f64(use_kernel, k):
    """k-way chain: every per-edge row-sum vector and the chain total
    emitted by the fused sweep agree with the standalone f64 recomputation
    — kernel and fallback paths."""
    rng = np.random.default_rng(17 + k)
    sizes = [60, 70, 50][:k]
    embeddings = [_unit_rows(rng, n, 32) for n in sizes]
    exponent, floor = 2.0, 1e-3
    info = sweep_pass_chain(embeddings, n_bins=64, exponent=exponent,
                            floor=floor, block=64, use_kernel=use_kernel)
    refs = edge_row_sums_raw(embeddings, exponent, floor)
    assert info.row_sums is not None and len(info.row_sums) == k - 1
    for got, ref in zip(info.row_sums, refs):
        np.testing.assert_allclose(got, ref, rtol=REL_TOL)
    ref_total = chain_total_weight(embeddings, exponent, floor)
    assert info.total_weight == pytest.approx(ref_total, rel=REL_TOL)


def test_naive_f32_fails_where_compensated_passes():
    """The regression the compensated accumulator exists for: one large
    summand followed by thousands of small ones.  A running f32 sum loses
    the entire small mass (each add rounds to nothing against the large
    partial); the kernel's two-float reduction (run inside a kernel, where
    its lane rotations lower) keeps it."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from repro.kernels.sim_sweep.kernel import comp_block_sum

    vals = np.ones(4096, np.float32)
    vals[0] = np.float32(1e8)
    ref = vals.astype(np.float64).sum()

    naive = np.float32(0.0)
    for x in vals:
        naive = np.float32(naive + x)
    assert abs(float(naive) - ref) / ref > REL_TOL     # naive f32 fails

    def kernel(x_ref, hi_ref, lo_ref):
        hi_ref[...], lo_ref[...] = comp_block_sum(x_ref[...])

    tile = jax.ShapeDtypeStruct((8, 128), jnp.float32)
    x = jnp.broadcast_to(jnp.asarray(vals), (8, 4096))
    hi, lo = pl.pallas_call(kernel, out_shape=(tile, tile), interpret=True)(x)
    comp = float(np.asarray(hi)[0, 0]) + float(np.asarray(lo)[0, 0])
    assert abs(comp - ref) / ref < 1e-9                # compensated passes


def test_fused_sweep_absorbs_adversarial_back_vector():
    """End-to-end version of the regression: the same large/small spread
    arriving through the backward chain vector still meets the 1e-6 rel
    contract inside the fused sweep (cross-block carry is compensated too).

    Every score is ~1, the first column carries 2**26 and the other 16,383
    carry 0.03: each 128-column block adds ~3.8 to a running total whose
    half-ulp is 4, so an uncompensated carry drops every block after the
    first (~7e-6 relative) while the compensated one keeps them all."""
    rng = np.random.default_rng(9)
    u = _unit_rows(rng, 1, 16)
    e1, e2 = np.repeat(u, 40, axis=0), np.repeat(u, 128 * 128, axis=0)
    v = np.full(len(e2), 0.03, np.float32)
    v[0] = np.float32(2**26)
    out = sim_sweep(e1, e2, n_bins=64, exponent=1.0, floor=1e-3,
                    block=64, back_v=v)
    _, w32 = _kernel_f32_weights(e1, e2, 1.0, 1e-3, block=64)
    ref = (w32.astype(np.float64) * v.astype(np.float64)).sum(axis=1)
    np.testing.assert_allclose(out.row_sums, ref, rtol=REL_TOL)


# ----------------------------------------------------------------------------
# zero standalone passes: walk setup consumes the fused statistics
# ----------------------------------------------------------------------------

def _small_query(budget=900):
    from repro.core import Agg, Query
    from repro.data import make_clustered_tables

    ds = make_clustered_tables(150, 150, n_entities=80, noise=0.4, seed=5)
    return Query(spec=ds.spec(), agg=Agg.COUNT, oracle=ds.oracle(),
                 budget=budget)


def _pass_delta(fn):
    before = dict(similarity.PASS_COUNTS)
    result = fn()
    return result, {k: similarity.PASS_COUNTS[k] - before[k]
                    for k in before}


def test_cold_fused_query_launches_zero_standalone_passes():
    from repro.core.bas_streaming import run_bas_streaming

    r, delta = _pass_delta(lambda: run_bas_streaming(_small_query(), seed=0))
    assert delta == {"edge_row_sums": 0, "chain_total_weight": 0}
    assert r.telemetry.stratify.extra["walk_setup"] == "fused"


def test_warm_index_query_launches_zero_standalone_passes(tmp_path):
    from repro.core import IndexStore
    from repro.core.bas_streaming import run_bas_streaming

    store = IndexStore(root=tmp_path)
    # cold build populates the store (and computes sums inside the sweep)
    r_cold, delta_cold = _pass_delta(
        lambda: run_bas_streaming(_small_query(), seed=0, index_store=store)
    )
    assert delta_cold == {"edge_row_sums": 0, "chain_total_weight": 0}
    # warm hit: statistics hydrate from the artifact — no sweep, no passes
    r_warm, delta_warm = _pass_delta(
        lambda: run_bas_streaming(_small_query(), seed=0, index_store=store)
    )
    assert delta_warm == {"edge_row_sums": 0, "chain_total_weight": 0}
    assert r_warm.telemetry.index.hit is True
    assert r_warm.telemetry.stratify.extra["walk_setup"] == "fused"
    assert r_warm.estimate == r_cold.estimate


def test_two_pass_baseline_still_counts_passes():
    """The counter itself works: the retired two-pass schedule
    (use_sweep=False) launches both standalone passes."""
    from repro.core.bas_streaming import run_bas_streaming

    r, delta = _pass_delta(
        lambda: run_bas_streaming(_small_query(), seed=0, use_sweep=False)
    )
    assert delta["edge_row_sums"] >= 1
    assert delta["chain_total_weight"] >= 1
    assert r.telemetry.stratify.extra["walk_setup"] == "recompute"


# ----------------------------------------------------------------------------
# persistence: sums survive save/load and O(delta) append maintenance
# ----------------------------------------------------------------------------

def test_index_persists_and_appends_fused_sums(tmp_path):
    from repro.checkpoint.index_io import load_index, save_index
    from repro.core.index import append_rows, build_index

    rng = np.random.default_rng(3)
    e1, e2 = _unit_rows(rng, 60, 24), _unit_rows(rng, 75, 24)
    art = build_index([e1, e2], n_bins=64, exponent=1.5, floor=1e-2,
                      block=64)
    assert art.row_sums is not None and art.total_weight is not None

    # save/load round-trip is exact
    save_index(tmp_path / "idx", art)
    back = load_index(tmp_path / "idx", art.key)
    np.testing.assert_array_equal(back.row_sums[0], art.row_sums[0])
    assert back.total_weight == art.total_weight

    # O(delta) append maintenance matches a fresh cold build to 1e-6
    d1, d2 = _unit_rows(rng, 17, 24), _unit_rows(rng, 11, 24)
    grown = append_rows(art, 0, d1)
    grown = append_rows(grown, 1, d2)
    fresh = build_index([np.vstack([e1, d1]), np.vstack([e2, d2])],
                        n_bins=64, exponent=1.5, floor=1e-2, block=64)
    np.testing.assert_allclose(grown.row_sums[0], fresh.row_sums[0],
                               rtol=REL_TOL)
    assert grown.total_weight == pytest.approx(fresh.total_weight,
                                               rel=REL_TOL)


# ----------------------------------------------------------------------------
# autotuner: TPU-only, cached on disk, routed into the ops
# ----------------------------------------------------------------------------

def test_autotune_schedule(tmp_path, monkeypatch):
    from repro.kernels import autotune

    autotune.reset()
    try:
        # CPU / interpret mode: no measurement, no behaviour change
        assert autotune.schedule("sim_sweep", 512, 512, 32) is None
        # an unverified device kind raises instead of guessing a schedule
        with pytest.raises(RuntimeError, match="device kind"):
            autotune.schedule("sim_sweep", 512, 512, 32,
                              device_kind="TPU v9 hypothetical")
        kind = autotune.DEVICE_KINDS[0]

        calls = []

        def fake_measure(op, m, n, d, precision, candidates):
            calls.append((op, m, n, d, precision, tuple(candidates)))
            return candidates[-1]

        monkeypatch.setattr(autotune, "_measure", fake_measure)
        autotune.configure(tmp_path / "autotune.json")

        won = autotune.schedule("sim_sweep", 300, 500, 32, device_kind=kind)
        assert won in autotune.CANDIDATES
        assert len(calls) == 1
        # same shape bucket: served from memory, no re-measurement
        assert autotune.schedule("sim_sweep", 280, 510, 32,
                                 device_kind=kind) == won
        assert len(calls) == 1

        # the winner persisted — a fresh process (reset) rereads the disk
        # cache without measuring again
        autotune.reset()
        autotune.configure(tmp_path / "autotune.json")
        assert autotune.schedule("sim_sweep", 300, 500, 32,
                                 device_kind=kind) == won
        assert len(calls) == 1
    finally:
        autotune.reset()


def test_index_store_configures_autotune_cache(tmp_path, monkeypatch):
    """Opening a persistent IndexStore points the autotune disk cache next
    to the index artifacts, so tuned schedules ship with the store."""
    from repro.core import IndexStore
    from repro.kernels import autotune

    autotune.reset()
    try:
        monkeypatch.setattr(autotune, "_measure",
                            lambda *a: autotune.CANDIDATES[0])
        IndexStore(root=tmp_path)
        assert autotune.schedule(
            "sim_sweep", 128, 128, 32, device_kind=autotune.DEVICE_KINDS[0]
        ) == autotune.CANDIDATES[0]
        assert (tmp_path / "autotune.json").exists()
    finally:
        autotune.reset()
