"""Fig. 14 / Appendix A: CPU-phase latency decomposition of BAS (similarity,
stratification, pilot, allocation, execution, resampling CI) — the speedup of
the fused single-sweep stratification vs the paper's sort — and the
dense-vs-streaming crossover sweep that calibrates the memory-aware
dispatcher (``repro.core.dispatch``).

Run via ``python -m benchmarks.run --only latency`` (``--full`` for
paper-scale table sizes).  CI diffs the ``--json`` output against
``benchmarks/baselines/BENCH_latency.json`` warn-only (see
``scripts/bench_diff.py``)."""
from __future__ import annotations

import time


from repro.core import Agg, Query, choose_path, dense_weight_bytes, run_bas
from repro.core.bas_streaming import run_bas_streaming
from repro.core.similarity import pair_weights
from repro.core.stratify import stratify_dense, stratify_streaming
from repro.core.types import BASConfig
from repro.data import make_clustered_tables

from .common import row


def run(fast: bool = True, smoke: bool = False):
    rows = []
    n = 300 if smoke else 600 if fast else 2000
    ds = make_clustered_tables(n, n, n_entities=n, noise=0.4, seed=23)
    q = Query(spec=ds.spec(), agg=Agg.COUNT, oracle=ds.oracle(),
              budget=max(n * n // 40, 2000))
    res = run_bas(q, seed=0)
    t = res.telemetry.timings
    total = t["total_s"]
    for phase in ("similarity_s", "stratify_s", "pilot_s", "allocate_s",
                  "execute_s", "ci_s"):
        rows.append(row(f"fig14_{phase[:-2]}", t[phase],
                        f"{t[phase] / total:.3f}"))
    rows.append(row("fig14_total", total, f"{total:.3f}s"))

    # sort-based (paper) vs fused single-sweep stratification at scale
    w = pair_weights(ds.emb1, ds.emb2).reshape(-1)
    cfg = BASConfig()
    t0 = time.perf_counter()
    stratify_dense(w, 0.2, q.budget, cfg)
    dt_sort = time.perf_counter() - t0
    t0 = time.perf_counter()
    stratify_streaming(ds.emb1, ds.emb2, 0.2, q.budget, cfg, use_kernel=True)
    dt_sweep = time.perf_counter() - t0
    rows.append(row("fig14_stratify_sort", dt_sort, f"{dt_sort*1e3:.1f}ms"))
    rows.append(row("fig14_stratify_sweep_kernel", dt_sweep,
                    f"speedup_vs_sort_x={dt_sort / max(dt_sweep, 1e-9):.2f}"))
    rows.extend(walk_setup_rows(fast, smoke))
    rows.extend(crossover_sweep(fast, smoke))
    return rows


def walk_setup_rows(fast: bool = True, smoke: bool = False):
    """Walk-setup latency (per-edge row sums + chain total weight for the
    WWJ sampler), separated from sampling.

    ``walk_setup_twopass`` is the standalone recomputation that
    low-precision sweeps still take: two f64 passes over the cross product
    after stratification.  The fused sweep
    emits the same statistics inline (one-pass chain statistics, see
    docs/kernels.md), so a cold fused query's walk setup is just a read of
    the sweep output (``walk_setup_fused_cold``, measured end-to-end inside
    a streaming query) and a warm-index query hydrates them from the
    artifact with ZERO passes (``walk_setup_warm_index``, gated >= 5x
    faster than the two-pass recomputation)."""
    import numpy as np

    from repro.core.index import build_index
    from repro.core.similarity import chain_total_weight, edge_row_sums

    rows = []
    n = 300 if smoke else 600 if fast else 2000
    ds = make_clustered_tables(n, n, n_entities=n, noise=0.4, seed=23)
    embs = [np.asarray(ds.emb1, np.float32), np.asarray(ds.emb2, np.float32)]

    t0 = time.perf_counter()
    rs_ref = edge_row_sums(embs)
    total_ref = chain_total_weight(embs)
    dt_two = time.perf_counter() - t0

    # cold fused query: walk setup reads the statistics the stratification
    # sweep already emitted — timed end-to-end by the streaming pipeline
    res = run_bas_streaming(
        Query(spec=ds.spec(), agg=Agg.COUNT, oracle=ds.oracle(),
              budget=max(n * n // 40, 2000)), seed=0)
    dt_cold = res.telemetry.timings["walk_setup_s"]

    art = build_index(embs, n_bins=512)   # one cold sweep, not timed here
    t0 = time.perf_counter()
    info = art.sweep_info()
    rs, total = info.row_sums, info.total_weight
    dt_warm = time.perf_counter() - t0
    assert rs is not None and total is not None
    np.testing.assert_allclose(rs[0], rs_ref[0], rtol=1e-6)
    assert abs(total - total_ref) <= 1e-6 * total_ref
    warm_x = dt_two / max(dt_warm, 1e-9)
    cold_x = dt_two / max(dt_cold, 1e-9)
    assert warm_x >= 5.0, (
        f"warm-index walk setup only {warm_x:.1f}x vs two-pass recompute"
    )
    rows.append(row("walk_setup_twopass", dt_two,
                    "edge_row_sums+chain_total_weight"))
    rows.append(row("walk_setup_fused_cold", dt_cold,
                    f"twopass_over_fused_x={cold_x:.1f}"))
    rows.append(row("walk_setup_warm_index", dt_warm,
                    f"twopass_over_warm_x={warm_x:.1f}"))
    rows.append(row("fig14_walk_setup", dt_cold,
                    "streaming-query walk-setup phase"))
    return rows


def crossover_sweep(fast: bool = True, smoke: bool = False):
    """Dense vs streaming end-to-end latency across problem sizes.

    Emits one dense and one streaming row per size plus the dispatcher's
    choice under the default cap, so ``BASConfig.max_dense_weight_bytes``
    can be tuned from data instead of guesswork.  The streaming rows run
    the fused single-sweep stratification (the default)."""
    rows = []
    sizes = ([150, 300] if smoke else [150, 300, 600] if fast
             else [300, 600, 1200, 2400])
    for n in sizes:
        ds = make_clustered_tables(n, n, n_entities=max(n, 64), noise=0.4,
                                   seed=29)
        budget = max(n * n // 40, 2000)
        spec = ds.spec()
        t0 = time.perf_counter()
        run_bas(Query(spec=spec, agg=Agg.COUNT, oracle=ds.oracle(),
                      budget=budget), seed=0)
        dt_dense = time.perf_counter() - t0
        t0 = time.perf_counter()
        run_bas_streaming(Query(spec=spec, agg=Agg.COUNT, oracle=ds.oracle(),
                                budget=budget), seed=0)
        dt_stream = time.perf_counter() - t0
        mb = dense_weight_bytes(spec) / 2**20
        rows.append(row(f"crossover_dense_n{n}", dt_dense,
                        f"flat_weights_mb={mb:.1f}"))
        rows.append(row(
            f"crossover_streaming_n{n}", dt_stream,
            f"dense_over_streaming_x={dt_dense / max(dt_stream, 1e-9):.2f},"
            f"auto_path={choose_path(spec)}",
        ))
    return rows
