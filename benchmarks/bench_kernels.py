"""Kernel micro-benchmarks: Pallas (interpret on CPU — structural check) vs
pure-jnp reference, wall time + agreement.  On TPU the same entry points run
compiled.

The ``kernel_sim_sweep_fused`` row times the fused single-sweep pass and
asserts its count tiles equal the jnp reference's.
``kernel_sweep_plus_two_pass`` times the sweep followed by the standalone f64
walk-setup passes that low-precision sweeps still take: compiled (TPU) runs
must clear >= 1.5x fused over that; interpret-mode runs only assert
agreement and report the measured ratio (the CPU interpreter is
epilogue-bound).

Run via ``python -m benchmarks.run --only kernels``.  CI diffs the ``--json``
output against ``benchmarks/baselines/BENCH_kernels.json`` warn-only (see
``scripts/bench_diff.py``); kernel/reference agreement is asserted here and
in ``tests/test_kernels.py``."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.similarity import normalize

from .common import row


def _time(fn, *args, reps=3):
    jax.block_until_ready(fn(*args))  # warmup/compile, fully retired
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps, out


def run(fast: bool = True, smoke: bool = False):
    rows = []
    rng = np.random.default_rng(0)
    n = 256 if smoke else 512

    e1 = jnp.asarray(normalize(rng.standard_normal((n, 64))))
    e2 = jnp.asarray(normalize(rng.standard_normal((n, 64))))

    # sim_topk
    from repro.kernels.sim_topk.kernel import sim_topk_pallas
    from repro.kernels.sim_topk.ref import sim_topk_ref

    dt_k, (vk, ik) = _time(lambda a, b: sim_topk_pallas(a, b, k=8, bm=128, bn=128,
                                                        interpret=True), e1, e2)
    dt_r, (vr, ir) = _time(lambda a, b: sim_topk_ref(a, b, k=8), e1, e2)
    agree = bool(np.allclose(np.asarray(vk), np.asarray(vr), atol=1e-5))
    rows.append(row("kernel_sim_topk_pallas", dt_k, f"agree={agree}"))
    rows.append(row("kernel_sim_topk_ref", dt_r, f"ratio={dt_k/dt_r:.1f}"))

    # sim_sweep: the fused single pass, checked against its jnp reference
    from repro.kernels.sim_sweep.kernel import sim_sweep_pallas
    from repro.kernels.sim_sweep.ref import sim_sweep_ref

    interpret = jax.default_backend() != "tpu"

    def fused(a, b):
        return sim_sweep_pallas(a, b, n_bins=512, k=8, bm=128, bn=128,
                                interpret=interpret)

    dt_f, (bc, vf, jf, rs_f) = _time(fused, e1, e2)
    rbc = sim_sweep_ref(e1, e2, n_bins=512, k=8, bm=128)[0]
    agree = bool(np.array_equal(np.asarray(bc), np.asarray(rbc)))
    assert agree, "fused sweep count tiles disagree with the jnp reference"
    rows.append(row("kernel_sim_sweep_fused", dt_f, f"agree={agree}"))

    # one-pass chain statistics: the fused sweep already emitted the walk
    # row sums above for free — compare against the sweep followed by the
    # two standalone f64 walk-setup passes that low-precision sweeps take
    from repro.core.similarity import chain_total_weight, edge_row_sums_raw

    e1_np, e2_np = np.asarray(e1), np.asarray(e2)

    def sweep_plus_two_pass(a, b):
        out = sim_sweep_pallas(a, b, n_bins=512, k=8, bm=128, bn=128,
                               interpret=interpret)
        rs = edge_row_sums_raw([e1_np, e2_np])
        total = chain_total_weight([e1_np, e2_np])
        return out, rs, total

    dt_two, (_, rs_ref, total_ref) = _time(sweep_plus_two_pass, e1, e2)
    rs_fused = np.asarray(rs_f)[:, 0].astype(np.float64)
    np.testing.assert_allclose(rs_fused, rs_ref[0], rtol=1e-6)
    assert abs(float(rs_fused.sum()) - total_ref) <= 1e-6 * total_ref
    rowsum_speedup = dt_two / dt_f
    if not interpret:
        assert rowsum_speedup >= 1.5, (
            f"compiled fused-with-rowsums only {rowsum_speedup:.2f}x vs "
            "sweep plus two standalone passes"
        )
    rows.append(row("kernel_sweep_fused_rowsums", dt_f,
                    "sums_rel_err<=1e-6"))
    rows.append(row("kernel_sweep_plus_two_pass", dt_two,
                    f"fused_speedup_x={rowsum_speedup:.2f}"))

    # low-precision fast paths of the same fused pass
    for precision, dtype in (("bf16", jnp.bfloat16),):
        dt_l, _ = _time(
            lambda a, b: sim_sweep_pallas(a, b, n_bins=512, k=8, bm=128,
                                          bn=128, interpret=interpret,
                                          compute_dtype=dtype), e1, e2)
        rows.append(row(f"kernel_sim_sweep_{precision}", dt_l,
                        f"fp32_over_{precision}_x={dt_f/dt_l:.2f}"))

    # flash_attention
    from repro.kernels.flash_attention.kernel import flash_attention_pallas
    from repro.kernels.flash_attention.ref import flash_attention_ref

    q = jnp.asarray(rng.standard_normal((1, 4, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.float32)
    dt_k, ok = _time(lambda *a: flash_attention_pallas(*a, causal=True, bq=64,
                                                       bkv=64, interpret=True), q, k, v)
    dt_r, orf = _time(lambda *a: flash_attention_ref(*a, causal=True), q, k, v)
    agree = bool(np.allclose(np.asarray(ok), np.asarray(orf), atol=2e-4))
    rows.append(row("kernel_flash_attention_pallas", dt_k, f"agree={agree}"))
    rows.append(row("kernel_flash_attention_ref", dt_r, f"ratio={dt_k/dt_r:.1f}"))

    # rwkv6_scan
    from repro.kernels.rwkv6_scan.kernel import rwkv6_scan_pallas
    from repro.kernels.rwkv6_scan.ref import rwkv6_scan_ref

    r = jnp.asarray(rng.standard_normal((1, 4, 128, 32)), jnp.float32)
    kk = jnp.asarray(rng.standard_normal((1, 4, 128, 32)) * 0.3, jnp.float32)
    vv = jnp.asarray(rng.standard_normal((1, 4, 128, 32)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.8, 0.999, (1, 4, 128, 32)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((4, 32)) * 0.1, jnp.float32)
    dt_k, ok = _time(lambda *a: rwkv6_scan_pallas(*a, ct=32, interpret=True),
                     r, kk, vv, w, u)
    dt_r, orf = _time(rwkv6_scan_ref, r, kk, vv, w, u)
    agree = bool(np.allclose(np.asarray(ok), np.asarray(orf), atol=1e-3))
    rows.append(row("kernel_rwkv6_scan_pallas", dt_k, f"agree={agree}"))
    rows.append(row("kernel_rwkv6_scan_ref", dt_r, f"ratio={dt_k/dt_r:.1f}"))

    # rglru_scan
    from repro.kernels.rglru_scan.kernel import rglru_scan_pallas
    from repro.kernels.rglru_scan.ref import rglru_scan_ref

    a = jnp.asarray(rng.uniform(0.8, 0.999, (2, 256, 256)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((2, 256, 256)) * 0.1, jnp.float32)
    dt_k, ok = _time(lambda *x: rglru_scan_pallas(*x, ct=64, br=256, interpret=True),
                     a, g)
    dt_r, orf = _time(rglru_scan_ref, a, g)
    agree = bool(np.allclose(np.asarray(ok), np.asarray(orf), atol=1e-3))
    rows.append(row("kernel_rglru_scan_pallas", dt_k, f"agree={agree}"))
    rows.append(row("kernel_rglru_scan_ref", dt_r, f"ratio={dt_k/dt_r:.1f}"))
    return rows
