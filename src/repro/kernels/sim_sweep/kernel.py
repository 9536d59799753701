"""Single-sweep stratification kernel: histogram + top-k + per-block bins
+ compensated walk row-sums.

The streaming stratifier (``repro.core.stratify``) needs a weight histogram
that sets the top-m threshold, a per-row top-k that collects the blocking
regime, and the walk sums; this kernel emits all of it from **one** pass
over the blocked ``E1 @ E2^T`` product:

* per-(row-block, bin) count tiles — the global histogram is their exact
  integer column sum, and the tiles tell the collector/sampler which row
  blocks contain over-threshold mass so rescans touch only those blocks;
* the running per-row top-k of the raw clipped similarity (bit-identical
  semantics to ``sim_topk``: k static, maintained by k extract-max passes);
* per-left-row walk sums ``row_sums[i] = sum_c base(i,c)**rs_exponent *
  v[c]`` — the wandering-join proposal normaliser (and, via the backward
  vector ``v``, the chain-total-weight contraction) that previously cost a
  second full pass in numpy.

The histogram half bins the *sampling weight* ``max(clip(s,0,1), floor) **
exponent * scale`` (``scale`` is the per-left-row chain-prefix weight for
k-way joins); the top-k half ranks the raw clipped score, which is
monotone in the weight for any fixed row.  The walk-sum half applies the
same clip/floor transform at an independent static power ``rs_exponent``
(chain sweeps bin the geometric-mean weight at ``exponent * root`` but need
the raw full-exponent edge weight in the sums).

Walk sums are accumulated with **compensated f32 arithmetic**: each (bm, bn)
block is reduced by an error-free tree that carries (hi, lo) pairs through
branch-free Knuth two-sum steps — lane-aligned halving down to one vreg
width, then a lane-rotation butterfly — and the cross-block running total
lives in two VMEM scratch tiles (sum, compensation).  The result matches a
float64 reference to ~1 ulp of f32 (|rel err| ~1e-7) regardless of the
column count or magnitude spread — naive sequential f32 accumulation loses
several digits at these reduction lengths (see ``tests/test_chain_stats``).

Precision paths (static ``compute_dtype``): fp32 feeds the MXU f32 inputs
at ``HIGHEST`` precision (histogram bit-identical to ``sim_sweep_ref``;
Mosaic runs a default-precision f32 dot as one bf16 pass, ~5e-4 error on
unit vectors on a v5e); bf16 feeds the MXU
bf16 inputs with f32 accumulation; the int8 variant (``sim_sweep_q_pallas``)
takes per-row-quantised int8 embeddings + scales, accumulates in int32 on
the MXU and rescales to f32 scores.

Grid: (M/bm, N/bn); the N dimension iterates sequentially (TPU grid order),
the count tile, top-k and walk-sum scratch are initialised at j == 0.  Every
block shape obeys the TPU's (8, 128) rule: count tiles are (n_hi, lane)
(``repro.kernels.binning``) and the walk sums are carried lane-wide; the
wrapper reshapes both to the documented outputs outside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..binning import LANE, bin_counts, bin_layout, flat_counts
from ..sim_topk.kernel import F32_DOT, NEG, merge_topk


def two_sum(a, b):
    """Branch-free error-free transform (Knuth): a + b == s + err exactly."""
    s = a + b
    bv = s - a
    err = (a - (s - bv)) + (b - bv)
    return s, err


def comp_block_sum(x):
    """Error-free reduction along axis 1, inside a kernel: returns (hi, lo)
    tiles of width ``min(width, LANE)`` whose lane 0 satisfies
    ``sum(x, axis=1) == hi + lo`` to ~1 ulp.  Lane-aligned halving takes the
    width down to one vreg, then a rotation butterfly (``pltpu.roll``) folds
    the lanes; every step is a two-sum carrying per-lane compensation, so
    the reduction stays vectorised on the VPU."""
    hi = x
    lo = jnp.zeros_like(x)
    while hi.shape[1] > LANE:
        half = hi.shape[1] // 2
        s, e = two_sum(hi[:, :half], hi[:, half:])
        lo = lo[:, :half] + lo[:, half:] + e
        hi = s
    shift = hi.shape[1] // 2
    while shift:
        s, e = two_sum(hi, pltpu.roll(hi, shift, 1))
        lo = lo + pltpu.roll(lo, shift, 1) + e
        hi = s
        shift //= 2
    return hi, lo


def _fused_epilogue(scores, s, v, bc_ref, vals_ref, idx_ref, rs_ref, run_v,
                    run_i, rs_hi, rs_lo, idx_buf, *, n_bins, exponent,
                    rs_exponent, floor, k, bn, n_blocks):
    """Shared histogram + top-k + walk-sum epilogue over one (bm, bn) block."""
    j = pl.program_id(1)

    # ---- histogram half: sampling-weight transform + per-block bin counts
    base = jnp.maximum(jnp.clip(scores, 0.0, 1.0), floor)
    w = base if exponent == 1.0 else base**exponent
    w = w * s.astype(jnp.float32)  # (bm, 1) prefix weights broadcast
    idx = jnp.clip((w * n_bins).astype(jnp.int32), 0, n_bins - 1)
    bc_ref[...] = bc_ref[...] + bin_counts(idx, n_bins, idx_buf)

    # ---- walk-sum half: compensated accumulation of the raw edge weight
    # times the backward vector.  Padded columns carry v == 0 and vanish, so
    # unlike the histogram no host-side padding correction is needed.
    wr = base if rs_exponent == 1.0 else base**rs_exponent
    wr = wr * v.astype(jnp.float32)  # (1, bn) backward vector broadcast
    blk_hi, blk_lo = comp_block_sum(wr)
    acc_hi, acc_err = two_sum(rs_hi[...], blk_hi)
    rs_hi[...] = acc_hi
    rs_lo[...] = rs_lo[...] + (blk_lo + acc_err)

    # ---- top-k half: raw clipped scores, identical math to sim_topk
    sc = jnp.clip(scores, 0.0, 1.0)
    col = j * bn + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
    new_v, new_i = merge_topk(run_v[...], run_i[...], sc, col, k)
    run_v[...] = new_v
    run_i[...] = new_i

    @pl.when(j == n_blocks - 1)
    def _emit():
        vals_ref[...] = new_v
        idx_ref[...] = new_i
        rs_ref[...] = rs_hi[...] + rs_lo[...]


def _init(bc_ref, run_v, run_i, rs_hi, rs_lo):
    @pl.when(pl.program_id(1) == 0)
    def _():
        bc_ref[...] = jnp.zeros_like(bc_ref)
        run_v[...] = jnp.full_like(run_v, NEG)
        run_i[...] = jnp.zeros_like(run_i)
        rs_hi[...] = jnp.zeros_like(rs_hi)
        rs_lo[...] = jnp.zeros_like(rs_lo)


def _kernel(e1_ref, e2_ref, s_ref, v_ref, bc_ref, vals_ref, idx_ref, rs_ref,
            run_v, run_i, rs_hi, rs_lo, idx_buf, *, compute_dtype, **kw):
    _init(bc_ref, run_v, run_i, rs_hi, rs_lo)
    e1 = e1_ref[...].astype(compute_dtype)
    e2 = e2_ref[...].astype(compute_dtype)
    scores = jax.lax.dot_general(
        e1, e2, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=F32_DOT if compute_dtype == jnp.float32 else None,
    )
    _fused_epilogue(
        scores, s_ref[...], v_ref[...], bc_ref, vals_ref, idx_ref, rs_ref,
        run_v, run_i, rs_hi, rs_lo, idx_buf, **kw,
    )


def _kernel_q(q1_ref, q2_ref, s_ref, rs1_ref, rs2_ref, v_ref, bc_ref,
              vals_ref, idx_ref, rs_ref, run_v, run_i, rs_hi, rs_lo, idx_buf,
              **kw):
    _init(bc_ref, run_v, run_i, rs_hi, rs_lo)
    acc = jax.lax.dot_general(
        q1_ref[...], q2_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    scores = acc.astype(jnp.float32) * rs1_ref[...] * rs2_ref[...]
    _fused_epilogue(
        scores, s_ref[...], v_ref[...], bc_ref, vals_ref, idx_ref, rs_ref,
        run_v, run_i, rs_hi, rs_lo, idx_buf, **kw,
    )


def _sweep_call(kernel, operands, in_specs, *, m, n, n_bins, exponent,
                rs_exponent, floor, k, bm, bn, interpret):
    """pallas_call shared by both precision paths, plus the reshapes from
    the kernel's tile layouts to the documented outputs."""
    assert m % bm == 0 and n % bn == 0, "pad inputs to block multiples"
    assert k <= bn
    n_hi, lane = bin_layout(n_bins)
    rs_w = min(bn, LANE)
    rs_exp = exponent if rs_exponent is None else rs_exponent
    bc, vals, idx, rs = pl.pallas_call(
        functools.partial(
            kernel, n_bins=n_bins, exponent=exponent, rs_exponent=rs_exp,
            floor=floor, k=k, bn=bn, n_blocks=n // bn,
        ),
        grid=(m // bm, n // bn),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, n_hi, lane), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, rs_w), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m // bm, n_hi, lane), jnp.int32),
            jax.ShapeDtypeStruct((m, k), jnp.float32),
            jax.ShapeDtypeStruct((m, k), jnp.int32),
            jax.ShapeDtypeStruct((m, rs_w), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, k), jnp.float32),
            pltpu.VMEM((bm, k), jnp.int32),
            pltpu.VMEM((bm, rs_w), jnp.float32),
            pltpu.VMEM((bm, rs_w), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.int32),
        ],
        interpret=interpret,
        name="sim_sweep",
    )(*operands)
    return flat_counts(bc, n_bins), vals, idx, rs[:, :1]


def _row_col_operands(m, n, scale, v):
    scale = (jnp.ones((m, 1), jnp.float32) if scale is None
             else scale.reshape(m, 1).astype(jnp.float32))
    v = (jnp.ones((1, n), jnp.float32) if v is None
         else v.reshape(1, n).astype(jnp.float32))
    return scale, v


_STATIC = ("n_bins", "exponent", "rs_exponent", "floor", "k", "bm", "bn",
           "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC + ("compute_dtype",))
def sim_sweep_pallas(
    e1: jax.Array,
    e2: jax.Array,
    scale: jax.Array | None = None,
    v: jax.Array | None = None,
    *,
    interpret: bool,
    n_bins: int = 4096,
    exponent: float = 1.0,
    rs_exponent: float | None = None,
    floor: float = 1e-3,
    k: int = 8,
    bm: int = 256,
    bn: int = 256,
    compute_dtype=jnp.float32,
):
    """Fused pass: returns (block_counts (M/bm, n_bins) i32, vals (M, k) f32,
    idx (M, k) i32, row_sums (M, 1) f32).  The global histogram is
    ``block_counts.sum(axis=0)``; ``row_sums`` is the compensated
    ``sum_c base**rs_exponent * v`` walk sum (``rs_exponent`` defaults to
    ``exponent``, ``v`` to ones — pass zeros in padded columns)."""
    m, d = e1.shape
    n, _ = e2.shape
    scale, v = _row_col_operands(m, n, scale, v)
    return _sweep_call(
        functools.partial(_kernel, compute_dtype=compute_dtype),
        (e1, e2, scale, v),
        [
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        m=m, n=n, n_bins=n_bins, exponent=exponent, rs_exponent=rs_exponent,
        floor=floor, k=k, bm=bm, bn=bn, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=_STATIC)
def sim_sweep_q_pallas(
    q1: jax.Array,
    q2: jax.Array,
    rs1: jax.Array,
    rs2: jax.Array,
    scale: jax.Array | None = None,
    v: jax.Array | None = None,
    *,
    interpret: bool,
    n_bins: int = 4096,
    exponent: float = 1.0,
    rs_exponent: float | None = None,
    floor: float = 1e-3,
    k: int = 8,
    bm: int = 256,
    bn: int = 256,
):
    """int8 fast path: ``scores = (q1 @ q2^T) * rs1 * rs2^T`` with int32 MXU
    accumulation.  ``rs1`` is (M, 1) and ``rs2`` is (1, N) f32 row scales."""
    m, d = q1.shape
    n, _ = q2.shape
    scale, v = _row_col_operands(m, n, scale, v)
    return _sweep_call(
        _kernel_q,
        (q1, q2, scale, rs1.reshape(m, 1), rs2.reshape(1, n), v),
        [
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        m=m, n=n, n_bins=n_bins, exponent=exponent, rs_exponent=rs_exponent,
        floor=floor, k=k, bm=bm, bn=bn, interpret=interpret,
    )
