"""Fused cosine-similarity + histogram Pallas kernel.

The paper stratifies the cross product by sorting all N1*N2 similarity scores
(its profiled CPU hot spot, App. A).  TPU-native redesign: one pass of blocked
``E1 @ E2^T`` on the MXU with an in-VMEM histogram epilogue — the score matrix
is never materialised in HBM (O(n_bins) output), and the strata thresholds are
read off the histogram CDF (see ``repro.core.stratify``).

The optional per-left-row ``scale`` operand generalises the kernel to k-way
chain joins: the streaming stratifier enumerates the chain's *prefix* space in
blocks and passes the accumulated prefix chain weight as the scale, so the
kernel histograms ``scale_i * w(i, j)`` — the full chain weight — while still
never materialising anything bigger than one (bm, bn) block.

Grid: (M/bm, N/bn), sequential on TPU so the histogram accumulates safely in
the output block (same output block mapped to every program).

The binning epilogue is shared with ``sim_sweep`` (``repro.kernels.binning``):
a two-level one-hot + MXU combine in place of O(n_bins) compares per
element; the histogram accumulates as an (n_hi, lane) tile and is flattened
to (n_bins,) outside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..binning import bin_counts, bin_layout, flat_counts
from ..sim_topk.kernel import F32_DOT


def _kernel(e1_ref, e2_ref, s_ref, out_ref, idx_buf, *, n_bins: int,
            exponent: float, floor: float):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    e1 = e1_ref[...].astype(jnp.float32)
    e2 = e2_ref[...].astype(jnp.float32)
    scores = jax.lax.dot_general(
        e1, e2, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=F32_DOT,
    )
    w = jnp.clip(scores, 0.0, 1.0)
    w = jnp.maximum(w, floor)
    if exponent != 1.0:
        w = w**exponent
    w = w * s_ref[...].astype(jnp.float32)  # (bm, 1) prefix weights broadcast
    idx = jnp.clip((w * n_bins).astype(jnp.int32), 0, n_bins - 1)
    out_ref[...] = out_ref[...] + bin_counts(idx, n_bins, idx_buf)


@functools.partial(
    jax.jit,
    static_argnames=("n_bins", "exponent", "floor", "bm", "bn", "interpret"),
)
def sim_hist_pallas(
    e1: jax.Array,
    e2: jax.Array,
    scale: jax.Array | None = None,
    *,
    interpret: bool,
    n_bins: int = 4096,
    exponent: float = 1.0,
    floor: float = 1e-3,
    bm: int = 256,
    bn: int = 256,
) -> jax.Array:
    m, d = e1.shape
    n, _ = e2.shape
    assert m % bm == 0 and n % bn == 0, "pad inputs to block multiples"
    n_hi, lane = bin_layout(n_bins)
    if scale is None:
        scale = jnp.ones((m, 1), jnp.float32)
    else:
        scale = scale.reshape(m, 1).astype(jnp.float32)
    grid = (m // bm, n // bn)
    tile = pl.pallas_call(
        functools.partial(
            _kernel, n_bins=n_bins, exponent=exponent, floor=floor,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((n_hi, lane), lambda i, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_hi, lane), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        name="sim_hist",
    )(e1, e2, scale)
    return flat_counts(tile, n_bins)
