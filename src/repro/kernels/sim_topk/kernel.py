"""Fused similarity + per-row top-k Pallas kernel (nearest-neighbour blocking).

The paper's out-of-memory fallback joins each left record with its top-b'
right records (§5.3, NN-based blocking).  TPU-native: blocked ``E1 @ E2^T``
with a running top-k held in VMEM scratch across the N-block grid dimension —
k static, maintained by k extract-max passes (vector ops only, no sort).

Grid: (M/bm, N/bn); the N dimension iterates sequentially (TPU grid order) so
the scratch carries the running (values, indices) for the current row block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
_NO_COL = 2**31 - 1
# f32 scores at full f32: Mosaic runs a default-precision f32 dot as one
# bf16 pass (~5e-4 error on unit vectors, measured on a v5e)
F32_DOT = jax.lax.Precision.HIGHEST


def merge_topk(run_v, run_i, sc, col, k: int):
    """Top-k of the running candidates ``(run_v, run_i)`` (bm, k) and one
    block of clipped scores ``sc`` with column ids ``col`` (bm, bn).

    k extract-max passes; each takes the row maximum over both operands and,
    among equal values, the lowest column id — the order a stable sort by
    (value desc, column asc) gives, so the result does not depend on the
    block width.  Scores are clipped to [0, 1], so a NEG slot never wins
    while k <= bn.  Reductions and selects only: no lane concatenation, no
    argmax and no scatter, which Mosaic does not lower for these shapes."""
    lane = jax.lax.broadcasted_iota(jnp.int32, run_v.shape, 1)

    def extract(t, carry):
        run_v, sc, new_v, new_i = carry
        m = jnp.maximum(jnp.max(run_v, axis=1, keepdims=True),
                        jnp.max(sc, axis=1, keepdims=True))
        c = jnp.minimum(
            jnp.min(jnp.where(run_v == m, run_i, _NO_COL), axis=1,
                    keepdims=True),
            jnp.min(jnp.where(sc == m, col, _NO_COL), axis=1, keepdims=True),
        )
        return (jnp.where((run_v == m) & (run_i == c), NEG, run_v),
                jnp.where((sc == m) & (col == c), NEG, sc),
                jnp.where(lane == t, m, new_v),
                jnp.where(lane == t, c, new_i))

    init = (run_v, sc, jnp.full(run_v.shape, NEG, jnp.float32),
            jnp.zeros(run_i.shape, jnp.int32))
    _, _, new_v, new_i = jax.lax.fori_loop(0, k, extract, init)
    return new_v, new_i


def _kernel(e1_ref, e2_ref, vals_ref, idx_ref, run_v, run_i, *, k: int,
            bn: int, n_blocks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        run_v[...] = jnp.full_like(run_v, NEG)
        run_i[...] = jnp.zeros_like(run_i)

    e1 = e1_ref[...].astype(jnp.float32)
    e2 = e2_ref[...].astype(jnp.float32)
    scores = jax.lax.dot_general(
        e1, e2, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=F32_DOT,
    )
    scores = jnp.clip(scores, 0.0, 1.0)
    col = j * bn + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    new_v, new_i = merge_topk(run_v[...], run_i[...], scores, col, k)
    run_v[...] = new_v
    run_i[...] = new_i

    @pl.when(j == n_blocks - 1)
    def _emit():
        vals_ref[...] = new_v
        idx_ref[...] = new_i


@functools.partial(
    jax.jit, static_argnames=("k", "bm", "bn", "interpret")
)
def sim_topk_pallas(
    e1: jax.Array,
    e2: jax.Array,
    *,
    interpret: bool,
    k: int = 8,
    bm: int = 256,
    bn: int = 256,
):
    m, d = e1.shape
    n, _ = e2.shape
    assert m % bm == 0 and n % bn == 0
    assert k <= bn
    grid = (m // bm, n // bn)
    return pl.pallas_call(
        functools.partial(_kernel, k=k, bn=bn, n_blocks=n // bn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k), jnp.float32),
            jax.ShapeDtypeStruct((m, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, k), jnp.float32),
            pltpu.VMEM((bm, k), jnp.int32),
        ],
        interpret=interpret,
        name="sim_topk",
    )(e1, e2)
