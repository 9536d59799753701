"""Blocked online-softmax (Flash) GQA attention Pallas kernel.

Used for the Oracle transformer forward (the pairwise-evaluation hot spot the
paper pays for by the token).  Grid (B*Hq, Sq/bq, Skv/bkv) with running
(m, l, acc) in VMEM scratch; the KV block index_map folds the GQA group so
K/V are read once per kv-head.  Causal + sliding-window masking by absolute
positions.  VMEM working set per program: bq*d + bkv*d + bq*bkv scores.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, bq: int, bkv: int,
            n_kv_blocks: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)   # (bq, d)
    k = k_ref[0].astype(jnp.float32)   # (bkv, d)
    v = v_ref[0].astype(jnp.float32)   # (bkv, d)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                            # (bq, bkv)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    k_pos = kj * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = jnp.ones((bq, bkv), jnp.bool_)
    if causal:
        mask = q_pos >= k_pos
    if window > 0:
        mask = jnp.logical_and(mask, q_pos - k_pos < window)
    s = jnp.where(mask, s, NEG)

    m_prev = m_scr[...]                  # (bq, 1)
    m_cur = jnp.maximum(m_prev[:, 0], s.max(axis=1))[:, None]
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)
    l_cur = l_scr[...] * alpha + p.sum(axis=1)[:, None]
    acc = acc_scr[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_scr[...] = m_cur
    l_scr[...] = l_cur
    acc_scr[...] = acc

    @pl.when(kj == n_kv_blocks - 1)
    def _emit():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "bq", "bkv", "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,   # (B, Hq, Sq, d)
    k: jax.Array,   # (B, Hkv, Skv, d)
    v: jax.Array,   # (B, Hkv, Skv, d)
    causal: bool = True,
    window: int = 0,
    bq: int = 128,
    bkv: int = 128,
    *,
    interpret: bool,
) -> jax.Array:
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert sq % bq == 0 and skv % bkv == 0
    group = hq // hkv
    scale = d**-0.5
    grid = (b * hq, sq // bq, skv // bkv)

    def q_map(bh, i, j):
        return (bh, i, 0)

    def kv_map(bh, i, j):
        # fold batch*q-head back to batch*kv-head
        return ((bh // hq) * hkv + (bh % hq) // group, j, 0)

    qr = q.reshape(b * hq, sq, d)
    kr = k.reshape(b * hkv, skv, d)
    vr = v.reshape(b * hkv, skv, d)
    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, causal=causal, window=window, bq=bq,
            bkv=bkv, n_kv_blocks=skv // bkv,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bkv, d), kv_map),
            pl.BlockSpec((1, bkv, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, d), q_map),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, hq, sq, d)
