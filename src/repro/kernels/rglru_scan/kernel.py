"""RG-LRU (Griffin) gated linear recurrence Pallas kernel.

    h_t = a_t * h_{t-1} + g_t          (elementwise, diagonal recurrence)

TPU adaptation: channels tile the lane dimension (block br), the hidden state
h stays in VMEM scratch across the sequential time-chunk grid dimension, and
each chunk runs an in-register associative prefix:  within a chunk of length
ct we compute cumulative products A_t = prod a and prefix sums of g/A via a
log2(ct) Blelloch-style doubling loop — O(ct log ct) vector work instead of a
serial ct-step chain, which keeps the VPU busy at long sequence lengths.

Grid: (B, R/br, T/ct); time iterates sequentially carrying h.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, g_ref, o_ref, h_scr, *, ct: int, br: int):
    tj = pl.program_id(2)

    @pl.when(tj == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[0].astype(jnp.float32)  # (ct, br)
    g = g_ref[0].astype(jnp.float32)

    # inclusive scan of h_t = a_t h_{t-1} + g_t via operator doubling:
    # pairs (A, G) compose as (A2*A1, A2*G1 + G2).
    A, G = a, g
    shift = 1
    while shift < ct:
        A_prev = jnp.roll(A, shift, axis=0)
        G_prev = jnp.roll(G, shift, axis=0)
        t_idx = jax.lax.broadcasted_iota(jnp.int32, (ct, br), 0)
        valid = t_idx >= shift
        G = jnp.where(valid, A * G_prev + G, G)
        A = jnp.where(valid, A * A_prev, A)
        shift *= 2
    h0 = h_scr[...]                     # (1, br)
    hs = A * h0 + G                     # (ct, br)
    h_scr[...] = hs[ct - 1 :, :]
    o_ref[0] = hs.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("ct", "br", "interpret"))
def rglru_scan_pallas(
    a: jax.Array,   # (B, T, R) decay in (0,1)
    g: jax.Array,   # (B, T, R) gated input
    ct: int = 128,
    br: int = 512,
    *,
    interpret: bool,
) -> jax.Array:
    b, t, r = a.shape
    assert t % ct == 0 and r % br == 0
    grid = (b, r // br, t // ct)

    def x_map(bi, ri, tj):
        return (bi, tj, ri)

    out = pl.pallas_call(
        functools.partial(_kernel, ct=ct, br=br),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, ct, br), x_map),
            pl.BlockSpec((1, ct, br), x_map),
        ],
        out_specs=pl.BlockSpec((1, ct, br), x_map),
        out_shape=jax.ShapeDtypeStruct((b, t, r), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, br), jnp.float32)],
        interpret=interpret,
    )(a, g)
    return out
