"""RWKV6 (Finch) recurrence Pallas kernel — chunked over time.

Recurrence per head (state S in R^{dk x dv}):

    out_t = r_t @ (S + u * (k_t v_t^T))
    S    <- diag(w_t) S + k_t v_t^T

TPU adaptation: the state lives in VMEM scratch across the time-chunk grid
dimension; each program processes a (ct, hd) chunk of r/k/v/w for one
(batch*head), stepping through the chunk with a fori_loop of rank-1 updates.
HBM traffic is O(T*hd) per head instead of the O(T*hd^2) a naive scan
materialising per-step states would move.

Grid: (B*H, T/ct); time dimension iterates sequentially carrying S.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_scr, *, ct: int,
            hd: int, n_t_blocks: int):
    tj = pl.program_id(1)

    @pl.when(tj == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0].astype(jnp.float32)   # (ct, hd)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)  # (1, hd); u.T broadcasts over v-cols

    def step(t, carry):
        s, out = carry
        kt = jax.lax.dynamic_slice(k, (t, 0), (1, hd))   # (1, hd)
        vt = jax.lax.dynamic_slice(v, (t, 0), (1, hd))
        rt = jax.lax.dynamic_slice(r, (t, 0), (1, hd))
        wt = jax.lax.dynamic_slice(w, (t, 0), (1, hd))
        kv = kt.T @ vt                                    # (hd, hd)
        ot = rt @ (s + u.T * kv)                          # (1, hd)
        s = wt.T * s + kv
        out = jax.lax.dynamic_update_slice(out, ot, (t, 0))
        return s, out

    s0 = s_scr[...]
    s_fin, out = jax.lax.fori_loop(
        0, ct, step, (s0, jnp.zeros((ct, hd), jnp.float32))
    )
    s_scr[...] = s_fin
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("ct", "interpret"))
def rwkv6_scan_pallas(
    r: jax.Array,   # (B, H, T, hd)
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,   # decay in (0,1)
    u: jax.Array,   # (H, hd) bonus
    ct: int = 64,
    *,
    interpret: bool,
) -> jax.Array:
    b, h, t, hd = r.shape
    assert t % ct == 0
    grid = (b * h, t // ct)

    def x_map(bh, tj):
        return (bh, tj, 0)

    def u_map(bh, tj):
        return (bh % h, 0)

    rr = r.reshape(b * h, t, hd)
    out = pl.pallas_call(
        functools.partial(_kernel, ct=ct, hd=hd, n_t_blocks=t // ct),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, ct, hd), x_map),
            pl.BlockSpec((1, ct, hd), x_map),
            pl.BlockSpec((1, ct, hd), x_map),
            pl.BlockSpec((1, ct, hd), x_map),
            pl.BlockSpec((1, hd), u_map),
        ],
        out_specs=pl.BlockSpec((1, ct, hd), x_map),
        out_shape=jax.ShapeDtypeStruct((b * h, t, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(rr, k.reshape(b * h, t, hd), v.reshape(b * h, t, hd),
      w.reshape(b * h, t, hd), u)
    return out.reshape(b, h, t, hd)
