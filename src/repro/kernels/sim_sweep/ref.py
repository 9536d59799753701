"""Pure-jnp oracle for the fused sim_sweep kernel."""
import jax
import jax.numpy as jnp

from .kernel import two_sum


def comp_sum_ref(x):
    """Pure-jnp error-free pairwise reduction along axis 1: (hi, lo) column
    vectors with ``sum(x, axis=1) == hi + lo`` to ~1 ulp."""
    hi = x
    lo = jnp.zeros_like(x)
    while hi.shape[1] > 1:
        if hi.shape[1] % 2:  # pad one zero column so the halves line up
            hi = jnp.concatenate([hi, jnp.zeros_like(hi[:, :1])], axis=1)
            lo = jnp.concatenate([lo, jnp.zeros_like(lo[:, :1])], axis=1)
        half = hi.shape[1] // 2
        s, e = two_sum(hi[:, :half], hi[:, half:])
        lo = lo[:, :half] + lo[:, half:] + e
        hi = s
    return hi, lo


def sim_sweep_ref(e1, e2, n_bins=4096, exponent=1.0, floor=1e-3, k=8,
                  bm=None, scale=None, v=None, rs_exponent=None):
    """Returns (block_counts (M/bm, n_bins) i32, vals (M, k) f32,
    idx (M, k) i32, row_sums (M, 1) f32) — the same quadruple as
    ``sim_sweep_pallas``.  Row sums use a compensated pairwise reduction
    over the full width (:func:`comp_sum_ref`), so the oracle matches both
    the kernel and a float64 reference to ~1 ulp."""
    m = e1.shape[0]
    bm = m if bm is None else bm
    scores = jnp.dot(
        e1.astype(jnp.float32), e2.astype(jnp.float32).T,
        preferred_element_type=jnp.float32,
    )
    base = jnp.maximum(jnp.clip(scores, 0.0, 1.0), floor)
    w = base if exponent == 1.0 else base**exponent
    if scale is not None:
        w = w * scale.reshape(-1, 1).astype(jnp.float32)
    idx = jnp.clip((w * n_bins).astype(jnp.int32), 0, n_bins - 1)
    blk = jnp.arange(m, dtype=jnp.int32) // bm
    bc = jnp.zeros((m // bm, n_bins), jnp.int32).at[
        jnp.broadcast_to(blk[:, None], idx.shape).reshape(-1),
        idx.reshape(-1),
    ].add(1)
    vals, top_i = jax.lax.top_k(jnp.clip(scores, 0.0, 1.0), k)
    rs_exp = exponent if rs_exponent is None else rs_exponent
    wr = base if rs_exp == 1.0 else base**rs_exp
    if v is not None:
        wr = wr * v.reshape(1, -1).astype(jnp.float32)
    hi, lo = comp_sum_ref(wr)
    return bc, vals, top_i.astype(jnp.int32), hi + lo
