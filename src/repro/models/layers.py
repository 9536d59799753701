"""Model building blocks: norms, RoPE, chunked (flash-style) GQA attention,
SwiGLU/GeGLU MLPs, and Switch-style MoE with sort-based capacity dispatch.

Everything is functional JAX over dict param trees.  Activation sharding is
annotated with logical axis names (see ``repro.launch.sharding``) so the same
code runs on one CPU device and on the 512-chip production mesh.

Scans that the roofline analyzer must expand are wrapped in
``jax.named_scope`` with stable names:  ``layers_scan`` (trip = num_layers),
``attn_q_scan`` (trip = seq / q_chunk), ``rwkv_time_scan`` / ``rglru_time_scan``
(trip = seq).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.sharding import shard_activation
from .config import ModelConfig


def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ----------------------------------------------------------------------------
# init helpers
# ----------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    scale = scale if scale is not None else 1.0 / np.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def grad_cast(x, dt):
    """Identity whose cotangent is cast to ``dt`` — a gradient dtype barrier.
    Placed where an f32 compute island (softmax) meets the bf16 stream, it
    keeps the f32 from propagating through the whole backward pass."""
    return x


def _grad_cast_fwd(x, dt):
    return x, None


def _grad_cast_bwd(dt, _res, g):
    return (g.astype(dt),)


grad_cast.defvjp(_grad_cast_fwd, _grad_cast_bwd)


# ----------------------------------------------------------------------------
# norms
# ----------------------------------------------------------------------------

def rms_norm(x, weight, eps):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + weight.astype(jnp.float32))).astype(dt)


def layer_norm(x, weight, bias, eps):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


# ----------------------------------------------------------------------------
# RoPE
# ----------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def yarn_freqs(cfg: ModelConfig) -> np.ndarray:
    """YaRN's inverse frequencies for the ``qk_rope_head_dim`` rotary dims
    (DeepSeek-V2): the plain frequency f_i below the correction range,
    f_i / rope_factor above it, and a linear ramp between."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    f = rope_freqs(dim, base)
    if not cfg.rope_factor:
        return f

    def corr(rotations):
        return dim * np.log(cfg.rope_original_max_len / (rotations * 2 * np.pi)) / (
            2 * np.log(base))

    low = max(int(np.floor(corr(cfg.rope_beta_fast))), 0)
    high = min(int(np.ceil(corr(cfg.rope_beta_slow))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f * (1.0 - ramp) + f / cfg.rope_factor * ramp


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * np.log(factor) + 1.0 if factor > 1 and mscale else 1.0


def apply_rope(x, positions, theta: float, freqs=None):
    """x: (..., seq, head_dim); positions: (..., seq) int32.  ``freqs``
    (head_dim / 2 inverse frequencies) defaults to plain RoPE's."""
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(hd, theta)
    freqs = jnp.asarray(freqs, jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------------
# attention
# ----------------------------------------------------------------------------

def attention_params(key, cfg: ModelConfig, bias: Optional[bool] = None):
    dt = dtype_of(cfg)
    d, hd = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    bias = cfg.qkv_bias if bias is None else bias
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, nq * hd), dt),
        "wk": dense_init(ks[1], (d, nkv * hd), dt),
        "wv": dense_init(ks[2], (d, nkv * hd), dt),
        "wo": dense_init(ks[3], (nq * hd, d), dt),
    }
    if bias:
        p["bq"] = jnp.zeros((nq * hd,), dt)
        p["bk"] = jnp.zeros((nkv * hd,), dt)
        p["bv"] = jnp.zeros((nkv * hd,), dt)
    return p


def _qkv(p, cfg, x, kv_x=None):
    """Project to (B, S, n, hd) heads."""
    b, s, _ = x.shape
    kv_x = x if kv_x is None else kv_x
    skv = kv_x.shape[1]
    q = x @ p["wq"]
    k = kv_x @ p["wk"]
    v = kv_x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
    k = k.reshape(b, skv, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(b, skv, cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


def _grouped_scores(q, k):
    """q: (B, S, nq, hd), k: (B, T, nkv, hd) -> scores (B, nkv, G, S, T)
    without materialising repeated KV heads."""
    b, s, nq, hd = q.shape
    nkv = k.shape[2]
    g = nq // nkv
    qg = q.reshape(b, s, nkv, g, hd)
    return jnp.einsum(
        "bsngh,btnh->bngst", qg, k, preferred_element_type=jnp.float32
    )


def _grouped_out(probs, v):
    """probs: (B, nkv, G, S, T), v: (B, T, nkv, hd) -> (B, S, nq, hd)."""
    b, nkv, g, s, t = probs.shape
    out = jnp.einsum("bngst,btnh->bsngh", probs.astype(v.dtype), v)
    return out.reshape(b, s, nkv * g, v.shape[-1])


def chunked_attention(
    p,
    cfg: ModelConfig,
    x,
    positions,
    kv_x=None,
    kv_positions=None,
    causal: bool = True,
    window: int = 0,
    use_rope: bool = True,
):
    """Full-sequence attention, scanned over q chunks so the peak score
    buffer is (B, H, q_chunk, T) — the memory shape FlashAttention gives on
    TPU (the Pallas kernel in ``repro.kernels.flash_attention`` is the
    on-device fused version; this is the XLA-lowerable equivalent)."""
    b, s, _ = x.shape
    q, k, v = _qkv(p, cfg, x, kv_x)
    t = k.shape[1]
    kv_positions = positions if kv_positions is None else kv_positions
    if use_rope:
        q = apply_rope(q.swapaxes(1, 2), positions[:, None, :], cfg.rope_theta).swapaxes(1, 2)
        k = apply_rope(k.swapaxes(1, 2), kv_positions[:, None, :], cfg.rope_theta).swapaxes(1, 2)
    # kv_seq is a distinct logical axis: sequence-parallel rules shard the
    # residual "seq" but K/V must stay seq-replicated for attention
    k = shard_activation(k, ("batch", "kv_seq", "kv_heads", "head_dim"))
    v = shard_activation(v, ("batch", "kv_seq", "kv_heads", "head_dim"))
    if cfg.bf16_backward:
        # dtype barrier: the f32 softmax island otherwise leaks f32 cotangents
        # into every layer's backward (2x dgrad bytes and collectives)
        dt = x.dtype
        q, k, v = grad_cast(q, dt), grad_cast(k, dt), grad_cast(v, dt)
    scale = cfg.head_dim**-0.5
    out = _attend(q, k, v, positions, kv_positions, causal, window, scale,
                  cfg.attn_q_chunk)
    out = shard_activation(out, ("batch", "seq", "heads", "head_dim"))
    return out.reshape(b, s, -1) @ p["wo"]


def _attend(q, k, v, positions, kv_positions, causal, window, scale, q_chunk):
    """Attention of q (B, S, nq, dk) over k (B, T, nkv, dk) and v (B, T,
    nkv, dv), scanned over q chunks so the peak score buffer is (B, H,
    q_chunk, T) (the memory shape FlashAttention gives on TPU).  Returns
    (B, S, nq, dv)."""
    b, s, nq, dk = q.shape
    qc = max(min(q_chunk, s), 1)
    n_chunks = (s + qc - 1) // qc
    pad = n_chunks * qc - s
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        positions = jnp.pad(positions, ((0, 0), (0, pad)))
    qs = q.reshape(b, n_chunks, qc, nq, dk)
    pos_chunks = positions.reshape(b, n_chunks, qc)

    def body(carry, inp):
        qi, pi = inp  # (B, qc, nq, hd), (B, qc)
        scores = _grouped_scores(qi, k) * scale  # (B, nkv, G, qc, T) f32
        mask = jnp.ones((), jnp.bool_)
        if causal:
            mask = pi[:, None, None, :, None] >= kv_positions[:, None, None, None, :]
        if window > 0:
            wmask = pi[:, None, None, :, None] - kv_positions[:, None, None, None, :] < window
            mask = jnp.logical_and(mask, wmask)
        if causal or window > 0:
            scores = jnp.where(mask, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        out = _grouped_out(probs, v)  # (B, qc, nq, hd)
        return carry, out

    with jax.named_scope("attn_q_scan"):
        _, outs = jax.lax.scan(
            body, (), (qs.swapaxes(0, 1), pos_chunks.swapaxes(0, 1))
        )
    out = outs.swapaxes(0, 1).reshape(b, n_chunks * qc, nq, v.shape[-1])
    return out[:, :s]


def decode_attention(p, cfg: ModelConfig, x, cache_k, cache_v, position,
                     window: int = 0, use_rope: bool = True):
    """Single-token decode: append to the KV cache and attend over it.

    x: (B, 1, d); cache_k/v: (B, T_max, nkv, hd); position: scalar int32 (all
    rows at the same step) or (B,) int32 per-slot positions (continuous
    batching with mid-flight admission: each slot writes its KV at its own
    position and masks strictly by it, so a freshly admitted request never
    attends to a previous occupant's stale cache entries).
    Returns (out, new_cache_k, new_cache_v).
    """
    b = x.shape[0]
    q, k, v = _qkv(p, cfg, x)
    position = jnp.asarray(position, jnp.int32)
    per_slot = position.ndim == 1
    pos_b = position if per_slot else jnp.full((b,), position, jnp.int32)
    pos = pos_b[:, None]
    if use_rope:
        q = apply_rope(q.swapaxes(1, 2), pos[:, None, :], cfg.rope_theta).swapaxes(1, 2)
        k = apply_rope(k.swapaxes(1, 2), pos[:, None, :], cfg.rope_theta).swapaxes(1, 2)
    if per_slot:
        cache_k = cache_k.at[jnp.arange(b), pos_b].set(k[:, 0].astype(cache_k.dtype))
        cache_v = cache_v.at[jnp.arange(b), pos_b].set(v[:, 0].astype(cache_v.dtype))
    else:
        cache_k = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype), (0, position, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype), (0, position, 0, 0))
    t = cache_k.shape[1]
    kv_pos = jnp.arange(t)[None, :]
    scores = _grouped_scores(q, cache_k) * cfg.head_dim**-0.5  # (B,nkv,G,1,T)
    mask = kv_pos[:, None, None, None, :] <= pos_b[:, None, None, None, None]
    if window > 0:
        mask = jnp.logical_and(
            mask, kv_pos[:, None, None, None, :] > pos_b[:, None, None, None, None] - window
        )
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _grouped_out(probs, cache_v)
    out = out.reshape(b, 1, -1) @ p["wo"]
    return out, cache_k, cache_v


# ----------------------------------------------------------------------------
# MLPs
# ----------------------------------------------------------------------------

def mlp_params(key, cfg: ModelConfig, d_ff: Optional[int] = None):
    dt = dtype_of(cfg)
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.act in ("silu", "geglu"):
        return {
            "w_gate": dense_init(ks[0], (d, ff), dt),
            "w_up": dense_init(ks[1], (d, ff), dt),
            "w_down": dense_init(ks[2], (ff, d), dt),
        }
    return {  # plain 2-matrix MLP (whisper)
        "w_up": dense_init(ks[0], (d, ff), dt),
        "b_up": jnp.zeros((ff,), dt),
        "w_down": dense_init(ks[1], (ff, d), dt),
        "b_down": jnp.zeros((d,), dt),
    }


def mlp(p, cfg: ModelConfig, x):
    if "w_gate" in p:
        act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
        h = act(x @ p["w_gate"]) * (x @ p["w_up"])
        h = shard_activation(h, ("batch", "seq", "mlp"))
        return h @ p["w_down"]
    h = jax.nn.gelu(x @ p["w_up"] + p["b_up"])
    h = shard_activation(h, ("batch", "seq", "mlp"))
    return h @ p["w_down"] + p["b_down"]


# ----------------------------------------------------------------------------
# MoE: top-k routing + sort-based capacity dispatch (Switch/GShard on TPU)
# ----------------------------------------------------------------------------

def moe_params(key, cfg: ModelConfig):
    dt = dtype_of(cfg)
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d, e), jnp.float32),
        "w_gate": dense_init(ks[1], (e, d, ff), dt),
        "w_up": dense_init(ks[2], (e, d, ff), dt),
        "w_down": dense_init(ks[3], (e, ff, d), dt),
    }


def moe_mlp(p, cfg: ModelConfig, x):
    """x: (B, S, d).  Tokens are routed to top-k experts; dispatch goes through
    a (G, E, C, d) capacity buffer where G is the number of *batch shards* —
    every sort / gather / scatter carries the sharded leading group dimension,
    so dispatch stays shard-local (a global argsort would force XLA to
    all-gather the whole token set per layer).  Expert GEMMs contract across
    groups with the EP-sharded weights; overflow beyond capacity is dropped
    per group (standard Switch behaviour)."""
    from repro.launch.sharding import num_batch_shards

    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    g = num_batch_shards()
    if b % g != 0:
        g = 1
    t = (b // g) * s                                   # tokens per group
    xt = x.reshape(g, t, d)
    logits = xt.astype(jnp.float32) @ p["router"]      # (G, T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)             # (G, T, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    cap = int(np.ceil(t * k / e * cfg.moe_capacity_factor))
    cap = max(cap, 1)
    # pin dispatch intermediates to group-sharding only: without constraints
    # SPMD is free to shard the token axis over "model", which turns every
    # local sort/gather/scatter into masked-gather + all-reduce
    xt = shard_activation(xt, ("data_group", None, "embed"))
    flat_e = top_e.reshape(g, t * k)
    flat_e = shard_activation(flat_e, ("data_group", None))
    flat_tok = jnp.broadcast_to(
        jnp.repeat(jnp.arange(t), k)[None, :], (g, t * k)
    )
    flat_w = top_p.reshape(g, t * k)
    order = jnp.argsort(flat_e, axis=-1)               # group by expert, per shard
    e_sorted = jnp.take_along_axis(flat_e, order, axis=-1)
    tok_sorted = jnp.take_along_axis(flat_tok, order, axis=-1)
    w_sorted = jnp.take_along_axis(flat_w, order, axis=-1)
    tok_sorted = shard_activation(tok_sorted, ("data_group", None))
    # position within expert block, per group
    pos = jnp.broadcast_to(jnp.arange(t * k)[None, :], (g, t * k))
    start = jax.vmap(lambda es: jnp.searchsorted(es, jnp.arange(e)))(e_sorted)
    pos_in_e = pos - jnp.take_along_axis(start, e_sorted, axis=-1)
    keep = pos_in_e < cap
    slot = jnp.where(keep, e_sorted * cap + pos_in_e, e * cap)  # drop -> scratch

    # row-gather via vmapped integer indexing: take_along_axis would broadcast
    # the index to (T*k, d) u32 — terabytes of index traffic at 235B scale
    gathered = jax.vmap(lambda xg, idx: xg[idx])(xt, tok_sorted)  # (G,T*k,d)
    gathered = shard_activation(gathered, ("data_group", None, "embed"))
    buf = jnp.zeros((g, e * cap + 1, d), x.dtype)
    buf = jax.vmap(lambda bf, sl, xv: bf.at[sl].set(xv))(buf, slot, gathered)
    buf = buf[:, : e * cap].reshape(g, e, cap, d)
    buf = shard_activation(buf, ("data_group", "expert", "capacity", "embed"))

    act = jax.nn.silu if cfg.act in ("silu", "geglu") else jax.nn.gelu
    h = act(jnp.einsum("gecd,edf->gecf", buf, p["w_gate"])) * jnp.einsum(
        "gecd,edf->gecf", buf, p["w_up"]
    )
    h = shard_activation(h, ("data_group", "expert", "capacity", "expert_mlp"))
    out_buf = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
    out_buf = shard_activation(out_buf, ("data_group", "expert", "capacity", "embed"))

    out_flat = out_buf.reshape(g, e * cap, d)
    out_flat = shard_activation(out_flat, ("data_group", None, "embed"))
    picked = jax.vmap(lambda og, idx: og[idx])(
        out_flat, jnp.minimum(slot, e * cap - 1)
    )
    contrib = jnp.where(keep[..., None], picked, 0.0) * w_sorted[..., None].astype(
        x.dtype
    )
    out = jnp.zeros((g, t, d), x.dtype)
    out = jax.vmap(lambda o, tk, c: o.at[tk].add(c))(out, tok_sorted, contrib)
    out = shard_activation(out, ("data_group", None, "embed"))
    return out.reshape(b, s, d)


# ----------------------------------------------------------------------------
# latent attention (MLA, DeepSeek-V2 §2.1), prefill form without weight
# absorption
# ----------------------------------------------------------------------------

def mla_params(key, cfg: ModelConfig):
    dt = dtype_of(cfg)
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, h * (dn + dr)), dt),
        "wkv_a": dense_init(ks[1], (d, r + dr), dt),
        "ln_kv": jnp.zeros((r,), jnp.float32),
        "wkv_b": dense_init(ks[2], (r, h * (dn + dv)), dt),
        "wo": dense_init(ks[3], (h * dv, d), dt),
    }


def mla_attention(p, cfg: ModelConfig, x, positions):
    """q = x Wq -> [q_nope, q_pe] per head; [c_kv, k_pe] = x Wkv_a, c_kv
    RMS-normed, [k_nope, v] = c_kv Wkv_b per head; RoPE (YaRN frequencies,
    half-split layout) on q_pe and on k_pe, one vector shared by all heads;
    causal softmax of [q_nope, q_pe] . [k_nope, k_pe] x (dn + dr)^-0.5 x
    m^2, where m = yarn_mscale(factor, mscale_all_dim).  YaRN's cos and sin
    scale, mscale(factor, mscale) / mscale(factor, mscale_all_dim), is 1:
    the published config sets the two equal."""
    b, s, _ = x.shape
    h, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q = (x @ p["wq"]).reshape(b, s, h, dn + dr)
    kv = x @ p["wkv_a"]
    c = rms_norm(kv[..., :r], p["ln_kv"], cfg.norm_eps)
    kvb = (c @ p["wkv_b"]).reshape(b, s, h, dn + dv)
    freqs = yarn_freqs(cfg)
    pos = positions[:, None, :]
    q_pe = apply_rope(q[..., dn:].swapaxes(1, 2), pos, cfg.rope_theta, freqs)
    k_pe = apply_rope(kv[:, None, :, r:], pos, cfg.rope_theta, freqs)
    q = jnp.concatenate([q[..., :dn], q_pe.swapaxes(1, 2)], axis=-1)
    k = jnp.concatenate(
        [kvb[..., :dn], jnp.broadcast_to(k_pe.swapaxes(1, 2), (b, s, h, dr))],
        axis=-1)
    m = yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    scale = (dn + dr) ** -0.5 * m * m
    out = _attend(q, k, kvb[..., dn:], positions, positions, True, 0, scale,
                  cfg.attn_q_chunk)
    return out.reshape(b, s, h * dv) @ p["wo"]


# ----------------------------------------------------------------------------
# dropless experts (DeepSeekMoE, §2.2): every routed token is computed, by one
# grouped matmul over the tokens sorted by expert
# ----------------------------------------------------------------------------

GMM_ROWS = 256      # rows per grouped-matmul tile (the kernel's m tile)


def grouped_moe_params(key, cfg: ModelConfig):
    """Router (d, E) and the routed experts' SwiGLU weights, each matrix a
    leaf of its own laid out (E, fan_in, fan_out)."""
    dt = dtype_of(cfg)
    d, ff, e = cfg.d_model, cfg.expert_d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d, e), dt),
        "w_gate": dense_init(ks[1], (e, d, ff), dt),
        "w_up": dense_init(ks[2], (e, d, ff), dt),
        "w_down": dense_init(ks[3], (e, ff, d), dt),
    }


def _gmm(lhs, rhs, sizes):
    """lhs (m, k) rows sorted by group, rhs (E, k, n), ``sizes`` (E,) rows
    of each group: megablox's grouped matmul, the kernel named ``gmm`` in
    the device trace.  Rows past ``sizes.sum()`` belong to no group and are
    never written."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from repro.kernels import interpret_mode

    k, n = rhs.shape[1:]
    tiling = (GMM_ROWS, 512 if k % 512 == 0 else k, 1024 if n % 1024 == 0 else n)
    return gmm(lhs, rhs, sizes, lhs.dtype, tiling, interpret=interpret_mode())


def grouped_rows(sizes) -> int:
    """Rows the grouped matmul computes for these group sizes (..., E):
    each non-empty group's rows rounded out to whole ``GMM_ROWS`` tiles, a
    tile shared by two groups computed once for each."""
    sizes = np.asarray(sizes, np.int64)
    ends = np.cumsum(sizes, axis=-1)
    starts = ends - sizes
    tiles = np.where(sizes > 0, -(-ends // GMM_ROWS) - starts // GMM_ROWS, 0)
    return int(tiles.sum()) * GMM_ROWS


def grouped_moe(p, cfg: ModelConfig, x, valid=None):
    """x: (B, S, d) -> (routed experts' output (B, S, d), (E,) int32 rows
    routed to each expert).

    The gate is a float32 softmax over all experts; each token takes its
    ``num_experts_per_tok`` largest (greedy), weighted by their gate values
    as they are (not renormalised).  No token is dropped: the (token,
    expert) routes are sorted by expert and every one is computed by one
    grouped matmul per SwiGLU matrix.  Tokens where ``valid`` (B, S) is
    False (padding) are routed to no expert and get 0."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    t = b * s
    xt = x.reshape(t, d)
    gate = jax.nn.softmax(
        jnp.dot(xt.astype(jnp.float32), p["router"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST), axis=-1)
    top_p, top_e = jax.lax.top_k(gate, k)                       # (T, k)
    keys = top_e.reshape(t * k)
    if valid is not None:
        keys = jnp.where(jnp.repeat(valid.reshape(t), k), keys, e)
    order = jnp.argsort(keys, stable=True)
    sizes = jnp.bincount(keys, length=e + 1)[:e].astype(jnp.int32)
    m = -(-t * k // GMM_ROWS) * GMM_ROWS
    rows = jnp.pad(xt[order // k], ((0, m - t * k), (0, 0)))
    act = jax.nn.silu(_gmm(rows, p["w_gate"], sizes)) * _gmm(rows, p["w_up"], sizes)
    out = _gmm(act, p["w_down"], sizes)[: t * k]
    out = jnp.where((jnp.arange(t * k) < sizes.sum())[:, None], out, 0)
    out = out[jnp.argsort(order)].reshape(t, k, d)
    y = (out.astype(jnp.float32) * top_p[..., None]).sum(axis=1)
    return y.astype(x.dtype).reshape(b, s, d), sizes
