"""The dense oracle family: a tied-embedding SwiGLU decoder with RoPE.

The reference computes it layer by layer in float32 at the highest matmul
precision (its control with every matmul in float8 e4m3).  Its work per
block of pairs: 2 x the non-embedding parameters per real token, causal
attention over each pair's real length, and a 2-column head at the last
real position.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def check(cfg) -> None:
    if cfg.family != "dense" or not cfg.tied_embeddings or cfg.act != "silu":
        raise ValueError(f"{cfg.name}: the reference knows a dense, tied, "
                         "SwiGLU decoder")


# ---------------------------------------------------------------------------
# the yes / no logits
# ---------------------------------------------------------------------------

def _fp8(a):
    """Round to float8 e4m3 with a per-tensor scale, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@functools.partial(jax.jit,
                   static_argnames=("heads", "kv_heads", "eps", "control"))
def _layer(x, p, cos, sin, mask, heads, kv_heads, eps, control):
    hp = jax.lax.Precision.HIGHEST
    q8 = _fp8 if control else (lambda a: a)

    def mm(a, b):
        return jnp.matmul(q8(a), q8(b), precision=hp)

    def norm(v, w):
        v = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps)
        return v * (1.0 + w)

    def rope(t):
        t1, t2 = jnp.split(t, 2, axis=-1)
        return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)

    b, s, _ = x.shape
    hd = cos.shape[-1] * 2
    a = norm(x, p["ln1"])
    q = rope(mm(a, p["attn"]["wq"]).reshape(b, s, heads, hd))
    k = rope(mm(a, p["attn"]["wk"]).reshape(b, s, kv_heads, hd))
    v = mm(a, p["attn"]["wv"]).reshape(b, s, kv_heads, hd)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    sc = jnp.einsum("bqhd,bkhd->bhqk", q8(q), q8(k), precision=hp) * hd**-0.5
    att = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", q8(att), q8(v),
                   precision=hp).reshape(b, s, heads * hd)
    x = x + mm(o, p["attn"]["wo"])
    m = norm(x, p["ln2"])
    return x + mm(jax.nn.silu(mm(m, p["mlp"]["w_gate"])) * mm(m, p["mlp"]["w_up"]),
                  p["mlp"]["w_down"])


def yes_no_logits(oracle: dict, params, toks, last, yes: int, no: int,
                  control: bool = False) -> np.ndarray:
    """(B, 2) float64 [yes, no] logits at each row's ``last`` position of a
    dense, tied-embedding, SwiGLU decoder with RoPE (``oracle`` is the
    configuration's oracle section), computed layer by layer in float32."""
    want = {"embed", "ln_f", "layers"}
    if set(params) != want or set(params["layers"]) != {"ln1", "ln2", "attn", "mlp"}:
        raise ValueError(f"oracle weights hold {sorted(params)}; the "
                         "reference knows a dense tied-embedding decoder")
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    toks = np.asarray(toks)
    b, s = toks.shape
    hd = oracle["head_dim"]
    pos = np.arange(s)
    inv = 1.0 / oracle["rope_theta"] ** (np.arange(0, hd, 2) / hd)
    cos = f32(np.cos(pos[:, None] * inv))[None, :, None, :]
    sin = f32(np.sin(pos[:, None] * inv))[None, :, None, :]
    mask = jnp.asarray(pos[:, None] >= pos[None, :])
    eps = float(oracle["norm_eps"])
    embed = f32(params["embed"])
    x = embed[jnp.asarray(toks)]
    for layer in range(oracle["num_layers"]):
        p = jax.tree.map(lambda a: f32(a[layer]), params["layers"])
        x = _layer(x, p, cos, sin, mask, heads=oracle["num_heads"],
                   kv_heads=oracle["num_kv_heads"], eps=eps, control=control)
    h = x[jnp.arange(b), jnp.asarray(last)]
    h = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + eps)
    h = h * (1.0 + f32(params["ln_f"]))
    head = embed[jnp.asarray([yes, no])]
    if control:
        h, head = _fp8(h), _fp8(head)
    lg = jnp.matmul(h, head.T, precision=jax.lax.Precision.HIGHEST)
    return np.asarray(lg, np.float64)


# ---------------------------------------------------------------------------
# the work of scoring
# ---------------------------------------------------------------------------

def dense_params(o: dict) -> int:
    d, hd = o["d_model"], o["head_dim"]
    attn = d * hd * (2 * o["num_heads"] + 2 * o["num_kv_heads"])
    return o["num_layers"] * (attn + 3 * d * o["d_ff"])


def required_flops(o: dict, lens) -> float:
    lens = np.asarray(lens, np.float64)
    per_tok = 2.0 * dense_params(o)
    attn = 4.0 * o["num_layers"] * o["num_heads"] * o["head_dim"] * (
        lens * (lens + 1) / 2)
    head = 2.0 * o["d_model"] * 2
    return float(per_tok * lens.sum() + attn.sum() + head * len(lens))


def required_bytes(o: dict, lens) -> float:
    """bf16 weights read once per block, the real tokens' embedding rows
    and the two head rows."""
    return 2.0 * (dense_params(o) + (float(np.sum(lens)) + 2) * o["d_model"])
