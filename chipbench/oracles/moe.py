"""The moe oracle family: DeepSeek-V2's block of latent attention (MLA, no
query LoRA) with YaRN RoPE, leading dense SwiGLU layers, then DeepSeekMoE
layers of routed experts (a float32 softmax gate, greedy top-k, weights not
renormalised) beside shared experts, and an untied head.

The reference computes it layer by layer in float32 at the highest matmul
precision: the router in float32, and each routed expert on exactly the
tokens routed to it, gathered per expert (in chunks of ``ROWS`` rows, the
last padded with rows whose output is dropped, so that one shape
compiles), its weights cast to float32 one expert at a time.  Its control
runs every matmul but the router's in float8 e4m3.  Its work per block of
pairs: 2 x the active non-embedding matmul parameters per real token (the
dense layers; per MoE layer the MLA projections, the router, the shared
experts and ``num_experts_per_tok`` routed ones), causal attention over
each pair's real length, and a 2-column head at the last real position.

Departures from the published model, in the program and here alike: norms
are ``v * (1 + w)`` (the repo's parametrisation) where the checkpoint has
``w * v``; RoPE takes the half-split ``rotate_half`` layout, a fixed
permutation of the checkpoint's interleaved rope columns of ``Wq`` and
``Wkv_a``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 1024     # an expert's gathered rows go through in chunks of this many


def check(cfg) -> None:
    if cfg.family != "moe" or not cfg.kv_lora_rank:
        raise ValueError(f"{cfg.name}: chipbench/oracles/moe.py knows an MoE "
                         "with latent attention (DeepSeek-V2), not this one")
    if cfg.tied_embeddings or cfg.act != "silu" or not cfg.num_experts:
        raise ValueError(f"{cfg.name}: chipbench/oracles/moe.py knows routed "
                         "SwiGLU experts and an untied head")


# ---------------------------------------------------------------------------
# the yes / no logits
# ---------------------------------------------------------------------------

def _fp8(a):
    """Round to float8 e4m3 with a per-tensor scale, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(control):
    q8 = _fp8 if control else (lambda a: a)
    return q8, lambda a, b: jnp.matmul(q8(a), q8(b),
                                       precision=jax.lax.Precision.HIGHEST)


def _norm(v, w, eps):
    v = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps)
    return v * (1.0 + w)


def yarn(o: dict):
    """(inverse frequencies of the rope dims, softmax scale factor m^2).
    YaRN's scale of cos and sin is 1 where mscale equals mscale_all_dim,
    as the published config has them (the configuration's test)."""
    dim, base = o["qk_rope_head_dim"], float(o["rope_theta"])
    f = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    factor = float(o.get("rope_factor", 0.0))
    if not factor:
        return f, 1.0

    def corr(rot):
        return (dim * np.log(o["rope_original_max_len"] / (rot * 2 * np.pi))
                / (2 * np.log(base)))

    low = max(int(np.floor(corr(o["rope_beta_fast"]))), 0)
    high = min(int(np.ceil(corr(o["rope_beta_slow"]))), dim - 1)
    r = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)

    ms = o["rope_mscale_all_dim"]
    m = 0.1 * ms * np.log(factor) + 1.0 if factor > 1 and ms else 1.0
    return f * (1 - r) + f / factor * r, m * m


def _layer(stack, layer):
    """Layer ``layer`` of a stacked weight tree, in float32."""
    return jax.tree.map(lambda a: a[layer].astype(jnp.float32), stack)


@functools.partial(jax.jit, static_argnames=("o", "control"))
def _attention(x, stack, layer, cos, sin, mask, o, control):
    """x + MLA(norm(x)) of layer ``layer`` of ``stack``."""
    q8, mm = _mm(control)
    p = _layer({"ln1": stack["ln1"], "attn": stack["attn"]}, layer)
    o = dict(o)
    h, r, eps = o["num_heads"], o["kv_lora_rank"], o["norm_eps"]
    dn, dr, dv = o["qk_nope_head_dim"], o["qk_rope_head_dim"], o["v_head_dim"]

    def rope(t):
        t1, t2 = jnp.split(t, 2, axis=-1)
        return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)

    b, s, _ = x.shape
    a = _norm(x, p["ln1"], eps)
    q = mm(a, p["attn"]["wq"]).reshape(b, s, h, dn + dr)
    kv = mm(a, p["attn"]["wkv_a"])
    c = _norm(kv[..., :r], p["attn"]["ln_kv"], eps)
    kvb = mm(c, p["attn"]["wkv_b"]).reshape(b, s, h, dn + dv)
    k_pe = jnp.broadcast_to(rope(kv[:, :, None, r:]), (b, s, h, dr))
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:])], -1)
    k = jnp.concatenate([kvb[..., :dn], k_pe], -1)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q8(q), q8(k),
                    precision=jax.lax.Precision.HIGHEST) * (
                        (dn + dr) ** -0.5 * o["m2"])
    att = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", q8(att), q8(kvb[..., dn:]),
                     precision=jax.lax.Precision.HIGHEST).reshape(b, s, h * dv)
    return x + mm(out, p["attn"]["wo"])


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _swiglu(x, ln, mlp, layer, eps, control):
    """(norm(x), SwiGLU of it) with layer ``layer`` of the stacked ``ln``
    and ``mlp``."""
    _, mm = _mm(control)
    p = _layer(mlp, layer)
    m = _norm(x, ln[layer].astype(jnp.float32), eps)
    return m, mm(jax.nn.silu(mm(m, p["w_gate"])) * mm(m, p["w_up"]), p["w_down"])


@jax.jit
def _gate(m, router, layer):
    """The float32 softmax gate, in float32 whatever the control."""
    return jax.nn.softmax(jnp.matmul(m, router[layer].astype(jnp.float32),
                                     precision=jax.lax.Precision.HIGHEST), -1)


@functools.partial(jax.jit, static_argnames=("control",))
def _expert(y, m, idx, w, moe, layer, ex, control):
    """y[idx] += w x SwiGLU_e(m[idx]) for expert ``ex`` of MoE layer
    ``layer``, its bf16 weights (``moe``'s stacked leaves) cast to float32
    here; an ``idx`` past the last row pads and is dropped."""
    _, mm = _mm(control)
    wg, wu, wd = (moe[k][layer, ex].astype(jnp.float32)
                  for k in ("w_gate", "w_up", "w_down"))
    rows = m[jnp.minimum(idx, m.shape[0] - 1)]
    out = mm(jax.nn.silu(mm(rows, wg)) * mm(rows, wu), wd)
    return y.at[idx].add(w[:, None] * out, mode="drop")


def yes_no_logits(oracle: dict, params, toks, last, yes: int, no: int,
                  control: bool = False) -> np.ndarray:
    """(B, 2) float64 [yes, no] logits at each row's ``last`` position,
    computed layer by layer in float32 (``oracle`` is the configuration's
    oracle section).  Rows are padded to ``max_len`` columns, so that every
    block runs the same few compiled shapes: attention is causal, and
    columns after ``last`` change nothing at it."""
    want = {"embed", "ln_f", "head", "layers"} | (
        {"dense_layers"} if oracle.get("first_k_dense") else set())
    if set(params) != want or "moe" not in params["layers"]:
        raise ValueError(f"oracle weights hold {sorted(params)}; "
                         "chipbench/oracles/moe.py knows a dense-then-MoE "
                         "latent-attention decoder")
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    toks = np.asarray(toks)
    b, s = toks.shape[0], max(int(oracle["max_len"]), toks.shape[1])
    toks = np.pad(toks, ((0, 0), (0, s - toks.shape[1])))
    inv, m2 = yarn(oracle)
    pos = np.arange(s)
    cos = f32(np.cos(pos[:, None] * inv))[None, :, None, :]
    sin = f32(np.sin(pos[:, None] * inv))[None, :, None, :]
    mask = jnp.asarray(pos[:, None] >= pos[None, :])
    eps = float(oracle["norm_eps"])
    keys = ("num_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim")
    o = tuple(sorted({**{k: oracle[k] for k in keys}, "norm_eps": eps,
                      "m2": float(m2)}.items()))
    e, k = oracle["num_experts"], oracle["num_experts_per_tok"]

    x = f32(jnp.asarray(params["embed"])[jnp.asarray(toks)])
    for name in ("dense_layers", "layers"):
        stack = params.get(name)
        if stack is None:
            continue
        for layer in range(jax.tree.leaves(stack)[0].shape[0]):
            i = np.int32(layer)
            x = _attention(x, stack, i, cos, sin, mask, o=o, control=control)
            if name == "dense_layers":
                _, y = _swiglu(x, stack["ln2"], stack["mlp"], i, eps=eps,
                               control=control)
                x = x + y
                continue
            if "shared" in stack:
                m, y = _swiglu(x, stack["ln2"], stack["shared"], i, eps=eps,
                               control=control)
            else:
                m = _norm(x, f32(stack["ln2"][layer]), eps)
                y = jnp.zeros_like(x)
            m, y = m.reshape(b * s, -1), y.reshape(b * s, -1)
            mp = stack["moe"]
            top_p, top_e = (np.asarray(a) for a in
                            jax.lax.top_k(_gate(m, mp["router"], i), k))
            experts = {key: mp[key] for key in ("w_gate", "w_up", "w_down")}
            for ex in range(e):
                tok, slot = np.nonzero(top_e == ex)
                for c in range(0, len(tok), ROWS):
                    idx = np.full(ROWS, b * s, np.int32)
                    w = np.zeros(ROWS, np.float32)
                    n = min(ROWS, len(tok) - c)
                    idx[:n] = tok[c:c + n]
                    w[:n] = top_p[tok[c:c + n], slot[c:c + n]]
                    y = _expert(y, m, jnp.asarray(idx), jnp.asarray(w), experts,
                                i, np.int32(ex), control=control)
            x = x + y.reshape(b, s, -1)
    h = x[jnp.arange(b), jnp.asarray(last)]
    h = _norm(h, f32(params["ln_f"]), eps)
    head = f32(jnp.asarray(params["head"])[:, jnp.asarray([yes, no])])
    if control:
        h, head = _fp8(h), _fp8(head)
    lg = jnp.matmul(h, head, precision=jax.lax.Precision.HIGHEST)
    return np.asarray(lg, np.float64)


# ---------------------------------------------------------------------------
# the work of scoring
# ---------------------------------------------------------------------------

def attention_params(o: dict) -> int:
    d, h, r = o["d_model"], o["num_heads"], o["kv_lora_rank"]
    dn, dr, dv = o["qk_nope_head_dim"], o["qk_rope_head_dim"], o["v_head_dim"]
    return d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d


def active_params(o: dict) -> int:
    """Matmul parameters one token passes through: every layer's MLA
    projections; the dense layers' SwiGLU; per MoE layer the router, the
    shared experts and ``num_experts_per_tok`` routed ones."""
    d = o["d_model"]
    lead = o.get("first_k_dense", 0)
    expert = 3 * d * o["moe_d_ff"]
    moe = (d * o["num_experts"]
           + (o.get("num_shared_experts", 0) + o["num_experts_per_tok"]) * expert)
    return (o["num_layers"] * attention_params(o) + lead * 3 * d * o["d_ff"]
            + (o["num_layers"] - lead) * moe)


def held_params(o: dict) -> int:
    """Matmul parameters the stage holds: every routed expert, and the
    embedding and head rows aside."""
    d = o["d_model"]
    lead = o.get("first_k_dense", 0)
    unused = (o["num_experts"] - o["num_experts_per_tok"]) * 3 * d * o["moe_d_ff"]
    return active_params(o) + (o["num_layers"] - lead) * unused


def required_flops(o: dict, lens) -> float:
    lens = np.asarray(lens, np.float64)
    per_tok = 2.0 * active_params(o)
    per_pair = 2 * o["num_heads"] * (o["qk_nope_head_dim"] + o["qk_rope_head_dim"]
                                     + o["v_head_dim"])
    attn = 1.0 * o["num_layers"] * per_pair * (lens * (lens + 1) / 2)
    head = 2.0 * o["d_model"] * 2
    return float(per_tok * lens.sum() + attn.sum() + head * len(lens))


def required_bytes(o: dict, lens) -> float:
    """The stage's bf16 weights read once per block (at these token counts
    every expert is hit), the real tokens' embedding rows and the two head
    rows."""
    return 2.0 * (held_params(o) + (float(np.sum(lens)) + 2) * o["d_model"])
