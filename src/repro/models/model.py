"""Model assembly: decoder LMs (dense / MoE / VLM), RWKV6, RecurrentGemma-style
hybrids, and the Whisper-style encoder-decoder — all with scan-over-layers so
the HLO is O(1) in depth, with a uniform interface:

    init_params(cfg, key)                          -> params
    forward(cfg, params, batch)                    -> logits        (train/prefill)
    last_position_logits(cfg, params, tokens, last, cols, valid)
                                                   -> (logits, aux) (scoring)
    loss_fn(cfg, params, batch)                    -> scalar loss   (next-token CE)
    init_cache(cfg, batch, max_len)                -> cache
    decode_step(cfg, params, cache, tokens, pos)   -> (logits, cache)

``batch`` is a dict: {"tokens": (B, S)} plus, per modality,
{"frames": (B, T_enc, d)} (audio stub) or {"patches": (B, P, d)} (vision stub).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.sharding import shard_activation
from .config import ModelConfig
from .layers import (
    attention_params,
    chunked_attention,
    decode_attention,
    dense_init,
    dtype_of,
    grouped_moe,
    grouped_moe_params,
    mla_attention,
    mla_params,
    mlp,
    mlp_params,
    moe_mlp,
    moe_params,
    rms_norm,
)
from .recurrent import (
    rglru_mix,
    rglru_params,
    rglru_state_init,
    rwkv_channel_mix,
    rwkv_params,
    rwkv_state_init,
    rwkv_time_mix,
)


# ----------------------------------------------------------------------------
# per-layer init / apply
# ----------------------------------------------------------------------------

def _layer_params(key, cfg: ModelConfig, kind: str):
    dt = dtype_of(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p = {"ln1": jnp.zeros((d,), jnp.float32), "ln2": jnp.zeros((d,), jnp.float32)}
    if kind == "dense":
        p["attn"] = attention_params(ks[0], cfg)
        p["mlp"] = mlp_params(ks[1], cfg)
    elif kind == "moe":
        p["attn"] = attention_params(ks[0], cfg)
        p["moe"] = moe_params(ks[1], cfg)
    elif kind == "attn":  # hybrid local-attention block
        p["attn"] = attention_params(ks[0], cfg)
        p["mlp"] = mlp_params(ks[1], cfg)
    elif kind == "rec":
        p["rec"] = rglru_params(ks[0], cfg)
        p["mlp"] = mlp_params(ks[1], cfg)
    elif kind == "rwkv":
        p.update(rwkv_params(ks[0], cfg))
    elif kind == "enc":
        p["attn"] = attention_params(ks[0], cfg, bias=False)
        p["mlp"] = mlp_params(ks[1], cfg)
    elif kind == "dec":  # decoder layer with cross-attention
        p["attn"] = attention_params(ks[0], cfg, bias=False)
        p["xattn"] = attention_params(ks[1], cfg, bias=False)
        p["lnx"] = jnp.zeros((d,), jnp.float32)
        p["mlp"] = mlp_params(ks[2], cfg)
    else:
        raise ValueError(kind)
    return p


def _apply_layer(cfg: ModelConfig, kind: str, p, x, positions, state=None,
                 enc_out=None, enc_positions=None):
    """Full-sequence layer application.  Returns (x, new_state)."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    new_state = state
    if kind in ("dense", "moe", "attn", "enc", "dec"):
        window = cfg.window
        causal = cfg.causal and kind != "enc"
        a = chunked_attention(
            p["attn"], cfg, h, positions, causal=causal, window=window,
            use_rope=(cfg.family != "encdec"),
        )
        x = x + a
        if kind == "dec":
            hx = rms_norm(x, p["lnx"], cfg.norm_eps)
            xa = chunked_attention(
                p["xattn"], cfg, hx, positions, kv_x=enc_out,
                kv_positions=enc_positions, causal=False, use_rope=False,
            )
            x = x + xa
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        if kind == "moe":
            x = x + moe_mlp(p["moe"], cfg, h2)
        else:
            x = x + mlp(p["mlp"], cfg, h2)
    elif kind == "rec":
        out, h_t, conv = rglru_mix(p["rec"], cfg, h, state["h"], state["conv"])
        x = x + out
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + mlp(p["mlp"], cfg, h2)
        new_state = {"h": h_t, "conv": conv}
    elif kind == "rwkv":
        out, s, last_t = rwkv_time_mix(p["time"], cfg, h, state["s"], state["last_time"])
        x = x + out
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        out2, last_c = rwkv_channel_mix(p["channel"], cfg, h2, state["last_chan"])
        x = x + out2
        new_state = {"s": s, "last_time": last_t, "last_chan": last_c}
    else:
        raise ValueError(kind)
    x = shard_activation(x, ("batch", "seq", "embed"))
    return x, new_state


# ----------------------------------------------------------------------------
# params
# ----------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key) -> dict:
    dt = dtype_of(cfg)
    keys = jax.random.split(key, 8)
    params: dict = {
        "embed": dense_init(keys[0], (cfg.vocab_size, cfg.d_model), dt, scale=0.02),
        "ln_f": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    if not cfg.tied_embeddings:
        params["head"] = dense_init(keys[1], (cfg.d_model, cfg.vocab_size), dt)

    types = cfg.layer_types()
    if cfg.kv_lora_rank:
        lead = types.count("dense")
        if lead:
            params["dense_layers"] = jax.vmap(
                lambda k: _mla_layer_params(k, cfg, "dense"))(
                    jax.random.split(keys[2], lead))
        params["layers"] = jax.vmap(lambda k: _mla_layer_params(k, cfg, "moe"))(
            jax.random.split(keys[3], len(types) - lead))
    elif cfg.family == "hybrid":
        pat = cfg.pattern
        nb = cfg.num_layers // len(pat)
        tail = types[nb * len(pat):]

        def init_block(k):
            ks = jax.random.split(k, len(pat))
            return {f"l{i}_{kind}": _layer_params(ks[i], cfg, kind)
                    for i, kind in enumerate(pat)}

        params["blocks"] = jax.vmap(init_block)(jax.random.split(keys[2], nb))
        params["tail"] = [
            _layer_params(k, cfg, kind)
            for k, kind in zip(jax.random.split(keys[3], max(len(tail), 1)), tail)
        ]
    elif cfg.family == "encdec":
        params["enc"] = jax.vmap(lambda k: _layer_params(k, cfg, "enc"))(
            jax.random.split(keys[2], cfg.encoder_layers)
        )
        params["layers"] = jax.vmap(lambda k: _layer_params(k, cfg, "dec"))(
            jax.random.split(keys[3], cfg.num_layers)
        )
        params["ln_enc"] = jnp.zeros((cfg.d_model,), jnp.float32)
    else:
        kind = types[0]
        params["layers"] = jax.vmap(lambda k: _layer_params(k, cfg, kind))(
            jax.random.split(keys[2], cfg.num_layers)
        )
    if cfg.num_patches:
        params["patch_proj"] = dense_init(keys[4], (cfg.d_model, cfg.d_model), dt)
    return params


# ----------------------------------------------------------------------------
# forward (train / prefill)
# ----------------------------------------------------------------------------

def _sinusoidal(seq: int, d: int):
    pos = np.arange(seq)[:, None]
    i = np.arange(d // 2)[None, :]
    ang = pos / (10000 ** (2 * i / d))
    return jnp.asarray(
        np.concatenate([np.sin(ang), np.cos(ang)], axis=-1), jnp.float32
    )


def _embed_inputs(cfg: ModelConfig, params, batch):
    """Token (+ modality stub) embedding -> (x, positions)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = params["embed"][tokens]
    if cfg.num_patches and "patches" in batch:
        patches = batch["patches"].astype(x.dtype) @ params["patch_proj"]
        x = jnp.concatenate([patches, x], axis=1)
    positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None, :], (b, x.shape[1]))
    x = shard_activation(x, ("batch", "seq", "embed"))
    return x, positions


def _run_stack(cfg, stacked, x, positions, kind, enc_out=None, enc_positions=None,
               init_state_fn=None, scope="layers_scan"):
    """Scan over a stacked layer tree; heterogeneous state threaded through."""
    b = x.shape[0]

    def body(carry, layer_p):
        h = carry
        if init_state_fn is not None:
            st = init_state_fn(cfg, b)
        else:
            st = None
        h, _ = _apply_layer(cfg, kind, layer_p, h, positions, state=st,
                            enc_out=enc_out, enc_positions=enc_positions)
        return h, ()

    fn = body
    if cfg.remat:
        policy = (
            jax.checkpoint_policies.checkpoint_dots
            if cfg.remat_policy == "dots" else None
        )
        fn = jax.checkpoint(body, prevent_cse=False, policy=policy)
    with jax.named_scope(scope):
        x, _ = jax.lax.scan(fn, x, stacked)
    return x


# ----------------------------------------------------------------------------
# the latent-attention family (DeepSeek-V2): leading dense layers, then the
# MoE layers, each stack scanned
# ----------------------------------------------------------------------------

def _mla_layer_params(key, cfg: ModelConfig, kind: str):
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    p = {"ln1": jnp.zeros((d,), jnp.float32), "ln2": jnp.zeros((d,), jnp.float32),
         "attn": mla_params(ks[0], cfg)}
    if kind == "dense":
        p["mlp"] = mlp_params(ks[1], cfg)
        return p
    p["moe"] = grouped_moe_params(ks[1], cfg)
    if cfg.num_shared_experts:
        p["shared"] = mlp_params(ks[2], cfg,
                                 d_ff=cfg.num_shared_experts * cfg.expert_d_ff)
    return p


def _mla_layer(cfg: ModelConfig, kind: str, p, x, positions, valid):
    """x + MLA(norm(x)), then + SwiGLU (dense) or + routed experts + the
    shared experts (one SwiGLU of their summed width).  Returns (x, rows
    routed to each expert, or None)."""
    x = x + mla_attention(p["attn"], cfg, rms_norm(x, p["ln1"], cfg.norm_eps),
                          positions)
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if kind == "dense":
        return x + mlp(p["mlp"], cfg, h), None
    routed, sizes = grouped_moe(p["moe"], cfg, h, valid)
    if "shared" in p:
        routed = routed + mlp(p["shared"], cfg, h)
    return x + routed, sizes


def _mla_stack(cfg: ModelConfig, params, tokens, valid=None):
    """The residual stream after every layer, and the (MoE layers, E) rows
    routed to each expert.  Tokens where ``valid`` is False are routed to no
    expert."""
    x, positions = _embed_inputs(cfg, params, {"tokens": tokens})
    sizes = None
    for stack, kind in (("dense_layers", "dense"), ("layers", "moe")):
        if stack not in params:
            continue

        def body(h, p, kind=kind):
            return _mla_layer(cfg, kind, p, h, positions, valid)

        fn = jax.checkpoint(body, prevent_cse=False) if cfg.remat else body
        with jax.named_scope("layers_scan"):
            x, out = jax.lax.scan(fn, x, params[stack])
        if kind == "moe":
            sizes = out
    return x, sizes


def _residual(cfg: ModelConfig, params, batch, valid=None):
    """The residual stream after the last layer, before the final norm
    (B, S_total, d), and the (MoE layers, E) rows routed to each expert of
    the latent-attention family (``None`` for the others).  ``valid`` marks
    the tokens the experts see."""
    if cfg.family == "encdec":
        return _encdec_residual(cfg, params, batch), None
    if cfg.kv_lora_rank:
        return _mla_stack(cfg, params, batch["tokens"], valid)
    x, positions = _embed_inputs(cfg, params, batch)
    if cfg.family == "hybrid":
        x = _forward_hybrid(cfg, params, x, positions)
    elif cfg.family == "ssm":
        x = _run_stack(cfg, params["layers"], x, positions, "rwkv",
                       init_state_fn=rwkv_state_init)
    else:
        x = _run_stack(cfg, params["layers"], x, positions,
                       cfg.layer_types()[0])
    return x, None


def _head(cfg: ModelConfig, params, cols=None):
    """The (d, V) vocabulary head, or its columns ``cols`` only."""
    if cols is None:
        return params["embed"].T if cfg.tied_embeddings else params["head"]
    cols = jnp.asarray(cols)
    if cfg.tied_embeddings:
        return params["embed"][cols].T
    return params["head"][:, cols]


def last_position_logits(cfg: ModelConfig, params, tokens, last, cols, valid):
    """float32 logits of the vocabulary columns ``cols`` at each row's
    ``last`` position (B, len(cols)), and the rows routed to each expert per
    MoE layer (``None`` for a family without experts): the scoring program.
    The final norm works per position, so the residual stream is gathered at
    ``last`` first and the head applied to one position and to the columns
    asked for only.  ``valid`` (B, L) routes padding to no expert; causal
    attention already keeps a row's trailing padding out of ``last``."""
    x, sizes = _residual(cfg, params, {"tokens": tokens}, valid)
    h = rms_norm(x[jnp.arange(x.shape[0]), last], params["ln_f"], cfg.norm_eps)
    return jnp.dot(h, _head(cfg, params, cols),
                   preferred_element_type=jnp.float32), sizes


def forward(cfg: ModelConfig, params, batch) -> jax.Array:
    """Full-sequence forward -> logits (B, S_total, V)."""
    x, _ = _residual(cfg, params, batch)
    logits = rms_norm(x, params["ln_f"], cfg.norm_eps) @ _head(cfg, params)
    if cfg.family == "encdec" or cfg.kv_lora_rank:
        return logits  # these two families' logits carry no sharding constraint
    return shard_activation(logits, ("batch", "seq", "vocab"))


def _forward_hybrid(cfg: ModelConfig, params, x, positions):
    pat = cfg.pattern
    b = x.shape[0]

    def block_body(carry, block_p):
        h = carry
        for i, kind in enumerate(pat):
            st = rglru_state_init(cfg, b) if kind == "rec" else None
            h, _ = _apply_layer(cfg, kind, block_p[f"l{i}_{kind}"], h, positions, state=st)
        return h, ()

    if cfg.remat:
        policy = (
            jax.checkpoint_policies.checkpoint_dots
            if cfg.remat_policy == "dots" else None
        )
        fn = jax.checkpoint(block_body, prevent_cse=False, policy=policy)
    else:
        fn = block_body
    with jax.named_scope("layers_scan"):
        x, _ = jax.lax.scan(fn, x, params["blocks"])
    types = cfg.layer_types()
    tail = types[(cfg.num_layers // len(pat)) * len(pat):]
    for p, kind in zip(params["tail"], tail):
        st = rglru_state_init(cfg, b) if kind == "rec" else None
        x, _ = _apply_layer(cfg, kind, p, x, positions, state=st)
    return x


def _encdec_residual(cfg: ModelConfig, params, batch):
    frames = batch["frames"]  # (B, T_enc, d) precomputed conv-frontend output
    b, t_enc, _ = frames.shape
    enc_x = frames.astype(dtype_of(cfg)) + _sinusoidal(t_enc, cfg.d_model).astype(
        dtype_of(cfg)
    )
    enc_pos = jnp.broadcast_to(jnp.arange(t_enc)[None, :], (b, t_enc))
    with jax.named_scope("enc"):
        enc_x = _run_stack(cfg, params["enc"], enc_x, enc_pos, "enc",
                           scope="encoder_scan")
    enc_x = rms_norm(enc_x, params["ln_enc"], cfg.norm_eps)

    tokens = batch["tokens"]
    s = tokens.shape[1]
    x = params["embed"][tokens] + _sinusoidal(s, cfg.d_model).astype(dtype_of(cfg))
    pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    x = _run_stack(cfg, params["layers"], x, pos, "dec", enc_out=enc_x,
                   enc_positions=enc_pos)
    return x


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _ce_bf16(logits, targets, _dt):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def _ce_bf16_fwd(logits, targets, _dt):
    return _ce_bf16(logits, targets, _dt), (logits, targets)


def _ce_bf16_bwd(_dt, res, g):
    logits, targets = res
    # softmax recomputed in f32; the cotangent leaving the CE is cast to the
    # model dtype so the entire transformer backward runs in bf16 (the f32
    # upcast of a straightforward CE otherwise poisons every dgrad/collective)
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    onehot = jax.nn.one_hot(targets, logits.shape[-1], dtype=jnp.float32)
    d = (p - onehot) * g[..., None]
    return (d.astype(_dt), None)


_ce_bf16.defvjp(_ce_bf16_fwd, _ce_bf16_bwd)


def loss_fn(cfg: ModelConfig, params, batch) -> jax.Array:
    """Next-token cross entropy over the text positions."""
    logits = forward(cfg, params, batch)
    tokens = batch["tokens"]
    if cfg.num_patches and "patches" in batch:
        logits = logits[:, batch["patches"].shape[1]:, :]
    targets = tokens[:, 1:]
    logits = logits[:, :-1, :]
    if cfg.bf16_backward:
        nll = _ce_bf16(logits, targets, logits.dtype)
    else:
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    if mask is not None:
        mask = mask[:, 1:]
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()


# ----------------------------------------------------------------------------
# decode (serving)
# ----------------------------------------------------------------------------

def _no_latent_cache(cfg: ModelConfig, what: str) -> None:
    if cfg.kv_lora_rank:
        raise NotImplementedError(
            f"{what}: {cfg.name} ({cfg.family} family, latent attention) has no "
            "decode cache; it serves only by scoring (last_position_logits)")


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    _no_latent_cache(cfg, "init_cache")
    dt = dtype_of(cfg)
    nkv, hd = cfg.num_kv_heads, cfg.head_dim
    if cfg.family == "ssm":
        st = rwkv_state_init(cfg, batch)
        return {
            "state": jax.tree.map(
                lambda a: jnp.broadcast_to(a, (cfg.num_layers,) + a.shape), st
            )
        }
    if cfg.family == "hybrid":
        pat = cfg.pattern
        nb = cfg.num_layers // len(pat)
        w = min(cfg.window if cfg.window else max_len, max_len)
        block = {}
        for i, kind in enumerate(pat):
            if kind == "rec":
                st = rglru_state_init(cfg, batch)
                block[f"l{i}_state"] = jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (nb,) + a.shape), st
                )
            else:
                block[f"l{i}_k"] = jnp.zeros((nb, batch, w, nkv, hd), dt)
                block[f"l{i}_v"] = jnp.zeros((nb, batch, w, nkv, hd), dt)
        tail_types = cfg.layer_types()[nb * len(pat):]
        tail = []
        for kind in tail_types:
            if kind == "rec":
                tail.append({"state": rglru_state_init(cfg, batch)})
            else:
                tail.append({
                    "k": jnp.zeros((batch, w, nkv, hd), dt),
                    "v": jnp.zeros((batch, w, nkv, hd), dt),
                })
        return {"blocks": block, "tail": tail}
    cache = {
        "k": jnp.zeros((cfg.num_layers, batch, max_len, nkv, hd), dt),
        "v": jnp.zeros((cfg.num_layers, batch, max_len, nkv, hd), dt),
    }
    if cfg.family == "encdec":
        # encoder seq padded to a sharding-friendly multiple; the decode
        # cross-attention masks positions >= cfg.encoder_seq
        t_enc = -(-cfg.encoder_seq // 64) * 64
        cache["xk"] = jnp.zeros((cfg.num_layers, batch, t_enc, nkv, hd), dt)
        cache["xv"] = jnp.zeros((cfg.num_layers, batch, t_enc, nkv, hd), dt)
    return cache


def _decode_layer_attn(cfg, p, x, k_cache, v_cache, position, window=0,
                       use_rope=True, xk=None, xv=None):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    a, k_cache, v_cache = decode_attention(
        p["attn"], cfg, h, k_cache, v_cache, position, window=window,
        use_rope=use_rope,
    )
    x = x + a
    if xk is not None:  # cross-attention over precomputed encoder KV
        hx = rms_norm(x, p["lnx"], cfg.norm_eps)
        b = hx.shape[0]
        q = (hx @ p["xattn"]["wq"]).reshape(b, 1, cfg.num_heads, cfg.head_dim)
        from .layers import _grouped_out, _grouped_scores

        scores = _grouped_scores(q, xk) * cfg.head_dim**-0.5
        valid = jnp.arange(xk.shape[1]) < cfg.encoder_seq  # mask cache padding
        scores = jnp.where(valid[None, None, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        out = _grouped_out(probs, xv).reshape(b, 1, -1) @ p["xattn"]["wo"]
        x = x + out
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        x = x + moe_mlp(p["moe"], cfg, h2)
    else:
        x = x + mlp(p["mlp"], cfg, h2)
    return x, k_cache, v_cache


def decode_step(cfg: ModelConfig, params, cache: dict, tokens, position):
    """One decode step.  tokens: (B, 1) int32; position: scalar int32 (same
    for the whole batch) or, when ``cfg.has_positional_cache``, (B,) int32
    per-slot positions — continuous batching passes the latter so slots
    admitted mid-flight rewind to position 0 without attending to a previous
    occupant's stale KV entries.  Families without a positional cache only
    support the scalar form; their batcher gates admission instead.
    Returns (logits (B, V), cache)."""
    _no_latent_cache(cfg, "decode_step")
    x = params["embed"][tokens]
    b = x.shape[0]

    if cfg.family == "ssm":
        def body(carry, inp):
            h = carry
            layer_p, st = inp
            h, new_st = _apply_layer(cfg, "rwkv", layer_p, h, None, state=st)
            return h, new_st

        with jax.named_scope("layers_scan"):
            x, new_states = jax.lax.scan(body, x, (params["layers"], cache["state"]))
        cache = {"state": new_states}
    elif cfg.family == "hybrid":
        x, cache = _decode_hybrid(cfg, params, cache, x, position)
    elif cfg.family == "encdec":
        def body(carry, inp):
            h = carry
            layer_p, kc, vc, xk, xv = inp
            h, kc, vc = _decode_layer_attn(
                cfg, layer_p, h, kc, vc, position, use_rope=False, xk=xk, xv=xv)
            return h, (kc, vc)

        with jax.named_scope("layers_scan"):
            x, (nk, nv) = jax.lax.scan(
                body, x, (params["layers"], cache["k"], cache["v"], cache["xk"], cache["xv"]))
        cache = dict(cache, k=nk, v=nv)
    else:
        def body(carry, inp):
            h = carry
            layer_p, kc, vc = inp
            h, kc, vc = _decode_layer_attn(cfg, layer_p, h, kc, vc, position)
            return h, (kc, vc)

        with jax.named_scope("layers_scan"):
            x, (nk, nv) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
        cache = dict(cache, k=nk, v=nv)

    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = params["embed"].T if cfg.tied_embeddings else params["head"]
    logits = (x @ head)[:, 0, :]
    return logits, cache


def _decode_hybrid(cfg, params, cache, x, position):
    pat = cfg.pattern
    attn_i = next(i for i, k in enumerate(pat) if k == "attn")
    w = cache["blocks"][f"l{attn_i}_k"].shape[2]

    def block_body(carry, inp):
        h = carry
        block_p, block_c = inp
        new_c = {}
        for i, kind in enumerate(pat):
            p = block_p[f"l{i}_{kind}"]
            if kind == "rec":
                st = block_c[f"l{i}_state"]
                h, new_st = _apply_layer(cfg, "rec", p, h, None, state=st)
                new_c[f"l{i}_state"] = new_st
            else:
                kc, vc = block_c[f"l{i}_k"], block_c[f"l{i}_v"]
                hh = rms_norm(h, p["ln1"], cfg.norm_eps)
                a, kc, vc = _ring_decode_attention(cfg, p["attn"], hh, kc, vc, position, w)
                h = h + a
                h2 = rms_norm(h, p["ln2"], cfg.norm_eps)
                h = h + mlp(p["mlp"], cfg, h2)
                new_c[f"l{i}_k"], new_c[f"l{i}_v"] = kc, vc
        return h, new_c

    with jax.named_scope("layers_scan"):
        x, new_blocks = jax.lax.scan(block_body, x, (params["blocks"], cache["blocks"]))
    new_tail = []
    types = cfg.layer_types()
    nb = cfg.num_layers // len(pat)
    tail_types = types[nb * len(pat):]
    for p, kind, c in zip(params["tail"], tail_types, cache["tail"]):
        if kind == "rec":
            x, st = _apply_layer(cfg, "rec", p, x, None, state=c["state"])
            new_tail.append({"state": st})
        else:
            hh = rms_norm(x, p["ln1"], cfg.norm_eps)
            a, kc, vc = _ring_decode_attention(cfg, p["attn"], hh, c["k"], c["v"], position, w)
            x = x + a
            h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
            x = x + mlp(p["mlp"], cfg, h2)
            new_tail.append({"k": kc, "v": vc})
    return x, {"blocks": new_blocks, "tail": new_tail}


def _ring_decode_attention(cfg, p, x, k_cache, v_cache, position, w):
    """Sliding-window decode with a ring-buffer KV cache of size w."""
    from .layers import _grouped_out, _grouped_scores, _qkv, apply_rope

    b = x.shape[0]
    q, k, v = _qkv(p, cfg, x)
    pos = jnp.full((b, 1), position, jnp.int32)
    q = apply_rope(q.swapaxes(1, 2), pos[:, None, :], cfg.rope_theta).swapaxes(1, 2)
    k = apply_rope(k.swapaxes(1, 2), pos[:, None, :], cfg.rope_theta).swapaxes(1, 2)
    slot = position % w
    k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype), (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype), (0, slot, 0, 0))
    # absolute position stored in each ring slot
    idx = jnp.arange(w)
    slot_pos = position - ((position - idx) % w)
    valid = (slot_pos <= position) & (slot_pos > position - w) & (slot_pos >= 0)
    scores = _grouped_scores(q, k_cache) * cfg.head_dim**-0.5
    scores = jnp.where(valid[None, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _grouped_out(probs, v_cache).reshape(b, 1, -1) @ p["wo"]
    return out, k_cache, v_cache


def prefill(cfg: ModelConfig, params, batch, max_len: int):
    """Prefill: full forward + populate the KV cache for subsequent decode.

    For attention families the cache is rebuilt by re-projecting K/V per layer
    (cheap relative to the forward); recurrent families return final states.
    Returns (logits, cache).  Used by the serving layer; the dry-run lowers
    ``forward`` for prefill cells (the logits are what serving samples from).
    """
    _no_latent_cache(cfg, "prefill")
    logits = forward(cfg, params, batch)
    cache = init_cache(cfg, batch["tokens"].shape[0], max_len)
    return logits, cache
