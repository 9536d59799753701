"""DeepSeek-V2's block served as the oracle: latent attention (MLA) with YaRN
RoPE, a leading dense layer, and dropless top-k experts beside a shared one,
through ``PairScorer`` against the plain reference of its family,
``chipbench/oracles/moe.py``, at a tiny size on the CPU.

The reference is imported from the checkout root, as ``chip_smoke`` is; it
takes nothing from the program.  Weights are the benchmark's seeded random
ones (norm weights drawn too, so the ``v * (1 + w)`` norms are exercised).
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

from chipbench import harness, reference  # noqa: E402
from chipbench.data import make_params  # noqa: E402
from chipbench.oracles import moe  # noqa: E402

# one dense layer and two MoE layers of 8 experts, top-2, one shared; an
# original length of 64 puts YaRN's ramp inside the 4 rope frequencies
TINY = {
    "arch": "deepseek-v2-lite", "family": "moe", "num_layers": 3,
    "first_k_dense": 1, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
    "d_ff": 128, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "num_experts": 8,
    "num_experts_per_tok": 2, "num_shared_experts": 1, "moe_d_ff": 32,
    "vocab_size": 512, "tied_embeddings": False, "rope_theta": 10000.0,
    "rope_factor": 40.0, "rope_original_max_len": 64, "rope_beta_fast": 32.0,
    "rope_beta_slow": 1.0, "rope_mscale_all_dim": 0.707,
    "norm_eps": 1e-6, "dtype": "bfloat16", "max_len": 64, "batch_size": 8,
}
YES, NO = 5, 6
# float32 on both sides: the same arithmetic in other orders (the grouped
# matmul's tiles, the gate's sums), a few ulps that grow over three layers
# to about 1e-6 of the largest logit
TOL_F32 = 1e-4
# bfloat16 rounds every activation and product to 8 bits of mantissa: over
# three layers the logits move by 1.5-6% of the largest one at this width
# on the seeds tried (the most where a near-tie of the gate routes a token
# to another expert); the float8 control reads 0.23 and more here
TOL_BF16 = 0.08


def _setup(o, seed=0, program=None, params_fn=None):
    """(program scorer, reference oracle section, reference weights)."""
    cfg = harness.oracle_config(o)
    params = make_params(cfg, seed)
    pcfg = dataclasses.replace(cfg, **(program or {}))
    pp = params_fn(params) if params_fn else params
    from repro.serve.serve_loop import PairScorer

    scorer = PairScorer(pcfg, pp, None, YES, NO, max_len=o["max_len"],
                        batch_size=o["batch_size"])
    return scorer, o, params


def _block(seed, b=8, s=64, lo=20):
    rng = np.random.default_rng(seed)
    lens = rng.integers(lo, s + 1, b)
    toks = np.zeros((b, s), np.int32)
    for i, n in enumerate(lens):
        toks[i, :n] = rng.integers(1, 264, n)
    return toks, (lens - 1).astype(np.int32)


def _dev(scorer, o, params, toks, last):
    got = np.asarray(scorer.yes_no_logits(toks, last), np.float64)
    want = reference.yes_no_logits(o, params, toks, last, YES, NO)
    return reference.logit_dev(got, want)


@pytest.mark.parametrize("dtype,tol", [("float32", TOL_F32), ("bfloat16", TOL_BF16)])
def test_scorer_matches_reference(dtype, tol):
    scorer, o, params = _setup(dict(TINY, dtype=dtype))
    for seed in (1, 2):
        assert _dev(scorer, o, params, *_block(seed)) <= tol


def _without_shared(params):
    return dict(params, layers={k: v for k, v in params["layers"].items()
                                if k != "shared"})


@pytest.mark.parametrize("fault,oracle,program,params_fn", [
    ("five of six experts", {"num_experts_per_tok": 6},
     {"num_experts_per_tok": 5}, None),
    ("no YaRN m^2 in the softmax scale", {},
     {"rope_mscale_all_dim": 0.0}, None),
    ("no shared experts", {}, {"num_shared_experts": 0}, _without_shared),
])
def test_planted_fault_fails_the_comparison(fault, oracle, program, params_fn):
    scorer, o, params = _setup(dict(TINY, dtype="float32", **oracle),
                               program=program, params_fn=params_fn)
    assert _dev(scorer, o, params, *_block(1)) > 100 * TOL_F32, fault


def test_pair_scores_the_same_alone_and_in_a_full_block():
    """Dropless routing: no pair's tokens are dropped or displaced by the
    other pairs of its block."""
    scorer, _, _ = _setup(TINY)
    toks, last = _block(3)
    full = np.asarray(scorer.yes_no_logits(toks, last))
    for row in (0, 5):
        alone = np.zeros_like(toks)
        alone[0] = toks[row]
        lone_last = np.zeros_like(last)
        lone_last[0] = last[row]
        got = np.asarray(scorer.yes_no_logits(alone, lone_last))[0]
        np.testing.assert_array_equal(got, full[row])


def test_block_where_every_token_picks_the_same_experts():
    """A zero router ties every expert, so every token takes the first k:
    two experts get every real token of the block, and the block still
    matches the reference."""
    def zero_router(params):
        moe_p = dict(params["layers"]["moe"])
        moe_p["router"] = jnp.zeros_like(moe_p["router"])
        return dict(params, layers=dict(params["layers"], moe=moe_p))

    o = dict(TINY, dtype="float32")
    scorer, _, params = _setup(o, params_fn=zero_router)
    params = zero_router(params)
    toks, last = _block(4)
    assert _dev(scorer, o, params, toks, last) <= TOL_F32
    sizes = np.asarray(scorer._side.sizes)
    real = int((last + 1).sum())
    assert sizes.shape == (2, 8)
    assert (sizes[:, :2] == real).all() and (sizes[:, 2:] == 0).all()


def test_grouped_layer_equals_a_per_token_loop():
    """``grouped_moe`` against each token's top-k experts computed one at a
    time, its gate weights as they are; pad tokens get nothing and change
    nothing."""
    from repro.models.layers import grouped_moe

    cfg = harness.oracle_config(dict(TINY, dtype="float32"))
    p = make_params(cfg, 7)["layers"]["moe"]
    p = {k: v[0] for k, v in p.items()}
    rng = np.random.default_rng(8)
    x = rng.standard_normal((3, 24, 64)).astype(np.float32)
    valid = np.ones((3, 24), bool)
    valid[1, 10:] = False
    valid[2] = False
    y, sizes = jax.jit(lambda x, v: grouped_moe(p, cfg, x, v))(x, valid)
    y = np.asarray(y)

    w = {k: np.asarray(v, np.float64) for k, v in p.items()}
    want = np.zeros_like(y, np.float64)
    counts = np.zeros(8, np.int64)
    for b, t in zip(*np.nonzero(valid)):
        z = x[b, t].astype(np.float64) @ w["router"]
        gate = np.exp(z - z.max())
        gate /= gate.sum()
        for e in np.argsort(-gate)[:2]:
            h = x[b, t] @ w["w_gate"][e]
            act = h / (1 + np.exp(-h)) * (x[b, t] @ w["w_up"][e])
            want[b, t] += gate[e] * (act @ w["w_down"][e])
            counts[e] += 1
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    assert (y[~valid] == 0).all()
    np.testing.assert_array_equal(np.asarray(sizes), counts)


def test_yarn_at_the_published_widths():
    """The ramp runs from dim 10 to 23 of 32; the softmax scale's m is
    0.1 x 0.707 x ln 40 + 1; the program and the reference agree."""
    from repro.configs import get_config
    from repro.models.layers import yarn_freqs, yarn_mscale

    cfg = get_config("deepseek-v2-lite")
    f = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    got = yarn_freqs(cfg)
    np.testing.assert_allclose(got[:11], f[:11], rtol=1e-12)
    np.testing.assert_allclose(got[23:], f[23:] / 40, rtol=1e-12)
    assert f[22] / 40 < got[22] < f[22]
    m = yarn_mscale(40.0, 0.707)
    assert m == pytest.approx(1.26080, abs=1e-5)
    inv, m2 = moe.yarn({"qk_rope_head_dim": 64, "rope_theta": 10000.0,
                        "rope_factor": 40.0, "rope_original_max_len": 4096,
                        "rope_beta_fast": 32.0, "rope_beta_slow": 1.0,
                        "rope_mscale_all_dim": 0.707})
    np.testing.assert_allclose(inv, got, rtol=1e-12)
    assert m2 == pytest.approx(m * m, rel=1e-12)


@pytest.mark.parametrize("call", ["init_cache", "prefill", "decode_step"])
def test_decoding_paths_refuse_latent_attention(call):
    from repro.configs import get_smoke_config
    from repro import models

    cfg = get_smoke_config("deepseek-v2-lite")
    toks = jnp.zeros((2, 4), jnp.int32)
    args = {"init_cache": (cfg, 2, 8),
            "prefill": (cfg, None, {"tokens": toks}, 8),
            "decode_step": (cfg, None, None, toks[:, :1], 0)}[call]
    with pytest.raises(NotImplementedError, match="moe family"):
        getattr(models, call)(*args)
