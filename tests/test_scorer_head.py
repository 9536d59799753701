"""The scorer's last-position head: ``PairScorer`` applies the final norm and
the head at each row's last real position, over the yes and no columns only,
and reads what the full-vocabulary ``forward`` would have given there.

Checked on the CPU at a tiny size against ``forward`` gathered at ``last``
(tied and untied heads, float32 and bfloat16, pad rows and mixed lengths in
one block), across the families ``last_position_logits`` serves, and
against the dense family's plain reference ``chipbench/oracles/dense.py``.
"""
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

from chipbench import harness, reference  # noqa: E402
from chipbench.data import make_params  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402
from repro.models import forward, last_position_logits  # noqa: E402
from repro.serve.serve_loop import PairScorer  # noqa: E402

YES, NO = 5, 6
B, L = 8, 32
# the benchmark's dense oracle at a CPU's width (two layers, d = 64)
TINY = {
    "arch": "joinml-oracle", "family": "dense", "num_layers": 2,
    "d_model": 64, "num_heads": 4, "num_kv_heads": 4, "head_dim": 16,
    "d_ff": 128, "vocab_size": 512, "rope_theta": 10000.0, "norm_eps": 1e-5,
    "dtype": "bfloat16", "max_len": 64, "batch_size": 8, "threshold": 0.5,
}
# float32 on both sides: the same sums in other orders over two layers
TOL_F32 = 1e-4
# bfloat16 rounds every activation and product to 8 bits of mantissa: the
# scorer reads 0.0035 and 0.0081 on the two seeds below, the reference with
# float8 matmuls (its control) 0.043 and 0.041
TOL_BF16 = 0.02


def _block(seed, b=B, s=L, pad_rows=2):
    """Padded tokens and last positions: mixed lengths, ``pad_rows`` all-zero
    rows at the end with ``last`` 0, as ``PairScorer.score`` pads a block."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(4, s + 1, b)
    lens[b - pad_rows:] = 0
    toks = np.zeros((b, s), np.int32)
    for i, n in enumerate(lens):
        toks[i, :n] = rng.integers(1, 256, n)
    return toks, np.maximum(lens - 1, 0).astype(np.int32)


def _dense(dtype, tied):
    cfg = get_smoke_config("joinml-oracle", dtype=dtype, tied_embeddings=tied)
    return cfg, make_params(cfg, 3)


def _gathered_forward(cfg, params, toks, last):
    logits = forward(cfg, params, {"tokens": jnp.asarray(toks)})
    return np.asarray(logits[np.arange(len(last)), last][:, [YES, NO]],
                      np.float64)


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_scorer_equals_forward_at_last(dtype, tied):
    cfg, params = _dense(dtype, tied)
    scorer = PairScorer(cfg, params, None, YES, NO, max_len=L, batch_size=B)
    for seed in (1, 2):
        toks, last = _block(seed)
        got = scorer.yes_no_logits(toks, last)
        assert got.shape == (B, 2) and got.dtype == jnp.float32
        want = _gathered_forward(cfg, params, toks, last)
        if dtype == "float32":
            np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                       atol=1e-5)
        else:
            # forward rounds its (B, L, V) logits to bfloat16; the head's
            # float32 result lies within that rounding (half an ulp, 2^-8
            # of the value) of them
            err = np.abs(np.asarray(got, np.float64) - want)
            assert (err <= 2.0**-8 * np.abs(want) + 1e-6).all(), err.max()


@pytest.mark.parametrize("arch", ["llama3.2-1b", "olmoe-1b-7b", "pixtral-12b",
                                  "rwkv6-1.6b", "recurrentgemma-9b"])
def test_last_position_logits_serves_every_family(arch):
    cfg = get_smoke_config(arch, dtype="float32")
    params = make_params(cfg, 4)
    toks, last = _block(5, s=16)
    valid = np.arange(toks.shape[1])[None, :] <= last[:, None]
    got, sizes = last_position_logits(cfg, params, jnp.asarray(toks),
                                      jnp.asarray(last), (YES, NO), valid)
    assert sizes is None and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got),
                               _gathered_forward(cfg, params, toks, last),
                               rtol=1e-5, atol=1e-5)


def test_pair_scores_the_same_alone_and_in_a_full_block():
    cfg, params = _dense("bfloat16", True)
    scorer = PairScorer(cfg, params, None, YES, NO, max_len=L, batch_size=B)
    toks, last = _block(3, pad_rows=0)
    full = np.asarray(scorer.yes_no_logits(toks, last))
    for row in (0, 5):
        alone = np.zeros_like(toks)
        alone[0] = toks[row]
        lone_last = np.zeros_like(last)
        lone_last[0] = last[row]
        got = np.asarray(scorer.yes_no_logits(alone, lone_last))[0]
        np.testing.assert_array_equal(got, full[row])


def _reference_dev(o, seed, shift=0):
    cfg = harness.oracle_config(o)
    params = make_params(cfg, seed)
    scorer = PairScorer(cfg, params, None, YES, NO, max_len=o["max_len"],
                        batch_size=o["batch_size"])
    toks, last = _block(seed, b=o["batch_size"], s=o["max_len"], pad_rows=0)
    got = np.asarray(scorer.yes_no_logits(toks, np.maximum(last - shift, 0)),
                     np.float64)
    want = reference.yes_no_logits(o, params, toks, last, YES, NO)
    return reference.logit_dev(got, want)


@pytest.mark.parametrize("dtype,tol", [("float32", TOL_F32),
                                       ("bfloat16", TOL_BF16)])
def test_scorer_matches_dense_reference(dtype, tol):
    for seed in (1, 2):
        assert _reference_dev(dict(TINY, dtype=dtype), seed) <= tol


def test_head_one_position_early_fails_the_comparison():
    """The comparison sees a head read one position before ``last``."""
    assert _reference_dev(dict(TINY, dtype="float32"), 1, shift=1) > 100 * TOL_F32


def test_sharded_scorer_gathers_per_shard():
    """Under the data-parallel ``shard_map`` (the host's devices) each shard
    gathers its own rows at ``last``."""
    from repro.launch.mesh import make_host_mesh

    cfg, params = _dense("float32", True)
    mesh = make_host_mesh()
    sharded = PairScorer(cfg, params, None, YES, NO, max_len=L, batch_size=B,
                         mesh=mesh)
    toks, last = _block(6)
    want = _gathered_forward(cfg, params, toks, last)
    np.testing.assert_allclose(np.asarray(sharded.yes_no_logits(toks, last)),
                               want, rtol=1e-5, atol=1e-5)
