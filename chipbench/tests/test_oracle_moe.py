"""The moe oracle family (DeepSeek-V2's latent attention and dropless
experts) in the harness at a tiny size on the CPU: a traced run is correct
and its work count is the scorer's own, the family refuses an MoE it cannot
represent, the float8 control fails the comparison the program passes, the
expert metrics read the scorer's counters and the trace, and the cell's
configuration states the published model and the stage it runs.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_oracle_moe.py -q
"""
import copy
import dataclasses
import json
import sys
import time
import types
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]

import test_program_metrics  # noqa: E402
from chipbench import harness, oracles  # noqa: E402
from chipbench.oracles import moe  # noqa: E402
from test_harness import TINY_ORACLE, tiny_cell  # noqa: E402

CELL = "abt-buy-dsv2-lite.fresh-b250"
TINY_MOE = {
    "arch": "deepseek-v2-lite", "family": "moe", "num_layers": 3,
    "first_k_dense": 1, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
    "d_ff": 128, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "num_experts": 8,
    "num_experts_per_tok": 2, "num_shared_experts": 1, "moe_d_ff": 32,
    "vocab_size": 512, "tied_embeddings": False, "rope_theta": 10000.0,
    "rope_factor": 40.0, "rope_original_max_len": 64, "rope_beta_fast": 32.0,
    "rope_beta_slow": 1.0, "rope_mscale_all_dim": 0.707,
    "norm_eps": 1e-6, "dtype": "bfloat16", "max_len": 64, "batch_size": 8,
    "threshold": 0.5,
}


def moe_cell(dispatch="dense", traffic="fresh"):
    cell = tiny_cell(dispatch, traffic)
    config = copy.deepcopy(cell.config)
    config["oracle"] = dict(TINY_MOE)
    return dataclasses.replace(cell, config=config)


def _config():
    return json.loads((ROOT / "chipbench/configs/abt-buy-dsv2-lite.json").read_text())


def test_load_finds_the_moe_family():
    assert oracles.load("moe") is moe
    assert all(callable(getattr(moe, f)) for f in oracles.FUNCTIONS)


def test_check_refuses_an_moe_without_latent_attention():
    from repro.configs import get_config

    with pytest.raises(ValueError, match="chipbench/oracles/moe.py"):
        harness.oracle_config(dict(TINY_ORACLE, arch="olmoe-1b-7b", family="moe"))
    with pytest.raises(ValueError, match="chipbench/oracles/moe.py"):
        moe.check(get_config("olmoe-1b-7b"))
    moe.check(harness.oracle_config(TINY_MOE))


def test_traced_run_counts_the_scorers_work(monkeypatch):
    """In a traced tiny run the family's required FLOPs over the window's
    blocks equal the scorer's own count (``serve_loop.required_flops`` of
    what its counters rose by), and ``expert_pad_share`` reads the expert
    counters."""
    from repro.serve import serve_loop

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    per_layer = [m for m in bench["per_layer"]
                 if m["name"] in ("pad_share", "oracle_mfu", "expert_pad_share")]
    monkeypatch.setattr(test_program_metrics, "tiny_cell",
                        lambda dispatch, traffic: moe_cell(dispatch, traffic))
    line, seen = test_program_metrics.traced_tiny_run("dense", monkeypatch,
                                                      per_layer, "moe")
    assert line["correct"], line["checks"]
    tokens, slots, causal, pairs = seen["window"]
    blocks = seen["probe"].blocks
    assert tokens > 0
    family = sum(moe.required_flops(TINY_MOE, lens) for _, _, lens in blocks)
    program = serve_loop.required_flops(seen["scorer"].cfg, tokens, causal, pairs)
    assert program == pytest.approx(family, rel=1e-12)
    got = seen["ctx"].scorer_counters
    k, layers = TINY_MOE["num_experts_per_tok"], 2
    assert got["routed_rows"] == tokens * k * layers
    assert got["expert_rows"] % 256 == 0 and got["expert_rows"] >= got["routed_rows"]
    assert 0 < got["expert_load_max"] <= got["routed_rows"]
    assert line["metrics"]["expert_pad_share"]["value"] == pytest.approx(
        100.0 * (1.0 - got["routed_rows"] / got["expert_rows"]), rel=1e-12)


def test_fp8_control_fails_where_the_program_passes():
    cell = moe_cell()
    line = harness.run(cell, 9876543210123, 1.5, False, time.perf_counter(),
                       require_tpu=False, controls=True)
    assert line["correct"], line["checks"]
    program = line["checks"]["scorer_logit_dev"]["value"]
    control = line["controls"]["scorer_logit_dev"]
    assert program < control, (program, control)


def test_expert_roofline_reads_the_kernel_time():
    from chipbench.metrics import expert_roofline

    o = _config()["oracle"]
    lens = np.array([300, 340])
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    trace = types.SimpleNamespace(op_s=lambda name: 0.01 if name == "gmm" else 0.0)
    ctx = types.SimpleNamespace(config={"oracle": o}, trace=trace,
                                blocks=[(512, 32, lens)], peaks=peaks)
    flops = 2 * 3 * 2048 * 1408 * 6 * 6 * 640
    weights = 2 * 64 * 3 * 2048 * 1408 * 6
    want = 100 * max(flops / 197e12, weights / 819e9) / 0.01
    assert expert_roofline.read(ctx) == pytest.approx(want, rel=1e-12)
    ctx.config = {"oracle": TINY_ORACLE}
    assert expert_roofline.read(ctx) is None


def test_configuration_states_the_published_model_and_its_stage():
    """The file's top level holds the published config (depth cut to the
    stage); the oracle section the harness reads says the same; the stage's
    weights are the count the deployment states."""
    import jax

    from repro.models import init_params

    c = _config()
    o = c["oracle"]
    rs = c["rope_scaling"]
    same = {"hidden_size": "d_model", "intermediate_size": "d_ff",
            "moe_intermediate_size": "moe_d_ff", "kv_lora_rank": "kv_lora_rank",
            "n_routed_experts": "num_experts", "n_shared_experts": "num_shared_experts",
            "num_experts_per_tok": "num_experts_per_tok",
            "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
            "qk_nope_head_dim": "qk_nope_head_dim", "qk_rope_head_dim": "qk_rope_head_dim",
            "v_head_dim": "v_head_dim", "vocab_size": "vocab_size",
            "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
            "first_k_dense_replace": "first_k_dense", "num_hidden_layers": "num_layers"}
    for top, field in same.items():
        assert c[top] == o[field], top
    assert (rs["factor"], rs["original_max_position_embeddings"], rs["beta_fast"],
            rs["beta_slow"], rs["mscale_all_dim"]) == (
        o["rope_factor"], o["rope_original_max_len"], o["rope_beta_fast"],
        o["rope_beta_slow"], o["rope_mscale_all_dim"])
    # YaRN scales cos and sin by 1 only where the two mscales are equal
    assert rs["mscale"] == rs["mscale_all_dim"]
    assert not c["norm_topk_prob"] and c["routed_scaling_factor"] == 1
    assert c["q_lora_rank"] is None and not c["tie_word_embeddings"]
    assert o["num_layers"] == 7 and set(c["reduced"]) == {"num_hidden_layers"}
    cfg = harness.oracle_config(o)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    held = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert held == 4_009_526_784
    assert moe.held_params(o) + 2 * 102400 * 2048 + 7 * (2 * 2048 + 512) + 2048 == held
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(x for x in bench["configs"] if x["name"] == c["name"])
    assert entry["reduced"] == ["num_hidden_layers"]
    assert CELL in [w["name"] for w in bench["workloads"]]
