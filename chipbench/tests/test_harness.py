"""The harness end to end at a tiny size on the CPU: a sound run is correct,
each planted fault of the timed path makes it incorrect, the controls fail
the comparison that the program passes, and without a TPU the benchmark
prints nothing and exits non-zero.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""
import copy
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, reference  # noqa: E402

TINY_ORACLE = {
    "arch": "joinml-oracle", "family": "dense", "num_layers": 2,
    "d_model": 64, "num_heads": 4, "num_kv_heads": 4, "head_dim": 16,
    "d_ff": 128, "vocab_size": 512, "rope_theta": 10000.0, "norm_eps": 1e-5,
    "dtype": "bfloat16", "max_len": 64, "batch_size": 8, "threshold": 0.5,
}


def tiny_cell(dispatch: str, traffic: str) -> harness.Cell:
    """A real configuration cut to a CPU's size (narrow oracle, small
    tables); ``streaming`` forces the sweep path by a zero dense cap."""
    base = json.loads((ROOT / "chipbench/configs/dblp-scholar.json").read_text())
    config = copy.deepcopy(base)
    config["tables"].update(n1=48, n2=320, d=32, n_entities=200, noise=0.5)
    for side in ("left", "right"):
        config["records"][side].update(median_tokens=10, sigma=0.5,
                                       min_tokens=4, max_tokens=24)
    config["oracle"] = dict(TINY_ORACLE)
    config["dispatch"] = dispatch
    config["bas"]["n_bootstrap"] = 200
    if dispatch == "streaming":
        config["bas"]["max_dense_weight_bytes"] = 0
    else:
        config["limits"]["dense_weight_dev"] = 1e-6
    tr = json.loads((ROOT / f"chipbench/traffic/{traffic}.json").read_text())
    tr.update(analysts=2, budget=60, stagger_s=0.2)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return harness.Cell(f"tiny.{dispatch}.{traffic}", 1, config, tr,
                        bench["end_to_end"],
                        [m for m in bench["per_layer"] if "workloads" not in m])


def run(cell, seed=12345678901, seconds=1.5, fault=None):
    return harness.run(cell, seed, seconds, False, time.perf_counter(),
                       require_tpu=False, fault=fault)


@pytest.mark.parametrize("dispatch,traffic", [("streaming", "fresh"),
                                              ("dense", "fresh"),
                                              ("streaming", "dashboard")])
def test_sound_run_is_correct(dispatch, traffic):
    line = run(tiny_cell(dispatch, traffic))
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"query_p50_s", "query_p90_s", "labels_per_s", "setup_s"} <= set(
        line["metrics"])
    assert {"estimate_rel_dev", "ci_rel_dev", "sample_weight_dev"} <= set(
        line["checks"])
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("dispatch,traffic,fault", [
    ("streaming", "fresh", "logits"),
    ("streaming", "fresh", "half_batch"),
    ("streaming", "fresh", "sweep"),
    ("streaming", "fresh", "sampler"),
    ("dense", "fresh", "sampler"),
    ("streaming", "fresh", "short_bootstrap"),
    ("dense", "fresh", "answer"),
    ("dense", "fresh", "answer_shift"),
    ("streaming", "dashboard", "answer_shift"),
])
def test_planted_fault_is_incorrect(dispatch, traffic, fault):
    line = run(tiny_cell(dispatch, traffic), fault=fault)
    assert not line["correct"], line["checks"]


def test_sweep_control_fails():
    """The sweep and the sampler's weights from products at
    ``Precision.HIGH`` (three bf16 passes) in the program's place read above
    their limits; the CPU computes HIGH exactly, so this runs on a TPU
    only."""
    import jax

    if jax.default_backend() != "tpu":
        pytest.skip("Precision.HIGH is exact off the TPU")
    from chipbench import data

    e1, e2 = data.make_tables(3, 256, 16384, 384, 8000, 1.0)
    ctl = reference.sweep_blocks(e1, e2, [0], 256, 4096, 32, 1.0, 1e-3,
                                 control=True)
    ref = reference.sweep_blocks(e1, e2, [0], 256, 4096, 32, 1.0, 1e-3,
                                 score_ids=[ctl["idx"]])
    nums = reference.sweep_numbers(ctl, ref)
    limits = json.loads((ROOT / "chipbench/configs/dblp-scholar.json")
                        .read_text())["limits"]
    assert any(v > limits[k] for k, v in nums.items()), nums
    rng = np.random.default_rng(4)
    pairs = np.stack([rng.integers(0, 256, 600), rng.integers(0, 16384, 600)],
                     axis=1)
    draws = [(0, pairs, np.ones(600)), (1, pairs[:300], np.ones(300))]
    dev = reference.sample_weight_dev(draws, e1, e2, 1.0, 1e-3, (0,),
                                      control=True)
    assert dev > limits["sample_weight_dev"], dev


def test_estimation_control_fails():
    """COUNT's estimate and CI computed in float32 in the program's place
    read above the limits that the program's float64 is held to."""
    rng = np.random.default_rng(5)
    strata = []
    for n, scale in ((400, 1e-9), (120, 1e-4), (60, 1e-3)):
        q = scale * rng.uniform(0.5, 2.0, n)
        strata.append(((rng.random(n) < 0.3).astype(float), q))
    blocked = (rng.random(200) < 0.5).astype(float)
    state = np.random.default_rng(6).bit_generator.state
    ref = reference.ht_count_ci(strata, blocked, 0.95, 1000, state)
    ctl = reference.ht_count_ci(strata, blocked, 0.95, 1000, state,
                                dtype=np.float32)
    limits = json.loads((ROOT / "chipbench/configs/dblp-scholar.json")
                        .read_text())["limits"]
    scale = abs(ref[0])
    assert (abs(ctl[0] - ref[0]) / scale > limits["estimate_rel_dev"]
            or max(abs(ctl[1] - ref[1]), abs(ctl[2] - ref[2])) / scale
            > limits["ci_rel_dev"]), (ref, ctl)


def test_oracle_control_fails():
    """The oracle reference with float8 matmuls in the program's place
    reads above the limit the program is held to."""
    from chipbench import data

    cell = tiny_cell("dense", "fresh")
    o = cell.config["oracle"]
    cfg = harness.oracle_config(o)
    params = data.make_params(cfg, 7)
    rng = np.random.default_rng(1)
    toks = rng.integers(8, 264, (8, 32)).astype(np.int32)
    toks[:, 0] = 1
    last = rng.integers(10, 32, 8).astype(np.int32)
    want = reference.yes_no_logits(o, params, toks, last, 5, 6)
    ctl = reference.yes_no_logits(o, params, toks, last, 5, 6, control=True)
    limit = json.loads((ROOT / "chipbench/configs/dblp-scholar.json")
                       .read_text())["limits"]["scorer_logit_dev"]
    assert reference.logit_dev(ctl, want) > limit


def test_no_tpu_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "dblp-scholar.fresh", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
