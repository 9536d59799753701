"""Rehearsal of ``chip_smoke.py`` on the CPU: its phase functions at a tiny
size under the Pallas interpreter, its four-chip phase on four virtual CPU
devices, and the script itself refusing to run without a TPU."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
SRC = os.path.join(ROOT, "src")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = dict(n1=40, n2=300, d=16, n_entities=150, noise=1.0)


@pytest.fixture(scope="module")
def tiny():
    ds, left, right = chip_smoke.make_join(0, **TINY)
    cfg, params, tok = chip_smoke.load_model(chip_smoke.ORACLE, "smoke", 0)
    scorer = chip_smoke.make_scorer(cfg, params, tok, left, right,
                                    batch_size=8)
    return ds, left, right, scorer


def test_join_has_records_per_table(tiny):
    from repro.data.pipeline import entity_records

    ds, left, right, _ = tiny
    assert (len(left), len(right)) == (TINY["n1"], TINY["n2"])
    assert ds.emb1.shape == (TINY["n1"], TINY["d"])
    # a matching pair's records are noisy copies of one shared base string
    i, j = map(int, next(zip(*ds.truth.nonzero())))
    n_ent = TINY["n_entities"]
    base = entity_records(ds.ids1[i:i + 1], n_ent, 0, table=0, noise=0.0)[0]
    assert base == entity_records(ds.ids2[j:j + 1], n_ent, 0, table=1,
                                  noise=0.0)[0]
    for rec in (left[i], right[j]):
        assert len(rec) == len(base)
        assert sum(a == b for a, b in zip(rec, base)) >= 0.6 * len(base)


def test_sweep_phase_within_bounds(tiny):
    ds = tiny[0]
    dev = chip_smoke.phase_sweep(ds.emb1, ds.emb2, n_bins=256)
    assert dev["tiles_outside_margin"] == 0
    assert dev["row_sum_rel"] <= chip_smoke.SWEEP_BOUNDS["row_sum_rel"]


def test_oracle_phase_matches_f32_reference(tiny):
    _, left, right, scorer = tiny
    out = chip_smoke.phase_oracle(scorer, len(left), len(right), seed=0)
    assert out["oracle_logit_dev"] <= out["oracle_bound"]


def test_query_phase_streams_on_the_kernel(tiny):
    from repro.core import BASConfig

    ds, _, _, scorer = tiny
    # tiny tables fit the dense path; a zero cap routes them as the chip's
    # 2,616 x 64,263 join routes by size
    cfg = BASConfig(max_dense_weight_bytes=0, n_bootstrap=100)
    out = chip_smoke.phase_query(ds.emb1, ds.emb2, scorer, seed=0,
                                 queries=2, budget=600, bas_cfg=cfg)
    assert out["query_s"] > 0


def test_data_parallel_phase_on_four_virtual_devices():
    code = f"""
import sys
sys.path.insert(0, {ROOT!r})
import chip_smoke as cs
ds, left, right = cs.make_join(0, **{TINY!r})
cfg, params, tok = cs.load_model(cs.ORACLE, "smoke", 0)
out = cs.phase_data_parallel(cfg, params, tok, left, right, seed=0, batch=8)
print("DP", out)
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "DP {" in out.stdout


def test_script_refuses_to_run_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 2
    assert "needs a TPU" in out.stderr
    lines = out.stdout.strip().splitlines()
    assert not lines or '"ok"' not in lines[-1]
    with pytest.raises(json.JSONDecodeError):
        json.loads(lines[-1] if lines else "")


def test_compile_cache_goes_where_the_environment_says(tmp_path, monkeypatch):
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        env_dir = str(tmp_path / "from-env")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert enable_compile_cache(tmp_path) == env_dir
        assert jax.config.jax_compilation_cache_dir == before  # set nothing
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = str(tmp_path / ".jax_cache")
        assert enable_compile_cache(tmp_path) == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
