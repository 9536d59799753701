"""The cells' scorer programs compile for a described TPU v5e (no chip
needed): the published-width oracle at batch 32 and the padded lengths the
cells' traffic reaches most, up to 512 in both configurations, and the
reference's float32 layer at the longest of them.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_tpu_compile.py -q
"""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402
from chipbench.oracles import dense  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _oracle(name):
    return json.loads((ROOT / f"chipbench/configs/{name}.json").read_text())["oracle"]


@pytest.mark.parametrize("name,pad", [("dblp-scholar", 128), ("dblp-scholar", 256),
                                      ("dblp-scholar", 512), ("abt-buy", 256),
                                      ("abt-buy", 512)])
def test_scorer_bucket_compiles_for_v5e(one_chip, name, pad):
    from repro.models import init_params
    from repro.serve.serve_loop import PairScorer

    o = _oracle(name)
    cfg = harness.oracle_config(o)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    params = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip), shapes)
    scorer = PairScorer(cfg, None, None, yes_id=5, no_id=6,
                        max_len=o["max_len"], batch_size=o["batch_size"])
    b = o["batch_size"]
    batch = {"tokens": _spec((b, pad), jnp.int32, one_chip),
             "last": _spec((b,), jnp.int32, one_chip)}
    compiled = scorer._fwd.lower(params, batch).compile()
    assert compiled.out_info.shape == (b, 2)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16e9


def test_reference_layer_compiles_for_v5e(one_chip):
    o = _oracle("abt-buy")
    b, s, d, ff = o["batch_size"], o["max_len"], o["d_model"], o["d_ff"]
    hd, h = o["head_dim"], o["num_heads"]
    f32 = jnp.float32
    p = {"ln1": _spec((d,), f32, one_chip), "ln2": _spec((d,), f32, one_chip),
         "attn": {k: _spec((d, h * hd) if k != "wo" else (h * hd, d), f32, one_chip)
                  for k in ("wq", "wk", "wv", "wo")},
         "mlp": {"w_gate": _spec((d, ff), f32, one_chip),
                 "w_up": _spec((d, ff), f32, one_chip),
                 "w_down": _spec((ff, d), f32, one_chip)}}
    args = (_spec((b, s, d), f32, one_chip), p,
            _spec((1, s, 1, hd // 2), f32, one_chip),
            _spec((1, s, 1, hd // 2), f32, one_chip),
            _spec((s, s), jnp.bool_, one_chip))
    for control in (False, True):
        dense._layer.lower(*args, heads=h, kv_heads=o["num_kv_heads"],
                           eps=o["norm_eps"], control=control).compile()
