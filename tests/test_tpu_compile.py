"""Compile the main path for a described TPU v5e, without a chip.

The TPU compiler is installed with JAX and compiles for a topology that is
described, not attached: Mosaic refuses a kernel here exactly as it would on
the chip (block shapes off the (8, 128) tiling, primitives it does not
lower, more VMEM than a kernel may use).  Covered:

* the ``sim_sweep`` kernel at the chip smoke's join (2,616 x 64,263 rows,
  d=384, 4,096 bins, k=32) for fp32, bf16 and int8, at every block shape in
  ``autotune.CANDIDATES`` — a candidate that stops compiling must leave that
  list;
* the ``PairScorer`` forward at ``joinml-oracle``'s published widths
  (B=32, L=48), from ``jax.eval_shape`` parameters.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune

pytestmark = pytest.mark.pallas

M, N, D, N_BINS, K = 2616, 64263, 384, 4096, 32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile can be written to the persistent cache but
    # not read back without the chip, so keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _round_up(n, b):
    return -(-n // b) * b


def test_v5e_is_a_tuned_device_kind(one_chip):
    (device,) = one_chip.device_set
    assert device.device_kind in autotune.DEVICE_KINDS


@pytest.mark.parametrize("block", autotune.CANDIDATES,
                         ids=lambda b: f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("precision", ["fp32", "bf16", "int8"])
def test_sim_sweep_compiles_for_v5e(one_chip, precision, block):
    from repro.kernels.sim_sweep.kernel import (sim_sweep_pallas,
                                                sim_sweep_q_pallas)

    bm, bn = block
    m, n = _round_up(M, bm), _round_up(N, bn)
    common = dict(n_bins=N_BINS, k=K, bm=bm, bn=bn, interpret=False)
    row = _spec((m,), jnp.float32, one_chip)
    col = _spec((n,), jnp.float32, one_chip)
    if precision == "int8":
        def fn(q1, q2, r1, r2, s, v):
            return sim_sweep_q_pallas(q1, q2, r1, r2, s, v, **common)
        args = (_spec((m, D), jnp.int8, one_chip),
                _spec((n, D), jnp.int8, one_chip),
                _spec((m, 1), jnp.float32, one_chip),
                _spec((1, n), jnp.float32, one_chip), row, col)
    else:
        dtype = jnp.bfloat16 if precision == "bf16" else jnp.float32

        def fn(e1, e2, s, v):
            return sim_sweep_pallas(e1, e2, s, v, compute_dtype=dtype,
                                    **common)
        args = (_spec((m, D), jnp.float32, one_chip),
                _spec((n, D), jnp.float32, one_chip), row, col)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pair_scorer_forward_compiles_for_v5e(one_chip):
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serve.serve_loop import PairScorer

    cfg = get_config("joinml-oracle")
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    params = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip), shapes)
    scorer = PairScorer(cfg, None, None, yes_id=5, no_id=6, max_len=48,
                        batch_size=32)
    batch = {"tokens": _spec((32, 48), jnp.int32, one_chip),
             "last": _spec((32,), jnp.int32, one_chip)}
    compiled = scorer._fwd.lower(params, batch).compile()
    out = compiled.out_info
    assert out.shape == (32, 2)
    assert compiled.memory_analysis() is not None
