"""Production mesh construction.

Single pod: 16 x 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis composes with "data" for batch/FSDP sharding so adding pods widens
the outer axis (elastic scaling reshards checkpoints, see repro.checkpoint).
"""
from __future__ import annotations

import jax


def _auto(axes) -> tuple:
    """Auto sharding for every axis: the compiler propagates shardings."""
    return (jax.sharding.AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def make_host_mesh(model: int = 1):
    """Debug mesh over whatever devices exist (tests, examples)."""
    n = len(jax.devices())
    data = n // model
    axes = ("data", "model")
    return jax.make_mesh((data, model), axes, axis_types=_auto(axes))
