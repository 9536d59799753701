"""Logical-axis sharding rules (MaxText/t5x style) with divisibility fallback.

Model code annotates parameters and activations with *logical* axis names
("batch", "embed", "heads", "mlp", "vocab", "expert", ...).  A rule table maps
logical names to mesh axes.  A logical dim is sharded on its mesh axis only if
the dim size is divisible by the axis size — otherwise it falls back to the
next rule or replication (e.g. qwen2's 12 heads stay replicated on a 16-way
"model" axis while its d_ff=8960 shards).

Activations are annotated through :func:`shard_activation`, which is a no-op
outside a sharding context — so the same model code runs in single-device
smoke tests and in the 512-chip dry-run.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axis (or tuple of mesh axes, or list of candidate
# mesh-axis assignments tried in order).
Rules = dict

# Default training rules: FSDP over (pod, data), tensor parallel over model.
TRAIN_RULES: Rules = {
    "batch": ("pod", "data"),
    "seq": None,
    "kv_seq": None,  # attention K/V stay seq-replicated even under SP
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "vocab": "model",
    "expert": "model",
    "expert_mlp": None,
    "capacity": None,
    "data_group": ("pod", "data"),  # MoE dispatch group = one per batch shard
    "layers": None,
    "fsdp": ("pod", "data"),   # weight-shard axis for FSDP
    "rnn": "model",
    "conv": None,
    "frames": None,
    # parameter logical axes (see repro.models.partition)
    "model_dim": "model",
}

# Serving rules: batch over data; weights 2D-sharded (model x data) so even
# the 235B MoE fits per-chip HBM without FSDP gathers of full layers.
SERVE_RULES: Rules = {
    **TRAIN_RULES,
    "batch": ("pod", "data"),
    "fsdp": "data",
}

# Decode adds KV-cache sequence sharding over the model axis (decode
# activations have seq=1, which falls back to replicated automatically).
DECODE_RULES: Rules = {
    **SERVE_RULES,
    "seq": "model",
    "frames": "model",
}

# §Perf variants -------------------------------------------------------------
# Sequence parallelism: residual-stream activations sharded over the model
# axis between blocks (XLA turns the TP all-reduces into reduce-scatter +
# all-gather pairs around the sharded region).
TRAIN_RULES_SP: Rules = {**TRAIN_RULES, "seq": "model"}

# Decode without 2D weight sharding (small models: no per-layer weight
# collectives; weights must fit per-chip on the model axis alone).
DECODE_RULES_1D: Rules = {**DECODE_RULES, "fsdp": None}


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: Optional[Rules] = None


_CTX = _Ctx()


@contextlib.contextmanager
def sharding_context(mesh: Mesh, rules: Rules):
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh, _CTX.rules = mesh, rules
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def active() -> bool:
    return _CTX.mesh is not None


def num_batch_shards() -> int:
    """How many ways the batch is sharded under the active rules (1 outside a
    sharding context).  Model code uses this to keep data-local operations
    (e.g. MoE dispatch sort) from acquiring global semantics."""
    if not active():
        return 1
    target = _CTX.rules.get("batch")
    if target is None:
        return 1
    axes = _mesh_axes_for(_CTX.mesh, target)
    out = 1
    for a in axes:
        out *= _CTX.mesh.shape[a]
    return out


def _axis_size(mesh: Mesh, axis: Union[str, tuple]) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        out = 1
        for a in axis:
            out *= mesh.shape[a]
        return out
    return mesh.shape[axis]


def _mesh_axes_for(mesh: Mesh, axis) -> tuple:
    """Filter a rule target down to axes present in the mesh."""
    if axis is None:
        return ()
    axes = axis if isinstance(axis, tuple) else (axis,)
    return tuple(a for a in axes if a in mesh.shape)


def spec_for(
    logical: Sequence[Optional[str]],
    shape: Sequence[int],
    rules: Optional[Rules] = None,
    mesh: Optional[Mesh] = None,
) -> P:
    """PartitionSpec for a value with given logical axes and shape, applying
    the divisibility fallback per dimension and never reusing a mesh axis."""
    mesh = mesh or _CTX.mesh
    rules = rules or _CTX.rules
    assert mesh is not None and rules is not None, "no sharding context"
    used: set = set()
    parts = []
    for name, dim in zip(logical, shape):
        assigned = None
        if name is not None and name in rules:
            target = rules[name]
            candidates = target if isinstance(target, list) else [target]
            for cand in candidates:
                axes = _mesh_axes_for(mesh, cand)
                axes = tuple(a for a in axes if a not in used)
                if not axes:
                    continue
                size = 1
                for a in axes:
                    size *= mesh.shape[a]
                if size > 1 and dim % size == 0:
                    assigned = axes if len(axes) > 1 else axes[0]
                    used.update(axes)
                    break
        parts.append(assigned)
    return P(*parts)


def sharding_for(logical, shape, mesh=None, rules=None) -> NamedSharding:
    mesh = mesh or _CTX.mesh
    return NamedSharding(mesh, spec_for(logical, shape, rules, mesh))


def shard_activation(x: jax.Array, logical: Sequence[Optional[str]]):
    """Annotate an intermediate with a sharding constraint (no-op outside a
    sharding context)."""
    if not active():
        return x
    spec = spec_for(logical, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(_CTX.mesh, spec))


def mesh_batch_axes(mesh: "Mesh", rules: Optional[Rules] = None) -> tuple:
    """Mesh axes the batch dimension maps to under ``rules`` (no context
    needed — used by the serving layer to size device-sharded score batches)."""
    rules = rules or SERVE_RULES
    return _mesh_axes_for(mesh, rules.get("batch"))


def mesh_batch_shards(mesh: "Mesh", rules: Optional[Rules] = None) -> int:
    """How many ways a batch dimension is sharded on ``mesh`` under ``rules``."""
    out = 1
    for a in mesh_batch_axes(mesh, rules):
        out *= mesh.shape[a]
    return out


def data_parallel(fn, mesh: "Mesh", rules: Optional[Rules] = None):
    """Wrap ``fn(params, batch)`` in a data-parallel ``shard_map``: params are
    replicated, the leading (batch) dimension of every ``batch`` leaf — and of
    the output — is sharded over the rules' batch axes.  Callers must pad the
    batch dim to a multiple of :func:`mesh_batch_shards`.  Identity when the
    rules give the mesh no batch axis (e.g. a model-only mesh)."""
    axes = mesh_batch_axes(mesh, rules)
    if not axes:
        return fn
    spec = P(axes if len(axes) > 1 else axes[0])
    return jax.shard_map(fn, mesh=mesh, in_specs=(P(), spec), out_specs=spec,
                         check_vma=False)


def tree_shardings(specs_tree, shapes_tree, mesh=None, rules=None):
    """Map a tree of logical-axis tuples + shapes to NamedShardings."""
    mesh = mesh or _CTX.mesh
    rules = rules or _CTX.rules
    return jax.tree.map(
        lambda logical, shape: sharding_for(logical, shape, mesh, rules),
        specs_tree,
        shapes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x
        ),
    )
