"""Oracle families: each family's plain reference forward and work count.

``load(family)`` imports ``chipbench/oracles/<family>.py``, found by file as
``metrics/<name>.py`` is, so adding a family is adding its file.  The family
is the one ``repro.configs`` registers for the oracle's ``arch``, and the
configuration file's ``oracle.family`` names it too.  Each family module
holds:

- ``check(cfg)``: raises ``ValueError`` where the reference cannot represent
  this ``ModelConfig``.
- ``yes_no_logits(oracle, params, toks, last, yes, no, control=False)``:
  (B, 2) float64 [yes, no] logits at each row's ``last`` position (``oracle``
  is the configuration's oracle section, ``params`` the benchmark's
  weights): a plain float32 forward under ``Precision.HIGHEST``.  With
  ``control`` every matmul runs in float8 e4m3, the control one precision
  below the oracle's bfloat16.
- ``required_flops(o, lens)``, ``required_bytes(o, lens)``: the work of
  scoring pairs of these real lengths, counted the same whatever implements
  it: no padding and no full-vocabulary head.

A family module imports nothing of ``repro``: the reference takes nothing
from the program it is compared with.
"""
from __future__ import annotations

import importlib

FUNCTIONS = ("check", "yes_no_logits", "required_flops", "required_bytes")


def load(family: str):
    """The module of ``family``; ``ValueError`` where there is none."""
    name = f"{__name__}.{family}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
    raise ValueError(f"no reference for oracle family {family!r}: add "
                     f"chipbench/oracles/{family}.py with "
                     f"{', '.join(FUNCTIONS)} (chipbench/oracles/__init__.py)")
