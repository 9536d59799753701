"""Persistent XLA compile cache for the entry points.

Called by the launchers (``repro.launch.serve``, ``chip_smoke.py``) before
their first compile, never at library import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is set
here; otherwise the cache lives at ``<checkout>/.jax_cache``, a fixed path,
so the cache key (which includes the path) matches from one run to the next.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache(checkout: os.PathLike = CHECKOUT) -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(Path(checkout) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
