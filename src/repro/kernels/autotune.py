"""Block-shape autotuner for the fused similarity kernels.

The sim_sweep/sim_hist grids are parameterised by a (row-block, col-block)
schedule.  The historical defaults (256, 256) are sensible on one TPU
generation but leave throughput on the table on others — and hardcoding a
single shape makes per-accelerator perf gates (``bench_diff
--require-compiled``) brittle.  This module measures a small candidate set
on **first use on a TPU** per (op, device kind, dtype, shape bucket),
caches the winner in memory and on disk, and the ops route their block
choice through :func:`schedule`.

Behaviour contract:

* Off the TPU (the Pallas interpreter) :func:`schedule` returns ``None``
  immediately — zero measurement, zero behaviour change, so CI and the
  numerics tests never depend on tuning.
* On a TPU whose device kind is not in :data:`DEVICE_KINDS` it raises: the
  candidates are only known to compile (fit VMEM) for the kinds listed
  there, which ``tests/test_tpu_compile.py`` checks for every candidate.
* Shapes are bucketed to powers of two; one measurement serves every shape
  in the bucket.
* The disk cache is a single JSON file (``autotune.json``), written
  atomically next to the index store when one is configured
  (:meth:`repro.core.index.IndexStore`), so tuned schedules survive process
  restarts and ship with the index artifacts they accelerate.
* A candidate that fails to compile or run raises; nothing falls back to
  a default schedule.  A candidate that does not fit is removed from
  :data:`CANDIDATES`, not caught at run time.

The module deliberately avoids importing jax at module scope so that
configuring the cache path from the (jax-free) index layer stays cheap.
"""
from __future__ import annotations

import json
import os
import threading
from typing import Optional

# (row-block, col-block) candidates, all power-of-two so the ops' padding
# math and the kernel's pairwise reduction stay exact, and every one
# compile-checked for each kind in DEVICE_KINDS (tests/test_tpu_compile.py)
CANDIDATES = ((256, 256), (128, 256), (256, 128), (512, 256), (256, 512))
DEVICE_KINDS = ("TPU v5 lite",)

_lock = threading.Lock()
_memory: dict[str, tuple[int, int]] = {}
_path: Optional[str] = None
_loaded = False


def configure(path: Optional[str]) -> None:
    """Point the disk cache at ``path`` (a JSON file).  Existing entries are
    merged into the in-memory cache lazily on first :func:`schedule` call."""
    global _path, _loaded
    with _lock:
        _path = os.fspath(path) if path is not None else None
        _loaded = False


def reset() -> None:
    """Drop the in-memory cache and disk path (tests)."""
    global _path, _loaded
    with _lock:
        _memory.clear()
        _path = None
        _loaded = False


def cache_info() -> dict[str, tuple[int, int]]:
    with _lock:
        return dict(_memory)


def _bucket(x: int) -> int:
    return max(8, 1 << (max(int(x), 1) - 1).bit_length())


def _key(op: str, device_kind: str, precision: str,
         m: int, n: int, d: int) -> str:
    return (f"{op}/{device_kind.replace(' ', '_')}/{precision}/"
            f"{_bucket(m)}x{_bucket(n)}x{_bucket(d)}")


def _load_locked() -> None:
    global _loaded
    if _loaded:
        return
    _loaded = True
    if _path is None or not os.path.exists(_path):
        return
    try:
        with open(_path) as f:
            disk = json.load(f)
        for k, bmn in disk.items():
            _memory.setdefault(k, (int(bmn[0]), int(bmn[1])))
    except (OSError, ValueError, TypeError, IndexError):
        pass  # corrupt cache: re-measure and overwrite


def _save_locked() -> None:
    if _path is None:
        return
    try:
        os.makedirs(os.path.dirname(_path) or ".", exist_ok=True)
        tmp = f"{_path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({k: list(v) for k, v in sorted(_memory.items())}, f,
                      indent=1)
        os.replace(tmp, _path)
    except OSError:
        pass  # cache is best-effort; never fail the sweep over it


def _time_candidate(op: str, m: int, n: int, d: int, precision: str,
                    bm: int, bn: int) -> float:
    """Wall-time one schedule on synthetic data at the bucket shape (one
    warmup + compile, then best of two timed reps)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(0)
    e1 = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
    e2 = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    if op == "sim_hist":
        from .sim_hist.kernel import sim_hist_pallas

        def run():
            return sim_hist_pallas(e1, e2, bm=bm, bn=bn, interpret=False)
    elif precision == "int8":
        from .sim_sweep.kernel import sim_sweep_q_pallas

        q1, q2 = (jnp.clip(jnp.round(e * 32), -127, 127).astype(jnp.int8)
                  for e in (e1, e2))
        r1, r2 = jnp.ones((m, 1), jnp.float32), jnp.ones((1, n), jnp.float32)

        def run():
            return sim_sweep_q_pallas(q1, q2, r1, r2, bm=bm, bn=bn,
                                      interpret=False)
    else:
        from .sim_sweep.kernel import sim_sweep_pallas

        dtype = jnp.bfloat16 if precision == "bf16" else jnp.float32

        def run():
            return sim_sweep_pallas(e1, e2, bm=bm, bn=bn, interpret=False,
                                    compute_dtype=dtype)

    jax.block_until_ready(run())  # compile + warmup
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        best = min(best, time.perf_counter() - t0)
    return best


def _measure(op: str, m: int, n: int, d: int, precision: str,
             candidates) -> tuple[int, int]:
    """Return the fastest (bm, bn) candidate; a failing candidate raises."""
    times = {c: _time_candidate(op, m, n, d, precision, *c)
             for c in candidates}
    return min(times, key=times.get)


def schedule(op: str, m: int, n: int, d: int, precision: str = "fp32",
             device_kind: Optional[str] = None) -> Optional[tuple[int, int]]:
    """Tuned (row-block, col-block) for ``op`` at this shape bucket, or
    ``None`` off the TPU (callers keep their defaults).  ``device_kind``
    defaults to the first device's.  First use per bucket measures
    :data:`CANDIDATES` and persists the winner."""
    if device_kind is None:
        import jax

        if jax.default_backend() != "tpu":
            return None
        device_kind = jax.devices()[0].device_kind
    if device_kind not in DEVICE_KINDS:
        raise RuntimeError(
            f"no block schedules are verified for device kind "
            f"{device_kind!r} (known: {DEVICE_KINDS}); compile-check "
            "CANDIDATES for it and add it to DEVICE_KINDS"
        )
    key = _key(op, device_kind, precision, m, n, d)
    with _lock:
        _load_locked()
        if key in _memory:
            return _memory[key]
    bm_cap, bn_cap = _bucket(m), _bucket(n)
    cands = [(bm, bn) for bm, bn in CANDIDATES if bm <= bm_cap and bn <= bn_cap]
    if not cands:
        cands = [(min(CANDIDATES[0][0], bm_cap), min(CANDIDATES[0][1], bn_cap))]
    won = _measure(op, _bucket(m), _bucket(n), _bucket(d), precision, cands)
    with _lock:
        _memory[key] = won
        _save_locked()
    return won
