"""Host spans on the profiler's clock, each tagged with the query or service
window it belongs to.

:func:`span` opens a ``jax.profiler.TraceAnnotation``: while a profiler
session is active the span lands in the trace beside the device's programs,
on the line of the thread that opened it, with its ids as event stats
(``('query_id', 7)``); otherwise it records nothing.  Given a ``timings``
dict and a ``key``, it also adds its ``perf_counter`` length to
``timings[key]``, so repeated spans of one query accumulate.

Ids are bound per thread (a ``contextvars`` context) with :func:`bind`:
``JoinMLEngine.execute`` binds the query's ``query_id`` and the service's
dispatcher binds each window's ``window_id``, so every span opened inside
carries the id without passing it by hand.  :func:`bind` can also make a
query's ``timings`` dict reachable from code that is not handed it
(:func:`bound_timings`).  That is temporary: its one user is
``bas._label_draws``, whose ``(query, draws)`` signature the chip
benchmark's wrapper fixes; once the benchmark stops wrapping it,
``_label_draws`` takes ``timings`` as a parameter and ``bind(timings=)``,
:func:`bound_timings` and the ``run_stratified_pipeline`` / ``_pipeline``
split go.

The names the program opens, and on which thread (``docs/serving.md``
mirrors this list):

- query thread: ``query`` (root, ``JoinMLEngine.execute``), ``stratify``
  (``build_dense_space`` / ``build_streaming_space``), ``pilot``,
  ``allocate``, ``execute`` (the pipeline's stages), ``sample`` (each
  ``sample_stratum`` call; ``timings["sample_s"]``), ``walk_sample`` (the D0
  walk+rejection sampler inside ``sample``; ``walk_s``) holding
  ``walk.blocks`` (the walk steps' device work and fetches;
  ``walk_blocks_s``) and ``walk.draw`` (their host draws; ``walk_draw_s``),
  ``oracle.wait`` (blocked on the oracle's future; ``oracle_wait_s``),
  ``bootstrap``; the cascade path opens ``query``, ``stratify``, ``sample``
  and ``walk_sample`` (with its two) of these;
- the oracle service's dispatcher: ``service.starved`` (waiting on an empty
  queue: starved while a client is attached, else idle),
  ``service.assemble`` (first flush taken to dispatch), ``service.window``
  (``rows``, ``query_ids`` of its flushes) holding ``service.plan``, then
  per scorer call ``tokenize`` and per padded block ``scorer.pad``,
  ``scorer.forward``, ``scorer.fetch``, then ``service.commit``.
"""
from __future__ import annotations

import contextlib
import contextvars
import itertools
import time
from typing import Optional

from jax.profiler import TraceAnnotation

QUERY_SPANS = ("query", "stratify", "pilot", "allocate", "execute", "sample",
               "walk_sample", "walk.blocks", "walk.draw", "oracle.wait",
               "bootstrap")
DISPATCHER_SPANS = ("service.starved", "service.assemble", "service.window",
                    "service.plan", "tokenize", "scorer.pad",
                    "scorer.forward", "scorer.fetch", "service.commit")
SPANS = QUERY_SPANS + DISPATCHER_SPANS

_ids: contextvars.ContextVar[dict] = contextvars.ContextVar(
    "repro_span_ids", default={})
_timings: contextvars.ContextVar[Optional[dict]] = contextvars.ContextVar(
    "repro_span_timings", default=None)
_query_ids = itertools.count(1)


def next_query_id() -> int:
    """The next id of the process-wide query counter (from 1)."""
    return next(_query_ids)


def current_ids() -> dict:
    """The ids bound on this thread (do not mutate)."""
    return _ids.get()


def bound_timings() -> Optional[dict]:
    """The ``timings`` dict bound on this thread, or None."""
    return _timings.get()


@contextlib.contextmanager
def bind(timings: Optional[dict] = None, **ids):
    """Inside the block, every span opened on this thread carries ``ids``
    (on top of those bound outside it), and :func:`bound_timings` returns
    ``timings`` where one is given."""
    id_token = _ids.set({**_ids.get(), **ids})
    t_token = _timings.set(timings) if timings is not None else None
    try:
        yield
    finally:
        if t_token is not None:
            _timings.reset(t_token)
        _ids.reset(id_token)


class span:
    """``with span(name, timings=None, key=None, **ids):`` a profiler span
    carrying the bound ids plus ``ids``; with ``timings`` it also adds its
    length in seconds to ``timings[key]``."""

    __slots__ = ("_ann", "_timings", "_key", "_t0")

    def __init__(self, name: str, timings: Optional[dict] = None,
                 key: Optional[str] = None, **ids):
        bound = _ids.get()
        self._ann = TraceAnnotation(name, **({**bound, **ids} if bound
                                             else ids))
        self._timings, self._key = timings, key

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._timings is not None:
            self._timings[self._key] = (self._timings.get(self._key, 0.0)
                                        + time.perf_counter() - self._t0)
        self._ann.__exit__(*exc)
