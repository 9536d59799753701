"""Observability for the serving data plane: pluggable metric trackers and
the typed per-query telemetry tree.

- :mod:`repro.obs.tracker` — the :class:`Tracker` protocol plus noop,
  in-memory, and JSON-lines implementations (counters, gauges, streaming
  p50/p99 histograms with bounded memory).
- :mod:`repro.obs.telemetry` — :class:`QueryTelemetry`, the typed successor
  to ``QueryResult.detail``, with a deprecation-shimmed dict view.
- :mod:`repro.obs.prometheus` — OpenMetrics text rendering of any
  ``snapshot()`` dict plus a stdlib HTTP ``/metrics`` exporter.
- :mod:`repro.obs.spans` — host spans on the profiler's clock, tagged with
  the query or service window they belong to.
"""
from .prometheus import MetricsExporter, render_openmetrics
from .telemetry import (
    CascadeTelemetry,
    DispatchTelemetry,
    IndexTelemetry,
    OracleTelemetry,
    QueryTelemetry,
    StoreTelemetry,
    StratifyTelemetry,
    TelemetryView,
)
from .tracker import (
    NULL_TRACKER,
    InMemoryTracker,
    JsonlTracker,
    NoopTracker,
    StreamingHistogram,
    Tracker,
    make_tracker,
    merge_snapshots,
)

__all__ = [
    "CascadeTelemetry",
    "DispatchTelemetry",
    "IndexTelemetry",
    "InMemoryTracker",
    "JsonlTracker",
    "MetricsExporter",
    "NULL_TRACKER",
    "NoopTracker",
    "OracleTelemetry",
    "QueryTelemetry",
    "StoreTelemetry",
    "StratifyTelemetry",
    "StreamingHistogram",
    "TelemetryView",
    "Tracker",
    "make_tracker",
    "merge_snapshots",
    "render_openmetrics",
]
