"""The observability layer: trackers, the typed telemetry tree, and the
unified ``snapshot()`` stats surface.

Contracts under test: :class:`StreamingHistogram` quantiles reflect the
*recent* window while count/mean stay lifetime; every tracker folds counters,
gauges, and observations into one flat ``{dotted.name: float}`` snapshot;
``JsonlTracker`` additionally writes one parseable JSON line per signal;
``QueryTelemetry`` round-trips every legacy ``detail`` dict bit-for-bit
through ``from_detail``/``as_detail``; ``QueryResult.detail`` survives as a
deprecation-warned write-through view; and service + stores expose one merged
``snapshot()`` namespace.
"""
import json
import warnings

import numpy as np
import pytest

from repro.core import FnOracle, IndexStore, QueryResult
from repro.core.types import ConfidenceInterval
from repro.obs import (
    InMemoryTracker,
    JsonlTracker,
    NoopTracker,
    QueryTelemetry,
    StreamingHistogram,
    Tracker,
    make_tracker,
    merge_snapshots,
)
from repro.serve.label_store import LabelStore
from repro.serve.oracle_service import OracleService


# ----------------------------------------------------------------------------
# StreamingHistogram
# ----------------------------------------------------------------------------

def test_histogram_quantiles_track_recent_window_only():
    """Quantiles come from the last-N ring, lifetime stats from everything:
    after 1000 observations with window=100, p50 sits in the last hundred
    values while count/mean/max still cover all thousand."""
    h = StreamingHistogram(window=100)
    for v in range(1, 1001):                       # 1, 2, ..., 1000
        h.observe(float(v))
    assert h.count == 1000
    assert h.mean == pytest.approx(500.5)
    assert h.vmin == 1.0 and h.vmax == 1000.0
    assert 901.0 <= h.quantile(0.5) <= 1000.0      # recent window only
    assert h.quantile(0.0) == 901.0
    assert h.quantile(1.0) == 1000.0
    assert h.recent_mean() == pytest.approx(950.5)


def test_histogram_snapshot_names_and_empty():
    h = StreamingHistogram(window=8)
    assert h.snapshot("x") == {}                   # nothing observed: no keys
    h.observe(2.0)
    h.observe(4.0)
    snap = h.snapshot("service.window.assembly_ms")
    assert set(snap) == {
        "service.window.assembly_ms.count",
        "service.window.assembly_ms.mean",
        "service.window.assembly_ms.p50",
        "service.window.assembly_ms.p99",
        "service.window.assembly_ms.max",
    }
    assert snap["service.window.assembly_ms.count"] == 2.0
    assert snap["service.window.assembly_ms.mean"] == 3.0
    assert snap["service.window.assembly_ms.max"] == 4.0
    with pytest.raises(ValueError):
        StreamingHistogram(window=0)


# ----------------------------------------------------------------------------
# trackers
# ----------------------------------------------------------------------------

def test_in_memory_tracker_snapshot_is_flat_dotted_floats():
    t = InMemoryTracker()
    assert isinstance(t, Tracker)                  # satisfies the protocol
    t.count("transport.reconnects")
    t.count("transport.reconnects", 2)
    t.gauge("transport.inflight", 5)
    for ms in (1.0, 2.0, 3.0, 4.0):
        t.observe("transport.rtt_ms", ms)
    t.event("service.worker.dead", worker="h:1")
    snap = t.snapshot()
    assert snap["transport.reconnects"] == 3
    assert snap["transport.inflight"] == 5.0
    assert snap["transport.rtt_ms.count"] == 4.0
    assert snap["transport.rtt_ms.mean"] == 2.5
    assert snap["service.worker.dead.events"] == 1.0
    assert all(isinstance(v, float) or isinstance(v, int)
               for v in snap.values())
    assert t.histogram("transport.rtt_ms").count == 4
    assert t.histogram("never.observed") is None


def test_noop_tracker_is_protocol_and_empty():
    t = NoopTracker()
    assert isinstance(t, Tracker)
    t.count("a")
    t.gauge("b", 1.0)
    t.observe("c", 2.0)
    t.event("d", x=1)
    assert t.snapshot() == {}
    t.close()


def test_jsonl_tracker_writes_parseable_lines(tmp_path):
    path = tmp_path / "tracker.jsonl"
    t = JsonlTracker(path, flush_every=1)
    t.count("service.windows")
    t.observe("service.shard.local_ms", 1.5)
    t.event("service.worker.rejoined", worker="h:2")
    snap = t.snapshot()                            # in-memory view also live
    assert snap["service.windows"] == 1
    t.close()
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert [r["kind"] for r in lines] == ["count", "observe", "event"]
    assert lines[1]["name"] == "service.shard.local_ms"
    assert lines[1]["value"] == 1.5
    assert lines[2]["worker"] == "h:2"
    assert all("ts" in r for r in lines)
    t.count("after.close")                         # silently dropped, no raise


def test_make_tracker_factory(tmp_path):
    assert isinstance(make_tracker("none"), NoopTracker)
    assert isinstance(make_tracker(None), NoopTracker)
    assert isinstance(make_tracker("memory"), InMemoryTracker)
    jt = make_tracker("jsonl", path=tmp_path / "t.jsonl")
    assert isinstance(jt, JsonlTracker)
    jt.close()
    with pytest.raises(ValueError):
        make_tracker("jsonl")                      # needs a path
    with pytest.raises(ValueError):
        make_tracker("statsd")


def test_merge_snapshots_later_parts_win():
    assert merge_snapshots({"a": 1.0}, None, {"a": 2.0, "b": 3.0}) == {
        "a": 2.0, "b": 3.0,
    }


# ----------------------------------------------------------------------------
# QueryTelemetry <-> legacy detail dict
# ----------------------------------------------------------------------------

_LEGACY_DETAIL = {
    "mode": "bas",
    "beta": [0.5, 0.5],
    "num_strata": 4,
    "stratum_sizes": [10, 20, 30, 40],
    "pilot_n": [5, 5, 5, 5],
    "est_mse": 0.002,
    "stratify": {
        "path": "sweep",
        "index_hit": True,
        "index_version": 3,
        "delta_blocks": 2,
        "sweep_tiles": 7,
    },
    "timings": {"stratify_s": 0.1, "sample_s": 0.2},
    "oracle": {
        "calls": 100,
        "requests": 150,
        "batches": 4,
        "charged": 90,
        "store_hits": 10,
        "store_charge_saved": 10,
        "dedup_ratio": 0.33,
    },
    "dispatch": {
        "path": "sweep",
        "dense_weight_bytes": 1024,
        "max_dense_weight_bytes": 4096,
        "n_tuples": 10000,
        "sweep_precision": "bf16",
        "index_store": True,
    },
}


def test_telemetry_round_trips_legacy_detail_exactly():
    t = QueryTelemetry.from_detail(_LEGACY_DETAIL)
    assert t.mode == "bas"
    assert t.oracle.calls == 100
    assert t.store.hits == 10                      # split out of oracle stats
    assert t.index.hit is True and t.index.version == 3
    assert t.index.build_ms is None                # omitted key stays omitted
    assert t.stratify.path == "sweep"
    assert t.stratify.extra == {"sweep_tiles": 7}  # producer payload kept
    assert t.dispatch.sweep_precision == "bf16"
    assert t.as_detail() == _LEGACY_DETAIL


def test_telemetry_round_trips_sparse_details():
    for d in ({}, {"mode": "exact"},
              {"mode": "wwj", "weights": [1, 2]},
              {"oracle": {"calls": 1, "requests": 1, "batches": 1,
                          "charged": 1, "dedup_ratio": 0.0}},
              {"stratify": {"path": "dense-sort"}}):
        assert QueryTelemetry.from_detail(d).as_detail() == d


def test_query_result_detail_is_deprecated_write_through_view():
    res = QueryResult(1.0, ConfidenceInterval(0.5, 1.5, 0.95), 10,
                      detail=dict(_LEGACY_DETAIL))
    assert res.telemetry.oracle.requests == 150

    import repro.obs.telemetry as telem
    telem._warned = False                          # re-arm the one-shot warn
    with pytest.warns(DeprecationWarning):
        view = res.detail
    assert view["mode"] == "bas"
    assert view["oracle"]["calls"] == 100
    assert "dispatch" in view and "nonexistent" not in view
    assert dict(view) == _LEGACY_DETAIL

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        res.detail["mode"] = "exact"               # top-level write-through
        assert res.telemetry.mode == "exact"
        res.detail["custom"] = 42                  # unknown keys -> extra
        assert res.telemetry.extra["custom"] == 42
        del res.detail["stratify"]
        assert res.telemetry.stratify is None and res.telemetry.index is None
        with pytest.raises(KeyError):
            del res.detail["never-there"]


def test_query_result_rejects_detail_and_telemetry_together():
    t = QueryTelemetry(mode="bas")
    with pytest.raises(TypeError):
        QueryResult(1.0, ConfidenceInterval(0.5, 1.5, 0.95), 1,
                    detail={"mode": "bas"}, telemetry=t)
    res = QueryResult(1.0, ConfidenceInterval(0.5, 1.5, 0.95), 1, telemetry=t)
    assert res.telemetry is t


# ----------------------------------------------------------------------------
# the unified snapshot() surface
# ----------------------------------------------------------------------------

def test_store_snapshots_use_dotted_namespaces(tmp_path):
    ls = LabelStore()
    snap = ls.snapshot()
    assert "label_store.hit_rate" in snap
    assert "label_store.entries" in snap
    assert all(k.startswith("label_store.") for k in snap)
    assert all(isinstance(v, float) for v in snap.values())

    ix = IndexStore(root=str(tmp_path))
    snap = ix.snapshot()
    assert "index_store.warm_hits" in snap
    assert all(k.startswith("index_store.") for k in snap)


def test_service_snapshot_merges_tracker_stores_and_counters():
    tracker = InMemoryTracker()
    with OracleService(max_wait_ms=1.0, label_store=LabelStore(),
                       tracker=tracker) as svc:
        o = FnOracle(lambda idx: (idx.sum(axis=1) % 2).astype(np.float64))
        o.bind_sizes((100, 100))
        svc.attach(o)
        o.label(np.array([[1, 2], [3, 4], [3, 4]]))
        svc.detach(o)
        snap = svc.snapshot()
    assert snap["service.windows"] >= 1.0
    assert snap["service.segments"] >= 1.0
    assert 0.0 < snap["service.window.fill_ratio_recent"] <= 1.0
    assert "service.window.dedup_ratio" in snap
    assert "label_store.hit_rate" in snap          # store merged in
    assert "service.window.assembly_ms.p50" in snap  # tracker series merged
    assert "service.shard.local_ms.p99" in snap
    assert "service.class.default.flush_ms.count" in snap
    assert all(isinstance(v, float) for v in snap.values())


def test_noop_tracker_service_snapshot_still_has_base_keys():
    """snapshot() works without instrumentation: base counters and store
    namespaces are present even when the tracker records nothing."""
    with OracleService(max_wait_ms=1.0) as svc:
        snap = svc.snapshot()
    assert snap["service.windows"] == 0.0
    assert snap["service.admission.rejected"] == 0.0
    assert snap["service.worker.live"] == 0.0
    assert not any(k.endswith(".p50") for k in snap)


def test_launcher_prints_service_class_histograms(capsys):
    """The launcher shutdown print surfaces one line per deadline/query
    class, fed from the ``service.class.*`` snapshot keys an attached class
    generates (flush-latency percentiles + the per-class admission EWMA)."""
    from repro.launch.serve import _print_service_stats
    from repro.serve.oracle_service import OracleService

    tracker = InMemoryTracker()
    with OracleService(max_wait_ms=1.0, tracker=tracker) as svc:
        o = FnOracle(lambda idx: np.ones(len(idx), np.float64))
        o.bind_sizes((100, 100))
        svc.attach(o, deadline_ms=60_000.0, query_class="tight")
        o.label(np.array([[1, 2], [3, 4]]))
        snap = svc.snapshot()

    # the attached class produced its snapshot keys...
    assert "service.class.tight.flush_ms.p50" in snap
    assert "service.class.tight.flush_ms.p99" in snap
    assert snap["service.class.tight.rate_rows_per_s"] > 0.0
    # ...and the shutdown print renders them
    _print_service_stats("service", snap)
    out = capsys.readouterr().out
    assert "class 'tight':" in out
    assert "p50=" in out and "p99=" in out and "rate=" in out


# ----------------------------------------------------------------------------
# OpenMetrics exporter: snapshot() dicts -> Prometheus scrape surface
# ----------------------------------------------------------------------------

def test_render_openmetrics_contract():
    """Rendering mangles dotted names, types every sample as a gauge, drops
    non-finite values, and terminates with # EOF."""
    from repro.obs import render_openmetrics

    snap = {
        "service.window.fill_ratio": 0.25,
        "service.shard.rate.127.0.0.1:9000": 1234.5,
        "label_store.hits": 7,
        "bad.value": float("nan"),
        "9starts.with.digit": 1.0,
    }
    body = render_openmetrics(snap)
    lines = body.splitlines()
    assert lines[-1] == "# EOF" and body.endswith("\n")
    assert "# TYPE repro_service_window_fill_ratio gauge" in lines
    assert "repro_service_window_fill_ratio 0.25" in lines
    # ':' survives (legal in prometheus names); '.' does not
    assert "repro_service_shard_rate_127_0_0_1:9000 1234.5" in lines
    assert "repro_label_store_hits 7.0" in lines
    assert not any("bad_value" in ln for ln in lines)       # NaN dropped
    assert "_9starts_with_digit 1.0" in [
        ln for ln in lines if "digit" in ln and "TYPE" not in ln
    ][0]
    # every sample line is parseable as "name value"
    for ln in lines:
        if not ln.startswith("#"):
            name, val = ln.split(" ")
            float(val)


def test_metrics_exporter_http_roundtrip():
    """The /metrics endpoint serves the merged live snapshots with the
    OpenMetrics content type; a failing source is skipped, not fatal."""
    import urllib.request

    from repro.obs import MetricsExporter

    tracker = InMemoryTracker()
    tracker.count("scrapes", 3)

    def broken():
        raise RuntimeError("wedged store")

    with OracleService(max_wait_ms=1.0, tracker=tracker) as svc:
        o = FnOracle(lambda idx: np.ones(len(idx), np.float64))
        o.bind_sizes((100, 100))
        svc.attach(o)
        o.label(np.array([[1, 2], [3, 4]]))
        with MetricsExporter([svc.snapshot, broken], port=0) as exp:
            host, port = exp.address
            with urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=10
            ) as resp:
                assert resp.status == 200
                ctype = resp.headers["Content-Type"]
                body = resp.read().decode("utf-8")
        assert ctype.startswith("application/openmetrics-text")
        assert body.rstrip().endswith("# EOF")
        assert "repro_service_rows_labelled 2.0" in body
        assert "repro_scrapes 3.0" in body
        svc.detach(o)


def test_metrics_exporter_404_off_path():
    from repro.obs import MetricsExporter
    import urllib.error
    import urllib.request

    with MetricsExporter([lambda: {"x": 1.0}], port=0) as exp:
        host, port = exp.address
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"http://{host}:{port}/nope", timeout=10)
