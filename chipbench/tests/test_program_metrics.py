"""The per-layer metrics that read the program's own spans and counters, in
a traced run of the harness at a tiny size on the CPU, for both dispatch
paths: each reads a finite value, and the scorer's counters give the
padding share and the required FLOPs that the benchmark's wrapper counts.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_program_metrics.py -q
"""
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]

from chipbench import harness  # noqa: E402
from chipbench.metrics.scorer_roofline import required_flops  # noqa: E402
from test_harness import tiny_cell  # noqa: E402

PROGRAM_METRICS = ("sample_ms", "oracle_wait_ms", "queue_wait_ms",
                   "dispatcher_starved")
COUNTERS = ("tokens", "token_slots", "causal_pairs", "pairs_scored")


def traced_tiny_run(dispatch, monkeypatch, per_layer, tag):
    """A traced run of the tiny cell with these per-layer metrics, named
    with ``tag`` so that test files running at once trace to directories of
    their own.  Returns its line and what it kept: the scorer, the
    harness's ``Probe``, the metric ``Context``, and (``window``) what the
    scorer's counters rose by between the start and the end of ``_window``,
    in which the wrapper records every block it sees."""
    cell = tiny_cell(dispatch, "fresh")
    cell = dataclasses.replace(cell, name=f"{cell.name}.{tag}",
                               per_layer=per_layer)
    seen = {}
    build, window, probe = harness.build_scorer, harness._window, harness.Probe
    read = harness.read_metric

    def build_scorer(*args, **kwargs):
        seen["scorer"] = build(*args, **kwargs)
        return seen["scorer"]

    def counted_window(*args, **kwargs):
        s = seen["scorer"]
        before = [getattr(s, c) for c in COUNTERS]
        out = window(*args, **kwargs)
        seen["window"] = [getattr(s, c) - b for c, b in zip(COUNTERS, before)]
        return out

    def kept_probe(*args, **kwargs):
        seen["probe"] = probe(*args, **kwargs)
        return seen["probe"]

    def kept_read(name, ctx):
        seen["ctx"] = ctx
        return read(name, ctx)

    monkeypatch.setattr(harness, "build_scorer", build_scorer)
    monkeypatch.setattr(harness, "_window", counted_window)
    monkeypatch.setattr(harness, "Probe", kept_probe)
    monkeypatch.setattr(harness, "read_metric", kept_read)
    line = harness.run(cell, 12345678901, 1.5, True, time.perf_counter(),
                       require_tpu=False)
    return line, seen


@pytest.mark.parametrize("dispatch", ["streaming", "dense"])
def test_program_metrics_read_in_a_traced_run(dispatch, monkeypatch):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    per_layer = [m for m in bench["per_layer"]
                 if m["name"] in PROGRAM_METRICS + ("pad_share",)]
    assert len(per_layer) == len(PROGRAM_METRICS) + 1
    line, seen = traced_tiny_run(dispatch, monkeypatch, per_layer, "metrics")
    assert line["correct"], line["checks"]
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    for name in PROGRAM_METRICS:
        assert name in metrics and math.isfinite(metrics[name]), (name, metrics)
        assert metrics[name] >= 0
    tokens, slots, causal, pairs = seen["window"]
    assert slots > 0
    assert 100.0 * (1.0 - tokens / slots) == pytest.approx(
        metrics["pad_share"], rel=1e-12)
    # oracle_mfu's numerator: the wrapper's blocks against the counters
    from repro.serve import serve_loop

    wrapper = sum(required_flops(seen["ctx"].config["oracle"], lens)
                  for _, _, lens in seen["probe"].blocks)
    program = serve_loop.required_flops(seen["scorer"].cfg, tokens, causal,
                                        pairs)
    assert program == pytest.approx(wrapper, rel=1e-12)
