"""The trace reduction on a small trace recorded on a TPU v5e: three scorer
forwards (``jit_fwd``, batch 32 x 64 tokens), one ``sim_sweep`` of 512 x
4,096 rows and one walk-sampler product, under the spans ``query`` >
``scorer.forward`` / ``stratify`` / ``bootstrap``.

    python -m pytest chipbench/tests/test_trace.py -q
"""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import trace  # noqa: E402

DATA = Path(__file__).parent / "data"
SPANS = ("query", "stratify", "scorer.forward", "bootstrap")


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(DATA, SPANS, window_name="query")


def test_names():
    assert trace.op_name("%sim_sweep.1 = (s32[2]) custom-call(f32[4] %e1.1)") == "sim_sweep"
    assert trace.op_name("%copy-start = (bf16[2]) copy-start(%p)") == "copy-start"
    assert trace.program_name("jit_fwd(1247445615063903104)") == "jit_fwd"


def test_union_and_gaps():
    busy = trace.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert busy == [[0, 3], [5, 9]]
    assert trace.gaps(busy, -1, 12) == [(-1, 0), (3, 5), (9, 12)]
    named = trace.name_gaps([(3, 5), (9, 12)],
                            [(0, 20, "query"), (2, 6, "stratify")])
    assert named == {"stratify": 2, "query": 3}


def test_recorded_trace(summary):
    assert summary.devices == 1
    assert 0 < summary.busy_s < summary.window_s
    # three forwards of about 4.1 ms, the first clipped at the window's
    # start, and one sweep of about 1.8 ms
    assert summary.program_s("jit_fwd") == pytest.approx(0.012125359, rel=1e-6)
    assert summary.op_count("sim_sweep") == 1
    assert summary.op_s("sim_sweep") == pytest.approx(0.001819, rel=0.01)
    assert summary.program_s("jit_sim_sweep_pallas") >= summary.op_s("sim_sweep")
    # every idle gap inside the window is named by an open span
    assert sum(summary.idle.values()) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-6)
    assert set(summary.idle) <= set(SPANS)
    b = summary.breakdown()
    assert b["device_ops"][0][0] == "jit_fwd"
    assert len(b["idle_gaps"]) <= 10
