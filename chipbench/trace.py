"""Reduction of a profiler trace to what the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``.  Device planes are ``/device:TPU:<n>``; on each,
the ``XLA Ops`` line holds one event per operation that ran and the
``XLA Modules`` line one event per program execution.  Host planes carry
the harness's ``TraceAnnotation`` spans on the lines of the threads that
opened them; the ``window`` span bounds the measured window.

- busy: the union of the op intervals inside the window, per device,
  averaged over the devices that ran anything;
- program and op time: the summed device durations by name;
- idle gaps: the complement of busy inside the window, each named by the
  innermost host span open at the gap's middle (the one opened last), and
  summed by that name.
"""
from __future__ import annotations

import dataclasses
import heapq
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    devices: int
    programs: dict          # program name -> device seconds
    ops: dict               # op name -> [device seconds, count]
    idle: dict              # host span name -> idle device seconds

    def program_s(self, name: str) -> float:
        return self.programs.get(name, 0.0)

    def op_s(self, name: str) -> float:
        return self.ops.get(name, [0.0, 0])[0]

    def op_count(self, name: str) -> int:
        return self.ops.get(name, [0.0, 0])[1]

    def breakdown(self) -> dict:
        progs = sorted(self.programs.items(), key=lambda kv: -kv[1])[:TOP]
        idle = sorted(self.idle.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[n, s] for n, s in progs],
                "idle_gaps": [[n, s] for n, s in idle]}


def program_name(event_name: str) -> str:
    """``jit_fwd(1247...)`` -> ``jit_fwd``."""
    return event_name.split("(", 1)[0]


def op_name(event_name: str) -> str:
    """``%sim_sweep.1 = (s32[...]) custom-call(...)`` -> ``sim_sweep``: the
    HLO instruction's name without its ``%`` and numeric suffix."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    base, dot, num = head.rpartition(".")
    return base if dot and num.isdigit() else head


def find_xplane(trace_dir) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def union(intervals: list) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def gaps(busy: list, lo: float, hi: float) -> list:
    """The parts of [lo, hi] that the disjoint sorted ``busy`` leaves."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def name_gaps(gap_list: list, spans: list) -> dict:
    """Sum each gap's length under the innermost span (latest opened) open
    at its middle; ``spans`` holds (start, end, name)."""
    spans = sorted(spans)
    mids = sorted(((s + e) / 2, e - s) for s, e in gap_list)
    out, heap, i = {}, [], 0
    for m, length in mids:
        while i < len(spans) and spans[i][0] <= m:
            s, e, n = spans[i]
            heapq.heappush(heap, (-s, e, n))
            i += 1
        while heap and heap[0][1] < m:
            heapq.heappop(heap)
        name = heap[0][2] if heap else "no span"
        out[name] = out.get(name, 0.0) + length
    return out


def reduce(trace_dir, span_names, window_name: str = "window") -> Summary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(find_xplane(trace_dir)))
    spans, window = [], None
    device = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == window_name:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    if ev.name in span_names:
                        spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                      ev.name))
        elif plane.name.startswith("/device:TPU:") or plane.name.startswith(
                "/device:CPU:"):
            device.append(plane)
    if window is None:
        raise ValueError(f"the trace holds no {window_name!r} span")
    lo, hi = window
    busy_total, n_dev, idle = 0.0, 0, {}
    programs, ops = {}, {}
    for plane in device:
        intervals = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= lo or s >= hi:
                    continue
                d = (min(e, hi) - max(s, lo)) / 1e9
                if line.name == MODULES_LINE:
                    name = program_name(ev.name)
                    programs[name] = programs.get(name, 0.0) + d
                else:
                    acc = ops.setdefault(op_name(ev.name), [0.0, 0])
                    acc[0] += d
                    acc[1] += 1
                    intervals.append((max(s, lo), min(e, hi)))
        if not intervals:
            continue
        n_dev += 1
        busy = union(intervals)
        busy_total += sum(e - s for s, e in busy) / 1e9
        for name, sec in name_gaps(gaps(busy, lo, hi), spans).items():
            idle[name] = idle.get(name, 0.0) + sec / 1e9
    n = max(n_dev, 1)
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy_total / n,
                   devices=n_dev, programs=programs, ops=ops,
                   idle={k: v / n for k, v in idle.items()})
