"""Readings that the correctness limits are set from, on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 11,12,... \
        --seconds 8 [--controls 3]

Runs the cell once per seed in this one process (each run builds its own
tables, records and weights from its seed) with a short window at the
cell's own load, and prints one JSON line per run: the program's numbers
(``checks``) and, for the first ``--controls`` seeds, the controls' numbers
(the reference one precision lower in the program's place).  The largest
program reading over the seeds is a limit's lower reading, the smallest
control reading its upper one (PERF.md).  The readings the limits were
set from are kept, one ``CALIBRATE`` line a run, in
``chipbench/calibration/<cell>.jsonl``.  The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--controls", type=int, default=3)
    args = ap.parse_args(argv)

    from chipbench import harness

    cell = harness.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        line = harness.run(cell, seed, args.seconds, False, t0,
                           controls=i < args.controls)
        out = {"seed": seed, "correct": line["correct"],
               "attempted": line["attempted"],
               "checks": {k: v["value"] for k, v in line["checks"].items()},
               "controls": line.get("controls"),
               "metrics": {k: v["value"] for k, v in line["metrics"].items()},
               "peak": line["device"]["memory_peak_bytes"],
               "seconds": time.perf_counter() - t0}
        print("CALIBRATE " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
