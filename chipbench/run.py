"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  The run makes
its tables, records and oracle weights from ``--seed``, warms up the cell's
shapes (set-up), drives the served query path for ``--seconds`` seconds with
the cell's traffic, compares what the timed path produced with the plain
references, and prints one JSON line as the last line of standard output:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics from a profiler trace
of the window), ``device``, ``breakdown`` (traced runs) and ``checks`` (each
number compared, with its limit).  Without a TPU, or with fewer chips than
the cell asks for, it prints no result and exits with 3.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the TPU runtime's logs go to a fixed /tmp path unless told otherwise
if "TPU_LOG_DIR" not in os.environ:
    os.environ["TPU_LOG_DIR"] = os.path.join(ROOT, "chipbench", ".cache",
                                             "tpu_logs")
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness

    cell = harness.load_cell(args.workload)
    try:
        line = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                           T_START)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
