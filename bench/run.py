"""Run one cell of the benchmark and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``workloads`` of ``BENCHMARK.json``) is set up, warmed up and
measured for ``--seconds`` on the chips it asks for; its answers are
then checked against the float64 reference. The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the end-to-end metrics, or with ``--trace 1`` the per-layer
ones), ``device`` and, traced, ``breakdown``; ``checks`` comes last, each
compared number beside its limit, as do the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    from harness.cell import Cell, run
    from harness.device import require_tpu

    cell = Cell(args.workload)
    devices = require_tpu(cell.chips)
    result = run(cell, args.seed, args.seconds, bool(args.trace), devices)
    print(json.dumps(finite(result), allow_nan=False), flush=True)
    return 0


def finite(obj):
    """The result with every non-finite number (a compared number the run
    could not produce reads infinite) as ``null``, so the line is JSON."""
    if isinstance(obj, dict):
        return {k: finite(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [finite(v) for v in obj]
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    return obj


if __name__ == "__main__":
    sys.exit(main())
