"""The control of the correctness check: the float64 reference put in the
program's place and computed in bfloat16, on several seeds of one cell.

    python bench/control.py --workload <name> --seeds 11,12,13 --seconds 5

Each seed runs the cell as ``bench/run.py`` does, but the answers that
are checked come from the bfloat16 reference. It prints each seed's
compared numbers beside their limits, and exits 0 only if the check
found every seed's answers wrong, as it must.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    from harness.cell import Cell, run
    from harness.device import require_tpu

    cell = Cell(args.workload)
    devices = require_tpu(cell.chips)
    caught = True
    for seed in (int(s) for s in args.seeds.split(",")):
        result = run(cell, seed, args.seconds, False, devices, control=True)
        caught &= not result["correct"]
        line = {"seed": seed, "correct": result["correct"], "checks": result["checks"]}
        print(json.dumps(line), flush=True)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
