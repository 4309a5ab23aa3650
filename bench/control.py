#!/usr/bin/env python3
"""Run a cell with its timed path replaced (``faults``) and print the
numbers compared, one JSON line per seed, all seeds in one process.

    python3 bench/control.py --workload <name> --mode control --seeds 1,2,3 --seconds 5

``--mode control`` gives a limit's upper reading: the plain reference,
one precision lower, in the program's place. The benchmark's own runs
never run this. Needs the chips the cell asks for, like ``run.py``.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import faults  # noqa: E402
import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", required=True, choices=sorted({m for _, m in faults.SUBSTITUTES}))
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    cell = harness.find_cell(args.workload)
    why_not = harness.require_chips(cell.chips)
    if why_not:
        print(f"control: {args.workload} {why_not}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        with faults.substitute(cell.traffic["driver"], args.mode):
            out = harness.run_cell(cell, seed, args.seconds, False)
        print(json.dumps({"mode": args.mode, "seed": seed, "correct": out["correct"],
                          "attempted": out["attempted"], "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
