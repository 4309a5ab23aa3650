#!/usr/bin/env python3
"""Run one benchmark cell and print its result as the last stdout line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name
from ``BENCHMARK.json`` (see ``bench/harness.py``). The run needs a TPU
with as many chips as the cell asks for: without one it exits 2 and
prints no result. ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiler trace of the window.
The numbers compared for ``correct`` are printed beside their limits as
the last lines of stderr and under ``checks``, the result's last key.
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

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.find_cell(args.workload)
    why_not = harness.require_chips(cell.chips)
    if why_not:
        print(f"bench: {args.workload} {why_not}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace), t0=T0)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
