"""Benchmark harness entrypoint (deliverable d): one function per paper
table/figure. Prints ``name,us_per_call,derived`` CSV.

``--compare OLD.json [NEW.json]`` instead diffs two ``BENCH_*.json``
artifacts metric by metric (old, new, delta, percent) — the perf
trajectory check for a PR: run the smoke suite, then compare its fresh
artifact against the committed one. NEW defaults to ``BENCH_<name>.json``
in the current directory, with ``<name>`` taken from OLD's payload.
"""
from __future__ import annotations

import json
import os
import sys
import traceback


def compare_artifacts(old_path: str, new_path: str | None = None) -> int:
    """Print per-metric deltas between two benchmark artifacts.

    Numeric metrics get old/new/delta/percent columns; non-numeric ones
    (bools, lists) print old -> new and are flagged when they changed.
    A key present in only one artifact prints ``n/a`` for the missing
    side and no delta — suites gain and retire metrics across PRs, and
    a comparison against an older artifact must stay readable.
    Returns 1 when either artifact records a failed smoke gate, else 0 —
    regressions in individual metrics are reported, not gated, because
    what counts as "worse" is metric-specific (the suites' own gates
    hold the hard lines)."""
    with open(old_path) as f:
        old = json.load(f)
    if new_path is None:
        if "name" not in old:
            print(f"ERROR: {old_path} has no 'name'; pass NEW.json explicitly",
                  file=sys.stderr)
            return 2
        new_path = f"BENCH_{old['name']}.json"
    with open(new_path) as f:
        new = json.load(f)
    if old.get("name") != new.get("name"):
        print(
            f"WARNING: comparing different suites "
            f"({old.get('name')!r} vs {new.get('name')!r})"
        )
    om, nm = old.get("metrics", {}), new.get("metrics", {})
    keys = sorted(set(om) | set(nm))
    width = max((len(k) for k in keys), default=4)
    print(f"# {old.get('name', '?')}: {old_path} -> {new_path}")
    print(f"{'metric':<{width}}  {'old':>14}  {'new':>14}  {'delta':>14}  {'pct':>8}")
    for k in keys:
        a, b = om.get(k), nm.get(k)
        if k not in om or k not in nm:
            lhs = "n/a" if k not in om else f"{a!r}"
            rhs = "n/a" if k not in nm else f"{b!r}"
            print(f"{k:<{width}}  {lhs:>14}  {rhs:>14}  {'n/a':>14}  {'n/a':>8}")
            continue
        num = (
            isinstance(a, (int, float)) and not isinstance(a, bool)
            and isinstance(b, (int, float)) and not isinstance(b, bool)
        )
        if num:
            d = b - a
            pct = f"{100.0 * d / a:+8.1f}%" if a else "     n/a"
            print(f"{k:<{width}}  {a:>14.6g}  {b:>14.6g}  {d:>+14.6g}  {pct}")
        else:
            mark = "" if a == b else "  CHANGED"
            print(f"{k:<{width}}  {a!r:>14}  {b!r:>14}{mark}")
    po, pn = old.get("passed"), new.get("passed")
    if po is not None or pn is not None:
        print(f"passed: {po} -> {pn}")
    return 0 if pn in (True, None) and po in (True, None) else 1


def main() -> None:
    if "--compare" in sys.argv:
        i = sys.argv.index("--compare")
        paths = sys.argv[i + 1 : i + 3]
        if not paths:
            print("usage: run.py --compare OLD.json [NEW.json]", file=sys.stderr)
            sys.exit(2)
        sys.exit(compare_artifacts(paths[0], paths[1] if len(paths) > 1 else None))
    from benchmarks import (
        bench_hierarchy,
        bench_moe,
        bench_particles,
        bench_partitioner,
        bench_spmv,
    )

    suites = [
        ("kdtree (paper Figs 2-5)", bench_partitioner.bench_kdtree_build),
        ("sfc traversal (Figs 8-10)", bench_partitioner.bench_sfc_traversal),
        ("knapsack (SIII-C)", bench_partitioner.bench_knapsack),
        ("tree vs point partition (SIII-B)", bench_partitioner.bench_tree_vs_point_partition),
        ("dynamic trees (Table I)", bench_partitioner.bench_dynamic),
        ("queries (Figs 12-13)", bench_partitioner.bench_queries),
        ("incremental LB (SIV)", bench_partitioner.bench_migration),
        ("hierarchical reslice (nodes x devices)", bench_hierarchy.bench_hierarchy_rows),
        ("particle N-body + coupled PIC (SV-C)", bench_particles.bench_particles_rows),
        ("spmv tables (Tables II-VII)", bench_spmv.bench_spmv_tables),
        ("spmv execution", bench_spmv.bench_spmv_execution),
        ("moe dispatch (DESIGN S3)", bench_moe.bench_moe_dispatch),
        ("sequence packing", bench_moe.bench_packing),
        ("amortized controller (Alg 3)", bench_moe.bench_amortized_controller),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for title, fn in suites:
        print(f"# --- {title}")
        try:
            for name, us, derived in fn():
                print(f"{name},{us:.1f},{derived}")
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"# SUITE FAILED: {title}", file=sys.stderr)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
