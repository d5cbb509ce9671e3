"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--json OUT]

Output: ``name,us_per_call,derived`` CSV rows on stdout; with ``--json``
the same rows plus per-suite status land in OUT as JSON (the machine-
readable form the BENCH_*.json perf trajectory accumulates). Exits
non-zero when any suite fails.

Roofline numbers (EXPERIMENTS.md §Roofline) come from launch/dryrun.py,
which needs its own 512-device process — not run from here.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback

from . import common


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None,
                   help="run only suites whose name contains this substring")
    p.add_argument("--json", default=None, metavar="OUT",
                   help="write machine-readable results to this path")
    p.add_argument("--plan-cache", default=None, metavar="DIR",
                   help="persistent compiled-plan artifact dir (DESIGN.md "
                        "§14); equivalent to REPRO_PLAN_CACHE in the env")
    p.add_argument("--aot", action="store_true",
                   help="compile-farm mode: pre-populate the plan cache "
                        "with every bench program's negotiated geometries "
                        "and partitioned plans, then exit — a subsequent "
                        "run (or any worker sharing the dir) warm-starts "
                        "with zero negotiations (benchmarks/bench_aot.py)")
    args = p.parse_args()

    if args.plan_cache:
        from repro.core.artifact import set_plan_cache
        set_plan_cache(args.plan_cache)
    if args.aot:
        from . import bench_aot
        n = bench_aot.precompile()
        print(f"aot: published {n} compiled-plan artifacts", file=sys.stderr)
        return

    from . import (bench_aot, bench_blocksweep, bench_channels,
                   bench_core_overhead, bench_fusion, bench_graph,
                   bench_hotpath, bench_memhier, bench_obs, bench_opcount,
                   bench_prefix, bench_regions, bench_sched, bench_slo,
                   bench_sort, bench_stream)
    suites = {
        "fig3_blocksweep": bench_blocksweep.main,
        "fig4_stream": bench_stream.main,
        "table2_core_overhead": bench_core_overhead.main,
        "sec431_sort": bench_sort.main,
        "sec432_prefix": bench_prefix.main,
        "sec6_opcount": bench_opcount.main,
        "fusion_programs": bench_fusion.main,
        "sec31_memhier": bench_memhier.main,
        "sec6_graph_compiler": bench_graph.main,
        "sec12_hotpath": bench_hotpath.main,
        "sec13_sched": bench_sched.main,
        "sec14_aot": bench_aot.main,
        "sec15_obs": bench_obs.main,
        "sec16_regions": bench_regions.main,
        "sec18_channels": bench_channels.main,
        "sec19_slo": bench_slo.main,
    }
    if args.only and not any(args.only in name for name in suites):
        print(f"--only {args.only!r} matches no suite; have "
              f"{sorted(suites)}", file=sys.stderr)
        sys.exit(2)
    print("name,us_per_call,derived")
    common.reset_results()
    status: dict[str, str] = {}
    failed = []
    for name, fn in suites.items():
        if args.only and args.only not in name:
            continue
        try:
            fn()
            status[name] = "ok"
        except Exception:  # noqa: BLE001
            status[name] = "failed"
            failed.append(name)
            traceback.print_exc()

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"suites": status, "failed": failed,
                       "results": common.RESULTS}, f, indent=1)
        print(f"wrote {len(common.RESULTS)} results to {args.json}",
              file=sys.stderr)
    if failed:
        print(f"FAILED suites: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
