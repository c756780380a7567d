"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (harness contract) and saves
full JSON artifacts under results/bench/.  The kernel bench additionally
writes the machine-readable serving-search trajectory (QPS, hops, #dist,
peak search-state bytes per config) to ``BENCH_search.json`` at the repo
root — that file is committed, so serving-perf regressions show up as
review diffs instead of living in commit messages.  ``--quick`` runs the
cheap benches only; ``--only <prefix>`` filters.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only table4]
"""
from __future__ import annotations

import argparse
import importlib
import sys
import time
import traceback

BENCHES = [
    ("kernel", "benchmarks.kernel_microbench", {}),
    ("fig4", "benchmarks.fig4_build_breakdown", {}),
    ("fig5", "benchmarks.fig5_nlo_overlap", {}),
    ("table2", "benchmarks.table2_repeated_dist", {}),
    ("table5", "benchmarks.table5_ablation", {}),
    ("table6", "benchmarks.table6_rs_plus", {}),
    ("table4", "benchmarks.table4_tuning_efficiency", {}),
    ("table1", "benchmarks.table1_cost_decomposition", {}),
    ("fig7_9", "benchmarks.fig7_9_tuning_quality", {}),
]

QUICK = {"kernel", "fig4", "fig5", "table2"}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    print("name,us_per_call,derived")
    t0 = time.time()
    failures = 0
    for name, module, kw in BENCHES:
        if args.only and not name.startswith(args.only):
            continue
        if args.quick and name not in QUICK:
            continue
        try:
            mod = importlib.import_module(module)
            if name == "kernel" and args.quick:
                # quick mode must reach this bench: it selects the small
                # sweep AND routes its JSON to the gitignored quick file
                # instead of clobbering the committed trajectory
                # (BENCH_search.json)
                kw = {**kw, "quick": True}
            mod.run(**kw)
        except Exception:
            failures += 1
            print(f"{name}/ERROR,0,{traceback.format_exc(limit=1)!r}",
                  flush=True)
    print(f"# total_seconds,{time.time() - t0:.0f},failures={failures}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
