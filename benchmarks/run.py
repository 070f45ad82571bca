"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks.common.emit).
Scale note: quality benchmarks run reduced configs on CPU (synthetic
data + proxy-FID — DESIGN.md §1); the params/MACs/comm accounting runs
at FULL paper scale and reproduces Tables III/IV exactly.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module names (e.g. table4,fig1)")
    ap.add_argument("--skip-slow", action="store_true",
                    help="skip the FL-training quality tables")
    args = ap.parse_args()

    from benchmarks import (baseline_engine_bench, fig1_divergence,
                            fig5_selection, kernels_bench, mesh_engine_bench,
                            roofline_report, round_engine_bench, serve_bench,
                            table1_quality, table3_pruning, table4_efficiency,
                            table5_scalability)

    modules = {
        "table4": table4_efficiency,    # fast, exact accounting first
        "table3": table3_pruning,
        "fig5": fig5_selection,
        "kernels": kernels_bench,
        "round_engine": round_engine_bench,
        "baseline_engine": baseline_engine_bench,
        "mesh_engine": mesh_engine_bench,   # subprocess: 8 fake devices
        "serve": serve_bench,
        "roofline": roofline_report,
        "fig1": fig1_divergence,        # FL training (slow) last
        "table1": table1_quality,
        "table5": table5_scalability,
    }
    slow = {"fig1", "table1", "table5"}
    selected = (set(args.only.split(",")) if args.only else set(modules))
    if args.skip_slow:
        selected -= slow

    print("name,us_per_call,derived")
    failures = []
    for name, mod in modules.items():
        if name not in selected:
            continue
        try:
            mod.main()
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            failures.append(name)
    if failures:
        print(f"FAILED benchmarks: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    from repro.launch.env import enable_compile_cache
    enable_compile_cache()
    main()
