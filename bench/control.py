"""Readings of a cell's compared numbers over many seeds, for setting
and re-checking the limits of ``correct``; not run by the benchmark.

    python bench/control.py --workload <name> --seeds 11,12,13 \
        [--precision fp32[,bf16]] [--seconds 4]

For each seed, in one process (so that set-up compiles once): the
cell's set-up without the warm-up that only keeps compiles out of a
timed window, then its window for ``--seconds`` (0 skips it: a training
cell's numbers come from set-up), then the comparison with the
reference, and the driver's ``readings()``: the same numbers for the
faults planted in the reference.  ``--precision bf16`` runs the
program's own bf16 path, the control, instead of the configuration's
float32.  One JSON line per seed and precision.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as bench_run  # noqa: E402
from lib import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--precision", default="fp32")
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    jax = bench_run.configure_jax()
    devs = bench_run.require_chips(jax, cell.chips)
    mod = harness.load_driver(cell)
    for seed in [int(s) for s in args.seeds.split(",")]:
        for prec in args.precision.split(","):
            ctx = harness.Context(cell=cell, seed=seed, precision=prec,
                                  timed=False,
                                  log=lambda m: print(m, flush=True))
            ctx.counters["device_kind"] = devs[0].device_kind
            t0 = time.perf_counter()
            drv = mod.Cell(ctx)
            drv.setup()
            if args.seconds > 0:
                drv.window(args.seconds)
            drv.release()
            checks = drv.check()
            extra = drv.readings()
            del drv
            gc.collect()
            print(json.dumps({
                "workload": cell.name, "seed": seed, "precision": prec,
                "seconds": time.perf_counter() - t0,
                "failed": ctx.counters.get("failed", 0),
                "checks": {n: v for n, v, _ in checks}, "readings": extra,
                "correct": all(v <= lim for _, v, lim in checks)}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
