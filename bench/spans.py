"""One traced run of a cell, and its idle time put down to the
program's spans; not run by the benchmark.

    python bench/spans.py --workload <name> --seed <n> --seconds <s>

Runs the cell once as ``run.py --trace 1`` does, and prints the same
result line.  Then, from the trace that run read: a ``[spans]`` line
(``lib/spans.py`` ``program``: per program span name, its count, host
seconds, self seconds and device-idle seconds) and a ``[split]`` line
(``split``: ``idle_in_step``, ``idle_in_agg``, ``idle_in_other``, which
sum to the run's ``idle_share``, ``step_host_ms`` and ``agg_host_s``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as bench_run  # noqa: E402
from lib import spans  # noqa: E402
from lib import trace as trace_lib  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--seconds", required=True)
    args = ap.parse_args(argv)
    kept = []
    load = trace_lib.load

    def load_and_keep(logdir):          # the run reads its trace once
        kept.append(load(logdir))
        return kept[-1]

    trace_lib.load = load_and_keep
    rc = bench_run.main(["--workload", args.workload, "--seed", args.seed,
                         "--seconds", args.seconds, "--trace", "1"])
    if rc or not kept:
        return rc or 2
    tr = kept[-1]
    win = trace_lib.window_of(tr)
    print("[spans] " + json.dumps(spans.program(tr, win)), flush=True)
    print("[split] " + json.dumps(spans.split(tr, win)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
