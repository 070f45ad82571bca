"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout that holds the program (``src/repro``).
The cell, its configuration, traffic mix, driver and per-layer metrics
are found by name from ``BENCHMARK.json`` (``bench/lib/harness.py``).
Set-up (process start, device, data, weights, compiles or cache reads,
warm-up) is timed as ``setup_s``; then the window runs for the cell's
``--seconds``; then the program's state is freed and what the window
produced is compared with the plain reference.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number beside its
limit).  The last lines of standard error repeat the checks.  Without
a TPU, or with fewer chips than the cell asks for, it exits 3 and
prints no result; an unknown cell or a checkout without the program
exits 2.
"""
from __future__ import annotations

import time

_T0 = [time.perf_counter()]     # the process's start, for its first run

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from lib import harness  # noqa: E402
from lib.compile_stats import CompileStats  # noqa: E402

# the persistent compile cache: a fixed directory inside the checkout,
# whatever the machine's $JAX_COMPILATION_CACHE_DIR says, uncapped and
# keeping every program however fast it compiled
CACHE_DIR = os.path.join(harness.ROOT, ".jax_cache")
# a traced run measures at most this long: reading the profiler's trace
# of a longer window does not fit the run's time limit
TRACE_SECONDS = 20.0


def err(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def say(msg: str) -> None:
    print(msg, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def configure_jax(cache_dir=CACHE_DIR):
    import jax
    if cache_dir is None:
        return jax
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def require_chips(jax, chips: int):
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise harness.BenchError(f"no TPU: {e}") from None
    if devs[0].platform != "tpu":
        raise harness.BenchError(f"no TPU: JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise harness.BenchError(f"the cell needs {chips} chips, JAX "
                                 f"found {len(devs)}")
    return devs


def run(args, *, precision: str = "fp32", require_tpu: bool = True,
        root: str = harness.ROOT, cache_dir=CACHE_DIR,
        readings: bool = False) -> dict:
    """One run of one cell; returns the result object.  ``precision``,
    ``require_tpu``, ``cache_dir`` and ``readings`` (the control's and
    the planted faults' numbers, under ``"readings"``) exist for the
    control runs and the CPU tests."""
    t0 = _T0.pop() if _T0 else time.perf_counter()
    cell = harness.find_cell(args.workload, root)
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        raise harness.BenchError("the program (src/repro) is not in this "
                                 "checkout")
    sys.path.insert(0, os.path.join(root, "src"))
    jax = configure_jax(cache_dir)
    devs = require_chips(jax, cell.chips) if require_tpu else jax.devices()
    stats = CompileStats(jax.monitoring)
    ctx = harness.Context(cell=cell, seed=args.seed, precision=precision,
                          log=say)
    ctx.counters["device_kind"] = devs[0].device_kind
    drv = harness.load_driver(cell).Cell(ctx)

    drv.setup()
    setup_s = (time.perf_counter() - t0
               - ctx.counters.get("check_in_setup_s", 0.0))
    before = stats.snapshot()
    say("[setup] " + " ".join(f"{k}={v}" for k, v in before.items())
        + f" setup_s={setup_s}")
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    if tdir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench/window"):
            e2e = drv.window(min(args.seconds, TRACE_SECONDS)
                             if tdir else args.seconds)
    finally:
        if tdir:
            jax.profiler.stop_trace()
    after = stats.snapshot()
    ctx.counters["compile_s_window"] = after["compile_s"] - before["compile_s"]
    say("[window] " + " ".join(f"{k}={after[k] - before[k]}"
                               for k in after))
    mem = devs[0].memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    drv.release()
    gc.collect()
    checks = drv.check()
    e2e["setup_s"] = setup_s

    out: dict = {"correct": all(v <= lim for _, v, lim in checks)
                 and ctx.counters.get("failed", 0) == 0,
                 "attempted": int(ctx.counters.get("attempted", 0)),
                 "failed": int(ctx.counters.get("failed", 0))}
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    if tdir:
        from lib import trace as trace_lib
        tr = trace_lib.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        win = trace_lib.window_of(tr)
        red = trace_lib.reduce(tr, win) if win else None
        if red is None or red["ops"] == 0:
            raise harness.BenchError("the trace holds no device operation "
                                     "inside the window")
        metrics = {}
        for m in cell.per_layer:
            v = harness.load_metric(cell, m["name"]).read(ctx, red)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
        say("[trace] " + json.dumps({k: red[k] for k in
                                     ("window_s", "busy_s", "gemm_s",
                                      "ops")}))
    else:
        out["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
        out["device"] = device
    if readings:
        out["readings"] = drv.readings()
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    for n, v, lim in checks:
        err(f"check {n} value={v} limit={lim} "
            f"{'ok' if v <= lim else 'FAIL'}")
    return out


def main(argv=None) -> int:
    args = parse(argv)
    try:
        out = run(args)
    except harness.BenchError as e:
        err(f"bench: {e}")
        return 3 if "TPU" in str(e) or "chips" in str(e) else 2
    say(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
