"""On-chip smoke test: FedPhD train -> prune -> serve at paper width.

Drives the system's main path once through the entry points a user
calls (``ExperimentSpec`` -> ``run_spec`` -> method registry -> trainer,
then ``repro.serve``'s loader and ``DiffusionServer``) with the paper's
35.7M-parameter CIFAR-10 DDPM U-Net and its deployment (20 clients,
2 edges, 2 classes per client, batch 32, 44% pruning), random weights
from a seed and the in-repo synthetic data.  Only the schedule is cut.

    python chip_smoke.py              # one TPU chip, phases 1-6
    python chip_smoke.py --chips 4    # the client axis sharded over 4 chips

Phases (one chip): 1 device, 2 sequential-engine training across the
sparse -> prune -> compacted transition, 3 vectorized engine vs the
sequential one, 4 Pallas backend vs XLA, 5 serving from the checkpoint,
6 the result line.  Each phase prints one line of numbers; any failed
check exits nonzero before the last line.  The last line of a passing
run is ``{"ok": true, "device": {...}}``.

There is no CPU fallback: without a TPU the script exits nonzero.
Artifacts (checkpoint, HLO dumps) go to ``--out`` (gitignored).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# Agreement bounds between two runs of the same round.  On the CPU the
# engines and backends agree to atol 1e-5 (tests/test_round_engine.py,
# tests/test_ops_backends.py).  On the TPU an fp32 matmul runs at the
# default precision (operands rounded to bf16, fp32 accumulation) in
# XLA, and a Pallas kernel's dot may not round the same way, so two
# programs that tile, batch or fuse the same GEMMs differently drift.
# Each bound sits well above what one v5e read for the correct program
# and well below what a planted fault read (CHANGES.md has both):
#  - the round loss, a mean of O(1) DDPM losses over the round's steps,
#    read 1.6e-7 to 3.3e-7 relative in every phase;
#  - the round's parameter update (edge model minus the round's start),
#    relative in L2 norm, read 3.8e-2 for the vmapped engine against
#    the sequential one (Adam turns a gradient coordinate whose sign
#    flips under rounding into a full 2*lr step), 6.8e-4 for Pallas
#    against XLA and 4.9e-4 for the 4-chip mesh against one chip.
LOSS_RTOL = 1e-4
UPDATE_RTOL = {"vectorized": 0.1, "pallas": 1e-2, "mesh": 1e-2}


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


def say(phase: str, **nums) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in nums.items()),
          flush=True)


class CompileStats:
    """Backend compiles and persistent-cache traffic, from JAX's own
    monitoring events (seconds include cache reads).  ``requests``
    counts every compile that consulted the cache; JAX writes (and
    reports as a miss) only a program that took at least
    ``jax_persistent_cache_min_compile_time_secs`` to compile, so
    ``requests - hits - writes`` are small programs compiled anew."""

    def __init__(self, monitoring):
        self.seconds = 0.0
        self.requests = self.hits = self.writes = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1


def paper_spec(*, rounds: int, participation: float = 1.0, **over):
    """The runner's ``paper`` preset with the schedule cut: round 1 is a
    sparse (group-lasso) round, the round-2 cloud aggregation prunes
    44% of the channels, and later rounds train the compacted model."""
    from repro.experiment.runner import PRESETS
    base = PRESETS["paper"]
    fl = dataclasses.replace(base.fl, rounds=rounds, sparse_rounds=2,
                             cloud_agg_every=2, participation=participation)
    return base.replace(name="chip-smoke", fl=fl, backend="xla",
                        precision="fp32", **over)


def one_round(spec) -> dict:
    """Run round 1 of ``spec`` and keep only what the comparisons need
    on the host (loss, start and edge models), so the trainer's device
    buffers are freed before the next program runs."""
    import gc
    import jax
    import numpy as np
    from repro.experiment import Experiment
    gc.collect()                        # the previous round's trainer
    exp = Experiment(spec)
    start = jax.tree.map(np.asarray, exp.params)
    t0 = time.perf_counter()
    exp.run(1)
    seconds = time.perf_counter() - t0
    arrays, _ = exp.trainer.state()
    edges = arrays["edge_models"]
    devices = {d for leaf in jax.tree.leaves(edges) for d in leaf.devices()}
    rec = exp.history[-1]
    return {"loss": rec.loss, "clients": len(rec.selected),
            "seconds": seconds, "edge_model_devices": len(devices),
            "start": start, "edges": jax.tree.map(np.asarray, edges)}


def compare_rounds(phase: str, ref: dict, got: dict) -> None:
    """Round loss and per-edge parameter updates of two runs of the
    same round (same spec and seed, another engine or backend)."""
    import math
    import jax
    import numpy as np
    l_ref, l_got = ref["loss"], got["loss"]
    check(math.isfinite(l_ref) and math.isfinite(l_got),
          f"{phase}: non-finite loss {l_ref} / {l_got}")
    loss_rel = abs(l_got - l_ref) / abs(l_ref)
    check(ref["edges"].keys() == got["edges"].keys(),
          f"{phase}: edges differ {sorted(ref['edges'])} vs "
          f"{sorted(got['edges'])}")
    sq_diff = sq_upd = max_abs = 0.0
    for e, m_ref in ref["edges"].items():
        for a, b, s in zip(jax.tree.leaves(m_ref),
                           jax.tree.leaves(got["edges"][e]),
                           jax.tree.leaves(ref["start"])):
            d = b.astype(np.float64) - a
            check(np.isfinite(b).all(), f"{phase}: non-finite parameter")
            sq_diff += float(np.sum(d * d))
            sq_upd += float(np.sum((a.astype(np.float64) - s) ** 2))
            max_abs = max(max_abs, float(np.max(np.abs(d))))
    upd_rel = math.sqrt(sq_diff / sq_upd) if sq_upd else float("inf")
    upd_rtol = UPDATE_RTOL[phase]
    say(phase, loss_ref=l_ref, loss=l_got, loss_rel_diff=loss_rel,
        loss_rtol=LOSS_RTOL, update_rel_diff=upd_rel,
        update_rtol=upd_rtol, param_max_abs_diff=max_abs)
    check(loss_rel <= LOSS_RTOL,
          f"{phase}: loss differs by {loss_rel} > {LOSS_RTOL}")
    check(upd_rel <= upd_rtol,
          f"{phase}: updates differ by {upd_rel} > {upd_rtol}")


def phase_train(out: str, stats: CompileStats) -> str:
    """Phase 2: sequential engine, 4 rounds across the prune."""
    import math
    from repro.experiment import run_spec
    spec = paper_spec(rounds=4, engine="sequential", eval_every=1)
    ckpt = os.path.join(out, "ckpt.npz")
    stamps = [time.perf_counter()]
    c0 = stats.seconds
    # the eval hook fires once per finished round: a round clock
    exp = run_spec(spec, ckpt=ckpt, save_every=0,
                   eval_fn=lambda p, c, r: stamps.append(time.perf_counter()))
    hist = exp.history
    for rec, t0, t1 in zip(hist, stamps, stamps[1:]):
        say("train", round=rec.round, loss=rec.loss, params_m=rec.params_m,
            pruned=rec.pruned, seconds=t1 - t0)
        check(math.isfinite(rec.loss), f"train: round {rec.round} loss "
                                       f"{rec.loss}")
    pre, post = hist[0].params_m, hist[-1].params_m
    check(any(r.pruned for r in hist), "train: the prune never ran")
    check(post < pre, f"train: params_m did not drop ({pre} -> {post})")
    # rounds 1-3 each compile a new step program (sparse, dense plain,
    # compacted plain); round 4 is the first warm one
    say("train", params_m_before=pre, params_m_after=post,
        warm_round_seconds=stamps[-1] - stamps[-2],
        setup_and_first_round_seconds=stamps[1] - stamps[0],
        compile_seconds=stats.seconds - c0, ckpt=ckpt)
    return ckpt


def phase_vectorized() -> dict:
    """Phase 3: the round engine (2 clients vmapped, one per edge)
    against the sequential engine on the same spec.  Returns the
    sequential run, phase 4's reference."""
    spec = paper_spec(rounds=1, participation=0.1)
    seq = one_round(spec.replace(engine="sequential"))
    vec = one_round(spec.replace(engine="vectorized"))
    say("vectorized", clients=vec["clients"],
        seq_round_seconds=seq["seconds"],
        vec_first_round_seconds=vec["seconds"])
    compare_rounds("vectorized", seq, vec)
    return seq


def phase_pallas(xla_seq: dict) -> None:
    """Phase 4: the same sparse round on the Pallas backend, every
    kernel compiled; routing decisions recorded while the program
    traces.  Sequential engine: the vectorized Pallas program for 2
    clients compiles to 15.8 GiB for a v5e (its custom_vjp residuals
    keep the im2col patches), at the edge of the chip's HBM."""
    from collections import Counter
    from repro.kernels import record_routes
    spec = paper_spec(rounds=1, participation=0.1, engine="sequential")
    with record_routes() as routes:
        pal = one_round(spec.replace(backend="pallas"))
    paths = Counter((r.kernel, r.path) for r in routes)
    for k in sorted({r.kernel for r in routes}):
        falls = sorted({r.shape for r in routes
                        if r.kernel == k and r.path == "fallback"})
        say("pallas", kernel=k, compiled=paths[(k, "compiled")],
            interpreted=paths[(k, "interpret")],
            fallback=paths[(k, "fallback")],
            fallback_shapes=json.dumps(falls).replace(" ", ""))
    check(not any(r.path == "interpret" for r in routes),
          "pallas: a kernel ran in interpret mode on the chip")
    for k in ("block_masked_matmul", "flash_attention", "group_l2_norms"):
        check(paths[(k, "compiled")] > 0, f"pallas: {k} never compiled")
    say("pallas", clients=pal["clients"], round_seconds=pal["seconds"])
    compare_rounds("pallas", xla_seq, pal)


def phase_serve(ckpt: str) -> None:
    """Phase 5: serve the trained, pruned checkpoint."""
    import numpy as np
    from repro.serve import (DiffusionServer, Request, load_serving_artifact,
                             masks_for_ratio)
    params, cfg, _ = load_serving_artifact(ckpt)
    masks = masks_for_ratio(params, cfg, 0.44)
    server = DiffusionServer(params, cfg, slots=4, num_steps=10, masks=masks)
    res = server.run([Request(rid=r, seed=r) for r in range(8)])
    ticks = np.asarray(res.step_latencies_s[1:])   # the first compiles
    say("serve", served=f"{len(res.images)}/8", seconds=res.seconds,
        req_per_s=res.requests_per_s,
        p50_tick_ms=float(np.percentile(ticks, 50)) * 1e3,
        p99_tick_ms=float(np.percentile(ticks, 99)) * 1e3,
        first_tick_ms=res.step_latencies_s[0] * 1e3,
        compiles=server.compile_count())
    check(len(res.images) == 8, f"serve: {len(res.images)}/8 served "
                                f"({res.faults})")
    check(all(np.isfinite(im).all() for im in res.images.values()),
          "serve: non-finite image")
    check(server.compile_count() == 1,
          f"serve: tick compiled {server.compile_count()} times")


def phase_mesh(dump: str) -> None:
    """``--chips 4``: one sparse round with the client axis sharded over
    4 chips (one client each) against the sequential engine on chip 0."""
    import re
    spec = paper_spec(rounds=1, participation=0.2)
    seq = one_round(spec.replace(engine="sequential"))
    vec = one_round(spec.replace(engine="vectorized", mesh={"data": 4}))
    say("mesh", clients=vec["clients"], seq_round_seconds=seq["seconds"],
        mesh_first_round_seconds=vec["seconds"],
        seq_edge_model_devices=seq["edge_model_devices"],
        mesh_edge_model_devices=vec["edge_model_devices"])
    compare_rounds("mesh", seq, vec)
    texts = [os.path.join(dump, f) for f in os.listdir(dump)
             if "jit_engine" in f and f.endswith("after_optimizations.txt")]
    check(bool(texts), f"mesh: no optimized round program dumped in {dump}")
    hlo = "".join(open(p).read() for p in texts)
    ops = {op: len(re.findall(rf"\b{op}(?:-start)?\(", hlo))
           for op in ("all-reduce", "reduce-scatter", "all-gather",
                      "all-to-all", "collective-permute")}
    say("mesh", **{k.replace("-", "_"): v for k, v in ops.items()})
    check(ops["all-reduce"] > 0, "mesh: no all-reduce in the round program")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the whole path on one chip; 4: only the "
                         "client axis sharded over a 2x2 host")
    ap.add_argument("--out", default=os.path.join(HERE, ".chip_smoke"),
                    help="artifact directory (default: .chip_smoke/)")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(HERE, "src", "repro")):
        print("chip_smoke: the repro package is not next to this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(HERE, "src"))
    dump = os.path.join(args.out, "hlo")
    if args.chips == 4:
        shutil.rmtree(dump, ignore_errors=True)     # only this run's dump
        # XLA reads its flags once, at backend start: dump the round
        # program so its collectives can be counted
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + f" --xla_dump_to={dump}"
            " --xla_dump_hlo_as_text --xla_dump_hlo_module_re=jit_engine")

    from repro.launch.env import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    stats = CompileStats(jax.monitoring)

    # phase 1: the device; no CPU fallback
    devs = jax.devices()
    dev = devs[0]
    say("device", platform=dev.platform, kind=json.dumps(dev.device_kind),
        count=len(devs), compile_cache=cache,
        compile_cache_max_bytes=jax.config.jax_compilation_cache_max_size)
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found {dev.platform!r} devices")
    check(len(devs) >= args.chips,
          f"--chips {args.chips} but {len(devs)} device(s)")
    os.makedirs(args.out, exist_ok=True)

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(dump)
    else:
        ckpt = phase_train(args.out, stats)
        phase_pallas(phase_vectorized())
        phase_serve(ckpt)
    say("total", seconds=time.perf_counter() - t0,
        compile_seconds=stats.seconds, compile_requests=stats.requests,
        cache_hits=stats.hits, cache_writes=stats.writes)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
