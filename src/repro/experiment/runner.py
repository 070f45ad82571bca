"""CLI for the unified experiment API.

Replaces the ad-hoc wiring in the examples: a JSON spec (or a named
preset) is the whole experiment, and ``--resume`` continues a killed run
from its checkpoint::

    PYTHONPATH=src python -m repro.experiment.runner \
        --preset smoke --rounds 1 --out runs/smoke
    PYTHONPATH=src python -m repro.experiment.runner \
        --out runs/smoke --resume --rounds 2

Outputs land in ``--out``: ``spec.json`` (the resolved spec),
``ckpt.npz`` + ``ckpt.npz.manifest.json`` (the resumable checkpoint),
and ``history.json`` (the shared RoundRecord schema, one row per round).

``--sweep grid.json`` switches to grid mode: the JSON is a
:class:`repro.experiment.sweep.SweepSpec`, every expanded run executes
(and checkpoints) under ``--out/runs/<run_id>/``, the resumable sweep
manifest lands at ``--out/sweep.json``, and the aggregated report
(mean±std across seeds, grouped by the sweep's axes) at
``--out/report.json`` + ``report.md``.  Re-invoking the same command
resumes a killed sweep — mid-grid from the manifest and mid-run from
the interrupted run's checkpoint; ``--max-runs N`` stops after N runs
(a deterministic "kill" for smoke tests)::

    PYTHONPATH=src python -m repro.experiment.runner \
        --sweep examples/sweep_smoke.json --out runs/sweep
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Optional, Sequence, Union

from repro.configs.base import FLConfig
from repro.experiment.cli import (add_compute_flags, add_metrics_flag,
                                  add_obs_flags, cli_obs_spec, write_metrics)
from repro.experiment.report import report_markdown, write_report
from repro.experiment.run import Experiment, checkpoint_exists, run_spec
from repro.experiment.spec import DataSpec, ExperimentSpec
from repro.experiment.sweep import (SweepResult, SweepSpec, manifest_status,
                                    run_sweep)
from repro.obs.metrics import summarize_trace

PRESETS = {
    # the CI smoke config: 6 clients / 2 edges on the 16x16 smoke U-Net,
    # pruning at the round-2 cloud aggregation
    "smoke": ExperimentSpec(
        name="smoke", method="fedphd", model="ddpm-unet-smoke",
        fl=FLConfig(num_clients=6, num_edges=2, local_epochs=1,
                    edge_agg_every=1, cloud_agg_every=2, rounds=4,
                    sparse_rounds=2, prune_ratio=0.44, sh_a=1000.0),
        data=DataSpec(dataset="smoke", classes_per_client=1, batch_size=32)),
    # the paper's §V setup (accelerator scale)
    "paper": ExperimentSpec(
        name="paper", method="fedphd", model="ddpm-unet-cifar10",
        fl=FLConfig(num_clients=20, num_edges=2, local_epochs=1,
                    edge_agg_every=1, cloud_agg_every=5, rounds=100,
                    sparse_rounds=50, prune_ratio=0.44, sh_a=15000.0),
        data=DataSpec(dataset="cifar10-like", classes_per_client=2,
                      batch_size=32)),
}


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="repro.experiment.runner", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    src = ap.add_mutually_exclusive_group()
    src.add_argument("--spec", help="path to an ExperimentSpec JSON file")
    src.add_argument("--preset", choices=sorted(PRESETS), default="smoke",
                     help="named built-in spec (default: smoke)")
    src.add_argument("--sweep", help="path to a SweepSpec JSON file: run "
                                     "the whole grid with a resumable "
                                     "manifest + aggregated report")
    ap.add_argument("--executor", choices=("sequential", "process", "k8s"),
                    help="[--sweep] run the grid in-process (default), "
                         "over a spawn-context process pool, or as one "
                         "Kubernetes Job per grid point over shared "
                         "storage")
    ap.add_argument("--max-workers", type=int,
                    help="[--sweep --executor process|k8s] pool size / "
                         "max in-flight Jobs")
    ap.add_argument("--k8s-fake", action="store_true",
                    help="[--sweep --executor k8s] drive the executor "
                         "against the in-memory FakeCluster (no cluster, "
                         "no kubernetes package — the CI smoke path)")
    ap.add_argument("--image", default="repro:latest",
                    help="[--sweep --executor k8s] container image for "
                         "worker Jobs (default: repro:latest)")
    ap.add_argument("--namespace", default=None,
                    help="[--sweep --executor k8s] Kubernetes namespace "
                         "(default: default)")
    ap.add_argument("--max-runs", type=int,
                    help="[--sweep] stop after this many run attempts "
                         "in THIS invocation (failures count); the "
                         "manifest stays resumable")
    ap.add_argument("--group-by",
                    help="[--sweep] comma-separated axes for the "
                         "aggregated report (default: the sweep's "
                         "group_by, else its non-seed axes)")
    ap.add_argument("--timeout-s", type=float,
                    help="[--sweep --executor process] wall-clock budget "
                         "per run attempt; a run exceeding it is killed "
                         "and retried/quarantined")
    ap.add_argument("--max-retries", type=int,
                    help="[--sweep] retry a crashed/hung run this many "
                         "times (exponential backoff, resuming from its "
                         "checkpoint) before quarantining it as failed "
                         "(default 0)")
    ap.add_argument("--method", help="override spec.method (registry key)")
    ap.add_argument("--engine",
                    choices=("auto", "vectorized", "sequential"),
                    help="override spec.engine")
    # the shared CLI surface (same names/semantics as python -m
    # repro.serve): --backend/--precision/--trace/--metrics
    add_compute_flags(ap)
    add_obs_flags(ap)
    add_metrics_flag(ap)
    ap.add_argument("--seed", type=int, help="override spec.seed")
    ap.add_argument("--eval-every", type=int,
                    help="override spec.eval_every (the CLI's hook DDIM-"
                         "samples 64 images and records the proxy "
                         "inception score in RoundRecord.eval)")
    ap.add_argument("--rounds", type=int,
                    help="absolute target round (default spec.fl.rounds); "
                         "with --resume, rounds already in the checkpoint "
                         "are not re-run")
    ap.add_argument("--out", default="runs/experiment",
                    help="output directory (spec/ckpt/history)")
    ap.add_argument("--save-every", type=int, default=1,
                    help="checkpoint cadence in rounds while running "
                         "(a killed run loses at most this many rounds; "
                         "0 = only save at the end)")
    ap.add_argument("--resume", action="store_true",
                    help="continue from <out>/ckpt.npz (spec overrides are "
                         "ignored; the checkpointed spec wins)")
    return ap


def _apply_overrides(spec: ExperimentSpec,
                     args: argparse.Namespace) -> ExperimentSpec:
    over = {}
    if args.method is not None:
        over["method"] = args.method
    if args.engine is not None:
        over["engine"] = args.engine
    if args.seed is not None:
        over["seed"] = args.seed
    if args.eval_every is not None:
        over["eval_every"] = args.eval_every
    if args.backend is not None:
        over["backend"] = args.backend
    if args.precision is not None:
        over["precision"] = args.precision
    if args.trace is not None:
        # --trace [PATH] -> an explicitly-enabled ObsSpec (keeps the
        # spec's other obs knobs, e.g. flush_every from a spec file)
        over["obs"] = spec.obs.replace(enabled=True,
                                       trace=args.trace or spec.obs.trace)
    return spec.replace(**over) if over else spec


def _default_eval(params, cfg, r):
    """The CLI's eval hook (active at the spec's eval_every cadence):
    reference-free sample quality — DDIM-sample a small batch and score
    it with the proxy inception score."""
    from repro.diffusion import sample_images
    from repro.metrics import inception_score_proxy
    fake = sample_images(params, cfg, n=64, steps=10, seed=0)
    return {"is_proxy": float(inception_score_proxy(fake))}


def _main_sweep(args: argparse.Namespace) -> SweepResult:
    # single-run flags have no meaning on a grid — reject rather than
    # silently run something other than what the command line asked for
    bad = [flag for flag, val in (("--method", args.method),
                                  ("--engine", args.engine),
                                  ("--seed", args.seed),
                                  ("--eval-every", args.eval_every),
                                  ("--backend", args.backend),
                                  ("--precision", args.precision),
                                  ("--trace", args.trace),
                                  ("--metrics", args.metrics))
           if val is not None]
    if args.resume:
        bad.append("--resume")
    if bad:
        raise SystemExit(
            f"--sweep is incompatible with {', '.join(bad)}: declare "
            "per-run fields in the sweep JSON (base/axes — obs.* axes "
            "cover tracing); sweep resume is automatic from the "
            "manifest and the aggregated metrics land in report.json")
    with open(args.sweep) as f:
        sweep = SweepSpec.from_json(f.read())
    if args.rounds is not None:
        sweep = sweep.replace(rounds=args.rounds)
    executor = args.executor or "sequential"
    if args.max_workers is not None and executor not in ("process", "k8s"):
        raise SystemExit("--max-workers requires --executor process "
                         "or k8s (the sequential executor runs one grid "
                         "point at a time)")
    if (args.k8s_fake or args.namespace is not None) and executor != "k8s":
        raise SystemExit("--k8s-fake/--namespace require --executor k8s")
    if executor == "k8s":
        # construct the executor here so --k8s-fake can inject the
        # in-memory cluster double (no kubernetes package needed)
        from repro.experiment.cluster import FakeCluster, K8sExecutor
        executor = K8sExecutor(
            cluster=FakeCluster() if args.k8s_fake else None,
            image=args.image, namespace=args.namespace or "default",
            max_workers=args.max_workers,
            poll_s=0.0 if args.k8s_fake else 2.0)
    # the CLI's eval hook is live only on the sequential executor (a
    # Python callable can't cross the spawn boundary) and only fires
    # where a spec's eval_every says so
    eval_fn = _default_eval if executor == "sequential" else None
    res = run_sweep(sweep, args.out, executor=executor,
                    max_workers=args.max_workers, limit=args.max_runs,
                    eval_fn=eval_fn, save_every=args.save_every,
                    timeout_s=args.timeout_s,
                    max_retries=args.max_retries or 0)
    group_by = [g.strip() for g in (args.group_by or "").split(",")
                if g.strip()] or None
    report = write_report(res.manifest, args.out, group_by=group_by)
    counts = manifest_status(res.manifest)
    print(report_markdown(report))
    print(f"[{sweep.name}] {counts['done']}/{len(res.manifest['runs'])} "
          f"runs done ({counts['pending']} pending, "
          f"{counts['failed']} failed) -> {args.out}")
    if counts["failed"]:
        raise SystemExit(f"--sweep: {counts['failed']} run(s) failed "
                         f"(see {args.out}/sweep.json)")
    return res


def main(argv: Optional[Sequence[str]] = None
         ) -> Union[Experiment, SweepResult]:
    args = build_parser().parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    if args.sweep:
        return _main_sweep(args)
    # the mirror of _main_sweep's guard: sweep-only flags are
    # meaningless on a single run — refuse rather than silently ignore
    bad = [flag for flag, val in (("--executor", args.executor),
                                  ("--max-workers", args.max_workers),
                                  ("--max-runs", args.max_runs),
                                  ("--group-by", args.group_by),
                                  ("--timeout-s", args.timeout_s),
                                  ("--max-retries", args.max_retries),
                                  ("--namespace", args.namespace))
           if val is not None]
    if args.k8s_fake:
        bad.append("--k8s-fake")
    if args.image != "repro:latest":
        bad.append("--image")
    if bad:
        raise SystemExit(f"{', '.join(bad)} require --sweep")
    ckpt = os.path.join(args.out, "ckpt.npz")

    if args.resume:
        if not checkpoint_exists(ckpt):
            raise SystemExit(f"--resume: no checkpoint at {ckpt}")
        if args.trace is not None:
            # a resumed run replays the checkpointed spec, so --trace
            # routes through the env leg of the same resolution contract
            # (an explicit enabled=False in that spec still wins); the
            # trace appends next to the checkpoint, so a custom path
            # can't be honored here
            if args.trace:
                raise SystemExit("--trace PATH is incompatible with "
                                 "--resume (the resumed trace appends to "
                                 "<out>/ckpt.npz.trace.jsonl); use bare "
                                 "--trace")
            os.environ["FEDPHD_OBS"] = "on"
        exp = run_spec(None, rounds=args.rounds, ckpt=ckpt, resume=True,
                       save_every=args.save_every, eval_fn=_default_eval)
    else:
        if args.spec:
            with open(args.spec) as f:
                spec = ExperimentSpec.from_json(f.read())
        else:
            spec = PRESETS[args.preset]
        spec = _apply_overrides(spec, args)
        exp = run_spec(spec, rounds=args.rounds, ckpt=ckpt,
                       save_every=args.save_every, eval_fn=_default_eval)

    with open(os.path.join(args.out, "spec.json"), "w") as f:
        f.write(exp.spec.to_json() + "\n")
    with open(os.path.join(args.out, "history.json"), "w") as f:
        json.dump({"spec": exp.spec.to_dict(),
                   "history": [r.to_dict() for r in exp.history]},
                  f, indent=2)
        f.write("\n")

    last = exp.history[-1]
    total_comm = sum(r.comm_gb for r in exp.history)
    print(f"[{exp.spec.name}/{exp.spec.method}] round {last.round}: "
          f"loss={last.loss:.4f} params={last.params_m:.2f}M "
          f"total_comm={total_comm:.4f}GB -> {args.out}")

    metrics = {"name": exp.spec.name, "method": exp.spec.method,
               "rounds": last.round, "loss": last.loss,
               "params_m": last.params_m, "total_comm_gb": total_comm}
    if exp.tracer.enabled:
        exp.tracer.flush()
        ts = summarize_trace(exp.tracer.path)
        metrics.update(trace=exp.tracer.path,
                       overlap_ratio=ts["overlap_ratio"],
                       compiles=ts["compiles"],
                       recompiles=ts["recompiles"])
        print(f"trace -> {exp.tracer.path} "
              f"(overlap={ts['overlap_ratio']} compiles={ts['compiles']} "
              f"recompiles={ts['recompiles']})")
    if args.metrics:
        write_metrics(args.metrics, "experiment", metrics)
        print(f"wrote metrics to {args.metrics}")
    return exp


if __name__ == "__main__":
    from repro.launch.env import enable_compile_cache
    enable_compile_cache()
    main()
