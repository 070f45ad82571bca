"""CLI: serve sampling requests from a training checkpoint.

  PYTHONPATH=src python -m repro.serve --ckpt out/ckpt --requests 8 \
      --slots 4 --steps 10 --prune-ratio 0.44 --out samples/

Loads any ``repro.checkpoint`` artifact (e.g. the experiment runner's
``ckpt.npz``), optionally derives serving masks at ``--prune-ratio``,
and runs the continuous-batching server over ``--requests`` requests.
Prints requests/s + p50/p99 per-step latency and the dense-vs-masked
analytic MACs; ``--metrics`` dumps them as JSON for CI.
"""
from __future__ import annotations

import argparse
import os

import numpy as np

from repro.experiment.cli import (add_compute_flags, add_metrics_flag,
                                  add_obs_flags, make_cli_tracer,
                                  write_metrics)
from repro.metrics.flops import unet_macs
from repro.serve.artifact import load_serving_artifact, masks_for_ratio
from repro.serve.server import DiffusionServer, Request


def main():
    ap = argparse.ArgumentParser(prog="python -m repro.serve")
    ap.add_argument("--ckpt", required=True,
                    help="checkpoint path (runner's <out>/ckpt)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--steps", type=int, default=10, help="DDIM steps")
    ap.add_argument("--eta", type=float, default=0.0,
                    help="0 = deterministic DDIM; 1 ~ DDPM ancestral")
    ap.add_argument("--prune-ratio", type=float, default=0.0,
                    help="serve through masks at this ratio (0 = dense)")
    ap.add_argument("--criterion", default="l2", choices=("l2", "random"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="directory for req<rid>.npy images")
    # shared surface with repro.experiment.runner:
    # --backend/--precision/--trace/--metrics (repro.experiment.cli)
    add_compute_flags(ap)
    add_obs_flags(ap)
    add_metrics_flag(ap)
    args = ap.parse_args()

    params, cfg, meta = load_serving_artifact(args.ckpt,
                                              backend=args.backend)
    masks = None
    if args.prune_ratio > 0:
        masks = masks_for_ratio(params, cfg, args.prune_ratio,
                                criterion=args.criterion)
    dense_macs = unet_macs(params, cfg.image_size)
    macs = unet_macs(params, cfg.image_size, masks=masks)
    # --trace > $FEDPHD_OBS > off; default path next to the checkpoint
    tracer = make_cli_tracer(args.trace,
                             default_path=args.ckpt + ".serve.trace.jsonl")
    server = DiffusionServer(params, cfg, slots=args.slots,
                             num_steps=args.steps, eta=args.eta, masks=masks,
                             precision=args.precision or "",
                             tracer=tracer if tracer.enabled else None)
    reqs = [Request(rid=r, seed=args.seed + r) for r in range(args.requests)]
    res = server.run(reqs)

    p50 = res.latency_percentile(50) * 1e3
    p99 = res.latency_percentile(99) * 1e3
    print(f"model={cfg.name} backend={cfg.backend} "
          f"precision={server.precision} "
          f"prune_ratio={args.prune_ratio} steps={args.steps} "
          f"slots={args.slots}")
    print(f"MACs/forward: {macs / 1e6:.1f}M"
          + (f" (dense {dense_macs / 1e6:.1f}M, "
             f"{macs / dense_macs:.2f}x)" if masks is not None else ""))
    print(f"{len(res.images)}/{args.requests} images in {res.seconds:.2f}s "
          f"({res.requests_per_s:.2f} req/s); per-step latency "
          f"p50={p50:.1f}ms p99={p99:.1f}ms; compiles={server.compile_count()}")
    for f in res.faults:
        print(f"fault: {f}")

    if args.out:
        os.makedirs(args.out, exist_ok=True)
        for rid, img in res.images.items():
            np.save(os.path.join(args.out, f"req{rid}.npy"), img)
        print(f"wrote {len(res.images)} images to {args.out}")
    if tracer.enabled:
        tracer.close()
        print(f"trace -> {tracer.path}")
    if args.metrics:
        write_metrics(args.metrics, "serve", {
            "requests": args.requests,
            "images": len(res.images),
            "requests_per_s": res.requests_per_s,
            "p50_step_ms": p50,
            "p99_step_ms": p99,
            "compiles": server.compile_count(),
            "precision": server.precision,
            "macs_per_forward": macs,
            "dense_macs_per_forward": dense_macs,
            "faults": res.faults,
        })
        print(f"wrote metrics to {args.metrics}")
    if len(res.images) != args.requests:
        raise SystemExit(f"served {len(res.images)}/{args.requests} requests")


if __name__ == "__main__":
    from repro.launch.env import enable_compile_cache
    enable_compile_cache()
    main()
