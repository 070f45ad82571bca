import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).

Proves the distribution config is coherent without hardware:

  with mesh:
      lowered  = jax.jit(step, in_shardings=..., out_shardings=...).lower(*specs)
      compiled = lowered.compile()
      compiled.memory_analysis()     # per-device bytes -> fits / doesn't
      compiled.cost_analysis()       # raw XLA numbers (recorded as-is)
      analyze_hlo(compiled.as_text())  # roofline terms w/ scan trip counts

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out f.json]
"""
import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs import (ARCHS, INPUT_SHAPES, adapt_for_shape, get_config,
                           get_shape, sharding_rules)
from repro.configs.base import InputShape, ModelConfig
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import (batch_shardings, cache_shardings,
                                   opt_state_shardings, param_shardings,
                                   replicated)
from repro.launch.steps import (build_opt_init, build_serve_step,
                                build_train_step, build_prefill_step)
from repro.models import model
from repro.models.common import ApplyOptions
from repro.metrics.flops import active_params, count_params_analytic, model_flops
from repro.roofline import analyze_hlo, hw
from repro.optim import adam_init

_BF16_OPT_STATE = {"deepseek-v3-671b", "qwen3-moe-235b-a22b", "internvl2-76b"}


def _opts_for(cfg: ModelConfig, shape: InputShape,
              overrides: Dict[str, Any] | None = None, *,
              multi_pod: bool = False) -> ApplyOptions:
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    sizes = (("pod", 2), ("data", 16), ("model", 16)) if multi_pod \
        else (("data", 16), ("model", 16))
    kw = dict(attn_chunk=1024 if shape.seq_len > 2048 else 0,
              remat=shape.mode == "train", deterministic=True,
              act_batch_axes=batch_axes, act_model_axes=("model",),
              mesh_axis_sizes=sizes)
    if overrides:
        kw.update(overrides)
    return ApplyOptions(**kw)


def _analytic_memory(cfg: ModelConfig, shape: InputShape, n_chips: int,
                     *, opt_bf16: bool) -> int:
    """Per-chip HBM estimate for the fits-verdict.

    params (bf16, fully sharded) + optimizer (fp32 master + moments,
    ZeRO-1 sharded over all chips) + remat-saved layer carries + decode
    KV cache + a 1 GiB workspace.  CPU-backend memory_analysis() is
    recorded alongside but stages bf16 math through fp32 temporaries that
    a TPU build fuses, so it systematically overestimates.
    """
    n_params = count_params_analytic(cfg)
    bytes_per_param_opt = (4 + 2 + 2) if opt_bf16 else (4 + 4 + 4)
    mem = 2 * n_params / min(n_chips, 256)        # bf16 params, TP+EP sharded
    if shape.mode == "train":
        mem += bytes_per_param_opt * n_params / n_chips   # ZeRO-1
        mem += 2 * n_params / min(n_chips, 256)           # bf16 grads
        # remat carries: num_layers x (B, S, d) bf16, batch-sharded
        mem += (cfg.num_layers * shape.global_batch * shape.seq_len
                * cfg.d_model * 2) / n_chips * (16 / min(n_chips, 256))
        # working set: one layer's activations (batch-sharded)
        mem += (shape.global_batch * shape.seq_len * cfg.d_model * 2 * 8
                ) / (n_chips // 16 if n_chips >= 16 else 1)
    elif shape.mode == "prefill":
        mem += (shape.global_batch * shape.seq_len * cfg.d_model * 2 * 8
                ) / (n_chips // 16 if n_chips >= 16 else 1)
    else:  # decode: KV cache dominates
        from repro.configs.base import ATTN_GLOBAL, ATTN_LOCAL
        kv_bytes = 0
        for kind in cfg.layer_kinds():
            if kind == ATTN_GLOBAL:
                size = shape.seq_len
            elif kind == ATTN_LOCAL:
                size = min(cfg.sliding_window, shape.seq_len)
            else:
                continue
            if cfg.mla is not None:
                kv_bytes += (shape.global_batch * size
                             * (cfg.mla.kv_lora_rank
                                + cfg.mla.qk_rope_head_dim) * 2)
            else:
                kv_bytes += (2 * shape.global_batch * size
                             * cfg.num_kv_heads * cfg.head_dim * 2)
        mem += kv_bytes / min(n_chips, 256)       # batch x seq sharded
    return int(mem + (1 << 30))                   # + workspace


def lower_one(arch: str, shape_name: str, *, multi_pod: bool = False,
              opt_overrides: Dict[str, Any] | None = None,
              verbose: bool = True) -> Dict[str, Any]:
    """Lower + compile one (arch, shape, mesh); return the roofline record."""
    shape = get_shape(shape_name)
    cfg = adapt_for_shape(get_config(arch), shape)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    pod_size = 256
    rules = sharding_rules(cfg)
    opts = _opts_for(cfg, shape, opt_overrides, multi_pod=multi_pod)
    if opts.moe_ep and cfg.moe is not None:
        import dataclasses as _dc
        ep_axes = ("data", "model") \
            if cfg.moe.num_experts % 256 == 0 else ("model",)
        tok_axes = (("pod",) + ep_axes) if multi_pod else ep_axes
        opts = _dc.replace(opts, ep_mesh=mesh, ep_axes=ep_axes,
                           ep_token_axes=tok_axes)
        rules = _dc.replace(rules, moe_ep=True)

    rng = jax.random.PRNGKey(0)
    abstract_params = jax.eval_shape(lambda r: model.init(r, cfg), rng)
    p_sh = param_shardings(abstract_params, mesh, rules)
    specs = model.input_specs(cfg, shape)
    b_sh = batch_shardings(specs, mesh, rules)

    t0 = time.time()
    with mesh:
        if shape.mode == "train":
            state_dtype = ("bfloat16" if arch in _BF16_OPT_STATE else "float32")
            step = build_train_step(cfg, opts, state_dtype=state_dtype)
            opt_init = build_opt_init(cfg, state_dtype)
            abstract_opt = jax.eval_shape(opt_init, abstract_params)
            o_sh = opt_state_shardings(abstract_opt, abstract_params, mesh,
                                       rules)
            seed_spec = jax.ShapeDtypeStruct((), jnp.int32)
            jitted = jax.jit(step,
                             in_shardings=(p_sh, o_sh, b_sh, replicated(mesh)),
                             out_shardings=(p_sh, o_sh, replicated(mesh)))
            lowered = jitted.lower(abstract_params, abstract_opt, specs,
                                   seed_spec)
        elif shape.mode == "prefill":
            step = build_prefill_step(cfg, opts)
            jitted = jax.jit(step, in_shardings=(p_sh, b_sh),
                             out_shardings=replicated(mesh))
            lowered = jitted.lower(abstract_params, specs)
        else:  # decode
            step = build_serve_step(cfg, opts)
            abstract_cache = jax.eval_shape(
                lambda p: model.init_cache(p, cfg, shape.global_batch,
                                           shape.seq_len, opts=opts),
                abstract_params)
            c_sh = cache_shardings(abstract_cache, mesh, rules,
                                   shape.global_batch)
            tok_sh = b_sh["tokens"]
            jitted = jax.jit(step, in_shardings=(p_sh, c_sh, tok_sh),
                             out_shardings=(replicated(mesh), c_sh))
            lowered = jitted.lower(abstract_params, abstract_cache,
                                   specs["tokens"])
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    terms = analyze_hlo(hlo, pod_size=pod_size)
    analytic_mem = _analytic_memory(cfg, shape, n_chips,
                                    opt_bf16=arch in _BF16_OPT_STATE)

    mflops = model_flops(cfg, shape)
    flops_total = terms.flops * n_chips
    rec = {
        "arch": arch,
        "config_name": cfg.name,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": n_chips,
        "mode": shape.mode,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "params_total": count_params_analytic(cfg),
        "params_active": active_params(cfg),
        "memory": {
            "argument_bytes_per_device": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes_per_device": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes_per_device": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes_per_device": (
                getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "temp_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)),
            # CPU-backend buffer assignment stages bf16 math through fp32
            # temporaries a TPU build fuses — the analytic model below is
            # the fits-verdict (EXPERIMENTS.md caveats).
            "analytic_bytes_per_device": analytic_mem,
            "hbm_limit": hw.peaks(hw.V5E).hbm_bytes,
        },
        "xla_cost_analysis": {k: cost.get(k) for k in
                              ("flops", "bytes accessed", "transcendentals")
                              if cost and k in cost},
        "roofline": terms.to_dict(),
        "model_flops": mflops,
        "useful_flops_ratio": (mflops / flops_total) if flops_total else None,
    }
    if verbose:
        mem_gb = analytic_mem / 2**30
        xla_gb = rec["memory"]["peak_bytes_per_device"] / 2**30 \
            if rec["memory"]["peak_bytes_per_device"] else float("nan")
        fits = "FITS" if mem_gb < hw.peaks(hw.V5E).hbm_bytes / 2**30 \
            else "OVER-HBM"
        print(f"[{arch} x {shape_name} x {rec['mesh']}] "
              f"lower {t_lower:.1f}s compile {t_compile:.1f}s | "
              f"analytic {mem_gb:.2f} GiB/chip ({fits}; xla-cpu {xla_gb:.1f}) | "
              f"compute {terms.compute_s*1e3:.2f}ms "
              f"memory {terms.memory_s*1e3:.2f}ms "
              f"collective {terms.collective_s*1e3:.2f}ms "
              f"-> {terms.dominant()}-bound")
        sys.stdout.flush()
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="architecture id")
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape)")
    ap.add_argument("--multi-pod", action="store_true",
                    help="2x16x16 (pod,data,model) mesh instead of 16x16")
    ap.add_argument("--out", default=None, help="JSON output path")
    args = ap.parse_args()

    records = []
    if args.all:
        combos = [(a, s) for a in sorted(ARCHS) for s in INPUT_SHAPES]
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        combos = [(args.arch, args.shape)]

    failures = []
    for arch, shape in combos:
        try:
            records.append(lower_one(arch, shape, multi_pod=args.multi_pod))
        except Exception as e:  # noqa: BLE001 — report every failure at end
            failures.append((arch, shape, repr(e)))
            traceback.print_exc()
    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
        print(f"wrote {len(records)} records to {args.out}")
    if failures:
        print("FAILURES:")
        for f in failures:
            print(" ", f)
        sys.exit(1)
    print(f"dry-run OK: {len(records)} combination(s) lowered + compiled")


if __name__ == "__main__":
    main()
