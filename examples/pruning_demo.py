"""Structured pruning walkthrough on an assigned architecture.

Shows the FedPhD pruning pipeline outside the FL loop: dependency groups
-> L2 group-norm scores -> masks (sparse phase, with the Pallas
block-masked matmul) -> physical compaction -> smaller config.

  PYTHONPATH=src python examples/pruning_demo.py --arch qwen3-moe-235b-a22b
"""
import argparse

import jax
import jax.numpy as jnp

from repro.configs import list_archs, smoke_variant
from repro.configs.base import InputShape
from repro.core import pruning as P
from repro.kernels.block_masked_matmul.ops import masked_matmul
from repro.models import model, ops


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-20b", choices=list_archs())
    ap.add_argument("--ratio", type=float, default=0.44)
    args = ap.parse_args()

    cfg = smoke_variant(args.arch)
    rng = jax.random.PRNGKey(0)
    params = model.init(rng, cfg)
    n0 = sum(x.size for x in jax.tree.leaves(params))

    groups = P.build_groups(cfg, params)
    print(f"{cfg.name}: {len(groups)} dependency groups")
    for g in groups[:6]:
        print(f"  {g.name}: {g.size} {g.unit}s x {len(g.members)} members"
              f"{' (scan-stacked x' + str(g.stacked) + ')' if g.stacked else ''}")

    scores = P.l2_scores(params, groups)
    masks = P.make_masks(scores, groups, args.ratio)
    lambdas = P.depth_lambdas(groups, 1e-4)
    print(f"Omega(G,k) sparse-training regularizer: "
          f"{float(P.omega(params, groups, lambdas)):.4f}")

    batch = model.make_inputs(rng, cfg, InputShape("t", 64, 2, "train"))
    masked = P.apply_masks(params, groups, masks)
    l_masked = float(model.loss_fn(masked, cfg, batch, rng))
    pruned, cfg2, report = P.compact(params, cfg, groups, masks)
    l_compact = float(model.loss_fn(pruned, cfg2, batch, rng))
    n1 = sum(x.size for x in jax.tree.leaves(pruned))

    print(f"masked loss {l_masked:.4f} == compacted loss {l_compact:.4f} "
          f"(drift {abs(l_masked-l_compact):.2e})")
    print(f"params: {n0/1e6:.2f}M -> {n1/1e6:.2f}M ({1-n1/n0:.0%} cut)")
    if cfg2.moe:
        print(f"experts: {cfg.moe.num_experts} -> {cfg2.moe.num_experts}")

    # sparse-phase kernel: block-masked matmul skips pruned tiles
    x = jax.random.normal(rng, (128, 256))
    w = jax.random.normal(jax.random.fold_in(rng, 1), (256, 256))
    cm = jnp.repeat((jax.random.uniform(rng, (2,)) > 0.5).astype(jnp.float32),
                    128)
    y = masked_matmul(x, w, cm, jnp.ones(256),
                      interpret=ops.pallas_interpret())
    print(f"block-masked matmul: {int(jnp.sum(cm))}/256 cols active, "
          f"out nonzero cols = "
          f"{int(jnp.sum(jnp.any(jnp.abs(y) > 0, axis=0)))}")


if __name__ == "__main__":
    main()
