"""Client-side local training (paper Alg. 2) + baseline variants.

``make_local_step`` builds one jitted SGD/Adam step whose loss is
composed from the DM loss (Eq. 6 via model.loss_fn) plus, depending on
the method:
  - FedPhD sparse rounds: + Omega(G, k) group-lasso (Eq. 16),
  - FedProx:              + mu/2 ||theta - theta_global||^2,
  - MOON:                 + contrastive term on model output features,
  - SCAFFOLD:             gradient correction g - c_i + c.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FLConfig, ModelConfig
from repro.core.pruning import depth_lambdas, omega
from repro.data.pipeline import ClientData
from repro.models import model
from repro.models.ops import cast_floats, compute_dtype
from repro.obs.compile_tracker import cache_size
from repro.obs.trace import NULL_TRACER
from repro.optim import adam_init, adam_update


def tree_sq_dist(a, b):
    return sum(jnp.sum(jnp.square(x.astype(jnp.float32)
                                  - y.astype(jnp.float32)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def model_features(params, cfg: ModelConfig, batch, rng):
    """Representation for MOON's contrastive term.

    For the diffusion U-Net we use the pooled noise prediction at a fixed
    mid-schedule timestep — a function-space feature (MOON's penultimate-
    layer choice has no direct analogue for eps-predictors; DESIGN.md §8).
    """
    if cfg.arch_type == "unet":
        from repro.diffusion import linear_schedule, q_sample
        from repro.models.unet import apply_unet
        sched = linear_schedule(cfg.diffusion_steps)
        B = batch["images"].shape[0]
        t = jnp.full((B,), cfg.diffusion_steps // 2, jnp.int32)
        eps = jax.random.normal(rng, batch["images"].shape)
        x_t = q_sample(sched, batch["images"], t, eps)
        pred = apply_unet(params, cfg, x_t, t)
        return pred.reshape(B, -1)
    from repro.models.transformer import forward
    hidden, _ = forward(params, cfg, batch)
    return jnp.mean(hidden, axis=1)


def _cosine(a, b):
    num = jnp.sum(a * b, axis=-1)
    den = jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1) + 1e-8
    return num / den


def make_loss_fn(cfg: ModelConfig, fl: FLConfig, *, method: str = "fedphd",
                 sparse: bool = False, groups=None, prune_masks=None):
    """Method-parameterized local loss (the SINGLE definition both the
    sequential per-batch step and the vectorized round engine close
    over, so the two paths are equivalent by construction).

    Returns ``loss_fn(params, batch, rng, ctx)``; ctx carries the
    method's anchors ("global_params", "prev_params", "c_local",
    "c_global", ... — static structure per jit).

    ``cfg.backend`` selects the compute backend for every tensor-core
    op inside (repro.models.ops); ``prune_masks`` switches the U-Net
    forward to the masked sparse-phase path (col/row-masked GEMMs
    instead of training on pre-zeroed weights).

    ``cfg.precision`` is the mixed-precision boundary: under bf16 the
    float params are cast to bfloat16 HERE, inside the loss closure —
    so both consumers (``make_local_step`` and the round engine's
    ``make_train_one``) compute forward+backward in bf16 while
    ``value_and_grad`` transposes the ``astype`` back to fp32 grads;
    the params the optimizer sees remain the fp32 master weights.

    ``cfg.remat_policy`` (``rematerialize``) wraps the whole loss in
    ``jax.checkpoint``, once, for both consumers alike.
    """
    lambdas = depth_lambdas(groups, fl.lambda0) if (sparse and groups) else None
    dt = compute_dtype(cfg.precision)

    def loss_fn(params, batch, rng, ctx):
        if dt != jnp.float32:
            params = cast_floats(params, dt)
        loss = model.loss_fn(params, cfg, batch, rng, masks=prune_masks)
        if sparse and groups:
            loss = loss + omega(params, groups, lambdas, backend=cfg.backend)
        if method == "fedprox":
            loss = loss + 0.5 * fl.fedprox_mu * tree_sq_dist(
                params, ctx["global_params"])
        if method == "moon":
            rng_f = jax.random.fold_in(rng, 1)
            z = model_features(params, cfg, batch, rng_f)
            z_g = model_features(ctx["global_params"], cfg, batch, rng_f)
            z_p = model_features(ctx["prev_params"], cfg, batch, rng_f)
            sim_g = _cosine(z, z_g) / fl.moon_tau
            sim_p = _cosine(z, z_p) / fl.moon_tau
            con = -jnp.mean(sim_g - jnp.logaddexp(sim_g, sim_p))
            loss = loss + fl.moon_mu * con
        return loss

    return rematerialize(loss_fn, cfg.remat_policy)


# the policies ``ModelConfig.remat_policy`` may name
REMAT_POLICIES = {
    "dots_with_no_batch_dims_saveable":
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
}


def rematerialize(loss_fn, policy: str):
    """``loss_fn`` under ``jax.checkpoint`` with the named policy (see
    ``ModelConfig.remat_policy``), inside a ``remat`` named scope so the
    ops it recomputes in the backward pass carry the name in their HLO
    op names; ``loss_fn`` itself where ``policy`` is "", so the step
    traces as it does without the setting."""
    if not policy:
        return loss_fn
    if policy not in REMAT_POLICIES:
        raise ValueError(f"unknown remat_policy {policy!r}; expected \"\" "
                         f"or one of {sorted(REMAT_POLICIES)}")
    ckpt = jax.checkpoint(loss_fn, policy=REMAT_POLICIES[policy])

    def remat_loss_fn(params, batch, rng, ctx):
        with jax.named_scope("remat"):
            return ckpt(params, batch, rng, ctx)

    return remat_loss_fn


def scaffold_correction(grads, ctx):
    """SCAFFOLD variance-reduced gradient g - c_i + c (Karimireddy et al.)."""
    return jax.tree.map(lambda g, ci, c: g - ci + c, grads,
                        ctx["c_local"], ctx["c_global"])


def make_local_step(cfg: ModelConfig, fl: FLConfig, *, method: str = "fedphd",
                    sparse: bool = False, groups=None, lr: float = 2e-4,
                    prune_masks=None):
    """Returns jitted step(params, opt_state, batch, rng, ctx) -> (...)

    ctx: dict with optional "global_params", "prev_params", "c_local",
    "c_global" (present per method; static structure per jit).
    """
    loss_fn = make_loss_fn(cfg, fl, method=method, sparse=sparse,
                           groups=groups, prune_masks=prune_masks)

    @jax.jit
    def step(params, opt_state, batch, rng, ctx):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch, rng, ctx)
        if method == "scaffold":
            grads = scaffold_correction(grads, ctx)
        params, opt_state = adam_update(grads, opt_state, params, lr=lr,
                                        grad_clip=1.0)
        return params, opt_state, loss

    return step


def temp_bytes(step_fn, *args) -> Optional[int]:
    """Temporary bytes of the jitted ``step_fn`` compiled for ``args``
    (``memory_analysis()``: what the program allocates beyond its
    arguments and outputs); None where the backend gives no analysis.
    After a call with the same arguments this reads jit's own caches:
    nothing is traced or compiled again."""
    ma = step_fn.lower(*args).compile().memory_analysis()
    return None if ma is None else int(ma.temp_size_in_bytes)


@dataclasses.dataclass
class Client:
    """One federated client: local data + label distribution q_n."""
    cid: int
    data: ClientData
    num_classes: int

    def __post_init__(self):
        from repro.core.sh_score import label_distribution
        self.q_n = label_distribution(self.data.labels, self.num_classes)

    @property
    def n_samples(self) -> int:
        return len(self.data)


def run_local(step_fn, params, client: Client, *, epochs: int, rng,
              ctx: Optional[Dict[str, Any]] = None, opt_state=None,
              max_steps: Optional[int] = None, tracer=NULL_TRACER,
              remat: str = ""):
    """Run E local epochs (Alg. 2).  Returns (params, opt_state, mean loss).

    ``max_steps`` caps the number of executed steps (fault injection:
    straggler budgets / mid-round dropout).  The epoch generators are
    still drained past the cap so the shuffle RNG advances exactly as
    in an untruncated round — keeping the sequential path in lockstep
    with the vectorized engine, whose ``stacked_epochs`` stacking
    always consumes whole epochs and truncates via the valid mask.

    Each executed step is a ``client/step`` span of ``tracer`` (the
    caller's; repro.obs; its ``remat`` attribute is the step's
    ``ModelConfig.remat_policy``) holding ``client/h2d`` (the batch's copy),
    ``client/dispatch`` (the jitted step's call) and
    ``client/loss_sync`` (the loss read back to the host).  With the
    tracer enabled, a call that compiles ``step_fn`` anew (its jit cache
    grows) is followed by one ``step/temp_bytes`` counter (``remat``):
    the compiled step's temporary bytes (``temp_bytes``).
    """
    if opt_state is None:
        opt_state = adam_init(params)
    ctx = ctx or {}
    losses = []
    executed = 0
    compiled = cache_size(step_fn) if tracer.enabled else None
    for _ in range(epochs):
        for batch in client.data.epoch():
            if max_steps is not None and executed >= max_steps:
                continue                  # drain: shuffle RNG must advance
            with tracer.span("client/step", remat=remat):
                rng, sub = jax.random.split(rng)
                with tracer.span("client/h2d"):
                    jb = {k: jnp.asarray(v) for k, v in batch.items()}
                args = (params, opt_state, jb, sub, ctx)
                with tracer.span("client/dispatch"):
                    params, opt_state, loss = step_fn(*args)
                if compiled is not None and cache_size(step_fn) > compiled:
                    compiled = cache_size(step_fn)
                    tracer.counter("step/temp_bytes",
                                   temp_bytes(step_fn, *args), remat=remat)
                with tracer.span("client/loss_sync"):
                    losses.append(float(loss))
            executed += 1
    return params, opt_state, float(np.mean(losses)) if losses else 0.0
