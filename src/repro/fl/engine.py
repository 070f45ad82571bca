"""Device-resident vectorized round engine, method-parameterized.

The sequential reference paths (fl/client.py:run_local driven by
core/hfl.py and fl/baselines.py) dispatch one jitted step per batch
with a host sync per loss and aggregate pytrees leaf-by-leaf in
Python.  This module compiles ONE program per round shape that does
all of it on device, for FedPhD's hierarchical loop AND the flat
baselines (FedAvg / FedProx / FedDiffuse / MOON / SCAFFOLD):

    clients  -> jax.vmap  over a stacked leading client axis
    batches  -> jax.lax.scan over a shape-static step axis
                (ClientData.stacked_epochs pads ragged clients; padded
                steps are masked no-ops)
    ctx      -> stacked per-client context pytree: FedProx/MOON anchor
                params, SCAFFOLD control variates, FedDiffuse local
                (non-communicated) parameter subtrees.  CTX_AXES maps
                each entry to a vmap axis (0 = per-client (C, ...)
                stack, None = broadcast to every lane).
    edge agg -> fused (E, C) weight-matrix einsum per leaf (the flat
                baselines are the E=1 special case)
    scaffold -> c_i+ update and control-delta mean fused on device

Per-round losses come back as a single (C,) device array — one host
sync per round instead of one per batch.  Numerical equivalence with
the sequential paths is preserved by closing over the SAME loss
(fl/client.py:make_loss_fn), folding the per-client RNG exactly as
run_local does (split once per step, carry the first key), and
masking padded steps out of both the params update and the loss mean;
tests/test_round_engine.py and tests/test_baseline_engines.py assert
it per method.

Per-client optimizer state can persist across rounds: pass stacked
Adam moments (``stacked_adam_init`` + ``tree_gather``/``tree_scatter``
keyed by the round's participation selection) and the engine threads
them through the scan and returns the updated stack.

The stacked client axis is also the parallelism axis: lay it over the
device mesh with repro.launch.federated.shard_clients and jit's
partitioner splits the vmapped program across devices.
"""
from __future__ import annotations

import warnings
from functools import lru_cache, partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FLConfig, ModelConfig
from repro.core.aggregation import combine_leaf
# the single $FEDPHD_* precedence code path; resolve_engine is re-exported
# here for back-compat (see repro.experiment.resolve for the contract)
from repro.experiment.resolve import ENGINES, resolve_engine
from repro.fl.client import make_loss_fn, scaffold_correction
from repro.fl.compress import ef_roundtrip_stacked
from repro.optim import AdamState, adam_init, adam_update

# vmap axes for each method's stacked ctx pytree: 0 = per-client
# leading (C, ...) axis, None = one copy broadcast to every lane.
CTX_AXES = {
    "fedphd": {},
    "fedavg": {},
    "fedprox": {"global_params": None},
    "feddiffuse": {"local_params": 0},
    "moon": {"global_params": None, "prev_params": 0},
    "scaffold": {"c_local": 0, "c_global": None, "scale": 0},
}


# ---------------------------------------------------------------------------
# Stacked-pytree utilities (the "ctx stacking" substrate).
# ---------------------------------------------------------------------------

def stack_trees(trees):
    """Stack a list of congruent pytrees onto a leading member axis."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *trees)


def unstack_tree(stacked, n: int):
    """Inverse of stack_trees: n per-member pytrees."""
    return [jax.tree.map(lambda leaf, _i=i: leaf[_i], stacked)
            for i in range(n)]


def tree_gather(stacked, idx):
    """Rows ``idx`` of every leaf's leading axis (scalar idx drops it).

    Polymorphic over the stack's store: numpy leaves (the host store —
    see ``resolve_store``) fancy-index on the host, so only the gathered
    participant rows ever move to device; jax leaves gather on device.
    """
    np_idx = np.asarray(idx)

    def take(leaf):
        if isinstance(leaf, np.ndarray):
            return leaf[np_idx]
        return leaf[jnp.asarray(np_idx)]
    return jax.tree.map(take, stacked)


def tree_scatter(stacked, idx, rows):
    """Write ``rows`` back into every leaf at ``idx`` on the leading axis.

    With ``idx`` a permutation-free index set (participation selections
    are drawn without replacement) this is the exact inverse of
    ``tree_gather``: rows outside ``idx`` are untouched and the result
    is invariant to permuting ``(idx, rows)`` in lockstep.

    Numpy leaves (host store) are updated IN PLACE — the whole point of
    the host store is never materializing a second (N, ...) copy — and
    the device rows sync D2H here; jax leaves use the functional
    ``.at[].set``.
    """
    np_idx = np.asarray(idx)

    def put(leaf, r):
        if isinstance(leaf, np.ndarray):
            leaf[np_idx] = np.asarray(r)
            return leaf
        return leaf.at[jnp.asarray(np_idx)].set(r)
    return jax.tree.map(put, stacked, rows)


STORES = ("auto", "device", "host")


def resolve_store(store: str, n_clients: int,
                  n_participants: Optional[int] = None) -> str:
    """Resolve a stacked-state store choice to "device" or "host".

    Persistent per-client state (Adam moments, SCAFFOLD variates, MOON
    prev models, FedDiffuse local subtrees) lives in stacks with a
    leading (N,) client axis.  On device that is fine while N is small,
    but a population run (10k clients at 1% participation) must not
    materialize N full model copies in device memory when each round
    only touches C of them — the host store keeps the stacks as numpy
    and ``tree_gather``/``tree_scatter`` move just the participating
    slice per round.

    "auto" picks host when the population is large AND mostly idle per
    round (N >= 8*C and N >= 256); explicit "device"/"host" always win.
    """
    if store not in STORES:
        raise ValueError(f"unknown state store {store!r}; expected one "
                         f"of {STORES}")
    if store != "auto":
        return store
    c = max(int(n_participants or n_clients), 1)
    return "host" if (n_clients >= 8 * c and n_clients >= 256) else "device"


def stacked_zeros(tree, n: int, *, dtype=None, host: bool = False):
    """A (n, ...) zero stack congruent with ``tree`` in the given store
    (host = numpy leaves; device = jnp).  ``dtype`` overrides the leaf
    dtypes (e.g. float32 control variates over bf16 params)."""
    if host:
        return jax.tree.map(
            lambda p: np.zeros((n,) + p.shape, dtype or p.dtype), tree)
    return jax.tree.map(
        lambda p: jnp.zeros((n,) + p.shape, dtype or p.dtype), tree)


def store_tree(tree, store: str):
    """Move a stacked-state pytree into ``store`` ("host" -> numpy
    leaves, anything else -> device).  Checkpoint restore uses this so
    a host-store trainer doesn't round-trip its (N, ...) stacks through
    device memory."""
    if tree is None:
        return None
    conv = np.asarray if store == "host" else jnp.asarray
    return jax.tree.map(conv, tree)


def stacked_adam_init(params, n: int, *, host: bool = False) -> AdamState:
    """Adam state for ``n`` persistent clients: every moment leaf gains
    a leading (n,) axis and the step counter becomes an (n,) vector.
    Gather rows with ``tree_gather`` for the round's participants and
    scatter the engine's updated rows back with ``tree_scatter``.
    ``host=True`` keeps the stack as numpy (see ``resolve_store``)."""
    xp = np if host else jnp
    zeros = lambda p: xp.zeros((n,) + p.shape, xp.float32)
    return AdamState(step=xp.zeros((n,), xp.int32),
                     mu=jax.tree.map(zeros, params),
                     nu=jax.tree.map(zeros, params),
                     master=None)


def adam_stack_from_tree(t, store: str = "device") -> Optional[AdamState]:
    """Checkpoint-loading counterpart of ``stacked_adam_init``: rebuild
    the stacked AdamState in ``store`` (checkpoint arrays arrive as
    numpy, so the host store is a zero-copy rewrap)."""
    if t is None:
        return None
    if store != "host":
        from repro.optim import adam_from_tree
        return adam_from_tree(t)
    if isinstance(t, AdamState):
        step, mu, nu, master = t.step, t.mu, t.nu, t.master
    else:
        step, mu, nu, *rest = tuple(t)
        master = rest[0] if rest else None
    to_np = lambda x: jax.tree.map(np.asarray, x)
    return AdamState(step=np.asarray(step), mu=to_np(mu), nu=to_np(nu),
                     master=None if master is None else to_np(master))


# ---------------------------------------------------------------------------
# The engine.
# ---------------------------------------------------------------------------

def make_train_one(loss_fn, *, method: str = "fedphd", lr: float = 2e-4,
                   unroll: Optional[int] = None):
    """One client's local round as a masked scan over stacked batches.

    ``train_one(params, opt_state, batches, valid, rng, ctx, masked)``
    -> ``(params, opt_state, mean_loss)``.  Used under vmap by
    ``make_round_engine`` and directly (with toy loss_fns) by the
    property tests that pin the padding-mask invariant.

    ``unroll`` (None = by backend): XLA:CPU runs conv/dot thunks inside
    a while-loop body without the runtime thread pool, so block-
    unrolling 8 steps amortizes that there; an accelerator gains
    nothing from it and pays a compile of every unrolled copy (a
    paper-width U-Net step is minutes of TPU compile), so it gets 1.
    """
    if unroll is None:
        unroll = 8 if jax.default_backend() == "cpu" else 1
    def train_one(params, opt_state, batches, valid, rng, ctx, masked):
        def body(carry, xs):
            p, o, r = carry
            batch, v = xs
            r, sub = jax.random.split(r)
            loss, grads = jax.value_and_grad(loss_fn)(p, batch, sub, ctx)
            if method == "scaffold":
                grads = scaffold_correction(grads, ctx)
            new_p, new_o = adam_update(grads, o, p, lr=lr, grad_clip=1.0)
            if masked:
                # ragged clients: padded steps must be no-ops
                keep = lambda new, old: jnp.where(v, new, old)
                new_p = jax.tree.map(keep, new_p, p)
                new_o = jax.tree.map(keep, new_o, o)
                loss = jnp.where(v, loss, 0.0)
            return (new_p, new_o, r), loss
        # full unroll explodes compile time for long rounds
        (params, opt_state, _), losses = jax.lax.scan(
            body, (params, opt_state, rng), (batches, valid),
            unroll=min(unroll, valid.shape[0]))
        n_valid = jnp.maximum(jnp.sum(valid), 1) if masked \
            else valid.shape[0]
        return params, opt_state, jnp.sum(losses) / n_valid

    return train_one


def make_round_engine(cfg: ModelConfig, fl: FLConfig, *,
                      method: str = "fedphd", sparse: bool = False,
                      groups=None, lr: float = 2e-4,
                      unroll: Optional[int] = None,
                      prune_masks=None, mesh=None,
                      client_axis: str = "data", quant: str = "none"):
    """Build the jitted vectorized round program for ``method``.

    Plain (non-sparse) engines are memoized on the hashable
    ``(cfg, fl, method, lr, unroll, mesh, client_axis, quant)`` key: every
    trainer built with the same configs shares one engine function and
    therefore one XLA compile cache — constructing several trainers
    (equivalence tests, benches, sweeps) no longer recompiles the round
    program.

    ``mesh`` puts the stacked client axis on the device mesh: the
    caller stages batches and valid with :func:`stage_clients`, and the
    engine lays every other client-leading input (rngs, edge_idx, the
    gathered Adam rows, per-client ctx entries) over ``client_axis`` via
    ``repro.launch.federated.shard_clients`` before dispatch, so jit's
    partitioner runs each device's client slice locally and the fused
    (E, C) aggregation einsum lowers to a cross-device all-reduce.
    The engine's numerics stay atol-1e-5 equivalent to the unsharded
    program (reduction order inside the einsum may reassociate).

    ``cfg.backend`` selects the compute backend (repro.models.ops:
    xla | pallas | ref) for every tensor-core op the program traces —
    it is part of the frozen config, so it participates in both the
    memoization key and jit's own cache.  ``prune_masks`` (PruneGroup
    name -> 0/1 row) switches the forward to the masked sparse-phase
    path (block-masked GEMMs instead of pre-zeroed weights); masked
    engines are never memoized.

    ``quant`` (repro.fl.compress: "none" | "int8" | "fp8") enables the
    quantized-uplink path: the engine takes gathered per-client
    error-feedback rows via ``err=``, runs the delta quantize->
    dequantize round trip on device, aggregates the RECONSTRUCTED
    models ``start + deq`` (what the edge could decode from the wire),
    and returns the new residual rows as ``"err"``.  Late (staleness)
    deltas and SCAFFOLD control variates stay fp32 — quantization is
    the on-time reporting uplink only.

    Returns ``engine(edge_params, edge_idx, batches, valid, rngs, w_mat,
    ctx=None, opt_states=None, w_late=None, err=None, masked=True,
    per_client_opt=False)`` where

      edge_params: pytree, leaves (E, ...) — one model per edge server
                   (flat baselines: E = 1, the cloud model)
      edge_idx:    (C,) int32 — which edge each client starts from
      batches:     pytree, leaves (C, S, B, ...) — stacked_epochs output
      valid:       (C, S) bool — padded-step mask
      rngs:        (C, 2) uint32 — per-client fold of the round RNG
      w_mat:       (E, C) fp32 — normalized per-edge aggregation rows
      ctx:         method ctx pytree, stacked per CTX_AXES[method]
      opt_states:  stacked per-client Adam rows (with per_client_opt)
      w_late:      optional (E, C) fp32 — staleness-aggregation rows
                   over LATE clients' deltas (unnormalized shares)
      err:         stacked (C, ...) fp32 error-feedback rows (iff the
                   engine was built with ``quant != "none"``)

    and the result is a dict:

      "agg":    pytree of edge-aggregated models, leading (E,) axis
      "losses": (C,) per-client mean local loss
      "late":   per-edge weighted late-delta sums (iff w_late given)
      "opt":    updated stacked Adam rows        (iff per_client_opt)
      "err":    (C, ...) updated error-feedback rows (iff quantizing;
                the caller scatters back ONLY the on-time reporters)
      "trained": (C, ...) per-client trained params   (moon/feddiffuse,
                 which persist per-client state between rounds)
      "c_new", "dc_mean": SCAFFOLD c_i+ stack and mean control delta
    """
    if not sparse and groups is None and prune_masks is None:
        # jax meshes hash and compare by (devices, axis names), so the
        # memo key stays sound across trainers sharing one mesh object
        return _plain_round_engine(cfg, fl, method, lr, unroll, mesh,
                                   client_axis, quant)
    return _build_round_engine(cfg, fl, method=method, sparse=sparse,
                               groups=groups, lr=lr, unroll=unroll,
                               prune_masks=prune_masks, mesh=mesh,
                               client_axis=client_axis, quant=quant)


@lru_cache(maxsize=64)
def _plain_round_engine(cfg, fl, method, lr, unroll, mesh, client_axis,
                        quant):
    return _build_round_engine(cfg, fl, method=method, sparse=False,
                               groups=None, lr=lr, unroll=unroll,
                               mesh=mesh, client_axis=client_axis,
                               quant=quant)


def stage_clients(tree, mesh=None, axis: str = "data"):
    """Host -> device for client-leading operands (stacked batches,
    valid masks): straight onto ``axis`` of ``mesh`` when there is one,
    so a sharded round's data never stops on device 0 first; to the
    default device otherwise."""
    if mesh is None:
        return jax.tree.map(jnp.asarray, tree)
    from repro.launch.federated import shard_clients
    return shard_clients(tree, mesh, axis)


def _make_sharded_engine(engine, mesh, client_axis: str, ctx_axes):
    """Wrap a jitted round engine so every client-leading operand is
    laid over ``client_axis`` before dispatch.  The round data
    (``batches``, ``valid``) arrives laid out already: the trainers
    stage it host -> mesh (:func:`stage_clients`).  Inputs whose
    leading dim doesn't divide the axis (shard_clients warns once) and
    the small replicated operands (edge stack, (E, C) weight rows) pass
    through — jit partitions the program from the sharded operands."""
    from repro.launch.federated import shard_clients

    def sharded(edge_params, edge_idx, batches, valid, rngs, w_mat,
                ctx=None, opt_states=None, w_late=None, err=None,
                masked=True, per_client_opt=False):
        put = lambda t: shard_clients(t, mesh, client_axis)
        edge_idx, rngs = put(edge_idx), put(rngs)
        if opt_states is not None:
            opt_states = put(opt_states)
        if err is not None:
            err = put(err)
        if ctx:
            ctx = {k: put(v) if ctx_axes.get(k) == 0 else v
                   for k, v in ctx.items()}
        return engine(edge_params, edge_idx, batches, valid, rngs, w_mat,
                      ctx=ctx, opt_states=opt_states, w_late=w_late,
                      err=err, masked=masked, per_client_opt=per_client_opt)
    return sharded


def _build_round_engine(cfg: ModelConfig, fl: FLConfig, *, method: str,
                        sparse: bool, groups, lr: float,
                        unroll: Optional[int],
                        prune_masks=None, mesh=None,
                        client_axis: str = "data", quant: str = "none"):
    loss_fn = make_loss_fn(cfg, fl, method=method, sparse=sparse,
                           groups=groups, prune_masks=prune_masks)
    train_one = make_train_one(loss_fn, method=method, lr=lr, unroll=unroll)
    ctx_axes = CTX_AXES[method]
    return_trained = method in ("moon", "feddiffuse")

    # Donation (ROADMAP leftover from PR 1): the (E, ...) edge-model
    # stack and the gathered persistent-Adam rows are freshly
    # materialized by the callers every round, never reused after the
    # call, and alias the "agg" / "opt" outputs shape-for-shape — so
    # XLA writes the round's results in place instead of holding both
    # copies live.  (The stacked_epochs batch buffer has no matching
    # output to alias, so donating it would be a no-op plus a warning.)
    @partial(jax.jit, static_argnames=("masked", "per_client_opt"),
             donate_argnums=(0,), donate_argnames=("opt_states", "err"))
    def engine(edge_params, edge_idx, batches, valid, rngs, w_mat,
               ctx=None, opt_states=None, w_late=None, err=None,
               masked: bool = True, per_client_opt: bool = False):
        ctx = {} if ctx is None else ctx
        start = jax.tree.map(lambda leaf: leaf[edge_idx], edge_params)
        if method == "feddiffuse":
            # per-client local (never-communicated) subtrees override
            # the gathered start rows; the loss itself is plain FedAvg
            start = {**start, **ctx["local_params"]}
        if per_client_opt:
            opt0, opt_axes = opt_states, 0
        else:
            # one zero-tree, shared across all vmapped clients
            opt0 = adam_init(jax.tree.map(lambda leaf: leaf[0], edge_params))
            opt_axes = None
        trained, opt_out, losses = jax.vmap(
            lambda p, o, b, v, r, c: train_one(p, o, b, v, r, c, masked),
            in_axes=(0, opt_axes, 0, 0, 0, ctx_axes))(
                start, opt0, batches, valid, rngs, ctx)
        if quant != "none" and err is not None:
            # quantized uplink: the edge can only decode start + deq
            # from the wire, so THAT is what aggregates; the residual
            # rows go back to the caller for the next round's feedback
            up = jax.tree.map(lambda t, s: t.astype(jnp.float32)
                              - s.astype(jnp.float32), trained, start)
            deq, new_err = ef_roundtrip_stacked(up, err, quant)
            recon = jax.tree.map(lambda s, d: s.astype(jnp.float32) + d,
                                 start, deq)
            agg_src, err_out = recon, new_err
        else:
            agg_src, err_out = trained, None
        out = {"agg": jax.tree.map(lambda leaf: combine_leaf(leaf, w_mat),
                                   agg_src),
               "losses": losses}
        if err_out is not None:
            out["err"] = err_out
        if w_late is not None:
            # staleness aggregation: fused (E, C) einsum over the late
            # clients' deltas (their w_mat entries are zero, so they are
            # excluded from "agg"; the buffered delta sum merges into
            # the NEXT aggregate as base + gamma * late)
            delta = jax.tree.map(lambda t, s: t.astype(jnp.float32)
                                 - s.astype(jnp.float32), trained, start)
            out["late"] = jax.tree.map(lambda d: combine_leaf(d, w_late),
                                       delta)
        if per_client_opt:
            out["opt"] = opt_out
        if return_trained:
            out["trained"] = trained
        if method == "scaffold":
            # c_i+ = c_i - c + (x - y_i) / (K_i * lr), fused over the
            # stack; ctx["scale"] carries per-client 1 / (K_i * lr)
            def ci_new(ci, c, x, y):
                s = ctx["scale"].reshape((-1,) + (1,) * (x.ndim - 1))
                return ci - c + s * (x.astype(jnp.float32)
                                     - y.astype(jnp.float32))
            c_new = jax.tree.map(ci_new, ctx["c_local"], ctx["c_global"],
                                 start, trained)
            delta = jax.tree.map(lambda a, b: a - b, c_new, ctx["c_local"])
            uni = jnp.full((valid.shape[0],), 1.0 / valid.shape[0],
                           jnp.float32)
            out["c_new"] = c_new
            out["dc_mean"] = jax.tree.map(lambda d: combine_leaf(d, uni),
                                          delta)
        return out

    if mesh is not None:
        return _make_sharded_engine(engine, mesh, client_axis, ctx_axes)
    return engine


def uniform_batch_shape(clients) -> Optional[tuple]:
    """Common (B, H, W, C) batch shape across clients, or None if ragged.

    The vectorized engine needs a shape-static client axis; clients whose
    batch size differs (len(data) < batch_size somewhere) fall back to
    the sequential path.
    """
    shapes = {(c.data.batch_size,) + c.data.images.shape[1:]
              for c in clients}
    return shapes.pop() if len(shapes) == 1 else None


def route_engine(engine: str, strict: bool, round_clients, warned: bool,
                 trainer: str, method: str = "") -> Tuple[bool, bool]:
    """Shared auto/strict engine routing for one round.

    Returns ``(use_vectorized, warned)``.  Ragged clients fall back to
    the sequential path; a strict (explicitly requested) "vectorized"
    raises instead, and the fallback warns exactly once per trainer —
    FedPhD and FlatTrainer must not diverge on this contract.

    The warning text embeds ``(method, engine)``: Python's warnings
    registry dedupes on the message, so without them a second trainer
    hitting the same fallback in one process (e.g. two different flat
    baselines) would be silently suppressed even though its own
    ``warned`` flag was fresh.
    """
    if engine == "sequential":
        return False, warned
    uniform = uniform_batch_shape(round_clients) is not None
    if not uniform:
        if engine == "vectorized" and strict:
            raise ValueError("vectorized engine needs a uniform client "
                             "batch shape; use engine='auto' or "
                             "'sequential' for ragged clients")
        if not warned:
            warnings.warn(f"ragged client batch shapes: {trainer} "
                          f"(method={method or trainer}, engine={engine}) "
                          "falling back to the sequential round engine",
                          RuntimeWarning)
            warned = True
    return uniform, warned
