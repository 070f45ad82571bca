"""Flat (single-tier) FL baselines from the paper's Table II:

FedAvg [6], FedProx [21], FedDiffuse [15] (partial-parameter updates),
MOON [22] (model-contrastive), SCAFFOLD [23] (control variates), plus
centralized training.  All share the client substrate in fl/client.py.

Like FedPhD's hierarchical loop, every baseline runs on either of two
interchangeable engines (``run_flat_fl(..., engine=)``):

  "sequential"  — the numerical reference: one jitted step per batch,
                  Python-side aggregation (fl/client.py:run_local);
  "vectorized"  — ONE jitted program per round (vmap clients x scan
                  batches, fused FedAvg einsum, device-side SCAFFOLD
                  c_i+ update and delta mean) via the E=1 special case
                  of repro.fl.engine.make_round_engine, with the
                  method's per-client anchors (FedProx/MOON params,
                  SCAFFOLD control variates, FedDiffuse local subtrees)
                  stacked into a (C, ...) ctx pytree;
  "auto"        — vectorized whenever the selected clients share a
                  batch shape, sequential (with a one-time warning)
                  otherwise.

Method state that persists across rounds (MOON's previous local
models, FedDiffuse's local parameter subtrees, SCAFFOLD's c_i, and —
with ``persistent_opt`` — per-client Adam moments) lives in stacked
device buffers with a leading (N,) client axis, gathered/scattered by
the round's participation selection; both engines read and write the
same buffers, so "auto" may switch engines between rounds without
losing state.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FLConfig, ModelConfig
from repro.core.aggregation import (aggregate_fedavg, fedavg_weights,
                                    normalize_weights, uniform_weights,
                                    weighted_average,
                                    weighted_average_stacked)
from repro.data.pipeline import stack_round
from repro.fl.client import Client, make_local_step, run_local
from repro.fl.comm import CommModel
from repro.fl.compress import (QUANTS, downlink_bytes,
                               ef_roundtrip_jit as _ef_jit, uplink_bytes)
from repro.fl.engine import (adam_stack_from_tree, make_round_engine,
                             resolve_engine, resolve_store, route_engine,
                             stacked_adam_init, stacked_zeros, stage_clients,
                             store_tree, tree_gather, tree_scatter)
from repro.fl.faults import (FaultSpec, apply_late, late_delta,
                             make_fault_model)
from repro.fl.record import RoundRecord, RunResult, evals_of
from repro.models import model
from repro.models.ops import resolve_backend, resolve_precision
from repro.obs.compile_tracker import CompileTracker
from repro.obs.trace import NULL_TRACER
from repro.optim import adam_init, adam_update

FLAT_METHODS = ("fedavg", "fedprox", "feddiffuse", "moon", "scaffold")


# ---------------------------------------------------------------------------
# FedDiffuse parameter partition: shared (communicated) vs local subsets.
# de Goede et al. split the U-Net; we share the encoder half (down+mid+temb)
# and keep the decoder (up, out) local — their "UDEC" variant mirrored.
# ---------------------------------------------------------------------------
_SHARED_KEYS_UNET = ("conv_in", "temb1", "temb2", "down", "mid")


def _split_shared(params: Dict, cfg: ModelConfig):
    if cfg.arch_type == "unet":
        shared = {k: v for k, v in params.items() if k in _SHARED_KEYS_UNET}
        local = {k: v for k, v in params.items() if k not in _SHARED_KEYS_UNET}
        return shared, local
    # transformers: share everything except the lm head / final norm
    local_keys = ("final_norm", "lm_head")
    shared = {k: v for k, v in params.items() if k not in local_keys}
    local = {k: v for k, v in params.items() if k in local_keys}
    return shared, local


def _merge(shared: Dict, local: Dict) -> Dict:
    out = dict(shared)
    out.update(local)
    return out


def shared_fraction(params: Dict, cfg: ModelConfig) -> float:
    shared, local = _split_shared(params, cfg)
    sb = sum(x.size for x in jax.tree.leaves(shared))
    lb = sum(x.size for x in jax.tree.leaves(local))
    return sb / max(sb + lb, 1)


@dataclasses.dataclass
class FlatFLResult:
    """Legacy ``run_flat_fl`` return shim.  ``history`` now holds the
    shared :class:`repro.fl.record.RoundRecord` schema (dict-style
    ``h["loss"]`` access still works)."""
    history: List[RoundRecord]
    params: Dict


def _rows_or_default(rows, default_tree, seen_rows):
    """Per-leaf select: stored row if the client has participated
    before, the current global value otherwise (the sequential path's
    ``dict.get(cid, params)`` semantics, vectorized)."""
    m = jnp.asarray(np.asarray(seen_rows, bool))
    pick = lambda r, g: jnp.where(m.reshape((-1,) + (1,) * g.ndim),
                                  r, g[None])
    return jax.tree.map(pick, rows, default_tree)


class FlatTrainer:
    """Round-stepped flat-FL trainer (the substrate of ``run_flat_fl``;
    exposed so benchmarks can interleave engines round-by-round)."""

    def __init__(self, method: str, cfg: ModelConfig, fl: FLConfig,
                 clients: List[Client], *, lr: float = 2e-4,
                 rng_seed: int = 0, engine: Optional[str] = None,
                 persistent_opt: bool = False, state_store: str = "auto",
                 mesh=None, client_axis: str = "data",
                 eval_fn: Optional[Callable] = None, eval_every: int = 0,
                 aggregation: str = "fedavg",
                 fault: Optional[FaultSpec] = None,
                 quant: str = "none", tracer=None):
        assert method in FLAT_METHODS
        if quant not in QUANTS:
            raise ValueError(f"unknown quant {quant!r}; expected one of "
                             f"{QUANTS}")
        self.quant = quant
        self.method = method
        if aggregation not in ("fedavg", "staleness"):
            raise ValueError(f"unknown flat aggregation {aggregation!r}")
        if aggregation == "staleness" and method != "fedavg":
            raise ValueError("staleness aggregation is a FedAvg variant "
                             f"(got method={method!r})")
        # "staleness" == FedAvg over on-time reporters + the buffered
        # late-delta merge; with no stragglers it IS FedAvg exactly
        self.aggregation = aggregation
        # pin the resolved compute backend + precision (one code path:
        # repro.experiment.resolve) so every compiled step/round program
        # and the memoized engine key carry concrete values — mirrors
        # FedPhD
        self.cfg = cfg = cfg.replace(
            backend=resolve_backend(cfg.backend),
            precision=resolve_precision(cfg.precision))
        # obs tracing: NULL_TRACER (the default) makes every span/event
        # call site a no-op — tracing never touches RNG or numerics
        self._obs = NULL_TRACER
        self._obs_compile = None
        self.fl = fl
        self.clients = clients
        self.lr = lr
        self.engine, self._engine_strict = resolve_engine(engine)
        self.persistent_opt = persistent_opt
        self.eval_fn = eval_fn
        self.eval_every = eval_every
        self._warned_ragged = False

        # fault injection (mirrors FedPhD): disabled spec -> no model,
        # every fault branch collapses to the fault-free path
        self.fault = fault if (fault is not None and fault.enabled) else None
        self._faults = make_fault_model(self.fault, len(clients), rng_seed)
        self._late_buf = None   # flat topology: one edge, one buffer

        self.np_rng = np.random.default_rng(rng_seed)
        self.rng = jax.random.PRNGKey(rng_seed)
        self.rng, sub = jax.random.split(self.rng)
        self.params = model.init(sub, cfg)
        self.comm = CommModel()
        self.mbytes = sum(x.size * x.dtype.itemsize
                          for x in jax.tree.leaves(self.params))

        self.step_fn = make_local_step(cfg, fl, method=method, lr=lr)
        self._opt_zero = adam_init(self.params)  # shared fresh-Adam tree
        # unroll=1: block-unrolling the scan lets XLA fuse ACROSS local
        # steps, which reassociates fp ops enough that FedProx/SCAFFOLD
        # Adam trajectories drift past atol 1e-5 from the sequential
        # reference; step-at-a-time keeps the baselines bit-stable (the
        # speedup is dispatch-bound anyway — see baseline_engine_bench).
        # Built unconditionally (memoized, jit-compiled only on first
        # call) so a trainer may switch self.engine between rounds.
        self.mesh = mesh
        self.client_axis = client_axis
        self._round_engine = make_round_engine(cfg, fl, method=method,
                                               lr=lr, unroll=1,
                                               mesh=mesh,
                                               client_axis=client_axis,
                                               quant=quant)

        n = len(clients)
        # stacked (N,) method state lives on device by default; for
        # large populations with small participation it moves to host
        # numpy and only the selected rows are staged per round
        self._store = resolve_store(
            state_store, n, max(1, round(fl.participation * n)))
        host = self._store == "host"
        self._opt_stack = stacked_adam_init(self.params, n, host=host) \
            if persistent_opt else None
        zeros_like = lambda t: jax.tree.map(
            lambda p: jnp.zeros_like(p, jnp.float32), t)
        # method state, all with a leading (N,) client axis; `seen`
        # marks clients that have participated (unseen rows default to
        # the current global model, matching the reference dict.get)
        self.c_global = zeros_like(self.params) \
            if method == "scaffold" else None
        self._c_local_stack = stacked_zeros(self.params, n,
                                            dtype=jnp.float32, host=host) \
            if method == "scaffold" else None
        self._prev_stack = stacked_zeros(self.params, n, host=host) \
            if method == "moon" else None
        self._local_stack = stacked_zeros(
            _split_shared(self.params, cfg)[1], n, host=host) \
            if method == "feddiffuse" else None
        # per-client error-feedback residuals for the quantized uplink
        # (repro.fl.compress): fp32, params-congruent, same residency
        # rules as the other stacked method state
        self._err_stack = stacked_zeros(self.params, n,
                                        dtype=np.float32, host=host) \
            if quant != "none" else None
        self._seen = np.zeros(n, bool)

        self.history: List[RoundRecord] = []
        if tracer is not None:
            self.bind_tracer(tracer)

    # -- observability -------------------------------------------------------
    def bind_tracer(self, tracer) -> None:
        """Attach an obs tracer (repro.obs): subsequent rounds emit
        phase spans / fault events / compile counters through it.
        ``None`` (or the NULL_TRACER) keeps the no-op path."""
        self._obs = tracer if tracer is not None else NULL_TRACER
        self._obs_compile = CompileTracker(self._obs) \
            if (self._obs.enabled
                and getattr(self._obs, "compile_tracking", False)) else None
        if self._obs_compile is not None:
            self._obs_compile.watch("step_fn", self.step_fn)
            self._obs_compile.watch("round_engine", self._round_engine)

    # -- engine routing ------------------------------------------------------
    def _use_vectorized(self, round_clients) -> bool:
        use, self._warned_ragged = route_engine(
            self.engine, self._engine_strict, round_clients,
            self._warned_ragged, "FlatTrainer", method=self.method)
        return use

    # -- reference path ------------------------------------------------------
    def _round_sequential(self, sel, subs, faults=None):
        """Per-client reference loop.  Under an active fault schedule:
        non-arrived clients run zero steps (RNG lockstep preserved),
        budgets truncate local training, only on-time reporters enter
        the FedAvg einsum (renormalized) or update client-local state,
        and late clients feed the staleness buffer."""
        method, fl, cfg, params = self.method, self.fl, self.cfg, self.params
        client_models, counts, losses, c_deltas = [], [], [], []
        late_models, late_counts = [], []
        for i, cid in enumerate(sel):
            cid = int(cid)
            cl = self.clients[cid]
            budget = faults.budget_of(cid) if faults else None
            completed = faults is None or faults.completed_of(cid)
            reporting = faults is None or faults.reporting_of(cid)
            start = params
            if method == "feddiffuse" and self._seen[cid]:
                shared, _ = _split_shared(params, cfg)
                start = _merge(shared, tree_gather(self._local_stack, cid))
            ctx = {}
            if method in ("fedprox", "moon"):
                ctx["global_params"] = params
            if method == "moon":
                ctx["prev_params"] = tree_gather(self._prev_stack, cid) \
                    if self._seen[cid] else params
            if method == "scaffold":
                ctx["c_local"] = tree_gather(self._c_local_stack, cid)
                ctx["c_global"] = self.c_global
            opt_in = tree_gather(self._opt_stack, cid) \
                if self.persistent_opt else self._opt_zero
            new_p, opt_out, loss = run_local(self.step_fn, start, cl,
                                             epochs=fl.local_epochs,
                                             rng=subs[i], ctx=ctx,
                                             opt_state=opt_in,
                                             max_steps=budget,
                                             tracer=self._obs)
            losses.append(loss)
            if self.persistent_opt and completed:
                self._opt_stack = tree_scatter(self._opt_stack, cid, opt_out)
            if method == "moon" and completed:
                self._prev_stack = tree_scatter(self._prev_stack, cid, new_p)
                self._seen[cid] = True
            if method == "feddiffuse" and completed:
                shared, local = _split_shared(new_p, cfg)
                self._local_stack = tree_scatter(self._local_stack, cid,
                                                 local)
                self._seen[cid] = True
            if reporting:
                counts.append(cl.n_samples)
                up_p = new_p
                if self.quant != "none":
                    # quantized uplink: the server decodes start + deq;
                    # the residual persists as this client's error
                    # buffer.  Client-local state (MOON prev models,
                    # FedDiffuse local subtrees, SCAFFOLD variates)
                    # keeps the TRUE new_p above — it never hits the
                    # wire.  Delta base is the per-client start (for
                    # FedDiffuse that includes the local rows, matching
                    # the vectorized engine's lane start).
                    delta = jax.tree.map(lambda a, b: a - b, new_p, start)
                    e_row = store_tree(
                        tree_gather(self._err_stack, cid), "device")
                    deq, new_err = _ef_jit(delta, e_row, self.quant)
                    self._err_stack = tree_scatter(self._err_stack, cid,
                                                   new_err)
                    up_p = jax.tree.map(lambda s, d: s + d, start, deq)
                client_models.append(_split_shared(up_p, cfg)[0]
                                     if method == "feddiffuse" else up_p)
            elif faults is not None and faults.late_of(cid):
                late_models.append(new_p)
                late_counts.append(cl.n_samples)
            if method == "scaffold" and completed:
                # c_i+ = c_i - c + (x - y_i) / (K * lr); K = executed
                # steps (the fault budget when truncated; clamp dodges
                # a 0-step inf that the zero delta would NaN-multiply)
                steps = budget if faults else \
                    fl.local_epochs * cl.data.steps_per_epoch
                scale = 1.0 / (max(steps, 1) * self.lr)
                ci = ctx["c_local"]
                new_ci = jax.tree.map(
                    lambda ci_, c, x, y: ci_ - c + scale
                    * (x.astype(jnp.float32) - y.astype(jnp.float32)),
                    ci, self.c_global, start, new_p)
                c_deltas.append(jax.tree.map(lambda a, b: a - b, new_ci, ci))
                self._c_local_stack = tree_scatter(self._c_local_stack, cid,
                                                   new_ci)

        # graceful degradation: no reporter -> the server keeps params
        agg = aggregate_fedavg(client_models, counts) \
            if client_models else (_split_shared(params, cfg)[0]
                                   if method == "feddiffuse" else params)
        if self.aggregation == "staleness":
            buf, self._late_buf = self._late_buf, None
            if buf is not None:         # merge last round's stragglers
                agg = apply_late(agg, buf, self.fault.staleness
                                 if self.fault else 0.0)
            if late_models:
                tot = max(sum(counts) + sum(late_counts), 1)
                self._late_buf = late_delta(
                    late_models, params, [n / tot for n in late_counts])
        if method == "feddiffuse":
            _, local = _split_shared(params, cfg)
            self.params = _merge(agg, local)
        else:
            self.params = agg
        if method == "scaffold" and c_deltas:
            mean_dc = weighted_average(c_deltas,
                                       uniform_weights(len(c_deltas)))
            frac = len(c_deltas) / len(self.clients)
            self.c_global = jax.tree.map(lambda c, d: c + frac * d,
                                         self.c_global, mean_dc)
        return losses

    # -- device-resident path ------------------------------------------------
    def _round_vectorized(self, sel, subs, faults=None, r=0):
        """E=1 engine round.  Faults stay shape-static: budgets AND a
        prefix into the (C, S) valid mask, non-reporting clients get a
        zero aggregation weight (renormalized among reporters), and
        late deltas return via the ``w_late`` einsum."""
        method, fl, cfg, params = self.method, self.fl, self.cfg, self.params
        obs = self._obs
        with obs.span("round/host_prep", round=r):
            sel_arr = np.asarray(sel)
            sel_clients = [self.clients[int(cid)] for cid in sel]
            counts = [cl.n_samples for cl in sel_clients]
            rep = np.asarray([faults is None or faults.reporting_of(int(c))
                              for c in sel], bool)
            comp = np.asarray([faults is None or faults.completed_of(int(c))
                               for c in sel], bool)

            batches, valid, padded = stack_round(
                [cl.data for cl in sel_clients], fl.local_epochs)
            if faults is not None:
                budgets = np.asarray([faults.budget_of(int(c)) for c in sel])
                prefix = np.arange(valid.shape[1])[None, :] < budgets[:, None]
                padded = padded or not bool(prefix.all())
                valid = valid & prefix
        with obs.span("round/h2d", round=r):
            batches, valid = stage_clients((batches, valid), self.mesh,
                                           self.client_axis)
            rngs = jnp.stack(subs)
        # the flat topology is the E=1 special case of the edge engine
        server = jax.tree.map(lambda leaf: leaf[None], params)
        edge_idx = jnp.zeros((len(sel),), jnp.int32)
        w = np.zeros(len(sel), np.float32)
        if rep.any():
            w[rep] = normalize_weights(
                fedavg_weights([c for c, m in zip(counts, rep) if m]))
        w_row = jnp.asarray(w[None])
        w_late = None
        if self.aggregation == "staleness" and faults is not None:
            late = np.asarray([faults.late_of(int(c)) for c in sel], bool)
            if late.any():
                tot = max(int(np.sum(np.asarray(counts)[rep]))
                          + int(np.sum(np.asarray(counts)[late])), 1)
                wl = np.zeros(len(sel), np.float32)
                wl[late] = np.asarray(counts, np.float32)[late] / tot
                w_late = jnp.asarray(wl[None])

        ctx = None
        if method in ("fedprox", "moon"):
            ctx = {"global_params": params}
        if method == "moon":
            rows = tree_gather(self._prev_stack, sel_arr)
            ctx["prev_params"] = _rows_or_default(rows, params,
                                                  self._seen[sel_arr])
        if method == "feddiffuse":
            _, local_g = _split_shared(params, cfg)
            rows = tree_gather(self._local_stack, sel_arr)
            ctx = {"local_params": _rows_or_default(rows, local_g,
                                                    self._seen[sel_arr])}
        if method == "scaffold":
            steps = np.asarray(budgets, np.float64) if faults is not None \
                else np.asarray([fl.local_epochs * cl.data.steps_per_epoch
                                 for cl in sel_clients], np.float64)
            # clamp: a 0-step budget would make scale inf and the zero
            # (x - y) delta NaN under inf*0
            scale = 1.0 / (np.maximum(steps, 1) * self.lr)
            ctx = {"c_local": tree_gather(self._c_local_stack, sel_arr),
                   "c_global": self.c_global,
                   "scale": jnp.asarray(scale, jnp.float32)}

        # host store: gathered rows are numpy — stage the opt rows to
        # device explicitly (numpy inputs would silently defeat the
        # engine's opt_states buffer donation)
        with obs.span("round/dispatch", round=r):
            out = self._round_engine(
                server, edge_idx, batches, valid, rngs, w_row, ctx=ctx,
                opt_states=(store_tree(tree_gather(self._opt_stack, sel_arr),
                                       "device")
                            if self.persistent_opt else None),
                w_late=w_late,
                err=(store_tree(tree_gather(self._err_stack, sel_arr),
                                "device")
                     if self.quant != "none" else None),
                masked=padded, per_client_opt=self.persistent_opt)
        # NO host sync here: the (C,) loss array stays a device future
        # until _finish_round — under the pipelined run() the next
        # round's host data prep + H2D overlap this round's compute
        losses = out["losses"]
        if rep.any():
            agg = jax.tree.map(lambda leaf: leaf[0], out["agg"])
        else:
            # a zero w_row makes the einsum a zero tree: keep params
            agg = _split_shared(params, cfg)[0] \
                if method == "feddiffuse" else params
        if self.aggregation == "staleness":
            buf, self._late_buf = self._late_buf, None
            if buf is not None:         # merge last round's stragglers
                agg = apply_late(agg, buf, self.fault.staleness
                                 if self.fault else 0.0)
            if w_late is not None:
                self._late_buf = jax.tree.map(lambda leaf: leaf[0],
                                              out["late"])
        comp_rel = np.flatnonzero(comp)

        if self.quant != "none":
            # only ON-TIME reporters shipped a quantized payload —
            # their lanes (and only theirs) persist a new residual
            rep_rel = np.flatnonzero(rep)
            if len(rep_rel):
                self._err_stack = tree_scatter(
                    self._err_stack, sel_arr[rep_rel],
                    tree_gather(out["err"], rep_rel))

        if self.persistent_opt and len(comp_rel):
            if faults is None:
                self._opt_stack = tree_scatter(self._opt_stack, sel_arr,
                                               out["opt"])
            else:   # only COMPLETED clients keep their updated moments
                self._opt_stack = tree_scatter(
                    self._opt_stack, sel_arr[comp_rel],
                    tree_gather(out["opt"], comp_rel))
        if method == "moon" and len(comp_rel):
            self._prev_stack = tree_scatter(
                self._prev_stack, sel_arr[comp_rel],
                tree_gather(out["trained"], comp_rel))
            self._seen[sel_arr[comp_rel]] = True
        if method == "feddiffuse":
            shared_g, local_g = _split_shared(params, cfg)
            if len(comp_rel):
                trained_local = {k: out["trained"][k] for k in local_g}
                self._local_stack = tree_scatter(
                    self._local_stack, sel_arr[comp_rel],
                    tree_gather(trained_local, comp_rel))
                self._seen[sel_arr[comp_rel]] = True
            # only the shared half of the fused aggregate is used; the
            # server keeps its own local subtree (never communicated)
            self.params = _merge({k: agg[k] for k in shared_g}, local_g)
        else:
            self.params = agg
        if method == "scaffold":
            if faults is None:
                self._c_local_stack = tree_scatter(
                    self._c_local_stack, sel_arr, out["c_new"])
                frac = len(sel) / len(self.clients)
                self.c_global = jax.tree.map(lambda c, d: c + frac * d,
                                             self.c_global, out["dc_mean"])
            elif len(comp_rel):
                # the engine's dc_mean averages every lane uniformly —
                # under faults recompute it over completed lanes only
                self._c_local_stack = tree_scatter(
                    self._c_local_stack, sel_arr[comp_rel],
                    tree_gather(out["c_new"], comp_rel))
                dc = jax.tree.map(lambda a, b: a - b, out["c_new"],
                                  ctx["c_local"])
                w_dc = comp.astype(np.float64) / len(comp_rel)
                mean_dc = weighted_average_stacked(dc, w_dc)
                frac = len(comp_rel) / len(self.clients)
                self.c_global = jax.tree.map(lambda c, d: c + frac * d,
                                             self.c_global, mean_dc)
        return losses

    # -- one round -----------------------------------------------------------
    def _wire_bytes(self):
        """Per-transfer volumes ``(up_quantized, up_full, down)`` in
        bytes-on-wire (repro.fl.compress).  Only the model subtree a
        method actually communicates is counted: FedDiffuse ships the
        shared half, SCAFFOLD adds its fp32 control variates (never
        quantized) in both directions."""
        comm_tree = _split_shared(self.params, self.cfg)[0] \
            if self.method == "feddiffuse" else self.params
        up_q = uplink_bytes(comm_tree, self.quant)
        up_f = uplink_bytes(comm_tree, "none")
        down = downlink_bytes(comm_tree, self.cfg.precision)
        if self.method == "scaffold":
            up_q += uplink_bytes(self.params, "none")
            up_f += uplink_bytes(self.params, "none")
            down += downlink_bytes(self.params, "fp32")
        return up_q, up_f, down

    def run_round(self, r: int) -> RoundRecord:
        return self._finish_round(self._start_round(r))

    def _start_round(self, r: int) -> Dict:
        """Dispatch one round — selection, RNG folding, the round
        program, method-state scatter, aggregation — everything except
        blocking on the device losses.  Returns the pending-round dict
        ``_finish_round`` turns into a RoundRecord (the FedPhD
        ``_start_round``/``_finish_round`` split, on the flat topology).

        On the vectorized engine nothing here forces a host sync, so
        ``run()`` double-buffers rounds: round r+1's ``stack_round``
        shuffle/stack and H2D copy run while round r's program is still
        executing.
        """
        fl, method = self.fl, self.method
        if self._faults is not None:
            # churn first (its own RNG stream), then sample participants
            # from the online pool only — with churn=0 the np_rng
            # consumption is identical to the fault-free path
            online = self._faults.begin_round()
            pool = np.flatnonzero(online)
            C = min(max(1, round(fl.participation * len(self.clients))),
                    len(pool))
            sel = pool[self.np_rng.choice(len(pool), size=C, replace=False)]
        else:
            C = max(1, round(fl.participation * len(self.clients)))
            sel = self.np_rng.choice(len(self.clients), size=C,
                                     replace=False)
        # identical RNG folding on both paths: one split per selected
        # client, in selection order
        subs = []
        for _ in range(C):
            self.rng, sub = jax.random.split(self.rng)
            subs.append(sub)

        faults = None
        if self._faults is not None:
            steps = [fl.local_epochs * self.clients[int(c)].data.steps_per_epoch
                     for c in sel]
            faults = self._faults.draw_round(
                sel, steps, self.aggregation == "staleness")

        if self._use_vectorized([self.clients[int(c)] for c in sel]):
            losses = self._round_vectorized(sel, subs, faults,
                                            r=r)               # dev future
        else:
            # the reference loop syncs per batch: host prep, compute and
            # aggregation interleave, so it gets one dispatch span
            with self._obs.span("round/dispatch", round=r):
                losses = self._round_sequential(sel, subs,
                                                faults)        # host floats

        up_q, up_f, down = self._wire_bytes()
        if faults is None:
            up_bytes = len(sel) * self.comm.edge_cloud(up_q)
            down_bytes = len(sel) * self.comm.edge_cloud(down)
        else:
            # downloads to every arrived client, uploads only from the
            # clients that finished (dropped clients = zero uplink);
            # only on-time reporters shipped the quantized payload
            n_arr = int(np.sum(faults.arrived))
            n_rep = sum(1 for c in sel if faults.completed_of(int(c))
                        and faults.reporting_of(int(c)))
            n_full = int(np.sum(faults.completed)) - n_rep
            up_bytes = n_rep * self.comm.edge_cloud(up_q) \
                + n_full * self.comm.edge_cloud(up_f)
            down_bytes = n_arr * self.comm.edge_cloud(down)
        # snapshot end-of-round state the record needs: the params the
        # eval hook sees must not leak mutations from a round
        # dispatched before this one is finalized
        return {
            "round": r, "losses": losses, "sel_ids": sel,
            "up_bytes": up_bytes, "down_bytes": down_bytes,
            "params_m": sum(x.size
                            for x in jax.tree.leaves(self.params)) / 1e6,
            "params": self.params, "cfg": self.cfg,
            "loss_mask": ([faults.budget_of(int(c)) > 0 for c in sel]
                          if faults else None),
            "availability": faults.availability() if faults else None,
        }

    def _finish_round(self, pend: Dict) -> RoundRecord:
        """Sync the pending round's losses and append its RoundRecord."""
        losses = pend["losses"]
        if not isinstance(losses, list):          # device future -> host
            with self._obs.span("round/loss_sync", round=pend["round"]):
                losses = [float(x) for x in np.asarray(losses)]
        r = pend["round"]
        mask = pend.get("loss_mask")
        if mask is not None:        # faults: average over executed clients
            losses = [l for l, m in zip(losses, mask) if m]
        rec = RoundRecord(
            round=r,
            loss=float(np.mean(losses)) if losses else 0.0,
            # totals as the sum of the ROUNDED up/down fields, so
            # comm_gb == comm_up_gb + comm_down_gb holds exactly (the
            # real value is the same; fault-free flat comm stays
            # bitwise-equal to the legacy 2n*edge_cloud(v)/1e9 because
            # rounding commutes with the exact power-of-2 doubling)
            comm_gb=pend["up_bytes"] / 1e9 + pend["down_bytes"] / 1e9,
            comm_up_gb=pend["up_bytes"] / 1e9,
            comm_down_gb=pend["down_bytes"] / 1e9,
            params_m=pend["params_m"],
            selected=[int(c) for c in pend["sel_ids"]],
            availability=pend.get("availability"),
        )
        # append BEFORE the eval hook: the round executed (trainer state
        # and RNG streams advanced), so a raising eval_fn must lose the
        # eval, not the round — otherwise a later run()/resume would
        # re-run an already-applied round and diverge
        self.history.append(rec)
        if self._obs_compile is not None:
            # compiles triggered by this round's dispatch/sync are in
            # the caches by now; growth beyond the allowance = a
            # shape/dtype leaked into a trace
            self._obs_compile.check(round=r)
        if self.eval_fn and self.eval_every and r % self.eval_every == 0:
            rec.eval = self.eval_fn(pend["params"], pend["cfg"], r)
        return rec

    def run(self, rounds: Optional[int] = None, *,
            eval_every: Optional[int] = None) -> RunResult:
        """Run rounds ``len(history)+1 .. rounds`` (continues after a
        restore) — the same ``Trainer`` contract as ``FedPhD.run``.

        Rounds are double-buffered exactly like ``FedPhD.run``: round
        r+1 is dispatched (``_start_round``) before round r's losses are
        synced (``_finish_round``); records finalize in round order and
        the numerics are identical to stepping ``run_round`` — only the
        sync point moves."""
        rounds = rounds or self.fl.rounds
        if eval_every is not None:
            self.eval_every = eval_every
        pend = None
        try:
            for r in range(len(self.history) + 1, rounds + 1):
                cur = self._start_round(r)
                # hand cur to the guard BEFORE finishing prev: if
                # _finish_round(prev) raises (eval hook), prev is
                # already in history (append-before-eval) and the
                # finally still finalizes the dispatched cur — no
                # executed round is ever orphaned
                prev, pend = pend, cur
                if prev is not None:
                    self._finish_round(prev)
        finally:
            # a raising _start_round (e.g. strict-vectorized hitting a
            # ragged selection) must not orphan the already-executed
            # previous round: finalize it so history matches the
            # advanced trainer state.  Finalize only when it extends
            # history contiguously — if prev's own finalize died before
            # its append, recording cur would leave a round-number gap
            if pend is not None and len(self.history) == pend["round"] - 1:
                self._finish_round(pend)
        return RunResult(self.history, evals_of(self.history))

    # -- checkpoint state (repro.experiment resume contract) -----------------
    def state(self):
        """``(arrays, meta)`` mirroring ``FedPhD.state``: the stacked
        per-client method buffers (SCAFFOLD variates, MOON prev models,
        FedDiffuse local subtrees, persistent Adam), global params, and
        every RNG stream the trajectory consumes."""
        arrays = {
            "params": self.params,
            "rng": self.rng,
            "opt_stack": self._opt_stack,
            "c_global": self.c_global,
            "c_local_stack": self._c_local_stack,
            "prev_stack": self._prev_stack,
            "local_stack": self._local_stack,
            "seen": self._seen,
            "late_buf": self._late_buf,
            "err_stack": self._err_stack,
        }
        meta = {
            "trainer": "flat",
            "method": self.method,
            "np_rng": self.np_rng.bit_generator.state,
            "client_rngs": [cl.data.rng_state() for cl in self.clients],
            "history": [rec.to_dict() for rec in self.history],
            "fault": self._faults.state() if self._faults else None,
        }
        return arrays, meta

    def restore(self, arrays, meta) -> None:
        """Inverse of ``state()`` on a trainer built with the same
        constructor arguments."""
        if meta.get("method", self.method) != self.method:
            raise ValueError(f"checkpoint is for method "
                             f"{meta['method']!r}, trainer is {self.method!r}")
        to_dev = lambda t: None if t is None \
            else jax.tree.map(jnp.asarray, t)
        # stacked (N,) buffers land wherever this trainer keeps them
        # (host numpy or device), non-stacked state always on device
        to_store = lambda t: store_tree(t, self._store)
        self.params = to_dev(arrays["params"])
        self.rng = jnp.asarray(arrays["rng"])
        self.c_global = to_dev(arrays["c_global"])
        self._c_local_stack = to_store(arrays["c_local_stack"])
        self._prev_stack = to_store(arrays["prev_stack"])
        self._local_stack = to_store(arrays["local_stack"])
        self._seen = np.asarray(arrays["seen"], bool).copy()
        self._late_buf = to_dev(arrays.get("late_buf"))
        if self.quant != "none" and arrays.get("err_stack") is not None:
            self._err_stack = to_store(arrays["err_stack"])
        if self.persistent_opt:
            self._opt_stack = adam_stack_from_tree(arrays["opt_stack"],
                                                   self._store)
        self.np_rng.bit_generator.state = meta["np_rng"]
        for cl, st in zip(self.clients, meta["client_rngs"]):
            cl.data.set_rng_state(st)
        if self._faults is not None and meta.get("fault"):
            self._faults.set_state(meta["fault"])
        self.history = [RoundRecord.from_dict(d) for d in meta["history"]]


def run_flat_fl(method: str, cfg: ModelConfig, fl: FLConfig,
                clients: List[Client], *, rounds: Optional[int] = None,
                lr: float = 2e-4, rng_seed: int = 0,
                eval_fn: Optional[Callable] = None,
                eval_every: int = 0, engine: Optional[str] = None,
                persistent_opt: bool = False) -> FlatFLResult:
    """Deprecated legacy front-end — use ``repro.experiment.run_spec``
    (declarative, resumable, traced) or construct :class:`FlatTrainer`
    directly; this wrapper will be removed after one release.

    method in {fedavg, fedprox, feddiffuse, moon, scaffold}.

    engine: "vectorized" | "sequential" | "auto" (None = $FEDPHD_ENGINE
    or auto); persistent_opt carries per-client Adam moments across
    rounds (off by default — the paper's baselines restart Adam each
    round).  ``eval_fn(params, cfg, round)`` results land in
    ``RoundRecord.eval`` (the unified hook contract).
    """
    warnings.warn(
        "run_flat_fl is deprecated; use repro.experiment.run_spec(...) "
        "or FlatTrainer(...) directly", DeprecationWarning, stacklevel=2)
    trainer = FlatTrainer(method, cfg, fl, clients, lr=lr,
                          rng_seed=rng_seed, engine=engine,
                          persistent_opt=persistent_opt,
                          eval_fn=eval_fn, eval_every=eval_every)
    trainer.run(rounds or fl.rounds)
    return FlatFLResult(history=trainer.history, params=trainer.params)


def run_centralized(cfg: ModelConfig, images: np.ndarray, *, steps: int,
                    batch_size: int, lr: float = 2e-4, rng_seed: int = 0,
                    use_ema: bool = True):
    """Centralized baseline (paper: 500K steps + EMA; scaled down here)."""
    from repro.optim import ema_init, ema_update
    rng = jax.random.PRNGKey(rng_seed)
    rng, sub = jax.random.split(rng)
    params = model.init(sub, cfg)
    opt_state = adam_init(params)
    ema = ema_init(params) if use_ema else None
    np_rng = np.random.default_rng(rng_seed)

    @jax.jit
    def step(params, opt_state, batch, rng):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss_fn(p, cfg, batch, rng))(params)
        params, opt_state = adam_update(grads, opt_state, params, lr=lr,
                                        grad_clip=1.0)
        return params, opt_state, loss

    losses = []
    for _ in range(steps):
        sel = np_rng.integers(0, len(images), size=batch_size)
        batch = {"images": jnp.asarray(images[sel])}
        rng, sub = jax.random.split(rng)
        params, opt_state, loss = step(params, opt_state, batch, sub)
        losses.append(float(loss))
        if use_ema:
            ema = ema_update(ema, params, 0.999)
    final = jax.tree.map(lambda e, p: e.astype(p.dtype), ema, params) \
        if use_ema else params
    return final, losses
