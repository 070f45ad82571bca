"""FedPhD hierarchical-FL orchestrator (paper Algorithm 1).

Simulates the three-tier topology — clients -> edge servers -> cloud —
with homogeneity-aware aggregation at both tiers, SH-driven edge
selection, and distributed structured pruning (sparse-train rounds with
the Eq. 16 regularizer, then one-shot compaction at the cloud at r = R_s;
or FedPhD-OS one-shot pruning at r = 0).

On a real multi-pod TPU deployment the two aggregation tiers map onto
ICI (intra-pod) and DCN (inter-pod) all-reduces — see
repro/launch/federated.py for the shard_map realization; this module is
the faithful event-level simulation the paper's tables are produced from.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (FLConfig, ModelConfig, config_from_dict,
                                config_to_dict)
from repro.core.aggregation import (aggregate_fedavg, aggregate_sh,
                                    fedavg_weights, normalize_weights,
                                    sh_weights, weighted_sum)
from repro.core.pruning import (build_groups, compact, l2_scores, make_masks,
                                random_scores)
from repro.core.selection import random_selection, select_edge
from repro.core.sh_score import AccumulatedDistribution, sh_score, uniform_target
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
# RoundRecord is re-exported here for compatibility: it moved to
# repro.fl.record when the flat baselines adopted the same schema.
from repro.fl.record import RoundRecord, RunResult, evals_of
from repro.models import model
from repro.models.ops import resolve_backend, resolve_precision
from repro.obs.compile_tracker import CompileTracker, cache_size
from repro.obs.trace import NULL_TRACER
from repro.optim import adam_init


class FedPhD:
    """The FedPhD trainer.

    method: "fedphd" (SH aggregation + SH selection),
            "fedphd-os" (one-shot pruning at init),
            ablations: selection="random", aggregation="fedavg".

    engine: "vectorized" — one jitted vmap(client)/scan(batch) program
            per round with fused on-device edge aggregation and a single
            loss sync (repro/fl/engine.py);
            "sequential" — the per-client Python reference loop;
            "auto" — vectorized whenever the selected clients share a
            batch shape, sequential (with a one-time warning) otherwise;
            None (default) — $FEDPHD_ENGINE if set, else "auto".
    cfg.backend: the compute backend every compiled program routes its
            tensor-core ops through (repro.models.ops: "xla" | "pallas"
            | "ref"; "" resolves via $FEDPHD_BACKEND at construction
            and the concrete name is baked into self.cfg).
    persistent_opt: carry per-client Adam moments across rounds in a
            stacked (N, ...) buffer, gathered/scattered by each round's
            participation selection.  Off by default (the paper restarts
            Adam every round); moments reset when pruning changes the
            parameter shapes at r = R_s.
    state_store: where that stacked buffer lives — "device", "host"
            (numpy; only the participating rows move to device per
            round, so a 10k-client population with 1% participation
            fits), or "auto" (host when N >> participants — see
            repro.fl.engine.resolve_store).
    mesh:   optional jax mesh; the stacked client axis of the vectorized
            engine is laid over ``client_axis`` inside the round engine
            (launch/federated.py shard_clients), so one run's vmapped
            local training partitions across devices.
    eval_fn/eval_every: the unified eval-hook contract —
            ``eval_fn(params, cfg, round)`` is called every
            ``eval_every`` rounds and its result stored in
            ``RoundRecord.eval`` (identical for the flat trainers).
    """

    def __init__(self, cfg: ModelConfig, fl: FLConfig, clients: List[Client],
                 *, rng_seed: int = 0, selection: str = "sh",
                 aggregation: str = "sh", prune: bool = True,
                 lr: float = 2e-4, engine: Optional[str] = None,
                 persistent_opt: bool = False, state_store: str = "auto",
                 mesh=None, client_axis: str = "data",
                 eval_fn: Optional[Callable] = None, eval_every: int = 0,
                 fault: Optional[FaultSpec] = None, quant: str = "none",
                 tracer=None):
        # bake the resolved compute backend AND precision into the
        # frozen config so every compiled program (and the checkpoint
        # manifest) pins concrete values even when they came from
        # $FEDPHD_BACKEND / $FEDPHD_PRECISION
        self.cfg = cfg = cfg.replace(
            backend=resolve_backend(cfg.backend),
            precision=resolve_precision(cfg.precision))
        # obs tracing: NULL_TRACER (the default) makes every span/event
        # call site a no-op — tracing never touches RNG or numerics
        self._obs = NULL_TRACER
        self._obs_compile = None
        if quant not in QUANTS:
            raise ValueError(f"unknown quant {quant!r}; expected one of "
                             f"{QUANTS}")
        self.quant = quant
        self.fl = fl
        self.clients = clients
        self.selection = selection
        self.aggregation = aggregation
        self.prune = prune
        self.lr = lr
        self.engine, self._engine_strict = resolve_engine(engine)
        self.persistent_opt = persistent_opt
        self._warned_ragged = False
        self.mesh = mesh
        self.client_axis = client_axis
        self._store = resolve_store(
            state_store, len(clients),
            max(1, round(fl.participation * len(clients))))
        self.eval_fn = eval_fn
        self.eval_every = eval_every
        self.np_rng = np.random.default_rng(rng_seed)
        self.rng = jax.random.PRNGKey(rng_seed)
        # fault injection: a disabled (or absent) spec yields no model
        # and every fault branch below collapses to the fault-free path
        self.fault = fault if (fault is not None and fault.enabled) else None
        self._faults = make_fault_model(self.fault, len(clients), rng_seed)
        # staleness aggregation: per-edge buffered late-delta sums,
        # merged into that edge's NEXT aggregate (dropped at the prune
        # boundary — parameter shapes change)
        self._late_buf: Dict[int, Dict] = {}

        num_classes = clients[0].num_classes
        self.q_u = uniform_target(num_classes)
        self.edges = [AccumulatedDistribution(num_classes)
                      for _ in range(fl.num_edges)]

        self.rng, sub = jax.random.split(self.rng)
        self.params = model.init(sub, cfg)
        self.groups = build_groups(cfg, self.params)
        self.comm = CommModel()
        self.history: List[RoundRecord] = []
        self.pruned = False

        if prune and fl.prune_mode.startswith("oneshot"):
            self._prune_now(mode=fl.prune_mode)

        self._rebuild_steps()
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
        self._watch_compiles()

    def _watch_compiles(self) -> None:
        """(Re)point the compile tracker at the current jitted entry
        points — called after every ``_rebuild_steps`` so the post-prune
        plain engine gets its own expected first compile."""
        if self._obs_compile is None:
            return
        for name, fn in (("step_plain", self.step_plain),
                         ("step_sparse", self.step_sparse),
                         ("engine_plain", self._engine_plain),
                         ("engine_sparse", self._engine_sparse)):
            if fn is not None:
                self._obs_compile.watch(name, fn)

    # -- pruning ------------------------------------------------------------
    def _prune_now(self, mode: str) -> None:
        if mode == "oneshot_random":
            self.rng, sub = jax.random.split(self.rng)
            scores = random_scores(sub, self.groups)
        else:  # group_norm or oneshot_l2
            scores = l2_scores(self.params, self.groups,
                               backend=self.cfg.backend)
        masks = make_masks(scores, self.groups, self.fl.prune_ratio)
        self.params, self.cfg, report = compact(self.params, self.cfg,
                                                self.groups, masks)
        self.groups = build_groups(self.cfg, self.params)
        self.pruned = True
        self.prune_report = report

    def _rebuild_steps(self) -> None:
        sparse = (self.prune and not self.pruned
                  and self.fl.prune_mode == "group_norm")
        self.step_sparse = make_local_step(self.cfg, self.fl, sparse=True,
                                           groups=self.groups, lr=self.lr) \
            if sparse else None
        self.step_plain = make_local_step(self.cfg, self.fl, sparse=False,
                                          lr=self.lr)
        self._engine_sparse = make_round_engine(
            self.cfg, self.fl, sparse=True, groups=self.groups,
            lr=self.lr, mesh=self.mesh, client_axis=self.client_axis,
            quant=self.quant) if sparse else None
        self._engine_plain = make_round_engine(self.cfg, self.fl,
                                               sparse=False, lr=self.lr,
                                               mesh=self.mesh,
                                               client_axis=self.client_axis,
                                               quant=self.quant)
        # one Adam zero-tree per model shape, shared by every client in
        # every sequential round (the vectorized engine builds its own
        # in-program constant)
        self._opt_zero = adam_init(self.params)
        # persistent per-client moments: a stacked (N, ...) buffer both
        # engines gather/scatter by participation.  Rebuilt (i.e. reset
        # to zeros) whenever pruning changes the parameter shapes.
        self._opt_stack = stacked_adam_init(self.params, len(self.clients),
                                            host=self._store == "host") \
            if self.persistent_opt else None
        # per-client error-feedback residuals for the quantized uplink:
        # fp32, congruent with params, reset here (= at the prune
        # boundary, where the leaf shapes change under them)
        self._err_stack = stacked_zeros(self.params, len(self.clients),
                                        dtype=np.float32,
                                        host=self._store == "host") \
            if self.quant != "none" else None
        self._watch_compiles()

    # -- bookkeeping ----------------------------------------------------------
    def _param_count_m(self) -> float:
        return sum(x.size for x in jax.tree.leaves(self.params)) / 1e6

    def _model_bytes(self) -> int:
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(self.params))

    def _wire_bytes(self):
        """Bytes-on-wire per transfer: ``(up, up_late, down)`` — the
        quantized on-time uplink (payload + per-leaf scales), the fp32
        late/staleness uplink, and the compute-dtype download."""
        return (uplink_bytes(self.params, self.quant),
                uplink_bytes(self.params, "none"),
                downlink_bytes(self.params, self.cfg.precision))

    # -- local training + edge aggregation (Alg. 1 lines 7-21) ---------------
    def _use_vectorized(self, round_clients) -> bool:
        use, self._warned_ragged = route_engine(
            self.engine, self._engine_strict, round_clients,
            self._warned_ragged, "FedPhD", method="fedphd")
        return use

    def _local_and_edge_sequential(self, r, assignment, sparse_round, wire,
                                   faults=None):
        """Reference path: one jitted step per batch, Python aggregation.

        Under an active fault schedule (``faults``): non-arrived clients
        run zero steps (their RNG streams still advance in lockstep with
        the stacked path), dropped/straggling clients truncate at their
        step budget, only reporting clients enter the edge aggregate
        (weights renormalized among them) and count uplink, and LATE
        clients' deltas buffer into ``_late_buf`` for the staleness
        merge at the edge's next aggregation.

        With ``quant`` active, each ON-TIME reporter's delta runs the
        error-feedback quantize->dequantize round trip and the edge
        aggregates the reconstructed ``start + deq`` — late deltas ship
        (and buffer) fp32.
        """
        fl = self.fl
        obs = self._obs
        up_q, up_f, down = wire
        step_fn = self.step_sparse if sparse_round else self.step_plain
        remat = self.cfg.remat_policy
        round_losses: List[float] = []
        loss_mask: List[bool] = []
        up_bytes, down_bytes = 0.0, 0.0
        for e, cids in assignment.items():
            if not cids:
                continue
            edge_model = getattr(self, "_edge_models", {}).get(e, self.params)
            client_models, counts, mus = [], [], []
            late_models, late_counts = [], []
            n_arrived = 0
            for cid in cids:
                cl = self.clients[cid]
                with obs.span("client/train", round=r, client=int(cid),
                              edge=e, remat=remat):
                    self.rng, sub = jax.random.split(self.rng)
                    budget = faults.budget_of(cid) if faults else None
                    opt_in = tree_gather(self._opt_stack, int(cid)) \
                        if self.persistent_opt else self._opt_zero
                    p, opt_out, loss = run_local(step_fn, edge_model, cl,
                                                 epochs=fl.local_epochs,
                                                 rng=sub, opt_state=opt_in,
                                                 max_steps=budget,
                                                 tracer=obs, remat=remat)
                completed = faults is None or faults.completed_of(cid)
                late = faults is not None and faults.late_of(cid)
                if self.persistent_opt and completed:
                    self._opt_stack = tree_scatter(self._opt_stack,
                                                   int(cid), opt_out)
                round_losses.append(loss)
                loss_mask.append(budget is None or budget > 0)
                if faults is not None and faults.arrived_of(cid):
                    n_arrived += 1
                if completed:
                    self.edges[e].update(cl.q_n, cl.n_samples)     # Eq. 19
                    up_bytes += self.comm.client_edge(up_f if late
                                                      else up_q)    # upload
                if late:
                    late_models.append(p)
                    late_counts.append(cl.n_samples)
                elif completed:                       # reporting on time
                    if self.quant != "none":
                        delta = jax.tree.map(lambda a, b: a - b, p,
                                             edge_model)
                        e_row = store_tree(
                            tree_gather(self._err_stack, int(cid)), "device")
                        deq, new_err = _ef_jit(delta, e_row, self.quant)
                        self._err_stack = tree_scatter(self._err_stack,
                                                       int(cid), new_err)
                        p = jax.tree.map(lambda s, d: s + d, edge_model, deq)
                    client_models.append(p)
                    counts.append(cl.n_samples)
                    mus.append(sh_score(cl.q_n, self.q_u))
            if r % fl.edge_agg_every == 0:
                with obs.span("round/edge_agg", round=r, edge=e,
                              clients=len(client_models),
                              programs=cache_size(weighted_sum)):
                    if client_models:
                        if self.aggregation == "sh":
                            agg = aggregate_sh(client_models, counts, mus,
                                               fl.sh_a, fl.sh_b)  # Eq. 23/24
                        else:
                            agg = aggregate_fedavg(client_models, counts)
                    else:
                        # no client reported: the edge keeps its model
                        agg = edge_model
                    if self.aggregation == "staleness":
                        buf = self._late_buf.pop(e, None)
                        if buf is not None:  # merge last round's stragglers
                            agg = apply_late(agg, buf, self.fault.staleness
                                             if self.fault else 0.0)
                        if late_models:
                            tot = max(sum(counts) + sum(late_counts), 1)
                            w = [n / tot for n in late_counts]
                            self._late_buf[e] = late_delta(late_models,
                                                           edge_model, w)
                if not hasattr(self, "_edge_models"):
                    self._edge_models = {}
                self._edge_models[e] = agg
                n_down = len(cids) if faults is None else n_arrived
                down_bytes += self.comm.client_edge(down) * n_down  # down
        return round_losses, up_bytes, down_bytes, loss_mask

    def _local_and_edge_vectorized(self, r, assignment, sparse_round, wire,
                                   faults=None):
        """Device-resident path: one program for all clients + edge agg.

        Fault injection stays shape-static: straggler/dropout budgets
        truncate the (C, S) valid mask as a data-only prefix AND (no
        recompilation), non-reporting clients are zeroed out of the
        (E, C) aggregation einsum with weights renormalized among the
        reporters, and late clients' staleness deltas come back via the
        ``w_late`` operand's in-engine einsum.
        """
        fl = self.fl
        obs = self._obs
        with obs.span("round/host_prep", round=r):
            order = [(e, cid) for e, cids in assignment.items()
                     for cid in cids]
            # identical RNG folding to the sequential loop: one split per
            # client in edge-iteration order
            subs = []
            for _ in order:
                self.rng, sub = jax.random.split(self.rng)
                subs.append(sub)
            clients = [self.clients[cid] for _, cid in order]
            # masking is identity when no client needed padding — elide
            # the per-step select ops at trace time in that (common) case
            batches, valid, masked = stack_round([cl.data for cl in clients],
                                                 fl.local_epochs)
            if faults is not None:
                # prefix truncation: client i executes only its first
                # budget_i steps.  Same shapes as the fault-free round.
                budgets = np.asarray([faults.budget_of(cid)
                                      for _, cid in order])
                prefix = np.arange(valid.shape[1])[None, :] < budgets[:, None]
                masked = masked or not bool(prefix.all())
                valid = valid & prefix
        with obs.span("round/h2d", round=r):
            batches, valid = stage_clients((batches, valid), self.mesh,
                                           self.client_axis)
            rngs = jnp.stack(subs)
            edge_models = getattr(self, "_edge_models", {})
            edge_stack = jax.tree.map(
                lambda *leaves: jnp.stack(leaves),
                *[edge_models.get(e, self.params)
                  for e in range(fl.num_edges)])
            edge_idx = jnp.asarray(np.asarray([e for e, _ in order],
                                              np.int32))

        # fused aggregation rows: W[e] = normalized Eq. 22/24 weights of
        # edge e's REPORTING clients, zero elsewhere (graceful
        # degradation: dropped/late clients never enter the einsum)
        staleness = self.aggregation == "staleness"
        w_mat = np.zeros((fl.num_edges, len(order)), np.float32)
        w_late = np.zeros((fl.num_edges, len(order)), np.float32) \
            if staleness else None
        any_late = False
        for e, cids in assignment.items():
            if not cids:
                continue
            rep = [cid for cid in cids
                   if faults is None or faults.reporting_of(cid)]
            if rep:
                counts = [self.clients[cid].n_samples for cid in rep]
                mus = [sh_score(self.clients[cid].q_n, self.q_u)
                       for cid in rep]
                w = sh_weights(counts, mus, fl.sh_a, fl.sh_b) \
                    if self.aggregation == "sh" else fedavg_weights(counts)
                idxs = [i for i, (ee, cid) in enumerate(order)
                        if ee == e and cid in rep]
                w_mat[e, idxs] = normalize_weights(w)
            if staleness and faults is not None:
                late = [cid for cid in cids if faults.late_of(cid)]
                if late:
                    any_late = True
                    tot = max(sum(self.clients[cid].n_samples for cid in rep)
                              + sum(self.clients[cid].n_samples
                                    for cid in late), 1)
                    for i, (ee, cid) in enumerate(order):
                        if ee == e and cid in late:
                            w_late[e, i] = self.clients[cid].n_samples / tot

        # self.mesh (when set) is handled INSIDE the engine: the
        # _make_sharded_engine wrapper lays every client-leading operand
        # over the mesh's client axis before dispatch
        engine = self._engine_sparse if sparse_round else self._engine_plain
        idx_arr = np.asarray([cid for _, cid in order])
        # host-store gathered rows are numpy: stage them to device
        # explicitly so the engine's opt_states donation stays live
        with obs.span("round/dispatch", round=r):
            out = engine(edge_stack, edge_idx, batches, valid, rngs,
                         jnp.asarray(w_mat),
                         opt_states=(store_tree(
                             tree_gather(self._opt_stack, idx_arr), "device")
                             if self.persistent_opt else None),
                         w_late=(jnp.asarray(w_late) if any_late else None),
                         err=(store_tree(
                             tree_gather(self._err_stack, idx_arr), "device")
                             if self.quant != "none" else None),
                         masked=masked, per_client_opt=self.persistent_opt)
        if self.persistent_opt:
            if faults is None:
                self._opt_stack = tree_scatter(self._opt_stack, idx_arr,
                                               out["opt"])
            else:
                # only COMPLETED clients keep their updated moments
                comp = np.asarray([i for i, (_, cid) in enumerate(order)
                                   if faults.completed_of(cid)])
                if len(comp):
                    self._opt_stack = tree_scatter(
                        self._opt_stack, idx_arr[comp],
                        tree_gather(out["opt"], comp))
        if self.quant != "none":
            # only ON-TIME reporters shipped a quantized payload, so
            # only their residual rows advance (mirrors the sequential
            # loop; late/dropped lanes keep their buffers)
            rep = np.asarray([i for i, (_, cid) in enumerate(order)
                              if faults is None or faults.reporting_of(cid)])
            if len(rep):
                self._err_stack = tree_scatter(
                    self._err_stack, idx_arr[rep],
                    tree_gather(out["err"], rep))
        agg_stack = out["agg"]
        # NO host sync here: the (C,) loss array stays a device future
        # until _finish_round — under the pipelined run() the next
        # round's host-side data prep and H2D copy overlap this round's
        # device compute before anything blocks on it
        round_losses = out["losses"]
        loss_mask = [faults is None or faults.budget_of(cid) > 0
                     for _, cid in order]

        up_q, up_f, down = wire
        up_bytes, down_bytes = 0.0, 0.0
        n_arrived = {e: 0 for e in assignment}
        for e, cid in order:
            cl = self.clients[cid]
            if faults is not None and faults.arrived_of(cid):
                n_arrived[e] += 1
            if faults is None or faults.completed_of(cid):
                self.edges[e].update(cl.q_n, cl.n_samples)      # Eq. 19
                late = faults is not None and faults.late_of(cid)
                up_bytes += self.comm.client_edge(up_f if late
                                                  else up_q)     # upload
        if r % fl.edge_agg_every == 0:
            with obs.span("round/edge_agg", round=r):
                if not hasattr(self, "_edge_models"):
                    self._edge_models = {}
                for e, cids in assignment.items():
                    if not cids:
                        continue
                    if np.any(w_mat[e] > 0):
                        agg = jax.tree.map(lambda leaf, _e=e: leaf[_e],
                                           agg_stack)
                    else:
                        # no client reported: a zero w_mat row makes the
                        # einsum row a zero tree — the edge keeps its model
                        agg = edge_models.get(e, self.params)
                    if staleness:
                        buf = self._late_buf.pop(e, None)
                        if buf is not None:  # merge last round's stragglers
                            agg = apply_late(agg, buf, self.fault.staleness
                                             if self.fault else 0.0)
                        if w_late is not None and np.any(w_late[e] > 0):
                            self._late_buf[e] = jax.tree.map(
                                lambda leaf, _e=e: leaf[_e], out["late"])
                    self._edge_models[e] = agg
                    n_down = len(cids) if faults is None else n_arrived[e]
                    down_bytes += self.comm.client_edge(down) * n_down
        return round_losses, up_bytes, down_bytes, loss_mask

    # -- one communication round (Alg. 1 lines 3-32) -------------------------
    def run_round(self, r: int) -> RoundRecord:
        return self._finish_round(self._start_round(r))

    def _start_round(self, r: int) -> Dict:
        """Dispatch one round: selection, host data prep + H2D, the
        round program, edge/cloud aggregation and (at r = R_s) pruning
        — everything except blocking on the device losses.  Returns the
        pending-round dict ``_finish_round`` turns into a RoundRecord.

        On the vectorized engine nothing here forces a host sync, so
        ``run()`` double-buffers rounds: round r+1's ``stacked_epochs``
        shuffle/stack and H2D copy (the one buffer donation could not
        cover — ROADMAP "Open items") run while round r's program is
        still executing.
        """
        fl = self.fl
        if self._faults is not None:
            # churn first (its own RNG stream), then sample participants
            # from the online pool only — with churn=0 the np_rng
            # consumption is identical to the fault-free path
            online = self._faults.begin_round()
            pool = np.flatnonzero(online)
            C = min(max(1, round(fl.participation * len(self.clients))),
                    len(pool))
            sel_ids = pool[self.np_rng.choice(len(pool), size=C,
                                              replace=False)]
        else:
            C = max(1, round(fl.participation * len(self.clients)))
            sel_ids = self.np_rng.choice(len(self.clients), size=C,
                                         replace=False)

        # line 4-5: clients select edge servers
        assignment: Dict[int, List[int]] = {e: [] for e in range(fl.num_edges)}
        for cid in sel_ids:
            cl = self.clients[cid]
            if self.selection == "sh":
                e = select_edge(self.np_rng, self.edges, cl.q_n,
                                cl.n_samples, a=fl.sh_a, b=fl.sh_b)
            else:
                e = random_selection(self.np_rng, fl.num_edges)
            assignment[e].append(cid)

        sparse_round = (self.prune and not self.pruned
                        and fl.prune_mode == "group_norm" and r < fl.sparse_rounds)

        faults = None
        if self._faults is not None:
            steps = [fl.local_epochs * self.clients[c].data.steps_per_epoch
                     for c in sel_ids]
            faults = self._faults.draw_round(
                sel_ids, steps, self.aggregation == "staleness")

        wire = self._wire_bytes()
        # lines 7-21: per-edge local training + edge aggregation
        if self._use_vectorized([self.clients[c] for c in sel_ids]):
            round_losses, up_bytes, down_bytes, loss_mask = \
                self._local_and_edge_vectorized(
                    r, assignment, sparse_round, wire, faults)
        else:
            # the reference loop syncs per batch: host prep, compute and
            # aggregation interleave, so one dispatch span holds the
            # client/* and round/edge_agg spans
            with self._obs.span("round/dispatch", round=r):
                round_losses, up_bytes, down_bytes, loss_mask = \
                    self._local_and_edge_sequential(
                        r, assignment, sparse_round, wire, faults)

        pruned_this_round = False
        # lines 23-31: cloud aggregation every r_g rounds.  The
        # edge<->cloud tier ships fp32 uploads (quantization is the
        # client->edge uplink only) and compute-dtype broadcasts.
        if r % fl.cloud_agg_every == 0 and hasattr(self, "_edge_models"):
            with self._obs.span("round/cloud_agg", round=r,
                                programs=cache_size(weighted_sum)):
                models, counts, mus = [], [], []
                for e, m in self._edge_models.items():
                    models.append(m)
                    counts.append(self.edges[e].n)
                    mus.append(self.edges[e].sh(self.q_u))      # Eq. 20
                    up_bytes += self.comm.edge_cloud(wire[1])   # upload
                if models:
                    if self.aggregation == "sh":
                        self.params = aggregate_sh(
                            models, counts, mus, fl.sh_a, fl.sh_b)  # Eq. 21/22
                    else:
                        self.params = aggregate_fedavg(models, counts)
                # line 26-28: structured pruning at r = R_s
                if (self.prune and not self.pruned
                        and fl.prune_mode == "group_norm"
                        and r >= fl.sparse_rounds):
                    with self._obs.span("round/prune", round=r):
                        self._prune_now(mode="group_norm")
                        self._rebuild_steps()
                    pruned_this_round = True
                    wire = self._wire_bytes()
                    # buffered late deltas have pre-prune shapes: drop them
                    self._late_buf = {}
                # broadcast + refresh (lines 29-31)
                down_bytes += self.comm.edge_cloud(wire[2]) * fl.num_edges
                self._edge_models = {e: self.params
                                     for e in range(fl.num_edges)}
                for e in self.edges:
                    e.refresh()

        # snapshot end-of-round state the record needs: edge SH and the
        # params/cfg the eval hook sees must not leak mutations from a
        # round dispatched before this one is finalized
        return {"round": r, "losses": round_losses,
                "up_bytes": up_bytes, "down_bytes": down_bytes,
                "sel_ids": sel_ids,
                "pruned": pruned_this_round, "params": self.params,
                "cfg": self.cfg, "params_m": self._param_count_m(),
                "edge_sh": [e.sh(self.q_u) for e in self.edges],
                "loss_mask": loss_mask,
                "availability": faults.availability() if faults else None}

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
            loss=float(np.mean(losses)) if losses
            else (0.0 if mask is not None else float("nan")),
            # totals as the sum of the ROUNDED up/down fields, so
            # comm_gb == comm_up_gb + comm_down_gb holds exactly
            comm_gb=pend["up_bytes"] / 1e9 + pend["down_bytes"] / 1e9,
            comm_up_gb=pend["up_bytes"] / 1e9,
            comm_down_gb=pend["down_bytes"] / 1e9,
            params_m=pend["params_m"],
            selected=[int(c) for c in pend["sel_ids"]],
            edge_sh=pend["edge_sh"],
            pruned=pend["pruned"],
            availability=pend.get("availability"),
        )
        # append BEFORE the eval hook: the round executed (trainer state
        # and RNG streams advanced), so a raising eval_fn must lose the
        # eval, not the round — otherwise a later run()/resume would
        # re-run an already-applied round and diverge
        self.history.append(rec)
        if self._obs_compile is not None:
            # compiles triggered by this round's dispatch/sync are in
            # the caches by now; growth beyond the first per fn = a
            # shape/dtype leaked into a trace
            self._obs_compile.check(round=r)
        if self.eval_fn and self.eval_every and r % self.eval_every == 0:
            rec.eval = self.eval_fn(pend["params"], pend["cfg"], r)
        return rec

    def run(self, rounds: Optional[int] = None, *,
            eval_every: Optional[int] = None) -> RunResult:
        """Run rounds ``len(history)+1 .. rounds`` (continues after a
        restore).  Returns ``RunResult`` — unpacks as the legacy
        ``history, evals`` tuple; eval results also land in
        ``RoundRecord.eval`` (the unified hook contract).

        Rounds are double-buffered: round r+1 is dispatched
        (``_start_round`` — selection, stacked_epochs shuffle/stack,
        H2D copy, round-program dispatch) before round r's losses are
        synced (``_finish_round``), so host-side data prep overlaps
        device compute on the vectorized engine.  Records are
        finalized in round order and the per-round numerics are
        identical to stepping ``run_round`` directly — only the sync
        point moves.
        """
        rounds = rounds or self.fl.rounds
        if eval_every is not None:            # legacy per-call override
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
        """``(arrays, meta)``: everything the trajectory depends on.

        ``arrays`` is a pytree for ``repro.checkpoint.save``; ``meta``
        is JSON-serializable (RNG bit-generator states, the possibly
        post-prune ModelConfig, and the history records).  Restoring
        into a freshly constructed trainer reproduces an unbroken run
        bitwise on the sequential engine.
        """
        arrays = {
            "params": self.params,
            "rng": self.rng,
            "opt_stack": self._opt_stack,
            "edge_models": ({str(e): m for e, m in self._edge_models.items()}
                            if hasattr(self, "_edge_models") else None),
            "edge_counts": np.stack([e.counts for e in self.edges]),
            "edge_n": np.asarray([e.n for e in self.edges], np.int64),
            "late_buf": ({str(e): t for e, t in self._late_buf.items()}
                         or None),
            # quantized-uplink error-feedback residuals (None when
            # quant == "none"): restoring them bitwise is what keeps a
            # kill-and-resume trajectory identical to an unbroken run
            "err_stack": self._err_stack,
        }
        meta = {
            "trainer": "fedphd",
            "pruned": bool(self.pruned),
            "cfg": config_to_dict(self.cfg),
            "np_rng": self.np_rng.bit_generator.state,
            "client_rngs": [cl.data.rng_state() for cl in self.clients],
            "history": [rec.to_dict() for rec in self.history],
            "fault": self._faults.state() if self._faults else None,
        }
        return arrays, meta

    def restore(self, arrays, meta) -> None:
        """Inverse of ``state()`` on a trainer built with the same
        constructor arguments (same cfg/fl/clients/seed)."""
        to_dev = lambda t: jax.tree.map(jnp.asarray, t)
        cfg = config_from_dict(meta["cfg"])
        # pre-backend/precision checkpoints carry "" — resolve as at init
        self.cfg = cfg.replace(backend=resolve_backend(cfg.backend),
                               precision=resolve_precision(cfg.precision))
        self.pruned = bool(meta["pruned"])
        self.params = to_dev(arrays["params"])
        self.rng = jnp.asarray(arrays["rng"])
        self.groups = build_groups(self.cfg, self.params)
        if arrays.get("edge_models") is not None:
            self._edge_models = {int(e): to_dev(m)
                                 for e, m in arrays["edge_models"].items()}
        elif hasattr(self, "_edge_models"):
            del self._edge_models
        for i, e in enumerate(self.edges):
            e.counts = np.asarray(arrays["edge_counts"][i],
                                  np.float64).copy()
            e.n = int(arrays["edge_n"][i])
        self._late_buf = ({int(e): to_dev(t)
                           for e, t in arrays["late_buf"].items()}
                          if arrays.get("late_buf") else {})
        self.np_rng.bit_generator.state = meta["np_rng"]
        for cl, st in zip(self.clients, meta["client_rngs"]):
            cl.data.set_rng_state(st)
        if self._faults is not None and meta.get("fault"):
            self._faults.set_state(meta["fault"])
        self.history = [RoundRecord.from_dict(d) for d in meta["history"]]
        self._rebuild_steps()
        if self.quant != "none" and arrays.get("err_stack") is not None:
            # after _rebuild_steps (which zeroes the stack for the
            # restored cfg's shapes) — land the saved residuals where
            # this trainer keeps them
            self._err_stack = store_tree(arrays["err_stack"], self._store)
        if self.persistent_opt:
            self._opt_stack = adam_stack_from_tree(arrays["opt_stack"],
                                                   self._store)
