"""Training cells: FedPhD rounds through the program's own entry points
(``ExperimentSpec`` -> method registry -> ``FedPhD.run_round``).

Set-up builds the trainer from the traffic file's deployment, hands it
weights drawn from the seed (made on the device in one program by the
configuration's reference module), runs the program's aggregation once
for every client count an edge can get (so that none compiles in the
window), and runs round 1 through ``run_round``: that round compiles the
local step and is the one the check follows.  While it runs, a
pass-through wrapper on the trainer's local step keeps what the
comparison needs: the first client's first three steps (batch, key,
loss, the Adam state after step 1, the parameters after step 3), every
client's labels and its final model.  At the end of round 1 the edge
models are compared with the SH aggregation of those client models
(Eqs. 18, 24), and the client models are dropped; that comparison's
time is not counted in ``setup_s``.  Its inputs are the program's own
client models: it checks the aggregation, and only the first client's
first three steps are held against the reference's training.

The window runs whole rounds from round 2 on: it stops at the last
round boundary that fits in ``--seconds``, and holds one round where
one round is longer.  ``train_img_per_s`` is client batches times batch
size over the window's wall time, aggregation and host work included.

``check()`` runs the reference over the first client's three steps
(after the program's state is freed) and compares, by the worst leaf:
each step's loss, the norm of the first clipped gradient (read from
Adam's first moment after step 1), the norm of the parameters' change
after step 3, and the norm of the difference between the program's
and the reference's change (the one number that the program's own
bf16 path, the control, fails on a TPU); and, from set-up, the norm of
each edge model's change from the round's start.  ``readings()`` gives
the step numbers for a fault planted in the reference.
"""
from __future__ import annotations

import dataclasses
import math
import os
import time

import numpy as np

from lib import harness
from lib.macs import unet_macs


def _ref_module(ctx):
    return harness.load_module(os.path.join(
        ctx.cell.bench_dir, "configs", ctx.cell.config["reference"] + ".py"))


def leaf_gaps(prog, ref, exclude=None):
    """Per leaf: | ||prog|| - ||ref|| | over max(||ref||, the median
    leaf's ||ref||).  ``prog``/``ref`` are per-leaf norm vectors."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    return leaf_ratios(np.abs(prog - ref), ref, exclude)


def leaf_ratios(diff, ref, exclude=None):
    """Per leaf: ``diff`` over max(||ref||, the median leaf's ||ref||),
    0 for the leaves in ``exclude``."""
    diff, ref = np.asarray(diff, np.float64), np.asarray(ref, np.float64)
    keep = np.ones(len(ref), bool) if exclude is None else ~exclude
    med = float(np.median(ref[keep]))
    den = np.maximum(ref, med)
    gaps = np.where(den > 0, diff / np.where(den > 0, den, 1.0),
                    np.where(diff > 0, np.inf, 0.0))
    gaps[~keep] = 0.0
    return np.where(np.isnan(gaps), np.inf, gaps)


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.cell.config
        self.tr = ctx.cell.traffic
        self.ref = _ref_module(ctx)

    # -- set-up ---------------------------------------------------------------
    def _spec(self):
        from repro.experiment.runner import PRESETS
        base = PRESETS[self.tr["preset"]]
        fl = dataclasses.replace(base.fl, **self.tr.get("fl", {}))
        data = dataclasses.replace(base.data, **self.tr.get("data", {}))
        return base.replace(name=self.ctx.cell.name,
                            model=self.cfg["program_model"], fl=fl, data=data,
                            engine=self.tr["engine"], backend="xla",
                            precision=self.ctx.precision,
                            seed=self.ctx.seed % (2**31 - 1))

    def setup(self):
        import jax
        import jax.numpy as jnp
        from repro.experiment import Experiment
        ctx, ref = self.ctx, self.ref
        self.spec = spec = self._spec()
        t0 = time.perf_counter()
        self.exp = Experiment(spec)
        tr = self.trainer = self.exp.trainer
        key = jax.random.PRNGKey(ctx.seed % 2**31)
        make = jax.jit(lambda k: ref.init_params(k, self.cfg))
        self.w0 = make(jax.random.fold_in(key, 1))
        if (jax.tree.structure(self.w0) != jax.tree.structure(tr.params)
                or [a.shape for a in jax.tree.leaves(self.w0)]
                != [a.shape for a in jax.tree.leaves(tr.params)]):
            raise harness.BenchError("the reference's parameter layout "
                                     "differs from the program's")
        tr.params = self.w0
        self.macs = unet_macs(self.w0, self.cfg["image_size"])
        ctx.say("setup", build_s=time.perf_counter() - t0,
                clients=spec.fl.num_clients,
                params_m=sum(a.size for a in jax.tree.leaves(self.w0)) / 1e6,
                gmac_per_image=self.macs / 1e9)
        if ctx.timed:
            self._warm_aggregation()
        # round 1: compiles the step, feeds the check
        self.round_no = 1
        self._record_round(1)

    def _warm_aggregation(self):
        """The sequential engine aggregates an edge's client models
        eagerly, one stack and one weighted sum per leaf, so every
        client count an edge can get compiles programs of its own.
        Selection decides the counts round by round; run the program's
        aggregation once for every count from 1 to all clients (the
        cloud's, over the edges, among them) so that none compiles in
        the window."""
        import jax
        from repro.core.aggregation import aggregate_sh
        fl, p = self.spec.fl, self.w0
        t0 = time.perf_counter()
        for n in range(1, fl.num_clients + 1):
            jax.block_until_ready(aggregate_sh(
                [p] * n, [1] * n, [1.0] * n, fl.sh_a, fl.sh_b))
        self.ctx.counters["aggregation_warm_s"] = time.perf_counter() - t0

    def _step_attr(self):
        return "step_sparse" if self.trainer.step_sparse is not None \
            and self.tr["phase"] == "sparse" else "step_plain"

    def _record_round(self, r):
        import jax
        import jax.numpy as jnp
        tr, spec = self.trainer, self.spec
        attr = self._step_attr()
        orig = getattr(tr, attr)
        steps = spec.fl.local_epochs * tr.clients[0].data.steps_per_epoch
        if steps < 3:
            raise harness.BenchError(f"a client runs {steps} steps a round; "
                                     "the check follows three")
        nc = tr.clients[0].num_classes
        calls, finals, labels = [], [], []
        first = {}
        norms = jax.jit(lambda t: jnp.stack(
            [jnp.sqrt(jnp.sum(jnp.square(a))) for a in jax.tree.leaves(t)]))
        diff_norms = jax.jit(lambda a, b: jnp.stack(
            [jnp.sqrt(jnp.sum(jnp.square(x - y))) for x, y in
             zip(jax.tree.leaves(a), jax.tree.leaves(b))]))
        b1 = self.cfg["adam_b1"]

        def wrapped(params, opt_state, batch, rng, ctx_):
            out = orig(params, opt_state, batch, rng, ctx_)
            i = len(calls)
            calls.append(None)
            if i % steps == 0:
                labels.append(np.zeros(nc, np.int64))
            labels[-1] += np.bincount(np.asarray(batch["labels"]),
                                      minlength=nc)
            if i < 3:
                first.setdefault("images", []).append(batch["images"])
                first.setdefault("rngs", []).append(rng)
                first.setdefault("loss", []).append(out[2])
                if i == 0:
                    first["start"] = params
                    first["grad"] = np.asarray(norms(out[1].mu)) / (1 - b1)
                if i == 2:
                    first["change"] = np.asarray(diff_norms(out[0],
                                                            first["start"]))
                    first["p3"] = out[0]
            if i % steps == steps - 1:
                finals.append(out[0])
            return out

        setattr(tr, attr, wrapped)
        try:
            t0 = time.perf_counter()
            rec = tr.run_round(r)
            round_s = time.perf_counter() - t0
        finally:
            setattr(tr, attr, orig)
        t0 = time.perf_counter()
        self.first = {"images": first["images"], "rngs": first["rngs"],
                      "loss": [float(x) for x in first["loss"]],
                      "grad": first["grad"], "change": first["change"],
                      "p3": first["p3"],
                      "sparse": attr == "step_sparse"}
        self.edge_gap = self._edge_check(finals, labels, first["start"],
                                         diff_norms)
        check_s = time.perf_counter() - t0
        self.ctx.say("round1", seconds=round_s, loss=rec.loss,
                     clients=len(rec.selected), steps=len(calls),
                     edge_check_s=check_s)
        self.ctx.counters["check_in_setup_s"] = check_s

    def _edge_check(self, finals, labels, start, diff_norms):
        """Edge models against the SH-weighted mean of the clients that
        reported to each edge (in the order they ran, edge by edge; the
        split read from each edge's accumulated label counts)."""
        import jax
        import jax.numpy as jnp
        arrays, _ = self.trainer.state()
        edges = arrays["edge_models"]
        counts = np.asarray(arrays["edge_counts"], np.float64)
        fl = self.spec.fl
        acc = jax.jit(lambda a, t, w: jax.tree.map(
            lambda x, y: x + w * y, a, t))
        worst, pos = 0.0, 0
        for e in range(fl.num_edges):
            if str(e) not in edges:
                continue
            run = np.zeros(counts.shape[1])
            members = []
            while pos < len(labels) and not np.allclose(run, counts[e]):
                run = run + labels[pos]
                members.append(pos)
                pos += 1
            if not members or not np.allclose(run, counts[e]):
                return float("inf")
            w = self.ref.sh_weights([labels[i] for i in members],
                                    fl.sh_a, fl.sh_b)
            agg = jax.tree.map(jnp.zeros_like, start)
            for i, wi in zip(members, w):
                agg = acc(agg, finals[i], jnp.float32(wi))
            ref_n = np.asarray(diff_norms(agg, start))
            prog_n = np.asarray(diff_norms(edges[str(e)], start))
            worst = max(worst, float(np.max(leaf_gaps(prog_n, ref_n))))
        return worst if pos == len(labels) else float("inf")

    # -- window ---------------------------------------------------------------
    def window(self, seconds):
        import jax
        tr, fl = self.trainer, self.spec.fl
        bs = self.spec.data.batch_size
        spc = fl.local_epochs * tr.clients[0].data.steps_per_epoch
        t0 = time.perf_counter()
        images, rounds = 0, []
        while True:
            self.round_no += 1
            ts = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench/round"):
                rec = tr.run_round(self.round_no)
            te = time.perf_counter()
            rounds.append(te - ts)
            images += len(rec.selected) * spc * bs
            if not math.isfinite(rec.loss):
                self.ctx.counters["failed"] = \
                    self.ctx.counters.get("failed", 0) + 1
            if (te - t0) + (te - ts) > seconds:
                break
        jax.block_until_ready(tr.state()[0]["edge_models"])
        window_s = time.perf_counter() - t0
        c = self.ctx.counters
        c.update(attempted=len(rounds), images=images, window_s=window_s,
                 macs=self.macs, rounds_s=rounds)
        self.ctx.say("window", rounds=len(rounds), images=images,
                     window_s=window_s, round_s=rounds)
        return {"train_img_per_s": images / window_s}

    def release(self):
        import jax
        del self.trainer
        self.exp = None
        jax.clear_caches()

    # -- check ----------------------------------------------------------------
    def _reference(self, half=False):
        """The reference over the first client's three steps from the
        round's start: (losses, per-leaf norms of the first clipped
        gradient, per-leaf norms of the change after step 3, the
        parameters after step 3).  ``half`` leaves out the second half
        of each batch (a planted fault)."""
        import jax
        import jax.numpy as jnp
        ref, cfg, f = self.ref, self.cfg, self.first
        sparse = f["sparse"]

        def loss(p, x, k):
            if half:
                x = x[:x.shape[0] // 2]
            return ref.ddpm_loss(p, cfg, x, k, sparse=sparse)

        vg = jax.jit(jax.value_and_grad(loss))
        adam = jax.jit(lambda p, m, v, s, g: ref.adam_step(p, m, v, s, g,
                                                           cfg))
        norms = jax.jit(lambda t: jnp.stack(
            [jnp.sqrt(jnp.sum(jnp.square(a))) for a in jax.tree.leaves(t)]))
        diff_norms = jax.jit(lambda a, b: jnp.stack(
            [jnp.sqrt(jnp.sum(jnp.square(x - y))) for x, y in
             zip(jax.tree.leaves(a), jax.tree.leaves(b))]))
        p = self.w0
        mu = jax.tree.map(jnp.zeros_like, p)
        nu = jax.tree.map(jnp.zeros_like, p)
        losses, g_first = [], None
        for k in range(3):
            l, g = vg(p, f["images"][k], f["rngs"][k])
            p, mu, nu, gc = adam(p, mu, nu, jnp.float32(k + 1), g)
            losses.append(float(l))
            if k == 0:
                g_first = np.asarray(norms(gc))
        return losses, g_first, np.asarray(diff_norms(p, self.w0)), p

    def _compare(self, got, want):
        """The step numbers of a run ``got`` against ``want``, both as
        ``_reference`` returns them.  Leaves whose reference gradient is
        under a thousandth of the median leaf's move by round-off alone
        and are left out of every per-leaf number."""
        import jax
        import jax.numpy as jnp
        loss_g, grad_g, change_g, p_g = got
        loss_r, grad_r, change_r, p_r = want
        quiet = grad_r < 1e-3 * float(np.median(grad_r))
        diff = np.asarray(jax.jit(lambda a, b: jnp.stack(
            [jnp.sqrt(jnp.sum(jnp.square(x - y))) for x, y in
             zip(jax.tree.leaves(a), jax.tree.leaves(b))]))(p_g, p_r))
        return {"step_loss_gap": max(abs(a - b) / abs(b)
                                     for a, b in zip(loss_g, loss_r)),
                "first_grad_norm_gap": float(np.max(leaf_gaps(
                    grad_g, grad_r, quiet))),
                "change_norm_gap": float(np.max(leaf_gaps(
                    change_g, change_r, quiet))),
                "change_diff_gap": float(np.max(leaf_ratios(
                    diff, change_r, quiet)))}

    def check(self):
        f = self.first
        want = self._reference()
        nums = self._compare((f["loss"], f["grad"], f["change"], f["p3"]),
                             want)
        nums["edge_change_norm_gap"] = self.edge_gap
        self._want = want
        self.ctx.say("reference", loss_prog=f["loss"], loss_ref=want[0],
                     quiet_leaves=int(np.sum(
                         want[1] < 1e-3 * float(np.median(want[1])))))
        lim = self.cfg["check_limits"]["train"]
        return [(n, v, lim[n]) for n, v in nums.items()]

    def readings(self):
        """After ``check()``: the same step numbers for a fault planted
        in the reference put in the program's place (half of each batch
        left out).  The control is the program's own bf16 path
        (``bench/control.py --precision bf16``)."""
        return {"half_batch": self._compare(self._reference(half=True),
                                            self._want)}
