"""Seeded client-availability and fault-injection model.

Real federated deployments — the setting FedPhD targets — are dominated
by unreliable clients: devices that never show up for a round, crash
mid-round, compute at half speed, or leave the population entirely.
This module is the single source of truth for that behaviour:

  :class:`FaultSpec`   — the declarative, JSON-round-trippable knob set
                         (lives on ``ExperimentSpec.fault``, so sweeps
                         can grid over ``fault.dropout`` etc.);
  :class:`FaultModel`  — the seeded realization: one dedicated numpy
                         Generator (independent of the selection RNG)
                         draws each round's arrivals / dropouts /
                         straggler budgets / churn flips;
  :class:`RoundFaults` — one round's realized schedule, queried by both
                         the sequential and the vectorized engine.

The realization is engine-agnostic BY CONSTRUCTION: every round draws a
fixed number of variates (one churn vector, three uniform vectors over
the selection) regardless of which faults are active, so the stream —
and therefore the schedule — is bitwise identical across engines,
across kill-and-resume (the Generator state checkpoints), and across
aggregation modes.

Faults act on the round engine as *data*, never as shape: a client's
step budget truncates the existing shape-static ``valid`` masks of
``fl/engine.py`` (vectorized) or caps ``run_local`` (sequential), so no
fault pattern ever recompiles the round program.

Staleness (``aggregation="staleness"``): a straggler that cannot finish
by the deadline keeps training to completion and reports one round
LATE.  Its weighted delta sum is buffered and merged into the *next*
aggregate as ``base + gamma * sum_j w_j * (theta_j - start)`` with
``w_j = n_j / sum(all participating n)`` — FedAsync-style decay, so
with zero stragglers the mode is exactly FedAvg.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import weighted_average_stacked


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """Declarative fault model (all probabilities per round).

    arrival:        P(a selected client shows up at all).
    dropout:        P(an arrived client crashes mid-round).  A dropped
                    client completes a uniform prefix of its step budget
                    and never uploads (zero uplink).
    straggler_frac: fraction of the population running slow.
    slowdown:       slow clients' compute-time multiplier (>= 1).
    deadline:       round deadline in units of the nominal local-round
                    time; a client finishes ``floor(steps * deadline /
                    speed)`` steps by it.  1.0 = exactly the nominal
                    budget for full-speed clients.
    churn:          P(a client's membership flips between rounds) —
                    population churn; offline clients are not selectable.
    staleness:      gamma in [0, 1] weighting late deltas at the merge
                    round (only read under ``aggregation="staleness"``).
    seed:           fault-stream seed, combined with the experiment seed
                    so ``fault.seed`` is an independent sweep axis.
    """
    arrival: float = 1.0
    dropout: float = 0.0
    straggler_frac: float = 0.0
    slowdown: float = 2.0
    deadline: float = 1.0
    churn: float = 0.0
    staleness: float = 0.5
    seed: int = 0

    def __post_init__(self):
        for name in ("arrival", "dropout", "straggler_frac", "churn",
                     "staleness"):
            v = getattr(self, name)
            if not 0.0 <= v <= 1.0:
                raise ValueError(f"fault.{name}={v} not in [0, 1]")
        if self.slowdown < 1.0:
            raise ValueError(f"fault.slowdown={self.slowdown} < 1")
        if not 0.0 < self.deadline <= 1.0:
            raise ValueError(f"fault.deadline={self.deadline} not in (0, 1]")

    @property
    def enabled(self) -> bool:
        """True iff any fault can actually fire.  Trainers treat a
        disabled spec exactly as ``fault=None`` — bitwise-identical to
        the fault-free code path."""
        return (self.arrival < 1.0 or self.dropout > 0.0
                or self.churn > 0.0 or self.deadline < 1.0
                or (self.straggler_frac > 0.0 and self.slowdown > 1.0))

    def replace(self, **kw) -> "FaultSpec":
        return dataclasses.replace(self, **kw)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "FaultSpec":
        known = {k: v for k, v in d.items()
                 if k in {f.name for f in dataclasses.fields(cls)}}
        return cls(**known)


@dataclasses.dataclass
class RoundFaults:
    """One round's realized schedule over the selected clients.

    All arrays are aligned with ``sel_ids`` (selection order).
    ``budget`` is the number of local steps each client executes;
    ``reporting`` marks clients whose model enters this round's
    aggregation; ``completed`` additionally includes late clients
    (arrived, finished, reporting next round) — client-local state
    (persistent Adam, MOON/FedDiffuse/SCAFFOLD buffers) updates for
    ``completed`` clients only.
    """
    sel_ids: np.ndarray
    arrived: np.ndarray
    dropped: np.ndarray
    late: np.ndarray
    budget: np.ndarray
    n_online: int

    def __post_init__(self):
        self._pos: Dict[int, int] = {int(c): i
                                     for i, c in enumerate(self.sel_ids)}

    @property
    def completed(self) -> np.ndarray:
        return self.arrived & ~self.dropped

    @property
    def reporting(self) -> np.ndarray:
        return self.completed & ~self.late

    # -- per-client queries (the sequential path iterates clients) ----------
    def arrived_of(self, cid: int) -> bool:
        return bool(self.arrived[self._pos[int(cid)]])

    def completed_of(self, cid: int) -> bool:
        return bool(self.completed[self._pos[int(cid)]])

    def reporting_of(self, cid: int) -> bool:
        return bool(self.reporting[self._pos[int(cid)]])

    def late_of(self, cid: int) -> bool:
        return bool(self.late[self._pos[int(cid)]])

    def budget_of(self, cid: int) -> int:
        return int(self.budget[self._pos[int(cid)]])

    def availability(self) -> dict:
        """The JSON record stored in ``RoundRecord.availability`` — the
        cross-engine bitwise determinism artifact."""
        ids = self.sel_ids
        return {
            "online": int(self.n_online),
            "arrived": [int(c) for c in ids[self.arrived]],
            "dropped": [int(c) for c in ids[self.dropped]],
            "late": [int(c) for c in ids[self.late]],
            "budgets": [int(b) for b in self.budget],
        }


class FaultModel:
    """The seeded realization of a :class:`FaultSpec` over one client
    population.  Owns a dedicated RNG stream (independent of the
    selection ``np_rng``) whose state checkpoints with the trainer.
    """

    def __init__(self, spec: FaultSpec, num_clients: int, base_seed: int):
        self.spec = spec
        self.num_clients = num_clients
        self.rng = np.random.default_rng([base_seed, spec.seed])
        # compute-speed heterogeneity is a population property, drawn
        # once: straggler_frac of the clients run `slowdown` x slower
        n_slow = int(round(spec.straggler_frac * num_clients))
        perm = self.rng.permutation(num_clients)
        self.speed = np.ones(num_clients, np.float64)
        self.speed[perm[:n_slow]] = spec.slowdown
        self.online = np.ones(num_clients, bool)

    # -- per-round draws (FIXED count: engine/mode-independent stream) ------
    def begin_round(self) -> np.ndarray:
        """Advance population churn; returns the online mask the round's
        selection draws from.  Always consumes one (N,) uniform vector
        so the stream is identical for churn = 0."""
        flips = self.rng.random(self.num_clients) < self.spec.churn
        self.online ^= flips
        if not self.online.any():
            # an empty population would deadlock the round; force one
            # client back online (deterministic given the stream)
            self.online[int(self.rng.integers(self.num_clients))] = True
        return self.online.copy()

    def draw_round(self, sel_ids: np.ndarray, steps: Sequence[int],
                   staleness_mode: bool) -> RoundFaults:
        """Realize one round's schedule over the selected clients.

        Consumes exactly three (C,) uniform vectors regardless of which
        faults are active.  ``steps`` is each client's nominal step
        count (local_epochs * steps_per_epoch); ``staleness_mode``
        routes deadline-missing clients to a LATE full run instead of
        truncation.
        """
        sel_ids = np.asarray(sel_ids)
        steps = np.asarray(steps, np.int64)
        u_arrive = self.rng.random(len(sel_ids))
        u_drop = self.rng.random(len(sel_ids))
        u_prefix = self.rng.random(len(sel_ids))
        spec = self.spec

        arrived = u_arrive < spec.arrival
        dropped = arrived & (u_drop < spec.dropout)
        # deadline -> per-client step budget: a `speed`x slower client
        # finishes steps * deadline / speed of its nominal steps in time
        cap = np.minimum(steps, np.floor(
            steps * spec.deadline / self.speed[sel_ids]).astype(np.int64))
        late = (arrived & ~dropped & (cap < steps)) if staleness_mode \
            else np.zeros(len(sel_ids), bool)
        budget = np.where(late, steps, cap)
        # a dropped client crashes at a uniform prefix of its budget
        budget = np.where(dropped,
                          np.floor(u_prefix * cap).astype(np.int64), budget)
        budget = np.where(arrived, budget, 0)
        return RoundFaults(sel_ids=sel_ids, arrived=arrived, dropped=dropped,
                           late=late, budget=budget,
                           n_online=int(self.online.sum()))

    # -- checkpoint support --------------------------------------------------
    def state(self) -> dict:
        """JSON-serializable state (speed re-derives at construction —
        the init-time permutation draw is part of the seeded stream)."""
        return {"rng": self.rng.bit_generator.state,
                "online": [bool(b) for b in self.online]}

    def set_state(self, st: dict) -> None:
        self.rng.bit_generator.state = st["rng"]
        self.online = np.asarray(st["online"], bool).copy()


# ---------------------------------------------------------------------------
# Staleness-aggregation helpers (shared by both engines and topologies).
# ---------------------------------------------------------------------------

def apply_late(base, delta, gamma: float):
    """Merge a buffered late-delta sum: ``base + gamma * delta`` in fp32,
    cast back to the base dtypes."""
    return jax.tree.map(
        lambda b, d: (b.astype(jnp.float32)
                      + gamma * d.astype(jnp.float32)).astype(b.dtype),
        base, delta)


def late_delta(models: List, base, weights: Sequence[float]):
    """Weighted late-delta sum ``sum_j w_j * (theta_j - base)`` (fp32;
    weights are used AS GIVEN — they are the late clients' share of the
    round's total sample mass, deliberately not renormalized to 1).

    The sequential reference for the engine's fused ``w_late`` einsum.
    """
    deltas = [jax.tree.map(lambda a, b: a.astype(jnp.float32)
                           - b.astype(jnp.float32), m, base)
              for m in models]
    stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *deltas)
    return weighted_average_stacked(stacked, np.asarray(weights, np.float32))


def make_fault_model(fault: Optional[FaultSpec], num_clients: int,
                     base_seed: int) -> Optional[FaultModel]:
    """The one trainer-side gate: a missing or disabled spec yields no
    model, and every fault code path collapses to today's exactly."""
    if fault is None or not fault.enabled:
        return None
    return FaultModel(fault, num_clients, base_seed)
