"""Model aggregation: FedAvg weighting and FedPhD homogeneity-aware
weighting (paper Eqs. 21–24).

All aggregations are weighted pytree sums, identical at the edge and
cloud tiers — only the weights differ.  The weights are computed on the
host (numpy); the sums run on the device: ``weighted_average`` as
compiled programs (a weighted sum per member count, a flatten and an
unflatten per tree layout), the stacked forms inside the vectorized
engine's own program.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np


def normalize_weights(weights: Sequence[float]) -> np.ndarray:
    """Normalize to a convex combination; uniform fallback if degenerate."""
    w = np.asarray(weights, np.float64)
    total = w.sum()
    if total <= 0:
        return np.full_like(w, 1.0 / len(w))
    return w / total


def combine_leaf(stacked: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """einsum-contract the leading member axis of one stacked leaf.

    ``stacked``: (N, ...) member-stacked leaf; ``w``: (N,) or (G, N)
    weights.  Accumulates once in fp32 and casts back, rounding integer
    leaves (e.g. the Adam step counter) instead of truncating.
    """
    acc = jnp.einsum("gn,n...->g..." if w.ndim == 2 else "n,n...->...",
                     w.astype(jnp.float32), stacked.astype(jnp.float32))
    return _cast_back(acc, stacked.dtype)


def _cast_back(acc: jnp.ndarray, dtype) -> jnp.ndarray:
    """fp32 accumulator -> the leaf's dtype; integers round to nearest."""
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.round(acc).astype(dtype)
    return acc.astype(dtype)


def weighted_average_stacked(stacked_tree, weights):
    """sum_n w_n * theta_n over the leading member axis of every leaf.

    ``weights`` may be (N,) — one averaged tree — or a (G, N) matrix of
    per-group weight rows (fused multi-edge aggregation), in which case
    every output leaf keeps a leading group axis.  Rows are used as
    given (callers normalize; see ``normalize_weights``).
    """
    w = jnp.asarray(np.asarray(weights, np.float32))
    return jax.tree.map(lambda leaf: combine_leaf(leaf, w), stacked_tree)


@jax.jit
def _ravel(tree) -> jnp.ndarray:
    """One member as a single fp32 row, leaves in ``jax.tree.leaves``
    order."""
    return jnp.concatenate([jnp.ravel(x).astype(jnp.float32)
                            for x in jax.tree.leaves(tree)])


@jax.jit
def weighted_sum(rows: tuple, w: jnp.ndarray) -> jnp.ndarray:
    """sum_i w[i] * rows[i] over fp32 member rows, in one program.

    An elementwise fp32 multiply-add (no MXU contraction with bf16
    operands).  The program depends only on ``len(rows)`` and the row
    length: ``w`` is an fp32 ``(n,)`` array, so new weights reuse it.
    """
    acc = w[0] * rows[0]
    for i in range(1, len(rows)):
        acc = acc + w[i] * rows[i]
    return acc


@jax.jit
def _unravel(row: jnp.ndarray, like):
    """The inverse of :func:`_ravel`: ``row`` cut into ``like``'s leaves
    and cast back to their dtypes (only their shapes and dtypes are
    read)."""
    leaves, treedef = jax.tree.flatten(like)
    out, start = [], 0
    for x in leaves:
        out.append(_cast_back(row[start:start + x.size].reshape(x.shape),
                              x.dtype))
        start += x.size
    return jax.tree.unflatten(treedef, out)


def weighted_average(param_trees: Sequence, weights: Sequence[float]):
    """sum_i w_i * theta_i with weights normalized to 1.

    Each member is flattened to one fp32 row (one program per tree
    layout), the rows are summed by :func:`weighted_sum` (one program
    per member count), and the sum is cut back into the tree (one
    program per layout).  A single program over all ``n x leaves``
    buffers would compile in time proportional to their number, about a
    minute at n = 20 for the paper's U-Net; over rows it is ``n``
    buffers.  fp32 accumulation, single up/downcast; integer leaves
    (Adam ``t``) survive via round-to-nearest.  Nothing is donated:
    callers keep their members.
    """
    w = jnp.asarray(normalize_weights(weights), jnp.float32)
    rows = tuple(_ravel(t) for t in param_trees)
    return _unravel(weighted_sum(rows, w), param_trees[0])


def uniform_weights(n: int) -> np.ndarray:
    """Unnormalized equal weights — ``normalize_weights`` turns them into
    exactly 1/n (SCAFFOLD's unweighted control-variate mean)."""
    return np.ones(n)


def fedavg_weights(sample_counts: Sequence[int]) -> np.ndarray:
    """rho_n = D_n / D (Eq. 10)."""
    n = np.asarray(sample_counts, np.float64)
    return n / max(n.sum(), 1.0)


def sh_weights(sample_counts: Sequence[int], sh_scores: Sequence[float],
               a: float, b: float) -> np.ndarray:
    """Eqs. 22/24: rho = ReLU(n + a*mu + b) / sum ReLU(...)."""
    n = np.asarray(sample_counts, np.float64)
    mu = np.asarray(sh_scores, np.float64)
    raw = np.maximum(n + a * mu + b, 0.0)
    total = raw.sum()
    if total <= 0:                      # degenerate: fall back to FedAvg
        return fedavg_weights(sample_counts)
    return raw / total


def aggregate_fedavg(param_trees: Sequence, sample_counts: Sequence[int]):
    return weighted_average(param_trees, fedavg_weights(sample_counts))


def aggregate_sh(param_trees: Sequence, sample_counts: Sequence[int],
                 sh_scores: Sequence[float], a: float, b: float):
    """Homogeneity-aware aggregation (edge: Eq. 23/24; cloud: Eq. 21/22)."""
    return weighted_average(param_trees, sh_weights(sample_counts, sh_scores,
                                                    a, b))
