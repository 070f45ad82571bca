"""FedPhD's hierarchy mapped onto TPU topology (DESIGN.md §3.3).

On a multi-pod machine the paper's two aggregation tiers ARE the two
bandwidth tiers: edge aggregation = intra-pod all-reduce over ICI
(cheap, every r_e steps), cloud aggregation = inter-pod all-reduce over
DCN (expensive, every r_g steps).  Each data-parallel group plays one
client; a pod plays one edge server.

``hierarchical_aggregate`` is the shard_map realization of Eqs. 21-24:
SH-weighted within the pod, then SH-weighted across pods, with the
ReLU(n + a*mu + b) weights computed from per-client sample counts and SH
scores that ride along as tiny scalars.
"""
from __future__ import annotations

import warnings
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def _sh_weight(n, mu, a: float, b: float):
    return jnp.maximum(n + a * mu + b, 0.0)


def shard_clients(tree, mesh, axis: str = "data"):
    """Lay the leading client axis of every leaf over one mesh axis.

    This is the bridge between the vectorized round engine
    (repro/fl/engine.py) and the TPU topology: the engine's stacked
    client axis is placed over ``axis`` so jit's partitioner runs each
    device's client slice locally — the vmapped local training becomes
    data parallelism for free, and the fused (E, C) aggregation einsum
    lowers to the ICI all-reduce of ``hierarchical_aggregate``.

    Leaves whose leading dim does not divide the axis size (or a None
    mesh) are returned unsharded — with a once-per-process warning, so
    a participation count that silently defeats the mesh is visible.
    (Scalar leaves have no client axis and skip quietly; the CPU
    1-device path shards trivially and never warns.)
    """
    if mesh is None or axis not in mesh.axis_names:
        return tree
    n_dev = mesh.shape[axis]

    def put(leaf):
        global _WARNED_INDIVISIBLE
        if leaf.ndim == 0:
            return leaf
        if leaf.shape[0] % n_dev != 0:
            if not _WARNED_INDIVISIBLE:
                warnings.warn(
                    f"shard_clients: a leaf's leading dim "
                    f"({leaf.shape[0]}) does not divide mesh axis "
                    f"{axis!r} (size {n_dev}); leaving it UNSHARDED. "
                    "Pick a participant count divisible by the data-axis "
                    "size to keep the round on the mesh. (warning once "
                    "per process)", RuntimeWarning)
                _WARNED_INDIVISIBLE = True
            return leaf
        spec = P(*((axis,) + (None,) * (leaf.ndim - 1)))
        return jax.device_put(leaf, NamedSharding(mesh, spec))
    return jax.tree.map(put, tree)


_WARNED_INDIVISIBLE = False


def hierarchical_aggregate(params, n_samples, sh_score, *, mesh,
                           edge_axis: str = "data", cloud_axis: str = "pod",
                           a: float = 0.0, b: float = 0.0,
                           cloud_round: bool = True):
    """Two-tier homogeneity-aware aggregation.

    params:    pytree whose leaves are per-client replicas laid out over
               ``edge_axis`` (and ``cloud_axis``) — i.e. each (pod, data)
               slice holds one client's model.
    n_samples: () float32 per client (same layout).
    sh_score:  () float32 per client (Eq. 18).
    Returns the aggregated pytree: edge-level every call; cloud-level
    (across pods) additionally when ``cloud_round``.
    """
    axes = [a_ for a_ in (edge_axis, cloud_axis) if a_ in mesh.axis_names]
    edge_only = axes[:1]

    def local(p_leaves, n, mu):
        w = _sh_weight(n, mu, a, b)
        # --- edge tier: ICI all-reduce over the data axis (Eq. 23/24)
        wsum_e = jax.lax.psum(w, edge_only[0])
        agg = [jax.lax.psum(leaf * (w / wsum_e).astype(leaf.dtype),
                            edge_only[0]) for leaf in p_leaves]
        if cloud_round and cloud_axis in mesh.axis_names:
            # --- cloud tier: DCN all-reduce over the pod axis (Eq. 21/22)
            n_e = wsum_e                       # edge "sample mass"
            mu_e = jax.lax.psum(mu * w, edge_only[0]) / wsum_e
            w_c = _sh_weight(n_e, mu_e, a, b)
            wsum_c = jax.lax.psum(w_c, cloud_axis)
            agg = [jax.lax.psum(leaf * (w_c / wsum_c).astype(leaf.dtype),
                                cloud_axis) for leaf in agg]
        return tuple(agg)

    leaves, treedef = jax.tree.flatten(params)
    spec_axes = tuple(axes) if len(axes) > 1 else axes[0]
    leaf_specs = tuple(
        P(*((spec_axes,) + (None,) * (leaf.ndim - 1))) if leaf.ndim else P()
        for leaf in leaves)
    # client replicas are stacked on a leading axis sharded over the tiers
    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(leaf_specs, P(spec_axes), P(spec_axes)),
        out_specs=leaf_specs,
    )(tuple(leaves), n_samples, sh_score)
    return jax.tree.unflatten(treedef, list(out))


def federated_round_cost(model_bytes: int, *, n_pods: int = 2,
                         clients_per_pod: int = 256,
                         cloud_round: bool) -> dict:
    """Analytic per-round traffic of the TPU-mapped hierarchy — the
    ShapeFL cost model's ICI/DCN analogue (EXPERIMENTS.md)."""
    from repro.roofline import hw
    ici = 2 * model_bytes * (clients_per_pod - 1) / clients_per_pod
    dcn = 2 * model_bytes * (n_pods - 1) / n_pods if cloud_round else 0.0
    return {"ici_bytes": ici, "dcn_bytes": dcn,
            "ici_s": ici / hw.peaks(hw.V5E).ici_bw,
            "dcn_s": dcn / hw.DCN_BW}
