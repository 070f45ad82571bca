"""Production mesh construction.

A FUNCTION (not module-level constant) so importing never touches jax
device state.  The dry-run entrypoint sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 BEFORE any jax
import; smoke tests and benchmarks see the real single CPU device.

TPU-topology mapping (DESIGN.md §3.3): the "pod" axis is the DCN tier
(FedPhD's cloud aggregation), "data" x "model" the ICI tiers within a
16x16 v5e pod (edge aggregation / tensor sharding).
"""
from __future__ import annotations

import math
from typing import Mapping, Optional

import jax
import numpy as np
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes.

    The sharding code in this package (``shard_map`` bodies, GSPMD
    partitioning of the round engine, ``with_sharding_constraint``) was
    written for auto-sharded axes; ``jax.make_mesh`` now defaults to
    ``Explicit`` ones, under which e.g. the expert-parallel output
    reshape is a sharding type error."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Mesh over whatever devices exist (CPU smoke: 1 device).

    ``model_axis`` must divide the device count exactly: integer
    division would silently build a mesh over fewer devices than the
    host has, and every collective after that would be wrong about who
    its peers are.
    """
    n = len(jax.devices())
    if model_axis < 1 or n % model_axis != 0:
        raise ValueError(
            f"model_axis={model_axis} does not divide the {n} available "
            f"device(s); an uneven split would silently drop "
            f"{n % model_axis if model_axis >= 1 else n} of them")
    return make_mesh((n // model_axis, model_axis), ("data", "model"))


def make_spec_mesh(axes: Optional[Mapping[str, int]]):
    """Build a mesh from ``ExperimentSpec.mesh`` ({axis name -> size}).

    The JSON-round-trippable spec form of a mesh: insertion order is the
    axis order, size-1 axes are kept (named but trivial, so specs like
    ``{"data": 8, "model": 1}`` document the intended layout).  Uses the
    first prod(sizes) devices — an explicit error, not silent truncation,
    when the host has fewer.  None/empty means "no mesh" (the unsharded
    single-device path).
    """
    if not axes:
        return None
    names = tuple(axes)
    sizes = tuple(int(axes[k]) for k in names)
    if any(s < 1 for s in sizes):
        raise ValueError(f"spec.mesh sizes must be >= 1: {dict(axes)}")
    need = math.prod(sizes)
    devs = jax.devices()
    if need > len(devs):
        raise ValueError(
            f"spec.mesh {dict(axes)} needs {need} device(s) but only "
            f"{len(devs)} are available (hint: repro.launch.env.apply("
            f"devices={need}) before the first jax import)")
    return jax.sharding.Mesh(
        np.array(devs[:need]).reshape(sizes), names)


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
