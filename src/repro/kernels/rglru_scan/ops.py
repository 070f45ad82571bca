"""Jitted wrapper for the blocked linear-recurrence kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.rglru_scan.rglru_scan import rglru_scan
from repro.kernels.rglru_scan.ref import rglru_scan_ref


@partial(jax.jit, static_argnames=("bs", "interpret"))
def linear_recurrence(a, b, *, interpret: bool, bs: int = 256):
    """h_t = a_t h_{t-1} + b_t, blocked-VMEM kernel with jnp fallback."""
    B, S, W = a.shape
    if S % bs:
        return rglru_scan_ref(a, b)
    return rglru_scan(a, b, bs=bs, interpret=interpret)
