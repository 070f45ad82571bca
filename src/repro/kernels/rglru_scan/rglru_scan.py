"""Blocked linear-recurrence scan: h_t = a_t * h_{t-1} + b_t.

The RG-LRU / SSM hot-spot.  The sequence is processed in (bs)-length
blocks; the running state h lives in a VMEM scratch buffer that persists
across sequential grid steps (TPU grids iterate the trailing axis in
order), so HBM sees each (a, b) element exactly once — the naive
``lax.scan`` round-trips the state through HBM every step, which is why
the rwkv6/recurrentgemma baselines are so memory-bound in the roofline
table.  Within a block the recurrence is unrolled with a fori_loop over
vectorized (width,)-lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, o_ref, h_ref, *, bs: int):
    sb = pl.program_id(1)

    @pl.when(sb == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0].astype(jnp.float32)       # (bs, W)
    b = b_ref[0].astype(jnp.float32)

    def step(t, h):
        h = a[t] * h + b[t]
        o_ref[0, t, :] = h.astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, bs, step, h_ref[0])
    h_ref[0] = h


def rglru_scan(a, b, *, interpret: bool, bs: int = 256):
    """a, b: (B, S, W) -> h: (B, S, W) with h_t = a_t h_{t-1} + b_t."""
    B, S, W = a.shape
    assert S % bs == 0
    nsb = S // bs
    kernel = functools.partial(_kernel, bs=bs)
    return pl.pallas_call(
        kernel,
        grid=(B, nsb),
        in_specs=[
            pl.BlockSpec((1, bs, W), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bs, W), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs, W), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, W), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, W), jnp.float32)],
        interpret=interpret,
    )(a, b)
