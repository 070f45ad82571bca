"""Per-group L2 norms over channel-chunked weights — the pruning
criterion (Eq. 17/18) and the Omega regularizer's inner reduction.

A (K, G*C) weight is reduced to (G,) sums-of-squares in two steps: the
kernel walks row blocks of the full width, (bk, G*C) slabs in VMEM, and
accumulates per-column sums of squares into one resident (1, G*C) fp32
block; a chunk fold (C columns per group) outside the kernel finishes
the reduction.  Every block spans the whole lane axis, so the layout is
legal for Mosaic whatever the chunk — including the common chunk = 1
(one column per output channel).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# bytes of one input slab; double-buffered it stays far inside the
# default scoped VMEM
_BLOCK_BYTES = 2 * 2**20


def _row_block(K: int, N: int, itemsize: int) -> int:
    """Largest multiple-of-8 divisor of K whose (bk, N) slab fits
    _BLOCK_BYTES; the whole K (always a legal block) otherwise."""
    cap = max(_BLOCK_BYTES // max(N * itemsize, 1), 8)
    if K <= cap:
        return K
    for bk in range(cap - cap % 8, 7, -8):
        if K % bk == 0:
            return bk
    return K


def _kernel(w_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = w_ref[...].astype(jnp.float32)
    o_ref[...] += jnp.sum(w * w, axis=0, keepdims=True)


def group_l2_norms(w, num_groups: int, *, interpret: bool):
    """w: (K, G*C) -> (G,) per-group sum of squares along the column
    chunks (chunk = columns // num_groups)."""
    K, N = w.shape
    assert N % num_groups == 0
    bk = _row_block(K, N, w.dtype.itemsize)
    cols = pl.pallas_call(
        _kernel,
        grid=(K // bk,),
        in_specs=[pl.BlockSpec((bk, N), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, N), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, N), jnp.float32),
        interpret=interpret,
    )(w)
    return jnp.sum(cols.reshape(num_groups, N // num_groups), axis=1)
