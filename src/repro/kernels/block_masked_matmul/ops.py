"""Wrapper: masked dense layer for the sparse-training phase."""
from __future__ import annotations

from repro.kernels import note_route
from repro.kernels.block_masked_matmul.block_masked_matmul import (
    block_masked_matmul)
from repro.kernels.block_masked_matmul.ref import block_masked_matmul_ref


def masked_matmul(x, w, col_mask, row_mask, *, interpret: bool,
                  bm: int = 128, bk: int = 128, bn: int = 128):
    """2-D or 3-D x against a channel-masked weight.

    Falls back to the jnp reference when shapes are not tile-aligned
    (smoke-scale models, the U-Net's K=27 stem conv and its batch-M
    timestep denses); every decision goes to :func:`note_route`.
    """
    orig_shape = x.shape
    if x.ndim == 3:
        x = x.reshape(-1, x.shape[-1])
    M, K = x.shape
    N = w.shape[1]
    fallback = bool(M % bm or K % bk or N % bn)
    note_route("block_masked_matmul", (M, K, N), fallback=fallback,
               interpret=interpret)
    if fallback:
        out = block_masked_matmul_ref(x, w, col_mask, row_mask)
    else:
        out = block_masked_matmul(x, w, col_mask, row_mask, bm=bm, bk=bk,
                                  bn=bn, interpret=interpret)
    if len(orig_shape) == 3:
        out = out.reshape(orig_shape[0], orig_shape[1], N)
    return out
