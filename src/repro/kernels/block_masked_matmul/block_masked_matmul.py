"""Block-masked matmul — structured pruning's TPU-native compute kernel.

The sparse-training phase (paper Eq. 16) runs a model whose pruned
channels are zero but whose shapes are unchanged (DESIGN.md §3.1).  On
GPU, DepGraph physically slices; on TPU the idiom is: keep MXU-aligned
(bm, bk, bn) tiles and SKIP whole tiles whose channel-mask block is all
zero — `@pl.when` guards both the A-side (K blocks: pruned input
channels) and B-side (N blocks: pruned output channels), so a 44%-pruned
layer does ~44% fewer MXU passes without any reshaping.

y = x @ (w * colmask[None, :] * rowmask[:, None])
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(nflags_ref, kflags_ref, x_ref, w_ref, o_ref, acc_ref, *,
            n_kblocks: int):
    j = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # per-tile flags live in SMEM (scalar prefetch): Mosaic has no
    # legal VMEM block for a (1,) slice of a flag vector
    active = (nflags_ref[j] != 0) & (kflags_ref[kb] != 0)

    @pl.when(active)
    def _compute():
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(kb == n_kblocks - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def block_masked_matmul(x, w, col_mask, row_mask, *, bm: int = 128,
                        bk: int = 128, bn: int = 128,
                        interpret: bool):
    """x: (M, K); w: (K, N); col_mask: (N,) 0/1; row_mask: (K,) 0/1.

    Masks are reduced to per-block "any nonzero" flags, prefetched into
    SMEM; tiles whose flag is 0 are skipped entirely (their VMEM tiles
    never reach the MXU).
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (M, K, N)
    nmb, nkb, nnb = M // bm, K // bk, N // bn

    # per-block activity flags (tiny host-side reduction)
    nflags = (col_mask.reshape(nnb, bn).max(axis=1) != 0).astype(jnp.int32)
    kflags = (row_mask.reshape(nkb, bk).max(axis=1) != 0).astype(jnp.int32)
    # fine-grained mask applied to w once (keeps partially-masked active
    # blocks exact)
    wm = (w * col_mask[None, :].astype(w.dtype)
          * row_mask[:, None].astype(w.dtype))

    kernel = functools.partial(_kernel, n_kblocks=nkb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                               # nflags, kflags
        grid=(nmb, nnb, nkb),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k, nf, kf: (i, k)),   # x
            pl.BlockSpec((bk, bn), lambda i, j, k, nf, kf: (k, j)),   # w
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, nf, kf: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
    )(nflags, kflags, x, wm)
