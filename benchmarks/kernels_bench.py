"""Kernel micro-benchmarks (interpret mode off the TPU — correctness-
scale timings; the BlockSpec tiling is the TPU deliverable), plus an
xla-vs-pallas A/B of the repro.models.ops dispatch layer on the real
CIFAR-10 U-Net shapes the FedPhD hot path executes."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_fn
from repro.kernels.block_masked_matmul.ops import masked_matmul
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.rglru_scan.ops import linear_recurrence
from repro.models import ops


def _ab(name: str, fn, args, shape: str, backends=("xla", "pallas")) -> None:
    """Emit one ops-dispatch row per backend for the same call.

    ``fn(backend, *args)``; args stay jit arguments (a nullary closure
    would let XLA constant-fold the whole computation away).  On CPU
    the pallas leg runs interpret=True — timings quantify the
    interpreter overhead CI pays, not TPU performance; the xla rows
    are the ones the round-engine hot path executes by default.
    """
    for b in backends:
        jfn = jax.jit(partial(fn, b))
        emit(f"ops/{name}_{b}",
             time_fn(lambda: jfn(*args).block_until_ready()), shape)


def unet_ops_ab() -> None:
    """The paper U-Net's tensor-core ops at CIFAR-10 scale (base=128,
    attention at 16x16) — every shape tile-aligned so the pallas leg
    exercises the kernels, not the fallback oracles."""
    rng = jax.random.PRNGKey(0)
    ks = jax.random.split(rng, 8)
    B = 2

    # 3x3 res-conv 128 -> 256 at 16x16 (im2col GEMM: M=512, K=1152, N=256)
    p3 = {"w": jax.random.normal(ks[0], (3, 3, 128, 256)) * 0.05,
          "b": jnp.zeros((256,))}
    x3 = jax.random.normal(ks[1], (B, 16, 16, 128))
    _ab("conv3x3_128_256",
        lambda b, p, x: ops.conv(p, x, backend=b), (p3, x3),
        f"B={B};HW=16;K=1152;N=256")

    # 1x1 qkv conv 256 -> 768 (M=512, K=256, N=768)
    p1 = {"w": jax.random.normal(ks[2], (1, 1, 256, 768)) * 0.05,
          "b": jnp.zeros((768,))}
    x1 = jax.random.normal(ks[3], (B, 16, 16, 256))
    _ab("qkv1x1_256_768",
        lambda b, p, x: ops.conv(p, x, backend=b), (p1, x1),
        f"B={B};HW=16;K=256;N=768")

    # the same qkv GEMM at the paper's 44% sparse phase: block-masked
    cm = (jax.random.uniform(ks[4], (768,)) >= 0.44).astype(jnp.float32)
    rm = (jax.random.uniform(ks[5], (256,)) >= 0.44).astype(jnp.float32)
    _ab("qkv1x1_masked_r44",
        lambda b, p, x, c, r: ops.conv(p, x, backend=b, col_mask=c,
                                       row_mask=r), (p1, x1, cm, rm),
        f"B={B};HW=16;ratio=0.44")

    # attention block at 16x16, C=256 (S=256, single head of width C)
    q = jax.random.normal(ks[6], (B, 256, 1, 256))
    _ab("unet_attn_16x16_c256",
        lambda b, q_: ops.attention(q_, q_, q_, causal=False, backend=b),
        (q,), f"B={B};S=256;hd=256")

    # Eq. 17 group reduction over a conv1 member: (K=1152, G=256)
    w2d = jax.random.normal(ks[7], (1152, 256))
    _ab("group_sq_norms_1152x256",
        lambda b, w: ops.group_sq_norms_2d(w, 256, backend=b), (w2d,),
        "K=1152;G=256;C=1")


def main() -> None:
    interpret = ops.pallas_interpret()
    bmm = jax.jit(partial(masked_matmul, interpret=interpret))
    flash = jax.jit(partial(flash_attention, interpret=interpret,
                            causal=True))
    rglru = partial(linear_recurrence, interpret=interpret)
    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (256, 512))
    w = jax.random.normal(jax.random.fold_in(rng, 1), (512, 512))
    for ratio in (0.0, 0.44):
        cm = (jax.random.uniform(jax.random.fold_in(rng, 2), (512,))
              >= ratio).astype(jnp.float32)
        rm = jnp.ones(512)
        fn = lambda: bmm(x, w, cm, rm).block_until_ready()
        emit(f"kernels/masked_matmul_r{int(ratio*100)}", time_fn(fn),
             f"M=256;K=512;N=512")

    q = jax.random.normal(rng, (2, 256, 4, 64))
    k = jax.random.normal(jax.random.fold_in(rng, 3), (2, 256, 2, 64))
    v = jax.random.normal(jax.random.fold_in(rng, 4), (2, 256, 2, 64))
    fn = lambda: flash(q, k, v).block_until_ready()
    emit("kernels/flash_attention", time_fn(fn), "B=2;S=256;H=4;hd=64")

    if interpret:       # the TPU compiler refuses rglru_scan
        a = jax.random.uniform(rng, (2, 512, 256), minval=0.5, maxval=0.99)
        b = jax.random.normal(jax.random.fold_in(rng, 5), (2, 512, 256))
        fn = lambda: rglru(a, b).block_until_ready()
        emit("kernels/rglru_scan", time_fn(fn), "B=2;S=512;W=256")

    unet_ops_ab()


if __name__ == "__main__":
    main()
