"""Wrapper for the flash-attention kernel.

Accepts the model-layout (B, S, H, hd) tensors (GQA pre-expanded by the
caller) and dispatches to the Pallas kernel; non-tile-aligned shapes fall
back to the oracle.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import note_route
from repro.kernels.flash_attention.flash_attention import flash_attention_bhsd
from repro.kernels.flash_attention.ref import flash_attention_ref


def flash_attention(q, k, v, *, interpret: bool, causal: bool = True,
                    window: int = 0, bq: int = 128, bk: int = 128):
    """q: (B, Sq, Hq, hd); k, v: (B, Skv, Hkv, hd) -> (B, Sq, Hq, hd)."""
    B, Sq, Hq, hd = q.shape
    Hkv = k.shape[2]
    if Hq != Hkv:                          # expand GQA groups
        rep = Hq // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hq, -1, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hq, -1, hd)
    fallback = bool(Sq % bq or kf.shape[1] % bk)
    note_route("flash_attention", qf.shape, fallback=fallback,
               interpret=interpret)
    if fallback:
        out = flash_attention_ref(qf, kf, vf, causal=causal, window=window)
    else:
        out = flash_attention_bhsd(qf, kf, vf, causal=causal, window=window,
                                   bq=bq, bk=bk, interpret=interpret)
    return out.reshape(B, Hq, Sq, hd).transpose(0, 2, 1, 3)
