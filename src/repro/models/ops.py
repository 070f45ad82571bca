"""Pluggable compute-backend dispatch for the FedPhD hot path.

Every tensor-core op the training path executes — matmul, conv (im2col
-> matmul), attention, the Eq. 17 group reductions, and the
sparse-phase masked matmul — routes through ONE of three backends:

  ``xla``     today's einsum/dot formulations (the numerical default —
              the exact expressions the round engine compiled before
              this layer existed);
  ``pallas``  the Pallas TPU kernels under :mod:`repro.kernels`
              (compiled on TPU, interpreted elsewhere — decided only by
              :func:`pallas_interpret` — so CPU CI exercises the real
              BlockSpec tiling), with the pure-jnp oracle as fallback
              on non-tile-aligned shapes — the kernel ``ops.py``
              wrappers gate that and report every decision through
              :func:`repro.kernels.note_route`;
  ``ref``     the kernels' pure-jnp oracles (``ref.py``) — the
              slow-but-obvious reference the other two are locked
              against (atol 1e-5, ``tests/test_ops_backends.py``).

Selection: an explicit ``backend=`` argument wins; ``""``/``None``
falls back to ``$FEDPHD_BACKEND`` (the CI matrix knob, mirroring
``$FEDPHD_ENGINE``) and finally ``"xla"``.  The per-run route is the
``backend`` field threaded ``ExperimentSpec -> ModelConfig -> make_
round_engine -> make_local_step``: trainers resolve it once at
construction (``FedPhD``/``FlatTrainer`` bake the resolved name into
``cfg.backend``), so engine memoization and checkpoint manifests pin a
concrete backend and a mid-process env change cannot alias a stale
compiled round program.

Autodiff: ``pallas_call`` has no transpose rule, so every pallas route
that sits on the loss path carries a ``custom_vjp`` whose backward
reuses the kernels where the sparsity survives transposition (the
masked matmul's dx is itself a block-masked matmul with the masks
swapped) and the reference math elsewhere — flash-attention backward
is the standard recompute-from-residuals formulation.

All three backends of one op agree to atol 1e-5 on fp32 — including
under ``vmap`` (the engine's client axis) and inside ``lax.scan`` (the
engine's step axis); pallas_call's batching rule turns the client axis
into an outer grid dimension, so the kernels stay on the hot path of
the vectorized round program.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# the single $FEDPHD_* precedence code path; resolve_backend /
# resolve_precision below are its back-compat wrappers (safe at module
# scope: repro.experiment re-exports lazily, resolve.py is a leaf)
from repro.experiment.resolve import BACKENDS, PRECISIONS, resolve_backend, \
    resolve_precision
from repro.kernels.block_masked_matmul.ops import masked_matmul as _bmm_kernel
from repro.kernels.block_masked_matmul.ref import block_masked_matmul_ref
from repro.kernels.flash_attention.ops import flash_attention as _flash_kernel
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.group_l2_norms.ops import group_sq_norms_kernel
from repro.kernels.group_l2_norms.ref import group_l2_norms_ref

# compute-precision axis, resolved exactly like the backend: fp32 keeps
# today's numerics; bf16 runs the GEMMs/attention in bfloat16 while
# aggregation, Adam moments, and the master weights stay fp32 (the cast
# lives in make_train_one/make_local_step — see repro.fl.engine)
_COMPUTE_DTYPE = {"fp32": jnp.float32, "bf16": jnp.bfloat16}


def compute_dtype(precision: str):
    """The jnp dtype a resolved precision computes in."""
    return _COMPUTE_DTYPE[resolve_precision(precision)]


def cast_floats(tree, dtype):
    """Cast every floating leaf of a pytree to ``dtype`` (int/bool
    leaves — masks, step counters — pass through untouched).

    On the loss path this is the mixed-precision boundary: grads of the
    cast tree transpose back through ``astype`` to the original (fp32
    master) dtype, so Adam and aggregation never see low precision."""
    return jax.tree.map(
        lambda x: x.astype(dtype)
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x, tree)


def pallas_interpret() -> bool:
    """The one place that picks interpret mode: kernels run compiled on
    TPU and interpreted everywhere else (the kernel wrappers take the
    answer from their caller and have no default)."""
    return jax.default_backend() != "tpu"


_LOW_PRECISION = (jnp.bfloat16, jnp.float16)


def _gemm_cast(x, w):
    """GEMM-boundary activation cast: when the weights run a reduced
    compute dtype (the loss path casts params, not inputs — images,
    x_t, timestep embeddings arrive fp32) the activations follow, so
    the GEMM inputs stay homogeneous and the reduced-precision compute
    actually sticks on every backend.  Grads transpose back through the
    ``astype``.  Full-precision weights leave activations alone."""
    if w.dtype in _LOW_PRECISION and x.dtype != w.dtype:
        return x.astype(w.dtype)
    return x


# ---------------------------------------------------------------------------
# masked matmul (and plain matmul as its all-ones special case)
# ---------------------------------------------------------------------------

def _masked_wm(w, col_mask, row_mask):
    wm = w
    if col_mask is not None:
        wm = wm * col_mask[None, :].astype(w.dtype)
    if row_mask is not None:
        wm = wm * row_mask[:, None].astype(w.dtype)
    return wm


@jax.custom_vjp
def _masked_matmul_pallas(x, w, col_mask, row_mask):
    # the kernel wrapper handles tile-alignment fallback to the oracle
    return _bmm_kernel(x, w, col_mask, row_mask,
                       interpret=pallas_interpret())


def _masked_matmul_pallas_fwd(x, w, col_mask, row_mask):
    return _masked_matmul_pallas(x, w, col_mask, row_mask), \
        (x, w, col_mask, row_mask)


def _masked_matmul_pallas_bwd(res, g):
    x, w, col_mask, row_mask = res
    # dx = g @ (w*cm*rm).T — itself a block-masked matmul with the
    # masks swapped, so pruned tiles are skipped in the backward too
    dx = _masked_matmul_pallas(g, w.T, row_mask, col_mask).astype(x.dtype)
    dw = (jnp.dot(x.T.astype(jnp.float32), g.astype(jnp.float32))
          * row_mask[:, None] * col_mask[None, :]).astype(w.dtype)
    return dx, dw, jnp.zeros_like(col_mask), jnp.zeros_like(row_mask)


_masked_matmul_pallas.defvjp(_masked_matmul_pallas_fwd,
                             _masked_matmul_pallas_bwd)


def is_static_mask(m) -> bool:
    """Host-constant (numpy) masks trigger trace-time sparsity
    specialization; device/traced masks keep the exact training path."""
    return isinstance(m, np.ndarray)


def _static_masks(col_mask, row_mask) -> bool:
    if col_mask is None and row_mask is None:
        return False
    return (col_mask is None or is_static_mask(col_mask)) and \
        (row_mask is None or is_static_mask(row_mask))


def _masked_matmul_static(x2, w, col_mask, row_mask, b: str):
    """Serve-time masked matmul with *host-constant* masks: the pruned
    channels are known at trace time, so instead of multiplying by zero
    we gather the kept rows/columns, run a smaller GEMM, and scatter
    back — the compiled program genuinely shrinks with sparsity.

    Gathers are element-granular (kept channels need not be contiguous
    — the U-Net's GroupNorm between conv1 and conv2 forbids the
    function-preserving repack that would make top-k masks contiguous),
    except on the pallas backend when element granularity would knock a
    tile-aligned GEMM off the kernel: there the gather falls back to
    128-block granularity, dropping only whole all-pruned MXU tiles and
    keeping partial blocks' element masks inside the kernel.  Zero kept
    rows or columns short-circuits to zeros.  Matches the dynamic-mask
    path to fp32 reduction-order tolerance (the dropped terms are exact
    zeros).
    """
    K, N = w.shape
    rm = np.ones((K,), np.float32) if row_mask is None \
        else np.asarray(row_mask, np.float32)
    cm = np.ones((N,), np.float32) if col_mask is None \
        else np.asarray(col_mask, np.float32)
    out_dtype = jnp.promote_types(x2.dtype, w.dtype)
    bs = 128
    ridx = np.nonzero(rm)[0]
    cidx = np.nonzero(cm)[0]
    if b == "pallas":
        M = x2.shape[0]
        kernel_full = M % bs == 0 and K % bs == 0 and N % bs == 0
        kernel_elem = M % bs == 0 and ridx.size % bs == 0 \
            and cidx.size % bs == 0
        if kernel_full and not kernel_elem:
            # block-granular: keep any 128-block with a live unit
            rkeep = rm.reshape(-1, bs).max(axis=1) != 0
            ckeep = cm.reshape(-1, bs).max(axis=1) != 0
            ridx = np.nonzero(np.repeat(rkeep, bs))[0]
            cidx = np.nonzero(np.repeat(ckeep, bs))[0]
    if ridx.size == 0 or cidx.size == 0:
        return jnp.zeros((x2.shape[0], N), out_dtype)
    xr = x2 if ridx.size == K else x2[:, ridx]
    wr = w if ridx.size == K and cidx.size == N else w[np.ix_(ridx, cidx)]
    if b == "pallas":
        out_r = _masked_matmul_pallas(xr, wr, jnp.asarray(cm[cidx]),
                                      jnp.asarray(rm[ridx]))
    elif b == "ref":
        out_r = block_masked_matmul_ref(
            xr, wr, jnp.ones((cidx.size,), jnp.float32),
            jnp.ones((ridx.size,), jnp.float32))
    else:
        out_r = xr @ wr
    if cidx.size == N:
        return out_r
    return jnp.zeros((x2.shape[0], N), out_r.dtype).at[:, cidx].set(out_r)


def masked_matmul(x, w, col_mask=None, row_mask=None, *, backend: str = ""):
    """``x @ (w * col_mask[None] * row_mask[:, None])`` — the structured-
    pruning sparse-phase matmul.  x: (M, K) or (..., K); w: (K, N);
    masks are 0/1 fp32 vectors (``None`` = all ones).

    Mask *type* selects the strategy: device/traced masks run the exact
    training-time formulation (multiply by zero; pallas skips all-zero
    tiles via ``pl.when``), while host ``np.ndarray`` masks are serving
    constants and specialize the compiled program itself — see
    :func:`_masked_matmul_static`.
    """
    b = resolve_backend(backend)
    x = _gemm_cast(x, w)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if _static_masks(col_mask, row_mask):
        out = _masked_matmul_static(x2, w, col_mask, row_mask, b)
        return out.reshape(lead + (w.shape[1],))
    if b == "pallas":
        cm = jnp.ones((w.shape[1],), jnp.float32) if col_mask is None \
            else col_mask
        rm = jnp.ones((w.shape[0],), jnp.float32) if row_mask is None \
            else row_mask
        out = _masked_matmul_pallas(x2, w, cm, rm)
    elif b == "ref":
        cm = jnp.ones((w.shape[1],), jnp.float32) if col_mask is None \
            else col_mask
        rm = jnp.ones((w.shape[0],), jnp.float32) if row_mask is None \
            else row_mask
        out = block_masked_matmul_ref(x2, w, cm, rm)
    else:
        out = x2 @ _masked_wm(w, col_mask, row_mask)
    return out.reshape(lead + (w.shape[1],))


def matmul(x, w, *, backend: str = ""):
    """Plain dense matmul ``x @ w`` (masked_matmul's all-ones case)."""
    if resolve_backend(backend) == "xla":
        return x @ w            # today's path, verbatim
    return masked_matmul(x, w, backend=backend)


# ---------------------------------------------------------------------------
# dense / conv (im2col -> matmul)
# ---------------------------------------------------------------------------

def dense(p, x, *, backend: str = "", col_mask=None):
    """``x @ p["w"] + p["b"]``; ``col_mask`` prunes output features
    (weight columns AND bias — exactly ``apply_masks``' pre-zeroing)."""
    b = p["b"] if col_mask is None else p["b"] * jnp.asarray(col_mask)
    if resolve_backend(backend) == "xla" and not _static_masks(col_mask, None):
        w = p["w"] if col_mask is None else p["w"] * col_mask[None, :]
        return _gemm_cast(x, w) @ w + b
    return masked_matmul(x, p["w"], col_mask, None, backend=backend) + b


def _same_pads(size: int, k: int, stride: int):
    out = -(-size // stride)
    pad = max((out - 1) * stride + k - size, 0)
    return out, (pad // 2, pad - pad // 2)


def conv(p, x, *, stride: int = 1, padding: str = "SAME",
         backend: str = "", col_mask=None, row_mask=None):
    """SAME conv lowered as im2col + matmul (matches lax.conv numerics
    to fp32 tolerance).

    The matmul formulation matters twice over: under the round engine's
    vmap the conv WEIGHTS carry a client axis, which XLA:CPU executes
    as a pathologically slow batched-filter convolution (and conv
    thunks inside lax.scan additionally lose the runtime thread pool)
    — as a GEMM it batches cleanly; and a GEMM is exactly what the
    Pallas backends accept, so one lowering serves every backend.

    ``col_mask`` (cout,) prunes output channels — weight columns and
    bias; ``row_mask`` (cin,) prunes input channels (tiled across the
    kh*kw patch positions of the im2col K axis).  With masks this
    computes the ``apply_masks``-pre-zeroed forward exactly, but the
    pallas backend skips whole all-masked MXU tiles instead of
    multiplying by zero.
    """
    if padding != "SAME":
        raise ValueError(f"im2col conv supports SAME padding only, "
                         f"got {padding!r}")
    b = resolve_backend(backend)
    w = p["w"]
    kh, kw, cin, cout = w.shape
    bias = p["b"] if col_mask is None else p["b"] * jnp.asarray(col_mask)
    # host-constant serving masks take the GEMM route on every backend
    # so the static gather/scatter specialization can engage
    static = _static_masks(col_mask, row_mask)

    x = _gemm_cast(x, w)
    if kh == kw == 1 and stride == 1:
        w2 = w[0, 0]
        if b == "xla" and not static:
            w2 = _masked_wm(w2, col_mask, row_mask)
            return jnp.einsum("bhwc,cd->bhwd", x, w2) + bias
        out = masked_matmul(x.reshape(-1, cin), w2, col_mask, row_mask,
                            backend=b)
        return out.reshape(x.shape[:-1] + (cout,)) + bias

    H, W = x.shape[1], x.shape[2]
    oh, (ph0, ph1) = _same_pads(H, kh, stride)
    ow, (pw0, pw1) = _same_pads(W, kw, stride)
    xp = jnp.pad(x, ((0, 0), (ph0, ph1), (pw0, pw1), (0, 0)))
    cols = [xp[:, di:di + stride * (oh - 1) + 1:stride,
               dj:dj + stride * (ow - 1) + 1:stride, :]
            for di in range(kh) for dj in range(kw)]
    patches = jnp.stack(cols, axis=3)            # (B, oh, ow, kh*kw, cin)
    wk = w.reshape(kh * kw, cin, cout)
    if b == "xla" and not static:
        if col_mask is not None:
            wk = wk * col_mask[None, None, :]
        if row_mask is not None:
            wk = wk * row_mask[None, :, None]
        y = jnp.einsum("bhwkc,kcd->bhwd", patches, wk)
        return y + bias
    # flatten the patch axis into K; the cin row mask tiles across the
    # kh*kw patch positions (im2col K index = patch * cin + c).  np.tile
    # for host masks — jnp.tile would device-commit them and silently
    # drop the static specialization.
    rm = None if row_mask is None else \
        (np.tile(row_mask, kh * kw) if is_static_mask(row_mask)
         else jnp.tile(row_mask, kh * kw))
    flat = patches.reshape(-1, kh * kw * cin)
    y = masked_matmul(flat, wk.reshape(kh * kw * cin, cout), col_mask, rm,
                      backend=b)
    return y.reshape(x.shape[0], oh, ow, cout) + bias


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _attention_dense(qf, kf, vf, causal: bool, window: int):
    """Dense-softmax attention on flattened (B*H, S, hd) — the pre-ops
    U-Net formulation, generalized with the flash kernel's masking."""
    hd = qf.shape[-1]
    s = jnp.einsum("bqc,bkc->bqk", qf, kf) * (hd ** -0.5)
    if causal or window > 0:
        qpos = jnp.arange(qf.shape[1])[:, None]
        kpos = jnp.arange(kf.shape[1])[None, :]
        ok = jnp.ones(s.shape[1:], bool)
        if causal:
            ok &= kpos <= qpos
        if window > 0:
            ok &= (qpos - kpos) < window
        s = jnp.where(ok[None], s, -1e30)
    probs = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkc->bqc", probs, vf)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attention_pallas(q, k, v, causal, window):
    return _flash_kernel(q, k, v, causal=causal, window=window,
                         interpret=pallas_interpret())


def _attention_pallas_fwd(q, k, v, causal, window):
    return _attention_pallas(q, k, v, causal, window), (q, k, v)


def _attention_pallas_bwd(causal, window, res, g):
    q, k, v = res                 # flash-style recompute from residuals
    _, vjp = jax.vjp(
        lambda q_, k_, v_: attention(q_, k_, v_, causal=causal,
                                     window=window, backend="xla"), q, k, v)
    return vjp(g)


_attention_pallas.defvjp(_attention_pallas_fwd, _attention_pallas_bwd)


def attention(q, k, v, *, causal: bool = False, window: int = 0,
              backend: str = ""):
    """q: (B, Sq, H, hd); k, v: (B, Skv, Hkv, hd) -> (B, Sq, H, hd).

    The U-Net attention blocks call this with H = 1, hd = channels;
    the transformer stack with its model head layout (GQA expanded by
    the pallas wrapper).
    """
    b = resolve_backend(backend)
    if b == "pallas":
        return _attention_pallas(q, k, v, causal, window)
    B, Sq, H, hd = q.shape
    if k.shape[2] != H:                    # expand GQA groups, as the
        rep = H // k.shape[2]              # pallas wrapper does
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, -1, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, -1, hd)
    if b == "ref":
        out = flash_attention_ref(qf, kf, vf, causal=causal, window=window)
    else:
        out = _attention_dense(qf, kf, vf, causal, window)
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# group sum-of-squares reductions (Eq. 17)
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _group_sq_pallas(w2d, num_groups):
    return group_sq_norms_kernel(w2d, num_groups,
                                 interpret=pallas_interpret())


def _group_sq_pallas_fwd(w2d, num_groups):
    return _group_sq_pallas(w2d, num_groups), w2d


def _group_sq_pallas_bwd(num_groups, w2d, g):
    chunk = w2d.shape[1] // num_groups
    return (2.0 * w2d * jnp.repeat(g, chunk)[None, :],)


_group_sq_pallas.defvjp(_group_sq_pallas_fwd, _group_sq_pallas_bwd)


def group_sq_norms_2d(w2d, num_groups: int, *, backend: str = ""):
    """(K, G*C) -> (G,) per-group sum of squares over contiguous column
    chunks — the layout :func:`repro.core.pruning.criteria.member_unit_sq`
    produces for any non-scan-stacked group member."""
    b = resolve_backend(backend)
    if b == "pallas":
        return _group_sq_pallas(w2d, num_groups)
    if b == "ref":
        return group_l2_norms_ref(w2d, num_groups)
    K = w2d.shape[0]
    # fp32 accumulation regardless of compute dtype — the kernel and
    # ref oracle already upcast internally; the xla path must match
    w3 = w2d.astype(jnp.float32).reshape(K, num_groups, -1)
    return jnp.sum(w3 * w3, axis=(0, 2))
