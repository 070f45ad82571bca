"""Plain reference of the DDPM U-Net (Ho et al. 2020, arXiv:2006.11239)
and of everything the benchmark compares around it: the weights drawn
from a seed, the DDPM training loss, FedPhD's group-lasso term
(Eqs. 16-17), Adam with global-norm clipping and the SH aggregation
weights (Eqs. 18, 24).

It imports nothing of the system under test.  Widths are read from the
parameter shapes, so one function serves the dense net and the net whose
block-internal channels were pruned.  The parameter tree has the layout
the program consumes (its public weight format), which is how the
benchmark hands the same seeded weights to both.

Precision: a configuration states, in ``matmul_operands``, the type of
the operands of every matrix product: ``bfloat16`` (float32 storage and
arithmetic, products with bfloat16 operands and float32 accumulation:
JAX's default float32 matmul precision on a TPU) or ``float32``.  The
reference spells that rounding out (``_product``) instead of relying on
what a backend's DEFAULT means.  Everything that is not a matrix
product runs in float32.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

PREC = "bf16_operands"      # the default of the building blocks below


def precision_of(cfg: Dict) -> str:
    """The product precision a configuration states in its
    ``matmul_operands`` key: ``bfloat16`` (the TPU default) or
    ``float32``."""
    return {"bfloat16": "bf16_operands",
            "float32": "highest"}[cfg["matmul_operands"]]


# ---------------------------------------------------------------------------
# matrix products at the stated precision
# ---------------------------------------------------------------------------
def _bf(z):
    return z.astype(jnp.bfloat16).astype(jnp.float32)


_ROUND = {"bf16_operands": _bf}


def _product(f, prec):
    """``f(a, b)``, a float32 product at HIGHEST precision, taken at the
    precision ``prec``.  ``bf16_operands``: both operands rounded to
    bfloat16, and in the backward pass the
    cotangent too, so that every product of the forward and backward
    passes has operands of that type (exactly representable in float32,
    so HIGHEST multiplies them exactly) and float32 accumulation."""
    if prec == "highest":
        return f
    if prec not in _ROUND:
        raise ValueError(f"unknown precision {prec!r}")
    rnd = _ROUND[prec]

    @jax.custom_vjp
    def g(a, b):
        return f(rnd(a), rnd(b))

    def fwd(a, b):
        ar, br = rnd(a), rnd(b)
        return f(ar, br), (ar, br)

    def bwd(res, dy):
        _, vjp = jax.vjp(f, *res)
        return vjp(rnd(dy))

    g.defvjp(fwd, bwd)
    return g


def einsum(spec, a, b, prec=PREC):
    f = lambda x, y: jnp.einsum(spec, x, y, precision=lax.Precision.HIGHEST)
    return _product(f, prec)(a, b)


def conv2d(p, x, stride=1, prec=PREC):
    """Cross-correlation with SAME padding (TensorFlow's rule, as in
    Ho et al.'s code): output size ceil(n / stride), padding split
    low = total // 2."""
    w = p["w"]
    kh, kw = w.shape[:2]
    pads = []
    for n, k in ((x.shape[1], kh), (x.shape[2], kw)):
        out = -(-n // stride)
        tot = max((out - 1) * stride + k - n, 0)
        pads.append((tot // 2, tot - tot // 2))
    f = lambda a, b: lax.conv_general_dilated(
        a, b, (stride, stride), pads,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)
    return _product(f, prec)(x, w) + p["b"]


def dense(p, x, prec=PREC):
    return einsum("bi,io->bo", x, p["w"], prec) + p["b"]


def group_norm(x, p, eps=1e-5):
    """GroupNorm with 32 groups; a width that 32 does not divide (a
    pruned block) takes the largest group count under 32 that does."""
    n, h, w, c = x.shape
    g = min(32, c)
    while c % g:
        g -= 1
    xg = x.reshape(n, h, w, g, c // g)
    mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mu), axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) / jnp.sqrt(var + eps)
    return xg.reshape(n, h, w, c) * p["scale"] + p["bias"]


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def timestep_embedding(t, dim):
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    args = t.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


# ---------------------------------------------------------------------------
# the network
# ---------------------------------------------------------------------------
def resblock(p, x, temb, prec=PREC):
    h = conv2d(p["conv1"], silu(group_norm(x, p["norm1"])), prec=prec)
    h = h + dense(p["temb"], silu(temb), prec)[:, None, None, :]
    h = conv2d(p["conv2"], silu(group_norm(h, p["norm2"])), prec=prec)
    skip = conv2d(p["skip"], x, prec=prec) if "skip" in p else x
    return skip + h


def attnblock(p, x, prec=PREC):
    """Single-head self-attention over the H*W positions; the q, k, v
    thirds of the 1x1 projection may be pruned to ci < c channels."""
    B, H, W, C = x.shape
    qkv = conv2d(p["qkv"], group_norm(x, p["norm"]), prec=prec)
    ci = qkv.shape[-1] // 3
    qkv = qkv.reshape(B, H * W, 3, ci)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = einsum("bqc,bkc->bqk", q, k, prec) / math.sqrt(ci)
    s = s - jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s)
    a = e / jnp.sum(e, axis=-1, keepdims=True)
    o = einsum("bqk,bkc->bqc", a, v, prec).reshape(B, H, W, ci)
    return x + conv2d(p["proj"], o, prec=prec)


def unet(params, x, t, prec=PREC):
    """eps_theta(x_t, t); x (B, H, W, 3) NHWC, t (B,) int."""
    ch = params["temb1"]["w"].shape[0]
    temb = dense(params["temb2"], silu(dense(
        params["temb1"], timestep_embedding(t, ch), prec)), prec)
    h = conv2d(params["conv_in"], x, prec=prec)
    skips = [h]
    for lvl in params["down"]:
        for blk in lvl["blocks"]:
            h = resblock(blk["res"], h, temb, prec)
            if "attn" in blk:
                h = attnblock(blk["attn"], h, prec)
            skips.append(h)
        if "down" in lvl:
            h = conv2d(lvl["down"], h, stride=2, prec=prec)
            skips.append(h)
    h = resblock(params["mid"]["res1"], h, temb, prec)
    h = attnblock(params["mid"]["attn"], h, prec)
    h = resblock(params["mid"]["res2"], h, temb, prec)
    for lvl in params["up"]:
        for blk in lvl["blocks"]:
            h = resblock(blk["res"], jnp.concatenate([h, skips.pop()], -1),
                         temb, prec)
            if "attn" in blk:
                h = attnblock(blk["attn"], h, prec)
        if "up" in lvl:
            h = jnp.repeat(jnp.repeat(h, 2, axis=1), 2, axis=2)
            h = conv2d(lvl["up"], h, prec=prec)
    h = silu(group_norm(h, params["norm_out"]))
    return conv2d(params["conv_out"], h, prec=prec)


# ---------------------------------------------------------------------------
# weights from a seed
# ---------------------------------------------------------------------------
def kept_width(c: int, prune_ratio: float) -> int:
    """Channels a pruned group keeps: round(c * (1 - ratio)), rounded
    to a multiple of 8 for widths of 16 or more that 8 divides."""
    if prune_ratio <= 0:
        return c
    keep = max(1, round(c * (1.0 - prune_ratio)))
    if c >= 16 and c % 8 == 0:
        keep = max(8, round(keep / 8) * 8)
    return min(keep, c)


def layout(cfg: Dict, prune_ratio: float = 0.0):
    """(resblocks, attnblocks) in traversal order: each resblock as
    (path, cin, inner, cout), each attention block as (path, c, inner)."""
    ch, mults = cfg["base_channels"], cfg["channel_mults"]
    nres, attn_res = cfg["num_res_blocks"], cfg["attn_resolutions"]
    res = cfg["image_size"]
    kw = lambda c: kept_width(c, prune_ratio)
    blocks: List = []
    chans, cur = [ch], ch
    for lvl, m in enumerate(mults):
        for b in range(nres):
            blocks.append(("res", ("down", lvl, b), cur, kw(ch * m), ch * m))
            cur = ch * m
            if res in attn_res:
                blocks.append(("attn", ("down", lvl, b), cur, kw(cur)))
            chans.append(cur)
        if lvl != len(mults) - 1:
            chans.append(cur)
            res //= 2
    blocks.append(("res", ("mid", "res1"), cur, kw(cur), cur))
    blocks.append(("attn", ("mid", "attn"), cur, kw(cur)))
    blocks.append(("res", ("mid", "res2"), cur, kw(cur), cur))
    for lvl, m in reversed(list(enumerate(mults))):
        for b in range(nres + 1):
            cin = cur + chans.pop()
            blocks.append(("res", ("up", len(mults) - 1 - lvl, b), cin,
                           kw(ch * m), ch * m))
            cur = ch * m
            if res in attn_res:
                blocks.append(("attn", ("up", len(mults) - 1 - lvl, b),
                               cur, kw(cur)))
        if lvl != 0:
            res *= 2
    return blocks


def init_params(key, cfg: Dict, prune_ratio: float = 0.0):
    """Weights as a trained model's are spread: every conv and dense
    drawn N(0, 1/fan_in), biases N(0, 0.02^2), norm scales 1 + N(0,
    0.1^2).  No layer starts near zero, so every layer moves the loss
    and every sampler step.  Call under ``jax.jit`` with ``cfg`` static
    to make the whole tree on the device in one program."""
    ch = cfg["base_channels"]
    T = 4 * ch
    keys = iter(jax.random.split(key, 1024))

    def conv(kh, cin, cout):
        k1, k2 = jax.random.split(next(keys))
        return {"w": jax.random.normal(k1, (kh, kh, cin, cout))
                / math.sqrt(kh * kh * cin),
                "b": 0.02 * jax.random.normal(k2, (cout,))}

    def lin(cin, cout):
        k1, k2 = jax.random.split(next(keys))
        return {"w": jax.random.normal(k1, (cin, cout)) / math.sqrt(cin),
                "b": 0.02 * jax.random.normal(k2, (cout,))}

    def norm(c):
        k1, k2 = jax.random.split(next(keys))
        return {"scale": 1.0 + 0.1 * jax.random.normal(k1, (c,)),
                "bias": 0.1 * jax.random.normal(k2, (c,))}

    def res(cin, inner, cout):
        p = {"norm1": norm(cin), "conv1": conv(3, cin, inner),
             "temb": lin(T, inner), "norm2": norm(inner),
             "conv2": conv(3, inner, cout)}
        if cin != cout:
            p["skip"] = conv(1, cin, cout)
        return p

    def attn(c, inner):
        return {"norm": norm(c), "qkv": conv(1, c, 3 * inner),
                "proj": conv(1, inner, c)}

    mults = cfg["channel_mults"]
    params = {"temb1": lin(ch, T), "temb2": lin(T, T),
              "conv_in": conv(3, cfg["in_channels"], ch),
              "down": [{"blocks": []} for _ in mults],
              "mid": {},
              "up": [{"blocks": []} for _ in mults]}
    for kind, path, *dims in layout(cfg, prune_ratio):
        if path[0] == "mid":
            params["mid"][path[1]] = res(*dims) if kind == "res" \
                else attn(*dims)
            continue
        side, lvl, b = path
        blocks = params[side][lvl]["blocks"]
        if kind == "res":
            blocks.append({"res": res(*dims)})
        else:
            blocks[b]["attn"] = attn(*dims)
    for lvl, m in enumerate(mults):
        if lvl != len(mults) - 1:
            c = ch * m
            params["down"][lvl]["down"] = conv(3, c, c)
        up_lvl = len(mults) - 1 - lvl
        if lvl != 0:
            params["up"][up_lvl]["up"] = conv(3, ch * m, ch * m)
    params["norm_out"] = norm(ch)
    params["conv_out"] = conv(3, ch, cfg["in_channels"])
    return params


# ---------------------------------------------------------------------------
# training: DDPM loss, group lasso, Adam
# ---------------------------------------------------------------------------
def linear_alpha_bars(cfg: Dict):
    betas = jnp.linspace(cfg["beta_start"], cfg["beta_end"],
                         cfg["diffusion_steps"], dtype=jnp.float32)
    return jnp.cumprod(1.0 - betas)


def block_params(params, path):
    if path[0] == "mid":
        return params["mid"][path[1]]
    side, lvl, b = path
    return params[side][lvl]["blocks"][b]


def group_lasso(params, cfg: Dict):
    """Eq. 16: sum over the pruning groups (each resblock's inner
    channels, each attention block's channels) of lambda_g times the
    group's summed squares, lambda_g = lambda0 / max(|l - l_mid|, 0.5)
    with l the block's index in traversal order (Eq. 17's depth rule)."""
    blocks = layout(cfg)
    l_mid = (len(blocks) - 1) / 2.0
    total = jnp.zeros((), jnp.float32)
    for l, (kind, path, *_) in enumerate(blocks):
        bp = block_params(params, path)
        lam = cfg["lambda0"] / max(abs(l - l_mid), 0.5)
        if kind == "res":
            p = bp["res"] if path[0] != "mid" else bp
            leaves = [p["conv1"]["w"], p["conv1"]["b"], p["temb"]["w"],
                      p["temb"]["b"], p["norm2"]["scale"],
                      p["norm2"]["bias"], p["conv2"]["w"]]
        else:
            p = bp["attn"] if path[0] != "mid" else bp
            leaves = [p["qkv"]["w"], p["qkv"]["b"], p["proj"]["w"]]
        total = total + lam * sum(jnp.sum(jnp.square(a)) for a in leaves)
    return total


def ddpm_loss(params, cfg: Dict, images, rng, *, sparse: bool,
              prec: Optional[str] = None):
    """Eq. 6 with t ~ U{0..T-1} and eps ~ N(0, I) drawn from the step's
    key as the DDPM training step draws them: one split, the first half
    for t, the second for eps."""
    abar = linear_alpha_bars(cfg)
    k_t, k_e = jax.random.split(rng)
    B = images.shape[0]
    t = jax.random.randint(k_t, (B,), 0, cfg["diffusion_steps"])
    eps = jax.random.normal(k_e, images.shape, images.dtype)
    a = abar[t][:, None, None, None]
    x_t = jnp.sqrt(a) * images + jnp.sqrt(1.0 - a) * eps
    pred = unet(params, x_t, t, prec or precision_of(cfg))
    loss = jnp.mean(jnp.square(eps - pred))
    if sparse:
        loss = loss + group_lasso(params, cfg)
    return loss


def adam_step(params, mu, nu, step, grads, cfg: Dict):
    """Adam (Kingma & Ba) on gradients clipped to global L2 norm 1."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    scale = jnp.minimum(1.0, cfg["grad_clip"] / (gnorm + 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2 = cfg["adam_b1"], cfg["adam_b2"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    params = jax.tree.map(
        lambda p, m, v: p - cfg["lr"] * (m / c1) / (jnp.sqrt(v / c2)
                                                    + cfg["adam_eps"]),
        params, mu, nu)
    return params, mu, nu, grads


def sh_weights(label_counts: Sequence[np.ndarray], a: float, b: float):
    """Eqs. 18 and 24: mu_n = 2 - ||q_n - q_u||_2, rho_n proportional
    to max(n_n + a mu_n + b, 0); n_n and q_n from the client's labels."""
    raw = []
    for c in label_counts:
        c = np.asarray(c, np.float64)
        n = c.sum()
        q = c / n
        mu = 2.0 - np.sqrt(np.sum(np.square(q - 1.0 / len(c))))
        raw.append(max(n + a * mu + b, 0.0))
    raw = np.asarray(raw)
    return raw / raw.sum()
