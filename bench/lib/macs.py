"""Multiply-accumulates of one DDPM U-Net forward pass, counted from
the parameter shapes: every convolution (kh*kw*cin*cout per output
pixel), each resblock's timestep projection, and attention's two
products (QK^T and PV, each (HW)^2 * c with c the attention width).
The timestep MLP at the top of the net (two denses per image) is left
out, as the FedPhD paper's Tables III-IV leave it out.

The count is of GEMM work, the same whatever implements it (im2col dot
fusions, a native convolution or a Pallas kernel): a training step of
batch B is 3 * 2 * B * macs FLOPs (forward, and a backward of twice
the forward), a sampler tick over S slots 2 * S * macs."""
from __future__ import annotations


def _shape(x):
    return tuple(getattr(x, "shape", x))


def unet_macs(params, image_size: int) -> float:
    def conv(p, res):
        kh, kw, cin, cout = _shape(p["w"])
        return kh * kw * cin * cout * res * res

    def resblock(rp, res):
        inner = _shape(rp["conv1"]["w"])[-1]
        m = conv(rp["conv1"], res) + conv(rp["conv2"], res)
        if "skip" in rp:
            m += conv(rp["skip"], res)
        return m + _shape(rp["temb"]["w"])[0] * inner

    def attnblock(ap, res):
        c = _shape(ap["proj"]["w"])[2]
        return conv(ap["qkv"], res) + conv(ap["proj"], res) \
            + 2 * (res * res) ** 2 * c

    res = image_size
    total = conv(params["conv_in"], res)
    for lvl in params["down"]:
        for blk in lvl["blocks"]:
            total += resblock(blk["res"], res)
            if "attn" in blk:
                total += attnblock(blk["attn"], res)
        if "down" in lvl:
            res //= 2
            total += conv(lvl["down"], res)
    mid = params["mid"]
    total += resblock(mid["res1"], res) + attnblock(mid["attn"], res) \
        + resblock(mid["res2"], res)
    for lvl in params["up"]:
        for blk in lvl["blocks"]:
            total += resblock(blk["res"], res)
            if "attn" in blk:
                total += attnblock(blk["attn"], res)
        if "up" in lvl:
            res *= 2
            total += conv(lvl["up"], res)
    return float(total + conv(params["conv_out"], res))
