"""mfu.train: model FLOPs of the window's client training (3 x 2 x the
benchmark's MAC count per image) over window x peak bf16 FLOP/s, in
per cent.  Moves train_img_per_s."""
from lib.readers import peak_flops, train_flops


def read(ctx, red):
    c = ctx.counters
    if not c.get("images") or not c.get("window_s"):
        return None
    return 100.0 * train_flops(ctx) / (c["window_s"] * peak_flops(ctx))
