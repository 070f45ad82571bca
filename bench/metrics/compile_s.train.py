"""compile_s.train: backend compile seconds inside the window, from
JAX's monitoring events (bench/lib/compile_stats.py).  Moves
train_img_per_s."""


def read(ctx, red):
    return ctx.counters.get("compile_s_window")
