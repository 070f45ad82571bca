"""aggregation_warm_s.train: host-clock seconds that set-up spends
running the program's eager edge aggregation once for every client
count, so that none compiles in the window (bench/drivers/train.py
``_warm_aggregation``).  A jitted aggregation with a fixed client axis
would shrink it.  Moves setup_s."""


def read(ctx, red):
    return ctx.counters.get("aggregation_warm_s")
