"""Mean candidates per micro-batch over the window's batches, from the
scheduler's own counters (``scheduler_stats``: batched items over
batches, the difference across the window)."""


def read(ctx):
    s0, s1 = ctx.stats
    n = s1["n_batches"] - s0["n_batches"]
    if n <= 0:
        return None
    return (s1["n_batched_items"] - s0["n_batched_items"]) / n
