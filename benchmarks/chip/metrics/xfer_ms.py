"""Mean time the serving thread spent in a batch's host<->device
transfers, in ms: the fused step's upload of keys, buckets, valid mask
and features, and the readback of its answers (the scheduler's counters
``scheduler_stats``: summed ``xfer_s`` over ``n_batches``, the
difference across the window). None where the program keeps no such
counter."""


def read(ctx):
    s0, s1 = ctx.stats
    if "xfer_s" not in s1:
        return None
    n = s1["n_batches"] - s0["n_batches"]
    if n <= 0:
        return None
    return 1000.0 * (s1["xfer_s"] - s0["xfer_s"]) / n
