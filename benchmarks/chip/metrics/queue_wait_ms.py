"""Mean time a request waited in its replica's queue before its batch
started, in ms, over the responses the window's batches produced: the
scheduler's counters (``scheduler_stats``: summed ``queue_delay_s`` over
their number, the difference across the window). None where the program
keeps no such counter."""


def read(ctx):
    s0, s1 = ctx.stats
    if "n_queue_waits" not in s1:
        return None
    n = s1["n_queue_waits"] - s0["n_queue_waits"]
    if n <= 0:
        return None
    return 1000.0 * (s1["queue_wait_s"] - s0["queue_wait_s"]) / n
