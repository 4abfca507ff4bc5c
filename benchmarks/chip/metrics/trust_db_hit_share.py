"""Share of the window's batched candidates that the Trust DB answered,
in percent (``scheduler_stats``: ``n_cached`` over ``n_batched_items``,
the difference across the window). Candidates of requests rejected at
admission never reach a batch and count in neither. None where the
program keeps no such counter."""


def read(ctx):
    s0, s1 = ctx.stats
    if "n_cached" not in s1:
        return None
    n = s1["n_batched_items"] - s0["n_batched_items"]
    if n <= 0:
        return None
    return 100.0 * (s1["n_cached"] - s0["n_cached"]) / n
