"""Share of the expert layer's grouped-matmul rows that held a routed
(token, expert) pair, in percent: the held experts' routed rows over the
rows the grouped matmuls ran, padding included, summed over slices and
layers in the window's batches (``scheduler_stats``: ``n_expert_rows``
over ``n_expert_slots``, the difference across the window). None where
the program keeps no such counter or the evaluator has no experts."""


def read(ctx):
    s0, s1 = ctx.stats
    if "n_expert_slots" not in s1:
        return None
    slots = s1["n_expert_slots"] - s0["n_expert_slots"]
    if slots <= 0:
        return None
    return 100.0 * (s1["n_expert_rows"] - s0["n_expert_rows"]) / slots
