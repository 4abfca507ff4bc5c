"""Share of the evaluator's rows that scored a candidate, in percent:
candidates evaluated over the rows the evaluator computed, padding
included, in the window's batches (``scheduler_stats``: ``n_evaluated``
over ``n_eval_rows``, the difference across the window). None where the
program keeps no such counter."""


def read(ctx):
    s0, s1 = ctx.stats
    if "n_eval_rows" not in s1:
        return None
    rows = s1["n_eval_rows"] - s0["n_eval_rows"]
    if rows <= 0:
        return None
    return 100.0 * (s1["n_evaluated"] - s0["n_evaluated"]) / rows
