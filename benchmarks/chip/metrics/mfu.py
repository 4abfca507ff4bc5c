"""Model FLOP/s utilization of the fused shed step plus evaluator, in
percent of the chip's peak: candidates evaluated in the window times the
evaluator's operations per candidate (``families/*.flops_per_item``,
from the configuration's shapes) over the window times the peak.
Operations the step spends on padding or on candidates it does not
evaluate count for nothing."""
import numpy as np

from benchmarks.chip.refs.trust_tier import TIER_EVAL


def read(ctx):
    if not ctx.steps:
        return None
    n_eval = sum(int(np.sum(np.asarray(s.tier) == TIER_EVAL))
                 for s in ctx.steps)
    flops = n_eval * ctx.cell.family.flops_per_item(ctx.cell.config)
    return 100.0 * flops / (ctx.window_s * ctx.peaks["flops"])
