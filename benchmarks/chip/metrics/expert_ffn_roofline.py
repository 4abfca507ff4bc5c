"""Roofline share of the held experts' grouped matmul, in percent: the
least time the chip could take for the window's calls (the larger of
their operations over peak FLOP/s and their bytes over peak bandwidth,
``moe_kernels.expert_ffn_cost``) over the summed device time of the
calls' events in the trace.

A call is handed a static number of rows; the rows routed to held
experts are a share of them that the program counts (``scheduler_stats``:
``n_expert_rows`` over ``n_expert_slots`` across the window), and each
event is charged that share of its rows. None where the program keeps
no such counter or the trace holds no such event."""
from benchmarks.chip import moe_kernels, tracing

# The grouped matmul is a ``tpu_custom_call`` with one 2-D output: (rows,
# 2 x expert width) for the gate and up projection, (rows, hidden) for
# the down projection. It is found by that signature.
EVENT = r"= bf16\[(\d+),(\d+)\]\{[^}]*\} custom-call\(.*tpu_custom_call"


def read(ctx):
    if ctx.trace is None:
        return None
    s0, s1 = ctx.stats
    if "n_expert_slots" not in s1:
        return None
    slots = s1["n_expert_slots"] - s0["n_expert_slots"]
    if slots <= 0:
        return None
    use = (s1["n_expert_rows"] - s0["n_expert_rows"]) / slots
    cfg = ctx.cell.config
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    shapes = {2 * f: (d, 2 * f), d: (f, d)}        # output width -> (in, out)
    least = t = 0.0
    for m, dt in tracing.kernel_events(ctx.trace, EVENT):
        width = int(m.group(2))
        if width not in shapes:
            continue
        ops, nbytes = moe_kernels.expert_ffn_cost(
            use * int(m.group(1)), cfg["num_local_experts"], *shapes[width],
            itemsize=2)
        least += max(ops / ctx.peaks["flops"],
                     nbytes / ctx.peaks["hbm_bytes_per_s"])
        t += dt
    if t <= 0:
        return None
    return 100.0 * least / t
