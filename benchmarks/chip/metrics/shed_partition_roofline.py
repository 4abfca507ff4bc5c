"""Roofline share of the ``shed_partition`` kernel, in percent: the least
time the chip could take for the window's calls (the larger of their
operations over peak FLOP/s and their bytes over peak bandwidth,
``kernels.shed_partition_cost`` of the items each call was handed) over
the summed device time of the kernel's events in the trace."""
from benchmarks.chip import kernels, tracing

# The program gives its Pallas call no name, so the trace shows it as a
# ``tpu_custom_call`` named after the enclosing function. It is found by
# its signature: three (rows, 128) outputs (tier s32, cached value f32,
# eval rank s32) over the keys, valid flags and per-way candidate blocks.
EVENT = (r"= \(s32\[(\d+),128\]\{[^}]*\}, f32\[\1,128\]\{[^}]*\}, "
         r"s32\[\1,128\]\{[^}]*\}\) custom-call\(.*tpu_custom_call")


def read(ctx):
    if ctx.trace is None:
        return None
    events = tracing.kernel_events(ctx.trace, EVENT)
    t = sum(dt for _, dt in events)
    if not events or t <= 0:
        return None
    ways = ctx.cell.config["serving"]["trust_db_ways"]
    least = 0.0
    for m, _ in events:
        ops, nbytes = kernels.shed_partition_cost(int(m.group(1)) * 128, ways)
        least += max(ops / ctx.peaks["flops"],
                     nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
