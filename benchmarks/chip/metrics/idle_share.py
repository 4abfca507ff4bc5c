"""Device idle share: 1 - (union of device operation intervals) / window,
in percent, averaged over the chips traced (``tracing.busy_seconds``)."""
from benchmarks.chip import tracing


def read(ctx):
    if ctx.trace is None or not ctx.trace.device_ops:
        return None
    lo, hi = ctx.trace.window
    return 100.0 * (1.0 - tracing.busy_seconds(ctx.trace) / (hi - lo))
