"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals) / window."""

from bench.lib import trace as T


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    a, b = ctx.trace_window
    return 100.0 * (1.0 - T.busy(ctx.trace, a, b) / (b - a))
