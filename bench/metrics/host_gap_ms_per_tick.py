"""Device-idle time inside the benchmark's ``step_tick`` spans, per tick:
admission, bookkeeping and the token sync, as the device sees them."""

from bench.lib import trace as T


def read(ctx):
    if ctx.trace is None or not ctx.ticks or not ctx.trace.ops:
        return None
    idle = sum((t.b - t.a) - T.busy(ctx.trace, t.a, t.b) for t in ctx.ticks)
    return 1e3 * idle / len(ctx.ticks)
