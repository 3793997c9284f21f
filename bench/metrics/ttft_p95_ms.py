"""95th percentile of time to first token, from when each request of the
window fell due; a request that never got one counts as infinite."""

import math

from bench.lib.stats import pct


def read(ctx):
    xs = [(r.first - r.due) if not math.isnan(r.first) else math.inf
          for r in ctx.window.reqs]
    return pct(xs, 95) * 1e3 if xs else None
