"""95th percentile over requests of (last-token time - first-token time) /
(output tokens - 1).  Open loop: every token of a request followed to its
end; closed loop: the tokens each request received by the window's end."""

from bench.lib.stats import pct, tpot


def read(ctx):
    w = ctx.window
    closed = ctx.window_loop == "closed"
    xs = []
    for r in w.reqs:
        if closed:
            x = tpot(r.first, r.last_win, r.n_win)
        else:
            x = tpot(r.first, r.last, r.n_tok) if r.retired else float("nan")
        if x == x:
            xs.append(x)
    return pct(xs, 95) * 1e3 if xs else None
