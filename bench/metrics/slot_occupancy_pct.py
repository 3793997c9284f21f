"""Mean over the window's ticks of busy slots / max_slots."""


def read(ctx):
    ticks = ctx.window.ticks
    if not ticks:
        return None
    return 100.0 * sum(t.n_live for t in ticks) / (
        len(ticks) * ctx.serve.max_slots)
