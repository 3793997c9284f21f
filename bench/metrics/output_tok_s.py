"""Output tokens delivered in the window's ticks, per second of the window
(whole ticks, from the window's opening to the end of its last tick) and
per chip."""


def read(ctx):
    w = ctx.window
    return sum(t.tokens for t in w.ticks) / w.seconds / ctx.chips
