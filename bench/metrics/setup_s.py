"""Process start to window start: loading, weights, compiles, warm-up."""


def read(ctx):
    return ctx.setup_s
