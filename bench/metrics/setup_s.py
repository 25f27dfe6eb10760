"""Process start to window start: imports, weights, warm-up, compiles."""


def read(ctx):
    return ctx.setup_s
