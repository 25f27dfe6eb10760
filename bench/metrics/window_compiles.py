"""Programs JAX compiled (or fetched from its cache) inside the window,
from its monitoring events.  There should be none."""


def read(ctx):
    return ctx.window_compiles
