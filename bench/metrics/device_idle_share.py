"""1 - (union of device operation intervals) / the traced span."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace["idle_share"]
