"""Mean device time of the whole ``jit(decode_step)`` runs in the traced
span."""

from bench.trace import program_time


def read(ctx):
    if ctx.trace is None:
        return None
    secs, runs = program_time(ctx.trace, "decode_step")
    return 1e3 * secs / runs if runs else None
