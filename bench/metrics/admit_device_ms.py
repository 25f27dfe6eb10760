"""Device busy time outside ``jit(decode_step)`` (prefill, splice, page
writes, argmax) in the traced span, per admission made in the span."""

from bench.trace import program_time
from bench.work import admitted_in_span


def read(ctx):
    if ctx.trace is None:
        return None
    decode, _ = program_time(ctx.trace, "decode_step")
    admitted = len(admitted_in_span(ctx.traced))
    if not admitted:
        return None
    return 1e3 * (ctx.trace["busy_s"] - decode) / admitted
