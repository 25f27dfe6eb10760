"""Least time over device time of the decode steps in the traced span.

The least time of a step is the larger of its operations over peak and
its bytes over bandwidth, for the work it needs: weights read once, the
KV of the live rows' positions only, and the new KV written, as the
family's ``Shapes`` counts it (``ctx.shapes``, from
``bench/families/<model_type>.py``).  Summed over the ``k`` whole
``jit(decode_step)`` runs in the span (the call's steps ``1..k``), over
their device time."""

from bench.counts import least_time
from bench.trace import program_time
from bench.work import decode_contexts


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    secs, runs = program_time(ctx.trace, "decode_step")
    if not runs:
        return None
    sh = ctx.shapes
    need = sum(least_time(sh.decode_flops(c), sh.decode_bytes(c), ctx.peak)
               for c in decode_contexts(ctx.traced.report, runs).values())
    return 100.0 * need / secs
