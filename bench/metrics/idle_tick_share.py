"""Device idle time in the traced span while the host was in the rest
of a decode tick: innermost in ``serve.tick``, ``serve.decode`` or
``serve.emit``, as a percentage of the span (``bench/spans.py``
``idle_shares``).  None where the trace holds no ``serve.*`` span."""

from bench.spans import idle_shares


def read(ctx):
    if ctx.trace is None or set(ctx.trace["idle_by_span"]) <= {"none"}:
        return None
    return idle_shares(ctx.trace["idle_by_span"],
                       ctx.trace["window_s"])["idle_tick_share"]
