"""Model operations in the traced span over (span x peak x chips): the
real prompt tokens prefilled by admissions that finished in the span and
the tokens of the span's whole decode steps, with no padding, masked
position or idle row counted, as the family's ``Shapes`` counts it
(``ctx.shapes``, from ``bench/families/<model_type>.py``)."""

from bench.trace import program_time
from bench.work import decode_contexts, prefills


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    _, steps = program_time(ctx.trace, "decode_step")
    sh = ctx.shapes
    flops = sum(sh.prefill_flops(n, start) for n, start in
                prefills(ctx.traced))
    flops += sum(sh.decode_flops(c) for c in
                 decode_contexts(ctx.traced.report, steps).values())
    if not flops:
        return None
    return 100.0 * flops / (ctx.trace["window_s"] * ctx.peak["bf16_flops"]
                            * ctx.chips)
