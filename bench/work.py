"""The work one ``serve()`` call did, from its ``ServeReport``: the live
rows of each decode step with their contexts, and the prompt tokens each
admission prefilled.

A request admitted at tick ``a`` with a prompt of ``P`` tokens and
``n`` emitted tokens takes the first from its prefill and the others
from decode steps ``a + 1 .. a + n - 1`` (a step advances the tick), in
which it attends to ``P + 1, P + 2, ..`` positions.  The traced span
starts with the call, so its ``k`` whole decode runs are steps ``1..k``,
and an admission lies in it when its first token came before the span's
end.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Dict, List


def decode_contexts(report, steps: int) -> Dict[int, List[int]]:
    """tick -> contexts of the rows live in that decode step, for the
    call's first ``steps`` decode steps."""
    out: Dict[int, List[int]] = defaultdict(list)
    for t in report.requests:
        for j in range(t.decode_tokens):
            tick = t.admit_tick + 1 + j
            if tick <= steps:
                out[tick].append(t.prompt_len + 1 + j)
    return dict(out)


def admitted_in_span(call) -> list:
    """Telemetry of the traced call's requests admitted inside the span."""
    end = call.trace_stop - call.engine_t0
    return [t for t in call.report.requests if t.ttft_s <= end]


def prefills(call) -> List[tuple]:
    """(tokens computed, first position) of each prefill in the span."""
    return [(t.prefill_tokens, t.prefix_hit_tokens)
            for t in admitted_in_span(call) if t.prefill_tokens > 0]
