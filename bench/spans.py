"""The serve loop's own host spans (``serve.*``) against the device trace.

``Engine.serve`` writes ``jax.profiler.TraceAnnotation`` spans into the
profiler's trace, on the clock of the device events: ``serve.call`` >
``serve.plan``, ``serve.tick`` > ``serve.admit`` / ``serve.decode`` /
``serve.emit`` (``docs/serving.md``).  Here each interval in which the
device ran nothing is put down to the innermost span the host was in,
``"none"`` where it was in none.

    python3 -m bench.spans <log_dir>

reads the newest ``.xplane.pb`` under a ``jax.profiler.trace`` directory
and prints one JSON object: the device numbers of ``bench/trace.py``'s
``reduce``, the idle seconds per span, the idle shares of admission and
of the rest of the tick, and the longest idle gaps, each labelled with
the span that covers most of it.  The window is the harness's
``bench.trace_start`` .. ``bench.trace_end`` where the trace has them,
else the first ``serve.call``'s start to the last one's end.

All times are nanoseconds on the trace's clock; results are seconds.
"""

from __future__ import annotations

import bisect
import json
import sys
from collections import defaultdict
from typing import Dict, List, Tuple

from bench import trace as tr

PREFIX = "serve."
NONE = "none"
# the spans a decode tick holds besides admission
TICK = ("serve.tick", "serve.decode", "serve.emit")


def serve_spans(ev: tr.Events) -> List[tuple]:
    """The trace's ``serve.*`` host spans in order of start:
    ``(name, start, end, stats)``, where ``stats`` holds the span's
    keyword arguments (``rid``, ``tick`` ...)."""
    return sorted((s for s in ev.spans if s[0].startswith(PREFIX)),
                  key=lambda s: (s[1], -s[2]))


def innermost(spans: List[tuple]) -> List[tuple]:
    """Disjoint ``(start, end, name)`` pieces, sorted: over each, the
    innermost span that covers it.  Spans of one thread nest; where the
    host is in no span there is no piece."""
    out: List[tuple] = []
    stack: List[tuple] = []       # open spans: (end, name)
    cur = None

    def emit(end, name):
        nonlocal cur
        if end > cur:
            out.append((cur, end, name))
            cur = end

    for name, s, t, *_ in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][0] <= s:
            emit(*stack.pop())
        if stack:
            emit(s, stack[-1][1])
        cur = s if cur is None else max(cur, s)
        stack.append((t, name))
    while stack:
        emit(*stack.pop())
    return out


def idle_intervals(ev: tr.Events, window: tr.Interval) -> Dict[str, list]:
    """Per device, the intervals of ``window`` in which it ran no
    operation."""
    lo, hi = window
    out = {}
    for dev, dev_ops in ev.ops.items():
        merged = tr.union([(s, t) for _, s, t in tr.clip(dev_ops, lo, hi)])
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        out[dev] = [(a, b) for a, b in zip(edges[0::2], edges[1::2])
                    if b > a]
    return out


def _split(a: float, b: float, pieces: List[tuple], starts: List[float]):
    """``(name, ns)`` parts of ``[a, b)`` by ``pieces``; the rest is
    ``NONE``."""
    parts = defaultdict(float)
    covered = 0.0
    i = max(0, bisect.bisect_right(starts, a) - 1)
    while i < len(pieces) and pieces[i][0] < b:
        s, t, name = pieces[i]
        d = min(b, t) - max(a, s)
        if d > 0:
            parts[name] += d
            covered += d
        i += 1
    if b - a > covered:
        parts[NONE] += (b - a) - covered
    return parts


def idle_by_span(ev: tr.Events, spans: List[tuple],
                 window: tr.Interval) -> Dict[str, float]:
    """Seconds of device idle time in ``window`` under each innermost
    ``serve.*`` span (``"none"`` outside all), averaged over devices as
    ``reduce``'s ``busy_s`` is: the parts sum to window − busy."""
    pieces = innermost(spans)
    starts = [p[0] for p in pieces]
    total = defaultdict(float)
    idle = idle_intervals(ev, window)
    for ivs in idle.values():
        for a, b in ivs:
            for name, ns in _split(a, b, pieces, starts).items():
                total[name] += ns / 1e9
    return {k: v / len(idle) for k, v in sorted(total.items())}


def idle_shares(idle: Dict[str, float], window_s: float) -> Dict[str, float]:
    """Idle time while the host admits, and while it is in the rest of a
    tick, as percentages of the window."""
    return {
        "idle_admit_share": 100.0 * idle.get("serve.admit", 0.0) / window_s,
        "idle_tick_share": 100.0 * sum(idle.get(n, 0.0) for n in TICK)
        / window_s,
    }


def labelled_gaps(ev: tr.Events, spans: List[tuple], window: tr.Interval,
                  top: int = 10) -> List[list]:
    """The ``top`` longest idle gaps as ``[label, seconds]``: the span
    that covers most of the gap (``serve call`` where none does), then
    the programs on either side."""
    lo, hi = window
    pieces = innermost(spans)
    starts = [p[0] for p in pieces]
    gaps = []
    for dev, ivs in idle_intervals(ev, window).items():
        runs = sorted(tr.clip(ev.programs.get(dev, []), lo, hi),
                      key=lambda r: r[1])
        run_starts = [r[1] for r in runs]
        run_ends = [r[2] for r in runs]
        for a, b in ivs:
            parts = _split(a, b, pieces, starts)
            parts.pop(NONE, None)
            where = (max(parts, key=parts.get) if parts else "serve call")
            i = bisect.bisect_right(run_ends, a) - 1
            j = bisect.bisect_left(run_starts, b)
            gaps.append((f"{where}: "
                         f"{runs[i][0] if i >= 0 else 'start'} -> "
                         f"{runs[j][0] if j < len(runs) else 'end'}",
                         (b - a) / 1e9))
    return [[n, s] for n, s in sorted(gaps, key=lambda g: -g[1])[:top]]


def window_of(ev: tr.Events, spans: List[tuple]) -> Tuple[float, float]:
    """The harness's traced span, else the serve calls' extent."""
    a = tr.span_window(ev, "bench.trace_start")
    b = tr.span_window(ev, "bench.trace_end")
    if a and b:
        return a[0], b[0]
    calls = [(s, t) for n, s, t, _ in spans if n == "serve.call"]
    if not calls:
        raise ValueError("the trace holds neither bench.trace_* nor "
                         "serve.call spans")
    return calls[0][0], max(t for _, t in calls)


def summarize(path: str, top: int = 10) -> dict:
    """Everything :func:`main` prints, for one ``.xplane.pb``."""
    ev = tr.load(path)
    spans = serve_spans(ev)
    window = window_of(ev, spans)
    red = tr.reduce(ev, window, top)
    idle = idle_by_span(ev, spans, window)
    counts = defaultdict(int)
    for n, *_ in spans:
        counts[n] += 1
    return {
        "window_s": red["window_s"], "busy_s": red["busy_s"],
        "idle_share": red["idle_share"],
        "top_programs": red["top_programs"],
        "span_counts": dict(sorted(counts.items())),
        "idle_by_span": idle,
        **idle_shares(idle, red["window_s"]),
        "idle_gaps": labelled_gaps(ev, spans, window, top),
    }


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    print(json.dumps(summarize(tr.find_xplane(args[0]))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
