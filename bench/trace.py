"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

Loaded with ``jax.profiler.ProfileData``.  On a TPU each chip is a plane
``/device:TPU:<n>`` whose line ``XLA Modules`` holds one event per
program run and ``XLA Ops`` one per operation.  On the CPU backend (the
tests) operations are host events that carry an ``hlo_module`` stat;
a program run is then the span of its operations with one ``run_id``.
The host spans kept are the harness's own (``bench.*``) and the serve
loop's (``serve.*``, read by ``bench/spans.py``).

All times are nanoseconds on the trace's clock until :func:`reduce`
turns them into seconds.
"""

from __future__ import annotations

import dataclasses
import glob
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]
SPANS = ("bench.", "serve.")


@dataclasses.dataclass
class Events:
    programs: Dict[str, List[tuple]]         # device -> [(name, start, end)]
    ops: Dict[str, List[tuple]]              # device -> [(name, start, end)]
    spans: List[tuple]              # host [(name, start, end, stats)]


def program_name(raw: str) -> str:
    """``jit_decode_step(123)`` -> ``jit_decode_step``."""
    return re.sub(r"\(.*\)$", "", raw).strip()


def load(path: str) -> Events:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    programs: Dict[str, list] = defaultdict(list)
    ops: Dict[str, list] = defaultdict(list)
    spans: List[tuple] = []
    cpu_runs: Dict[tuple, list] = {}
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" in lines:
            dev = plane.name
            for e in lines["XLA Ops"].events:
                ops[dev].append((e.name, e.start_ns, e.end_ns))
            if "XLA Modules" in lines:
                for e in lines["XLA Modules"].events:
                    programs[dev].append((program_name(e.name), e.start_ns,
                                          e.end_ns))
            continue
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith(SPANS):
                    spans.append((e.name, e.start_ns, e.end_ns,
                                  dict(e.stats)))
                    continue
                stats = dict(e.stats)
                mod = stats.get("hlo_module")
                if mod is None or e.duration_ns <= 0:
                    continue
                dev = f"/host:CPU:{stats.get('device_ordinal', 0)}"
                ops[dev].append((e.name, e.start_ns, e.end_ns))
                key = (dev, mod, stats.get("run_id"))
                run = cpu_runs.setdefault(key, [e.start_ns, e.end_ns])
                run[0] = min(run[0], e.start_ns)
                run[1] = max(run[1], e.end_ns)
    for (dev, mod, _), (s, t) in cpu_runs.items():
        programs[dev].append((mod, s, t))
    return Events(dict(programs), dict(ops), spans)


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def union(intervals: List[Interval]) -> List[Interval]:
    """Merge overlapping intervals; returns them sorted and disjoint."""
    out: List[list] = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def clip(items, lo: float, hi: float):
    return [(n, max(s, lo), min(t, hi)) for n, s, t in items
            if t > lo and s < hi]


def reduce(ev: Events, window: Interval, top: int = 10) -> dict:
    """Device numbers inside ``window`` (ns).  Busy is the union of the
    operations' intervals, per device, averaged over devices for
    ``busy_s``.  A program is timed by its runs that lie wholly inside the
    window."""
    lo, hi = window
    if not ev.ops:
        raise ValueError("the trace holds no device operation")
    busy = []
    prog_s, prog_n = defaultdict(float), defaultdict(int)
    for dev, dev_ops in ev.ops.items():
        merged = union([(s, t) for _, s, t in clip(dev_ops, lo, hi)])
        busy.append(sum(t - s for s, t in merged) / 1e9)
        # per program, only the runs wholly inside the window
        for name, s, t in ev.programs.get(dev, []):
            if lo <= s and t <= hi:
                prog_s[name] += (t - s) / 1e9
                prog_n[name] += 1
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy) / len(busy)
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "program_s": dict(prog_s),
        "program_runs": dict(prog_n),
        "top_programs": [[n, s] for n, s in sorted(
            prog_s.items(), key=lambda kv: -kv[1])[:top]],
    }


def span_window(ev: Events, name: str) -> Optional[Interval]:
    """The first host span called ``name``, or None."""
    for n, s, t, *_ in ev.spans:
        if n == name:
            return (s, t)
    return None


def program_time(red: dict, key: str) -> Tuple[float, int]:
    """Seconds and runs of every program whose name contains ``key``."""
    secs = sum(s for n, s in red["program_s"].items() if key in n)
    runs = sum(c for n, c in red["program_runs"].items() if key in n)
    return secs, runs
