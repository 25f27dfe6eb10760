"""One run of one cell: set-up, the measured window, the check against
the reference, and the metrics that ``BENCHMARK.json`` names.

A cell is data.  ``BENCHMARK.json`` names its configuration
(``bench/configs/<config>.json``), whose ``model_type`` names the family
module that builds, checks and counts the model
(``bench/families/<model_type>.py``), and its traffic mix
(``bench/traffic/<mix>.json``); its serve settings and the limits of its
check are in ``bench/cells/<workload>.json``; each metric is read by
``bench/metrics/<metric>.py``, a module with ``read(ctx)`` that returns
a number or None (nothing to read in this cell).

Offline batch serving: the generated stream is cut into calls of
``requests_per_call`` requests, and ``Engine.serve`` takes them back to
back.  No call starts once ``seconds`` have passed and call number
``trace_call`` has run; the window runs from the first call's start to
the last call's end.  A request is submitted at its call's start.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from typing import List, Optional

import numpy as np

from bench import traffic

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
TRACE_START, TRACE_END = "bench.trace_start", "bench.trace_end"


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and the
    programs compiled, from its own monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring

        self.total = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.total += duration
            self.compiles += event == self.EVENTS[-1]


class GcClock:
    """Seconds the interpreter's garbage collector runs, and how many of
    its collections take the oldest generation, while it is open."""

    def __init__(self):
        self.total, self.full, self._t = 0.0, 0, None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.total += time.perf_counter() - self._t
            self.full += info["generation"] == 2

    def close(self):
        gc.callbacks.remove(self._on)


def tick_stalls(tick_end_s: List[float]) -> str:
    """The median gap between ticks, and the gaps over three times it:
    whether a slow call is slow in every tick or in a few."""
    gaps = np.diff(tick_end_s) if len(tick_end_s) > 1 else np.zeros(1)
    med = float(np.median(gaps))
    long = gaps[gaps > 3 * med]
    return (f"ticks median {1e3 * med:.2f} ms, {len(long)} over 3x "
            f"({long.sum():.3f} s)")


@dataclasses.dataclass
class Call:
    """One ``serve()`` call of the window, on the harness's clock."""

    start: float
    end: float
    reqs: list
    outs: list
    report: object
    trace_stop: Optional[float] = None   # end of the traced span, if any

    @property
    def engine_t0(self) -> float:
        # the engine's clock starts inside serve(); wall_s is taken at its
        # end, so end - wall_s is that start, late by the report's own
        # bookkeeping (the latencies below err high, never low)
        return self.end - self.report.wall_s

    def ttft_s(self) -> List[float]:
        off = self.engine_t0 - self.start
        return [off + t.ttft_s for t in self.report.requests]

    def tpot_s(self) -> List[float]:
        return [(t.finish_s - t.ttft_s) / max(1, len(o) - 1)
                for t, o in zip(self.report.requests, self.outs)]


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def load_metric(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, workload: str, trace: bool) -> List[dict]:
    """The metrics a run of ``workload`` reports: the end-to-end ones,
    or with ``trace`` the per-layer ones, each where its ``workloads``
    (if given) names the cell."""
    pool = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in pool
            if "workloads" not in m or workload in m["workloads"]]


# ----------------------------------------------------------------- set-up
def serve_config(cell: dict, dtype: str, kv_dtype: Optional[str] = None):
    """The cell's ``ServeConfig``: its ``serve`` settings, the
    configuration's dtype for the cache, eos off and greedy decoding, so
    every request emits exactly its token budget.  ``kv_dtype`` switches
    on the program's own quantized cache (a control, never a cell)."""
    from repro.serve.engine import ServeConfig

    return ServeConfig(cache_dtype=dtype, kv_dtype=kv_dtype, eos_id=-1,
                       temperature=0.0, **cell["serve"])


def warmup_calls(engine, stream) -> List[list]:
    """``serve()`` calls that run every program the window will: one
    request per prefill width the stream's prompts can hit and, where
    prefixes are cached and shared, one prefix hit per suffix length (the
    continuation prefill is specialised on its exact length).  A hit
    matches whole pages, so its suffix is the part of its document past
    the last whole page and its own part; each such suffix is sent here
    after a one-page prefix.  Tokens come from a fixed generator no
    window draws from, so nothing here is hit later."""
    from repro.serve.queue import Request

    rng = np.random.Generator(np.random.PCG64(0x5EED))
    tok = lambda n: rng.integers(1, stream.vocab, n).astype(np.int32)
    shapes = stream.prompt_shapes()
    widths = {}
    for dl, own in shapes:
        widths.setdefault(engine._bucket_width(dl + own), dl + own)
    first = [tok(n) for _, n in sorted(widths.items())]
    calls = [[Request(prompt=p, max_new_tokens=2) for p in first]]
    cfg = engine.cfg
    if stream.docs and cfg.cache == "paged" and cfg.prefix_cache:
        ps = cfg.page_size
        page = first[-1][:ps]
        suffixes = sorted({dl % ps + own for dl, own in shapes if dl})
        calls.append([Request(prompt=np.concatenate([page, tok(n)]),
                              max_new_tokens=2) for n in suffixes])
    return calls


# ----------------------------------------------------------------- window
def run_window(engine, stream, cell: dict, seconds: float,
               trace_dir: Optional[str] = None):
    """Back-to-back ``serve()`` calls over consecutive blocks of the
    stream, until ``seconds`` have passed and, traced or not, call number
    ``trace_call`` (counted from 0) has run: a slow host never leaves a
    run fewer calls to check.  With ``trace_dir`` the profiler
    records the first ``trace_seconds`` of that call, between the host
    spans ``bench.trace_start`` and ``bench.trace_end``."""
    from repro.serve.queue import Request

    max_new = max(stream.outs)
    last = cell.get("trace_call", 1)
    traced = last if trace_dir is not None else -1
    calls: List[Call] = []
    gc_clock = GcClock()
    t_begin = time.monotonic()
    while time.monotonic() - t_begin < seconds or len(calls) <= last:
        chunk = stream.block(len(calls))
        reqs = [Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens)
                for r in chunk]
        tracer = None
        gc0 = (gc_clock.total, gc_clock.full)
        if len(calls) == traced:
            tracer = Tracer(trace_dir, cell.get("trace_seconds", 5.0))
        t0 = time.monotonic()
        outs = engine.serve(reqs, max_new)
        t1 = time.monotonic()
        if tracer is not None:
            tracer.finish()
        calls.append(Call(t0, t1, chunk, outs, engine.last_report,
                          tracer.t_stop if tracer else None))
        log(f"call {len(calls)}: {len(reqs)} requests, "
            f"{engine.last_report.total_tokens} tokens, "
            f"{engine.last_report.total_ticks} ticks, {t1 - t0:.3f} s; "
            f"{tick_stalls(engine.last_report.tick_end_s)}; gc "
            f"{gc_clock.total - gc0[0]:.3f} s, "
            f"{gc_clock.full - gc0[1]} full"
            + (" (traced)" if tracer else ""))
    gc_clock.close()
    return calls


class Tracer:
    """The profiler over a fixed span from now: a device trace of a whole
    call outgrows the profiler's buffer and loses its later events."""

    def __init__(self, log_dir: str, seconds: float):
        import threading

        import jax

        self._jax = jax
        self._lock = threading.Lock()
        self._on = True
        jax.profiler.start_trace(log_dir)
        with jax.profiler.TraceAnnotation(TRACE_START):
            pass
        self._timer = threading.Timer(seconds, self._stop)
        self._timer.start()

    def _stop(self) -> None:
        with self._lock:
            if self._on:
                with self._jax.profiler.TraceAnnotation(TRACE_END):
                    self.t_stop = time.monotonic()
                self._jax.profiler.stop_trace()
                self._on = False

    def finish(self) -> None:
        self._timer.cancel()
        self._stop()


# ------------------------------------------------------------------ check
def sample_requests(calls: List[Call], seed: int, tokens: int) -> list:
    """(prompt, served) pairs drawn from the seed: the request with the
    most served tokens, then others until ``tokens`` served tokens."""
    done = [(c.reqs[k].prompt, np.asarray(c.outs[k]))
            for c in calls for k in range(len(c.reqs))]
    rng = np.random.Generator(np.random.PCG64(seed + 1))
    longest = max(range(len(done)), key=lambda k: len(done[k][1]))
    order = [longest] + [k for k in rng.permutation(len(done))
                         if k != longest]
    picked, total = [], 0
    for k in order:
        if total >= tokens:
            break
        picked.append(done[k])
        total += len(done[k][1])
    return picked


def check(ref, params, calls: List[Call], cell: dict, seed: int,
          vocab: int, controls=()) -> dict:
    """Every number compared, with its limit.  For each sampled served
    token, its gap is how far its reference logit lies below the
    reference's best; ``max_logit_gap`` is the widest, ``mean_logit_gap``
    the mean over all of them.  For each precision in ``controls`` the
    reference in that precision stands in for the program (the first
    decides), and every reading is kept beside the limits."""
    wrong = sum(1 for c in calls for r, o in zip(c.reqs, c.outs)
                if len(o) != r.max_new_tokens or (np.asarray(o) < 0).any()
                or (np.asarray(o) >= vocab).any())
    failed = sum(c.report.failed_requests + c.report.shed_requests
                 for c in calls)
    gaps = {}
    for prompt, served in sample_requests(calls, seed, cell["check_tokens"]):
        for k, v in ref.served_gaps(params, prompt, served, controls).items():
            gaps.setdefault(k, []).append(np.asarray(v, np.float64))
    gaps = {k: np.concatenate(v) for k, v in gaps.items()}
    key = f"gap_{controls[0]}" if controls else "gap"
    limits = cell["limits"]
    out = {f"{stat}_logit_gap": {"value": float(fn(gaps[key])),
                                 "limit": limits[f"{stat}_logit_gap"]}
           for stat, fn in (("max", np.max), ("mean", np.mean))}
    out["wrong_outputs"] = {"value": wrong, "limit": 0}
    out["failed_requests"] = {"value": failed, "limit": 0}
    for k, g in gaps.items():
        if k != key:
            who = "program" if k == "gap" else k[len("gap_"):]
            for stat, fn in (("max", np.max), ("mean", np.mean)):
                out[f"{who}_{stat}_logit_gap"] = {"value": float(fn(g)),
                                                  "limit": None}
    return out


# -------------------------------------------------------------------- run
def run_cell(*, cfg: dict, mix: dict, cell: dict,
             metrics: List[dict], seed: int, seconds: float, trace: bool,
             t_process: float, controls=(), kv_dtype: Optional[str] = None,
             require_tpu: bool = True, peaks: Optional[dict] = None,
             model_cls=None) -> dict:
    """Everything after the platform check; returns the result line.
    ``controls`` and ``kv_dtype`` are the controls (see ``check`` and
    ``serve_config``); ``model_cls`` replaces the program's ``Model``
    (the tests break the timed path through it)."""
    import jax

    from bench import modeldef, spans
    from bench import trace as tr
    from repro.models import Model
    from repro.serve.engine import Engine

    fam = modeldef.family(cfg)
    log(f"program imported at {time.monotonic() - t_process:.3f} s")
    dev = jax.devices()[0]
    if require_tpu and dev.platform != "tpu":
        raise RuntimeError(f"no TPU: JAX found {dev.platform!r}")
    if peaks is None and dev.platform == "tpu":
        from bench.peaks import peak
        peaks = peak(dev.device_kind)
    clock = CompileClock()
    mcfg = fam.model_config(cfg)
    model = (model_cls or Model)(mcfg)
    modeldef.check_layout(fam.init_fn(cfg), model)
    params = modeldef.make_params(cfg, seed)
    log(f"weights made at {time.monotonic() - t_process:.3f} s; "
        f"{clock.compiles} programs compiled in {clock.total:.3f} s")
    engine = Engine(model, params,
                    serve_config(cell, cfg["torch_dtype"], kv_dtype))
    stream = traffic.Stream(mix, seed, mcfg.vocab_size,
                            cell["requests_per_call"])
    for reqs in warmup_calls(engine, stream):
        engine.serve(reqs, 2)
    log(f"warmed up at {time.monotonic() - t_process:.3f} s; "
        f"{clock.compiles} programs compiled in {clock.total:.3f} s")
    gc.collect()
    compiles0 = clock.compiles
    t_window = time.monotonic()
    setup_s = t_window - t_process
    with tempfile.TemporaryDirectory() as tmp:
        calls = run_window(engine, stream, cell, seconds,
                           trace_dir=tmp if trace else None)
        window_compiles = clock.compiles - compiles0
        red = None
        if trace:
            ev = tr.load(tr.find_xplane(tmp))
            host = spans.serve_spans(ev)
            window = (tr.span_window(ev, TRACE_START)[0],
                      tr.span_window(ev, TRACE_END)[0])
            red = tr.reduce(ev, window)
            red["idle_by_span"] = spans.idle_by_span(ev, host, window)
            red["idle_gaps"] = spans.labelled_gaps(ev, host, window)
            log(f"idle by span: {json.dumps(red['idle_by_span'])}")
    window_s = calls[-1].end - calls[0].start
    mem = dev.memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0))
    del engine
    gc.collect()
    t_check = time.monotonic()
    checks = check(fam.Reference(cfg), params, calls, cell, seed,
                   mcfg.vocab_size, tuple(controls))
    log(f"window {window_s:.3f} s, {window_compiles} compiles; check "
        f"{time.monotonic() - t_check:.3f} s; peak {peak_bytes} bytes")
    ctx = SimpleNamespace(
        calls=calls, trace=red,
        traced=calls[cell.get("trace_call", 1)] if trace else None,
        window_s=window_s, setup_s=setup_s, window_compiles=window_compiles,
        shapes=fam.Shapes.of(cfg), peak=peaks, cell=cell, chips=1)
    out_metrics = {}
    for m in metrics:
        v = load_metric(m["name"]).read(ctx)
        if v is not None:
            out_metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    result = {
        "correct": all(c["limit"] is None or c["value"] <= c["limit"]
                       for c in checks.values()),
        "attempted": sum(len(c.reqs) for c in calls),
        "failed": checks["failed_requests"]["value"],
        "metrics": out_metrics,
        "device": device,
    }
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = {
            "device_ops": red["top_programs"],
            "idle_gaps": red["idle_gaps"],
        }
    result["checks"] = checks
    return result


def print_result(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
