"""The serve loop's spans and named programs: idle time put down to the
innermost span on hand-made events, a profiler trace of the tiny engine
recorded on the CPU, and the readers of the per-layer metrics that the
spans, names and ``ServeReport.tick_end_s`` feed."""

import json
from types import SimpleNamespace as NS

import jax
import numpy as np
import pytest

from bench import harness, modeldef
from bench import spans as sp
from bench import trace as tr
from bench.tests.tiny import CONTIGUOUS, PAGED, config


# --------------------------------------------------------- hand-made events
def nested():
    """Device busy 0-10, 20-30, 60-70 of the window 0-100; the host in a
    call 0-100 holding ticks 5-50 (admit 12-25, decode 25-45) and
    55-95 (decode 58-90)."""
    ev = tr.Events(
        programs={"d": [("jit_decode_step", 0, 10),
                        ("jit_prefill_padded", 20, 30),
                        ("jit_decode_step", 60, 70)]},
        ops={"d": [("a", 0, 10), ("b", 20, 30), ("c", 60, 70)]},
        spans=[])
    spans = [("serve.call", 0, 100, {"requests": 8, "slots": 4}),
             ("serve.tick", 5, 50, {"tick": 0}),
             ("serve.admit", 12, 25, {"rid": 3}),
             ("serve.decode", 25, 45, {}),
             ("serve.tick", 55, 95, {"tick": 1}),
             ("serve.decode", 58, 90, {})]
    return ev, spans


def test_innermost_pieces_follow_the_nesting():
    _, spans = nested()
    assert sp.innermost(spans) == [
        (0, 5, "serve.call"), (5, 12, "serve.tick"), (12, 25, "serve.admit"),
        (25, 45, "serve.decode"), (45, 50, "serve.tick"),
        (50, 55, "serve.call"), (55, 58, "serve.tick"),
        (58, 90, "serve.decode"), (90, 95, "serve.tick"),
        (95, 100, "serve.call")]
    # disjoint spans leave the host in none between them
    assert sp.innermost([("serve.a", 0, 10), ("serve.b", 20, 30)]) == [
        (0, 10, "serve.a"), (20, 30, "serve.b")]


def test_idle_by_span_picks_the_innermost_and_sums_to_idle():
    ev, spans = nested()
    window = (0, 100)
    idle = sp.idle_by_span(ev, spans, window)
    # idle 10-20: tick 10-12, admit 12-20; 30-60: decode 30-45, tick 45-50
    # and 55-58, call 50-55, decode 58-60; 70-100: decode 70-90, tick
    # 90-95, call 95-100
    assert idle == pytest.approx({
        "serve.admit": 8e-9, "serve.call": 10e-9, "serve.decode": 37e-9,
        "serve.tick": 15e-9})
    red = tr.reduce(ev, window)
    assert sum(idle.values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    shares = sp.idle_shares(idle, red["window_s"])
    assert shares["idle_admit_share"] == pytest.approx(8.0)
    assert shares["idle_tick_share"] == pytest.approx(52.0)
    # past the spans the host is in none
    idle = sp.idle_by_span(ev, spans, (0, 120))
    assert idle["none"] == pytest.approx(20e-9)


def test_gaps_are_labelled_with_the_span_that_covers_most_of_them():
    ev, spans = nested()
    gaps = dict(sp.labelled_gaps(ev, spans, (0, 100)))
    assert gaps == pytest.approx({
        "serve.decode: jit_prefill_padded -> jit_decode_step": 30e-9,
        "serve.decode: jit_decode_step -> end": 30e-9,
        "serve.admit: jit_decode_step -> jit_prefill_padded": 10e-9})
    # a gap no span covers keeps the harness's label
    gaps = dict(sp.labelled_gaps(ev, [], (0, 100)))
    assert gaps["serve call: jit_decode_step -> jit_prefill_padded"] == \
        pytest.approx(10e-9)


# ------------------------------------------------- a recorded CPU trace
@pytest.fixture(scope="module")
def tiny_model():
    from repro.models import Model

    cfg = config()
    return (Model(modeldef.family(cfg).model_config(cfg)),
            modeldef.make_params(cfg, 7))


def traced_serve(tiny_model, cell, log_dir, n=8, mode="continuous"):
    """Serve ``n`` requests once to compile, then again under the
    profiler; returns the engine's report and the trace's path."""
    from repro.serve.engine import Engine
    from repro.serve.queue import Request

    model, params = tiny_model
    engine = Engine(model, params, harness.serve_config(
        dict(cell, serve=dict(cell["serve"], mode=mode)), "bfloat16"))
    rng = np.random.default_rng(11)
    reqs = [Request(prompt=rng.integers(1, 2048, int(ln)).astype(np.int32),
                    max_new_tokens=int(k))
            for ln, k in zip(rng.integers(4, 40, n), rng.integers(2, 9, n))]
    engine.serve(reqs, 8)
    jax.profiler.start_trace(str(log_dir))
    engine.serve(reqs, 8)
    jax.profiler.stop_trace()
    return engine.last_report, tr.find_xplane(str(log_dir))


@pytest.mark.parametrize("cell", [CONTIGUOUS, PAGED],
                         ids=["contiguous", "paged"])
def test_recorded_serve_has_its_span_tree_and_named_programs(
        cell, tiny_model, tmp_path):
    report, path = traced_serve(tiny_model, cell, tmp_path)
    ev = tr.load(path)
    spans = sp.serve_spans(ev)
    names = [s[0] for s in spans]
    assert names.count("serve.call") == 1 and names.count("serve.plan") == 1
    call = next(s for s in spans if s[0] == "serve.call")
    assert call[3] == {"requests": 8, "slots": 4}
    ticks = [s for s in spans if s[0] == "serve.tick"]
    assert len(ticks) == report.total_ticks
    assert [s[3]["tick"] for s in ticks] == list(range(report.total_ticks))
    admits = [s for s in spans if s[0] == "serve.admit"]
    assert sorted(s[3]["rid"] for s in admits) == list(range(8))
    assert all(s[3]["prompt_len"] == report.requests[s[3]["rid"]].prompt_len
               for s in admits)
    assert names.count("serve.decode") == report.total_ticks
    assert names.count("serve.emit") == report.total_ticks
    # every span lies inside the call
    assert all(call[1] <= s <= t <= call[2] for _, s, t, _ in spans)
    ends = report.tick_end_s
    assert len(ends) == report.total_ticks
    assert all(0 < a < b < report.wall_s for a, b in zip(ends, ends[1:]))

    programs = {n for runs in ev.programs.values() for n, _, _ in runs}
    assert "jit__lambda" not in programs
    assert {"jit_decode_step", "jit_prefill_padded",
            "jit_argmax_tokens"} <= programs
    writes = ({"jit_splice_cache"} if cell is CONTIGUOUS
              else {"jit_write_page", "jit_admit_paged_slot"})
    assert writes <= programs
    assert [n for n in programs if "decode_step" in n] == ["jit_decode_step"]

    window = sp.window_of(ev, spans)
    assert window == (call[1], call[2])
    red = tr.reduce(ev, window)
    idle = sp.idle_by_span(ev, spans, window)
    assert sum(idle.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
    assert set(idle) <= {"serve.call", "serve.plan", "serve.tick",
                         "serve.admit", "serve.decode", "serve.emit"}


def test_rounds_mode_has_one_call_span_and_no_tick_ends(tiny_model,
                                                        tmp_path):
    report, path = traced_serve(tiny_model, CONTIGUOUS, tmp_path,
                                mode="rounds")
    assert [s[0] for s in sp.serve_spans(tr.load(path))] == ["serve.call"]
    assert report.tick_end_s == []


def test_command_prints_the_summary(tiny_model, tmp_path, capsys):
    report, _ = traced_serve(tiny_model, CONTIGUOUS, tmp_path, n=4)
    assert sp.main([str(tmp_path)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["span_counts"]["serve.tick"] == report.total_ticks
    assert sum(out["idle_by_span"].values()) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-6)
    assert all(g[0].startswith("serve.") for g in out["idle_gaps"])
    assert sp.main([]) == 2


# ----------------------------------------------------------------- readers
def trace_ctx(program_s, program_runs):
    return NS(trace={"program_s": program_s, "program_runs": program_runs})


def test_prefill_device_ms_is_the_mean_of_whole_prefill_runs():
    read = harness.load_metric("prefill_device_ms").read
    ctx = trace_ctx({"jit_prefill_padded": 0.060, "jit_prefill_continue":
                     0.010, "jit_decode_step": 1.0, "jit_prefill": 5.0},
                    {"jit_prefill_padded": 3, "jit_prefill_continue": 2,
                     "jit_decode_step": 40, "jit_prefill": 1})
    assert read(ctx) == pytest.approx(14.0)
    # the parent's unnamed programs, or no trace: nothing to read
    assert read(trace_ctx({"jit__lambda": 1.0}, {"jit__lambda": 9})) is None
    assert read(NS(trace=None)) is None


def test_kv_write_device_ms_is_write_time_per_prefill():
    read = harness.load_metric("kv_write_device_ms").read
    ctx = trace_ctx({"jit_prefill_padded": 0.060, "jit_splice_cache": 0.018,
                     "jit_write_page": 0.004, "jit_admit_paged_slot": 0.002},
                    {"jit_prefill_padded": 3, "jit_splice_cache": 3,
                     "jit_write_page": 8, "jit_admit_paged_slot": 3})
    assert read(ctx) == pytest.approx(8.0)
    assert read(trace_ctx({"jit_prefill_padded": 0.06},
                          {"jit_prefill_padded": 3})) is None
    assert read(trace_ctx({"jit_splice_cache": 0.01},
                          {"jit_splice_cache": 1})) is None
    assert read(NS(trace=None)) is None


def test_tick_gap_p99_ms_pools_the_gaps_of_every_call():
    read = harness.load_metric("tick_gap_p99_ms").read
    a = NS(report=NS(tick_end_s=[0.0, 0.1, 0.2]))
    b = NS(report=NS(tick_end_s=[5.0, 5.05]))
    # gaps within each call; the time from one call's last tick to the
    # next call's first is no inter-token gap
    assert read(NS(calls=[a, b])) == pytest.approx(
        1e3 * np.percentile([0.1, 0.1, 0.05], 99))
    # a report without the counter (the parent's) reads nothing
    assert read(NS(calls=[NS(report=NS())])) is None
    assert read(NS(calls=[NS(report=NS(tick_end_s=[0.1]))])) is None


@pytest.mark.parametrize("name,want", [("idle_admit_share", 8.0),
                                       ("idle_tick_share", 52.0)])
def test_idle_share_readers_read_the_split_of_the_trace(name, want):
    read = harness.load_metric(name).read
    ev, spans = nested()
    red = tr.reduce(ev, (0, 100))
    red["idle_by_span"] = sp.idle_by_span(ev, spans, (0, 100))
    assert read(NS(trace=red)) == pytest.approx(want)
    # a trace with no serve.* span, or none at all: nothing to read
    red["idle_by_span"] = sp.idle_by_span(ev, [], (0, 100))
    assert read(NS(trace=red)) is None
    assert read(NS(trace=None)) is None
