"""Reduction of a profiler trace: hand-made events, and a small trace
recorded on the CPU."""

import jax
import jax.numpy as jnp
import pytest

from bench import spans as sp
from bench import trace as tr


def test_reduce_hand_made_events():
    ev = tr.Events(
        programs={"d": [("jit_decode_step", 0, 15), ("jit_prefill", 20, 30),
                        ("jit_decode_step", 35, 50)]},
        ops={"d": [("a", 0, 10), ("b", 5, 15), ("c", 20, 30), ("a", 35, 50)]},
        spans=[])
    red = tr.reduce(ev, (0, 40))
    assert red["window_s"] == pytest.approx(40e-9)
    assert red["busy_s"] == pytest.approx(30e-9)     # 0-15, 20-30, 35-40
    assert red["idle_share"] == pytest.approx(0.25)
    # a run cut by the window's end does not count
    assert red["program_runs"] == {"jit_decode_step": 1, "jit_prefill": 1}
    assert tr.program_time(red, "decode_step") == (pytest.approx(15e-9), 1)
    # idle gaps are named by the programs on either side of them
    gaps = dict(sp.labelled_gaps(ev, [], (0, 40)))
    assert gaps["serve call: jit_decode_step -> jit_prefill"] == \
        pytest.approx(5e-9)
    assert gaps["serve call: jit_prefill -> jit_decode_step"] == \
        pytest.approx(5e-9)
    assert red["top_programs"][0][0] == "jit_decode_step"


def test_union():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_program_name():
    assert tr.program_name("jit_decode_step(4950857752023182173)") == \
        "jit_decode_step"


def test_reduce_a_recorded_cpu_trace(tmp_path):
    def decode_step(x):
        return jnp.tanh(x @ x)

    step = jax.jit(decode_step)
    other = jax.jit(lambda x: (x * 2.0).sum())
    x = jnp.ones((128, 128), jnp.float32)
    step(x).block_until_ready()
    other(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.trace_start"):
        pass
    for _ in range(3):
        step(x).block_until_ready()
        other(x).block_until_ready()
    with jax.profiler.TraceAnnotation("bench.trace_end"):
        pass
    jax.profiler.stop_trace()
    ev = tr.load(tr.find_xplane(str(tmp_path)))
    lo = tr.span_window(ev, "bench.trace_start")[0]
    hi = tr.span_window(ev, "bench.trace_end")[0]
    red = tr.reduce(ev, (lo, hi))
    secs, runs = tr.program_time(red, "decode_step")
    assert runs == 3 and secs > 0
    assert 0 < red["busy_s"] <= red["window_s"]
    assert 0 <= red["idle_share"] < 1
    assert red["top_programs"][0][1] >= red["top_programs"][-1][1]
