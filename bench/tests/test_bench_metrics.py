"""The harness's arithmetic: tails are over every request of every call,
rates over the whole window, and ``BENCHMARK.json`` finds a file for
everything it names."""

import json
import re
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import harness

ROOT = Path(harness.__file__).resolve().parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def fake_call(start, wall, ttfts, finishes, tokens, gap=0.0):
    """A call that started at ``start``; the engine's clock began ``gap``
    seconds later and ran ``wall`` seconds."""
    tel = [NS(ttft_s=t, finish_s=f, queue_wait_ticks=0)
           for t, f in zip(ttfts, finishes)]
    report = NS(wall_s=wall, requests=tel, total_tokens=sum(tokens))
    return harness.Call(start=start, end=start + gap + wall,
                        reqs=[None] * len(tel),
                        outs=[[0] * n for n in tokens], report=report)


def ctx_of(calls):
    return NS(calls=calls, window_s=calls[-1].end - calls[0].start,
              setup_s=1.5, window_compiles=0)


def calls():
    a = fake_call(10.0, 4.0, [0.1, 0.2, 3.0], [1.1, 2.2, 4.0], [11, 21, 2],
                  gap=0.5)
    b = fake_call(15.0, 2.0, [0.3] * 7, [1.3] * 7, [5] * 7)
    return [a, b]


def test_rate_is_over_the_whole_window():
    got = harness.load_metric("tokens_per_s").read(ctx_of(calls()))
    # 34 + 35 tokens from the first call's start (10) to the last's end (17)
    assert got == pytest.approx(69 / 7.0)


def test_ttft_tail_is_over_every_request_of_every_call():
    c = calls()
    got = harness.load_metric("ttft_p90_s").read(ctx_of(c))
    # submission is the call's start: the first call's engine began 0.5 s
    # after it, so its first tokens came 0.6, 0.7 and 3.5 s after it
    pooled = [0.6, 0.7, 3.5] + [0.3] * 7
    assert got == pytest.approx(np.percentile(pooled, 90))
    assert got != pytest.approx(np.percentile([0.6, 0.7, 3.5], 90))


def test_tpot_tail_is_over_every_request_of_every_call():
    got = harness.load_metric("tpot_p90_ms").read(ctx_of(calls()))
    per = [1.0 / 10, 2.0 / 20, 1.0 / 1] + [1.0 / 4] * 7
    assert got == pytest.approx(1e3 * np.percentile(per, 90))


def test_every_named_piece_has_its_file():
    b = BENCH
    for c in b["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
    names = {c["name"] for c in b["configs"]}
    for w in b["workloads"]:
        assert w["config"] in names
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        cell = json.loads((ROOT / "bench" / "cells"
                           / f"{w['name']}.json").read_text())
        lim = cell["limits"]
        assert 0 < lim["mean_logit_gap"] < lim["max_logit_gap"]
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["end_to_end"] + b["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", [])) <= {w["name"]
                                               for w in b["workloads"]}


def test_names_and_bounds_keep_the_contract():
    b = BENCH
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [x["name"] for x in b[group]]
        assert len(seen) == len(set(seen))
        assert all(name.match(n) for n in seen)
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])
    assert all(len(w["why"]) <= 200 for w in b["workloads"])
    assert 1 <= b["run_seconds"] <= 51


def test_command_exits_without_a_result_where_there_is_no_tpu():
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    for workload in ("qwen2.5-3b.chat", "no-such-cell"):
        proc = subprocess.run(
            [sys.executable, *BENCH["command"][1:], "--workload", workload,
             "--seed", str(2**31 + 1), "--seconds", "1", "--trace", "0"],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 2, proc.stderr
        assert proc.stdout.strip() == ""
