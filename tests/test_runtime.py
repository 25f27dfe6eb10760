"""Runtime subsystem: persistent-pool reuse (bit-identical, fresh stats,
zero steady-state thread creation), cross-layer telemetry, the online
FAA-cost calibration's paper trends, and the device_parallel_for padding
branches."""

import os
import subprocess
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

from repro.core import cost_model as cm
from repro.core import parallel_for as pf
from repro.core import runtime
from repro.core.atomic_sim import UnitTask
from repro.core.schedulers import plan_admission
from repro.core.topology import AMD3970X, GOLD5225R, W3225R
from repro.data.pipeline import DataConfig, PrefetchIterator, SyntheticLM

TOPOLOGIES = (W3225R, GOLD5225R, AMD3970X)


def _materialize(n, pool, schedule="faa", block=7):
    out = np.zeros(n, np.int64)
    lock = threading.Lock()

    def task(i):
        with lock:
            out[i] += i * 3 + 1

    stats = pf.parallel_for_stats(task, n, pool=pool, schedule=schedule,
                                  block_size=block)
    return out, stats


# ---------------------------------------------------------------------------
# Pool reuse
# ---------------------------------------------------------------------------

def test_pool_reuse_bit_identical_and_fresh_stats():
    """The same task set run twice on one WorkerPool yields bit-identical
    results and fresh (non-accumulating) ScheduleStats."""
    pool = runtime.WorkerPool()
    try:
        scoped = pool.scoped(4)
        out1, s1 = _materialize(400, scoped)
        out2, s2 = _materialize(400, scoped)
        np.testing.assert_array_equal(out1, out2)
        np.testing.assert_array_equal(out1,
                                      np.arange(400, dtype=np.int64) * 3 + 1)
        # a fresh Recorder per run: nothing leaks from run 1 into run 2
        assert s2 is not s1
        assert s1.faa_total == s2.faa_total
        assert int(s1.items_per_thread.sum()) == 400
        assert int(s2.items_per_thread.sum()) == 400
        assert s1.claim_sizes == s2.claim_sizes
    finally:
        pool.shutdown()


def test_pool_reuse_across_schedulers_and_errors():
    """One pool serves every policy; a raising task leaves it reusable."""
    pool = runtime.WorkerPool()
    try:
        scoped = pool.scoped(3)
        for schedule in ("faa", "static", "guided", "hierarchical",
                         "stealing"):
            out, _ = _materialize(123, scoped, schedule=schedule)
            np.testing.assert_array_equal(
                out, np.arange(123, dtype=np.int64) * 3 + 1)

        class Boom(RuntimeError):
            pass

        def bad(i):
            if i == 7:
                raise Boom()

        with pytest.raises(Boom):
            pf.parallel_for_stats(bad, 50, pool=scoped, schedule="faa",
                                  block_size=5)
        out, _ = _materialize(50, scoped)   # pool survived the exception
        np.testing.assert_array_equal(
            out, np.arange(50, dtype=np.int64) * 3 + 1)
    finally:
        pool.shutdown()


def test_steady_state_creates_no_new_threads():
    """The acceptance criterion: once warm, parallel_for / data-pipeline /
    serve-admission calls create zero new threads — the per-call thread
    spawn is amortized away exactly as the paper amortizes the per-claim
    FAA."""
    data_cfg = DataConfig(vocab_size=64, seq_len=8, global_batch=32,
                          host_threads=4, prefetch=2)

    def exercise():
        pf.parallel_for(lambda i: None, 256, n_threads=4, schedule="faa",
                        block_size=8)
        SyntheticLM(data_cfg).batch(0)                     # data layer
        plan_admission(16, 4, "faa", block_size=2)         # serve admission
        it = PrefetchIterator(SyntheticLM(data_cfg), num_steps=2)
        drained = [next(it) for _ in range(2)]
        it.close()
        assert len(drained) == 2

    exercise()   # warm the pool to its high-water concurrency
    exercise()
    before = threading.active_count()
    for _ in range(3):
        exercise()
    assert threading.active_count() == before, (
        "steady-state calls spawned new threads despite the warm pool")


def test_cross_layer_telemetry_aggregates():
    """ScheduleStats no longer vanish with throwaway pools: the shared
    pool's telemetry accumulates per layer and resets cleanly."""
    runtime.telemetry().reset()
    pf.parallel_for(lambda i: None, 100, n_threads=2, block_size=10)
    SyntheticLM(DataConfig(vocab_size=16, seq_len=4, global_batch=20,
                           host_threads=2)).batch(0)
    plan_admission(12, 3, "faa", block_size=1)
    snap = runtime.telemetry().snapshot()
    assert {"parallel_for", "data", "admission"} <= set(snap)
    assert snap["parallel_for"]["runs"] >= 1
    assert snap["data"]["items"] == 20
    assert snap["admission"]["items"] == 12
    totals = runtime.telemetry().totals()
    assert totals["items"] >= 132
    runtime.telemetry().reset()
    assert runtime.telemetry().snapshot() == {}


def test_scoped_pool_records_claiming_tid():
    pool = runtime.WorkerPool()
    try:
        scoped = pool.scoped(4)
        seen = {}
        lock = threading.Lock()

        def task(i):
            with lock:
                seen[i] = scoped.current_tid()

        pf.parallel_for_stats(task, 40, pool=scoped, schedule="faa",
                              block_size=1)
        assert sorted(seen) == list(range(40))
        assert set(seen.values()) <= set(range(4))
    finally:
        pool.shutdown()


# ---------------------------------------------------------------------------
# Online calibration
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sim_ctx():
    """Fast simulate-only calibration — the 1-core CI fallback path."""
    return runtime.calibrate(simulate_only=True, fast=True, persist=False,
                             install=False)


def test_calibration_fits_from_points_not_published_weights(sim_ctx):
    assert sim_ctx.source == "simulated"
    assert sim_ctx.n_points >= 12
    assert np.isfinite(sim_ctx.fit_loss)
    for key in ("alpha", "beta", "delta0", "delta1"):
        assert not np.allclose(np.asarray(sim_ctx.params[key]),
                               np.asarray(cm.PAPER_WEIGHTS[key])), key


def test_calibrated_block_below_nt_on_all_topologies(sim_ctx):
    """The paper's empirical law, reproduced by the refit: B* < N/T on
    every simulated platform, at small and full thread counts."""
    n = 1024
    for topo in TOPOLOGIES:
        for t in (4, topo.total_cores):
            feats = cm.WorkloadFeatures(
                core_groups=topo.groups_used(t), threads=t,
                unit_read=1024, unit_write=1024, unit_comp=1024)
            b = sim_ctx.suggest_block(feats, n=n)
            assert 1 <= b < n / t, (topo.name, t, b)


def test_calibrated_ranking_consistent_with_sim(sim_ctx):
    """The fitted model and the event model agree on block-size ordering
    (rank correlation) and the fitted block lands near the simulated
    optimum on all three paper platforms."""
    for topo in TOPOLOGIES:
        row = runtime.ranking_consistency(sim_ctx, topo, topo.total_cores,
                                          UnitTask())
        assert row["spearman_sim_vs_analytic"] >= 0.3, row
        assert row["model_within_nt"], row
        assert (row["sim_at_model_block"]
                <= 3.0 * row["sim_at_best_block"]), row


def test_hierarchical_shared_faa_cut_at_calibrated_block(sim_ctx):
    """At the calibrated B, hierarchical claiming still cuts the shared
    counter traffic by the fanout factor — the cut survives recalibration
    because it is structural, not a weight artifact."""
    n, t, fanout = 2048, 8, 8
    feats = cm.WorkloadFeatures(core_groups=2, threads=t, unit_read=1024,
                                unit_write=1024, unit_comp=1024)
    b = sim_ctx.suggest_block(feats, n=n)
    flat = pf.parallel_for_stats(lambda i: None, n, n_threads=t,
                                 schedule="faa", block_size=b)
    hier = pf.parallel_for_stats(lambda i: None, n, n_threads=t,
                                 schedule="hierarchical", block_size=b)
    assert flat.faa_shared == -(-n // b) + t
    assert hier.faa_shared <= -(-n // (b * fanout)) + t
    assert hier.faa_shared < flat.faa_shared


def test_tuning_context_roundtrip_and_default(tmp_path, monkeypatch,
                                              sim_ctx):
    """Persistence: save -> load reproduces the context; with no file the
    process falls back to the published-weights default."""
    path = tmp_path / "calibration.json"
    monkeypatch.setenv("REPRO_CALIBRATION", str(path))
    runtime.reset_tuning()
    try:
        assert runtime.tuning().source == "default"   # no file yet
        runtime.save_calibration(sim_ctx, path)
        runtime.reset_tuning()
        loaded = runtime.tuning()
        assert loaded.source == sim_ctx.source
        for k, v in sim_ctx.params.items():
            np.testing.assert_allclose(np.asarray(loaded.params[k]),
                                       np.asarray(v), rtol=1e-6)
        feats = cm.WorkloadFeatures(core_groups=1, threads=4,
                                    unit_read=1024, unit_write=1024,
                                    unit_comp=1024)
        assert loaded.suggest_block(feats, n=512) == \
            sim_ctx.suggest_block(feats, n=512)
    finally:
        monkeypatch.setenv("REPRO_CALIBRATION", "off")
        runtime.reset_tuning()


def test_tuning_context_feeds_every_knob(sim_ctx):
    """The knobs the tentpole rewires all answer from one context."""
    assert sim_ctx.admission_block(0, 4) == 1
    assert sim_ctx.admission_block(7, 2) <= 2      # small queue stays dynamic
    deep = sim_ctx.admission_block(4096, 8)
    assert 1 <= deep <= 4096 // (2 * 8)
    assert sim_ctx.data_grain(4096, host_threads=8) >= 1
    assert 1 <= sim_ctx.microbatches(256, grad_bytes=2 * 3e9,
                                     step_flops=1e18) <= 32
    assert sim_ctx.choose_block(4096, 8) >= 1


def test_host_measurement_falls_back_on_small_hosts():
    """measure_host never fails: on a 1-core container the transfer ratio
    falls back to the reference platform and is flagged as such."""
    meas = runtime.measure_host()
    assert meas.faa_ns > 0
    assert meas.transfer_ns >= meas.faa_ns
    assert meas.dispatch_ns > 0
    assert meas.cores >= 1
    ctx_clocks = meas.transfer_clocks()
    assert np.isfinite(ctx_clocks) and ctx_clocks > 0


# ---------------------------------------------------------------------------
# device_parallel_for padding branches
# ---------------------------------------------------------------------------

def test_device_parallel_for_padding_branches():
    """Both padding branches (pad > 0 tail fill, and pad_blocks > 0
    block-grid fill) with a non-divisible n — needs >1 device, so run in a
    subprocess with forced host devices."""
    code = "\n".join([
        "import numpy as np, jax, jax.numpy as jnp",
        "from repro.core import parallel_for as pf",
        "from repro.launch.mesh import make_mesh",
        "mesh = make_mesh((4,), ('data',))",
        "items = jnp.arange(37.0)",
        "# b=5 -> blocks=8 (divisible by 4 workers): pad=3>0, pad_blocks=0",
        "out = pf.device_parallel_for(lambda x: x * 2 + 1, items,",
        "                             mesh=mesh, axis='data', block_size=5)",
        "np.testing.assert_allclose(np.asarray(out), np.arange(37.) * 2 + 1)",
        "# b=6 -> blocks=7: pad=5>0 AND pad_blocks=(-7)%4=1>0",
        "out = pf.device_parallel_for(lambda x: x * 3 - 2, items,",
        "                             mesh=mesh, axis='data', block_size=6)",
        "np.testing.assert_allclose(np.asarray(out), np.arange(37.) * 3 - 2)",
        "print('PAD-BRANCHES-OK')",
    ])
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + env.get("XLA_FLAGS", "")).strip()
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr
    assert "PAD-BRANCHES-OK" in r.stdout
