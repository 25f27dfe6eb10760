"""End-to-end behaviour: trainer loop (loss decreases, ckpt/restart,
preemption), data pipeline determinism + straggler path, serve engine,
autotuner wiring, roofline parser."""

import shutil
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import autotune
from repro.data.pipeline import DataConfig, PrefetchIterator, SyntheticLM
from repro.models import Model
from repro.serve.engine import Engine, ServeConfig
from repro.train.optimizer import AdamWConfig
from repro.train.trainer import Trainer, TrainerConfig


@pytest.fixture(scope="module")
def tiny_setup(tmp_path_factory):
    cfg = get_config("qwen2.5-3b").reduced()
    model = Model(cfg)
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                          global_batch=4, host_threads=2)
    return cfg, model, data_cfg, tmp_path_factory.mktemp("ckpt")


def test_trainer_loss_decreases_and_resumes(tiny_setup):
    cfg, model, data_cfg, ckpt_dir = tiny_setup
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)
    tr = Trainer(model, opt, data_cfg,
                 TrainerConfig(total_steps=8, ckpt_every=4,
                               ckpt_dir=str(ckpt_dir), log_every=4),
                 log_fn=lambda s: None)
    out = tr.run()
    assert out["final_step"] == 8
    first_loss = out["history"][0][1]
    last_loss = out["history"][-1][1]
    assert last_loss < first_loss

    # restart picks up at step 8 and continues to 12
    tr2 = Trainer(model, opt, data_cfg,
                  TrainerConfig(total_steps=12, ckpt_every=4,
                                ckpt_dir=str(ckpt_dir), log_every=4),
                  log_fn=lambda s: None)
    out2 = tr2.run()
    assert out2["final_step"] == 12
    assert out2["history"][-1][1] <= last_loss + 0.2


def test_trainer_skips_sync_save_when_final_step_committed(
        tiny_setup, tmp_path, monkeypatch):
    """The final-save race fix: when the async saver already committed a
    checkpoint for final_step (total_steps a multiple of ckpt_every), the
    closing synchronous save must not rewrite it."""
    cfg, model, data_cfg, _ = tiny_setup
    from repro.checkpoint import checkpoint as ckpt_mod
    saved_steps = []
    real_save = ckpt_mod.save

    def counting_save(tree, directory, step):
        saved_steps.append(step)
        return real_save(tree, directory, step)

    monkeypatch.setattr(ckpt_mod, "save", counting_save)
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)
    tr = Trainer(model, opt, data_cfg,
                 TrainerConfig(total_steps=4, ckpt_every=2,
                               ckpt_dir=str(tmp_path), log_every=2,
                               keep_ckpts=2),
                 log_fn=lambda s: None)
    out = tr.run()
    assert out["final_step"] == 4
    # async saves at 2 and 4 only — no trailing sync re-save of step 4
    assert saved_steps == [2, 4]
    assert ckpt_mod.latest_step(tmp_path) == 4


def test_preemption_saves_state(tiny_setup, tmp_path):
    cfg, model, data_cfg, _ = tiny_setup
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)
    tr = Trainer(model, opt, data_cfg,
                 TrainerConfig(total_steps=50, ckpt_every=100,
                               ckpt_dir=str(tmp_path), log_every=100),
                 log_fn=lambda s: None)
    tr._preempted = True  # simulate SIGTERM before the loop
    out = tr.run()
    assert out["preempted"]
    # nothing trained: the label must not claim an untrained batch — a
    # restart resumes AT step 0 and replays the identical sequence
    assert out["final_step"] == 0
    from repro.checkpoint import checkpoint as ckpt
    assert ckpt.latest_step(tmp_path) == 0


def test_trainer_in_order_view_reorders_straggler_retries():
    """Straggler retries reach the trainer out of order; the optimizer
    walk (and the 'checkpoint at N == batches < N applied' contract)
    needs the in-order view."""
    stream = [(0, "b0"), (2, "b2"), (1, "b1"), (3, "b3")]
    assert list(Trainer._in_order(iter(stream), 0)) == [
        (0, "b0"), (1, "b1"), (2, "b2"), (3, "b3")]
    # a resumed stream starts mid-sequence
    assert list(Trainer._in_order(iter([(6, "x"), (5, "y")]), 5)) == [
        (5, "y"), (6, "x")]


def test_data_pipeline_deterministic():
    cfg = DataConfig(vocab_size=1000, seq_len=16, global_batch=8,
                     host_threads=3)
    ds = SyntheticLM(cfg)
    b1 = ds.batch(5)["tokens"]
    b2 = ds.batch(5)["tokens"]
    np.testing.assert_array_equal(b1, b2)
    assert b1.shape == (8, 16)
    assert b1.max() < 1000
    # different step -> different batch
    assert not np.array_equal(b1, ds.batch(6)["tokens"])


def test_prefetch_iterator_orders_steps():
    cfg = DataConfig(vocab_size=100, seq_len=8, global_batch=2,
                     host_threads=2, prefetch=2)
    it = PrefetchIterator(SyntheticLM(cfg), start_step=3)
    steps = [next(it)[0] for _ in range(4)]
    it.close()
    assert steps == [3, 4, 5, 6]


def test_prefetch_iterator_bounded_stream_stops():
    """num_steps bounds the producer: the stream ends with StopIteration
    instead of producing past the consumer's last step forever."""
    cfg = DataConfig(vocab_size=100, seq_len=8, global_batch=2,
                     host_threads=2, prefetch=2)
    it = PrefetchIterator(SyntheticLM(cfg), start_step=3, num_steps=4)
    steps = [s for s, _ in it]
    assert steps == [3, 4, 5, 6]
    with pytest.raises(StopIteration):
        next(it)
    it.close()


def test_prefetch_iterator_retries_skipped_stragglers():
    """A straggler batch is skipped (the next index is served first) but
    then actually retried and delivered — the re-queue the docstring
    promises — and a bounded stream still delivers every step."""

    class OneSlowStep(SyntheticLM):
        def batch(self, step):
            out = super().batch(step)
            if step == 1 and 1 not in getattr(self, "_slowed", set()):
                self._slowed = {1}
                time.sleep(1.0)
            return out

    # the slow step takes five times the timeout; the timeout itself sits
    # far above an ordinary two-example batch even on a loaded host
    cfg = DataConfig(vocab_size=100, seq_len=8, global_batch=2,
                     host_threads=2, prefetch=4,
                     straggler_timeout_s=0.2)
    # warm the source (tuning context, worker pool) before the straggler
    # clock runs: a cold first batch must not read as a straggler
    SyntheticLM(cfg).batch(0)
    it = PrefetchIterator(OneSlowStep(cfg), start_step=0, num_steps=4)
    got = [s for s, _ in it]
    it.close()
    assert it.stragglers == [1]          # skipped once...
    assert sorted(got) == [0, 1, 2, 3]   # ...but delivered exactly once
    assert got.index(1) > got.index(2)   # after the index that replaced it


def test_serve_engine_greedy_deterministic(tiny_setup):
    cfg, model, data_cfg, _ = tiny_setup
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, ServeConfig(max_len=48))
    from repro.configs.inputs import make_dummy_batch
    batch = make_dummy_batch(cfg, 2, 8)
    a = eng.generate(batch, 6)
    b = eng.generate(batch, 6)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (2, 6)


def test_serve_engine_slot_refill(tiny_setup):
    """serve() rounds fallback: more requests than slots, refilled between
    rounds; the refill packing runs under a registered scheduler and
    reports stats.  (The continuous default is covered in
    tests/test_serve_continuous.py.)"""
    cfg, model, data_cfg, _ = tiny_setup
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, ServeConfig(max_len=48, slots=2,
                                            refill_schedule="faa",
                                            mode="rounds"))
    rng = np.random.RandomState(0)
    # ragged lengths: pad-masked prefill batches mixed widths, so cohorts
    # are simply consecutive requests.  [8,8,5,8,5] with 2 slots ->
    # rounds [8,8], [5,8], [5]
    lens = [8, 8, 5, 8, 5]
    prompts = [rng.randint(1, cfg.vocab_size, l).astype(np.int32)
               for l in lens]
    outs = eng.serve(prompts, 4)
    assert len(outs) == 5
    assert all(o.shape == (4,) for o in outs)
    assert len(eng.refill_stats) == 3
    assert sum(s.n for s in eng.refill_stats) == 5
    assert all(s.schedule == "faa" for s in eng.refill_stats)
    # every request — batched, refilled, or padded beside a longer cohort —
    # must match its solo generation exactly
    for i in (0, 2, 4):
        single = eng.serve([prompts[i]], 4)[0]
        np.testing.assert_array_equal(single, outs[i])
    # slots < 1 must fail fast, not spin forever
    bad = Engine(model, params, ServeConfig(max_len=48, slots=0))
    with pytest.raises(ValueError, match="slots"):
        bad.serve(prompts[:1], 2)


def test_data_pipeline_schedule_knob():
    """DataConfig.schedule selects the scheduler; stats become observable."""
    cfg = DataConfig(vocab_size=64, seq_len=8, global_batch=16,
                     host_threads=2, schedule="hierarchical")
    ds = SyntheticLM(cfg)
    b1 = ds.batch(0)["tokens"]
    stats = ds.last_schedule_stats
    assert stats is not None and stats.schedule == "hierarchical"
    assert int(stats.items_per_thread.sum()) == 16
    # same batch under a different policy is bit-identical (exactly-once,
    # index-deterministic examples)
    b2 = SyntheticLM(DataConfig(vocab_size=64, seq_len=8, global_batch=16,
                                host_threads=2,
                                schedule="stealing")).batch(0)["tokens"]
    np.testing.assert_array_equal(b1, b2)
    # schedule="cost_model" with no explicit grain must let the policy's
    # predictor choose (an explicit block would silently override it)
    ds3 = SyntheticLM(DataConfig(vocab_size=64, seq_len=8, global_batch=16,
                                 host_threads=2, schedule="cost_model"))
    b3 = ds3.batch(0)["tokens"]
    np.testing.assert_array_equal(b1, b3)
    assert ds3.last_schedule_stats.block_size is not None


def test_autotuner_outputs_sane():
    blocks = autotune.attention_block_sizes(4096, 4096, 128)
    assert blocks.block_q % 128 == 0
    assert blocks.block_k % 128 == 0
    assert blocks.vmem_bytes <= autotune.VMEM_BUDGET
    assert autotune.decode_split_k(32768) >= 1
    assert autotune.ssd_chunk_size(4096) in (64, 128, 256, 512)
    assert 1 <= autotune.microbatch_count(
        256, grad_bytes=2 * 3e9, step_flops=1e18) <= 32
    assert autotune.data_grain_size(1024) >= 1


def test_grad_compression_same_direction(tiny_setup):
    """bf16 grad compression must not change the update direction much."""
    cfg, model, data_cfg, _ = tiny_setup
    from repro.train.train_step import make_train_step
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    from repro.train.optimizer import init_state
    params = model.init(jax.random.PRNGKey(0))
    opt = init_state(params, opt_cfg)
    batch = {"tokens": jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (4, 32)),
        jnp.int32)}
    s1 = make_train_step(model, opt_cfg)
    s2 = make_train_step(model, opt_cfg, grad_compression="bf16")
    p1, _, m1 = s1(params, opt, batch)
    p2, _, m2 = s2(params, opt, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3
    d1 = jnp.concatenate([(a - b).flatten() for a, b in zip(
        jax.tree.leaves(p1), jax.tree.leaves(params))])
    d2 = jnp.concatenate([(a - b).flatten() for a, b in zip(
        jax.tree.leaves(p2), jax.tree.leaves(params))])
    cos = jnp.sum(d1 * d2) / (jnp.linalg.norm(d1) * jnp.linalg.norm(d2))
    assert float(cos) > 0.98


def test_microbatched_step_matches_single(tiny_setup):
    cfg, model, data_cfg, _ = tiny_setup
    from repro.train.train_step import make_train_step
    from repro.train.optimizer import init_state
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    params = model.init(jax.random.PRNGKey(0))
    opt = init_state(params, opt_cfg)
    batch = {"tokens": jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (4, 32)),
        jnp.int32)}
    p1, _, m1 = make_train_step(model, opt_cfg)(params, opt, batch)
    p2, _, m2 = make_train_step(model, opt_cfg, microbatches=2)(
        params, opt, batch)
    # losses agree; params close (fp32 accumulation reorders adds)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 2e-3
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-3)


def test_roofline_parser_counts_scanned_dots():
    """A k-layer scanned matmul must be counted k times."""
    from repro.launch.roofline import parse_hlo
    k, m = 5, 32

    def f(x, ws):
        def body(c, w):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, ws)
        return y.sum()

    hlo = jax.jit(jax.grad(f)).lower(
        jnp.ones((8, m)), jnp.ones((k, m, m))).compile().as_text()
    stats = parse_hlo(hlo)
    # fwd + bwd(2 dots per layer... grad wrt x and w) = 3 dots per layer
    expected = 3 * k * 2 * 8 * m * m
    assert stats.flops == pytest.approx(expected, rel=0.34), (
        stats.flops, expected)


def test_compile_cache_dir_follows_env_else_checkout(monkeypatch):
    """The entry points' compile cache: JAX_COMPILATION_CACHE_DIR when set
    (and then nothing is configured), else a fixed <checkout>/.jax_cache.
    Nothing is compiled, so the cache is never opened."""
    from repro.launch import compile_cache

    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/srv/shared-cache")
    assert compile_cache.enable_compile_cache() == "/srv/shared-cache"
    assert jax.config.jax_compilation_cache_dir == prev

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = str(Path(__file__).resolve().parents[1] / ".jax_cache")
    try:
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_serve_launcher_exits_nonzero_on_failed_requests(monkeypatch,
                                                         capsys):
    """launch/serve.py reports a failed request in its exit code, not
    only in the printed report."""
    from repro.core import faults
    from repro.launch import serve as launch

    monkeypatch.setattr(launch, "enable_compile_cache", lambda: None)
    argv = ["--arch", "qwen2.5-3b", "--reduced", "--requests", "3",
            "--prompt-len", "16", "--tokens", "4"]
    assert launch.main(argv) == 0
    plan = faults.FaultPlan(seed=1, specs=[faults.PoisonRequest(rids=(1,))])
    with faults.fault_scope(plan):
        assert launch.main(argv) == 1
    assert "failed                   1" in capsys.readouterr().out
