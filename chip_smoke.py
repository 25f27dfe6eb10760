"""Chip smoke: the serving path at published widths on one TPU, or the
on-device ParallelFor across four.

    python chip_smoke.py             # one chip: qwen2.5-3b served in bf16
    python chip_smoke.py --chips 4   # four chips: device_parallel_for only

One chip: ``qwen2.5-3b`` at its published widths (random weights from
seed 0, bfloat16 parameters and KV cache) serves 8 requests of 128-1024
prompt tokens and 32 greedy new tokens each through ``Engine.serve``
(continuous mode, 4 slots, ``max_len`` 2048), once with the contiguous
cache and once with the paged one.  Each phase must fail no request, and
every output must hold its 32 tokens inside the vocabulary.  The first
request served among the eight must match it served alone through the
same slots; served alone through one slot it must match
``Engine.generate`` on that prompt in a batch of one (the same programs:
bf16 programs of different batch shapes may round a near-tie argmax
apart).  The paged outputs must match the contiguous ones, and the
prefill logits must be finite.

Four chips: ``device_parallel_for`` over a 4-device ``data`` mesh under
every registered schedule, at n = 37, 41 and 2**20 bf16 rows of 256,
compared with ``jax.vmap`` of the same function on one chip; each row
must run on the worker the schedule's block-cyclic layout assigns it.

Every time printed is smoke timing from one cold run, never a benchmark
number.  The last line of stdout is one JSON object naming the device;
it is printed only when every check passed on a TPU.  Runs only from a
checkout (it imports ``src/repro``) and only where JAX finds a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

# committed state only: no calibration or tuning database left in the
# working tree may steer the run
os.environ["REPRO_CALIBRATION"] = "off"
os.environ["REPRO_TUNING"] = "off"

ARCH = "qwen2.5-3b"
WIDTHS = dict(d_model=2048, n_layers=36, n_heads=16, n_kv_heads=2,
              d_ff=11008, vocab_size=151936)
REQUESTS, PROMPT_LEN, NEW_TOKENS, SLOTS = 8, 1024, 32, 4
PF_SIZES = (37, 41, 1 << 20)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events; ``lap()`` returns the time since the last lap."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, monitoring):
        self.total = 0.0
        self.compiles = 0
        self._mark = (0.0, 0)
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.total += duration
            self.compiles += event == self.EVENTS[-1]

    def lap(self):
        secs = self.total - self._mark[0]
        n = self.compiles - self._mark[1]
        self._mark = (self.total, self.compiles)
        return secs, n


def log(msg: str) -> None:
    print(msg, flush=True)


def expect_same(what: str, got, want) -> None:
    same = bool(np.array_equal(got, want))
    log(f"{what}: {'match' if same else 'MISMATCH'}")
    if not same:
        raise AssertionError(f"{what}: {got} != {want}")


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def serve_phases(jax, clock) -> None:
    import jax.numpy as jnp

    from repro.launch import serve as launch
    from repro.serve.engine import Engine, ServeConfig

    dev = jax.devices()[0]
    t0 = time.perf_counter()
    model, params = launch.init_model(ARCH)
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    cfg = model.cfg
    got = {k: getattr(cfg, k) for k in WIDTHS}
    if got != WIDTHS:
        raise AssertionError(f"{ARCH} widths {got} != published {WIDTHS}")
    leaves = jax.tree.leaves(params)
    dtypes = sorted({str(a.dtype) for a in leaves})
    if dtypes != [launch.DTYPE]:
        raise AssertionError(f"parameter dtypes {dtypes}, want "
                             f"[{launch.DTYPE}]")
    nbytes = sum(a.nbytes for a in leaves)
    c_s, c_n = clock.lap()
    log(f"config {ARCH}: " + " ".join(f"{k}={v}" for k, v in got.items())
        + f" dtype={cfg.param_dtype} param_bytes={nbytes}")
    log(f"[smoke timing] init: {init_s:.3f}s incl. compile {c_s:.3f}s "
        f"({c_n} programs); peak_bytes_in_use={peak_bytes(dev)}")

    prompts = launch.make_prompts(cfg.vocab_size, REQUESTS, PROMPT_LEN)
    max_len = launch.serve_max_len(PROMPT_LEN, NEW_TOKENS)
    log(f"workload: {REQUESTS} requests, prompt lengths "
        f"{sorted(len(p) for p in prompts)}, {NEW_TOKENS} new tokens, "
        f"greedy, slots={SLOTS}, max_len={max_len}")

    served = {}
    for cache in ("contiguous", "paged"):
        eng = Engine(model, params, ServeConfig(
            max_len=max_len, slots=SLOTS, cache_dtype=launch.DTYPE,
            refill_schedule="faa", cache=cache, isolate_failures=False))
        t0 = time.perf_counter()
        outs = eng.serve(prompts, NEW_TOKENS)
        wall = time.perf_counter() - t0
        c_s, c_n = clock.lap()
        rep = eng.last_report
        page_size = eng._backend.ps if cache == "paged" else "n/a"
        log(f"phase {cache}: failed {rep.failed_requests} shed "
            f"{rep.shed_requests} tokens {rep.total_tokens} "
            f"admission_block={rep.admission.block_size} "
            f"page_size={page_size}")
        log(f"[smoke timing] {cache}: serve {wall:.3f}s incl. compile "
            f"{c_s:.3f}s ({c_n} programs); "
            f"peak_bytes_in_use={peak_bytes(dev)}")
        if rep.failed_requests or rep.shed_requests:
            raise AssertionError(f"{cache}: {rep.failed_requests} failed, "
                                 f"{rep.shed_requests} shed")
        if rep.total_tokens != REQUESTS * NEW_TOKENS:
            raise AssertionError(f"{cache}: {rep.total_tokens} tokens, "
                                 f"want {REQUESTS * NEW_TOKENS}")
        for i, o in enumerate(outs):
            if o.shape != (NEW_TOKENS,) or not (
                    (o >= 0) & (o < cfg.vocab_size)).all():
                raise AssertionError(f"{cache}: request {i} output {o}")
        served[cache] = np.stack(outs)

        if cache == "contiguous":
            p0 = prompts[0]
            # batch composition: request 0 served alone through the same
            # slots gives the tokens it got beside seven others
            expect_same("request 0 served among 8 vs alone",
                        eng.serve([p0], NEW_TOKENS)[0], outs[0])
            # serve vs generate on request 0 alone, both one batch row:
            # the same prefill and decode programs.  A program of another
            # batch shape rounds bf16 differently and may flip a near-tie
            # argmax, so generate's batch is the serving engine's slots
            one = Engine(model, params, ServeConfig(
                max_len=max_len, slots=1, cache_dtype=launch.DTYPE,
                refill_schedule="faa", isolate_failures=False))
            toks = np.zeros((1, eng._bucket_width(len(p0))), np.int32)
            toks[0, : len(p0)] = p0
            lens = np.array([len(p0)], np.int32)
            expect_same("serve vs generate (request 0, one slot)",
                        one.serve([p0], NEW_TOKENS)[0],
                        one.generate({"tokens": toks}, NEW_TOKENS,
                                     lengths=lens)[0])
            logits, _ = eng._prefill_padded(params, jnp.asarray(toks),
                                            jnp.asarray(lens))
            logits = np.asarray(logits)
            finite = bool(np.isfinite(logits).all())
            log(f"prefill logits {logits.shape} finite={finite} "
                f"argmax={int(logits[0].argmax())} first served token="
                f"{int(outs[0][0])}")
            if not finite or logits.shape != (1, cfg.vocab_size):
                raise AssertionError("prefill logits not finite")
            if int(logits[0].argmax()) != int(outs[0][0]):
                raise AssertionError("prefill argmax != first served token")
            c_s, c_n = clock.lap()
            log(f"[smoke timing] serve-alone, generate and logits checks: "
                f"compile {c_s:.3f}s ({c_n} programs)")
            del one
        del eng
    expect_same("paged vs contiguous outputs", served["paged"],
                served["contiguous"])


def parallel_for_phase(jax, clock) -> None:
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import parallel_for as pf
    from repro.core import schedulers
    from repro.launch.mesh import make_mesh

    devs = jax.devices()
    if len(devs) != 4:
        raise AssertionError(f"--chips 4 needs 4 devices, found {len(devs)}")
    mesh = make_mesh((4,), ("data",))
    one = devs[0]

    def fn(row):
        x = row.astype(jnp.float32)
        return (jnp.sin(x) * 2.0 + jnp.sum(x) / x.shape[0]).astype(row.dtype)

    def owner(row):
        return jnp.full((), jax.lax.axis_index("data"), jnp.int32)

    ref_fn = jax.jit(jax.vmap(fn))
    rng = np.random.RandomState(0)
    cases = {}
    for n in PF_SIZES:
        if n < 1024:
            host = rng.standard_normal((n, 16)).astype(np.float32)
            cases[n] = (jnp.asarray(host), dict(rtol=1e-6, atol=1e-6))
        else:
            # the large input arrives already sharded over the mesh: each
            # quarter of the rows on its own chip
            x = jax.random.normal(jax.random.PRNGKey(n), (n, 256),
                                  jnp.bfloat16)
            x = jax.device_put(x, NamedSharding(mesh, P("data", None)))
            shard_devs = [s.device for s in x.addressable_shards]
            if (len(set(shard_devs)) != 4
                    or {s.data.shape for s in x.addressable_shards}
                    != {(n // 4, 256)}):
                raise AssertionError(f"input shards {shard_devs}")
            cases[n] = (x, dict(rtol=1e-2, atol=1e-2))   # bf16: 8 mantissa bits
    log(f"mesh {dict(mesh.shape)} over {[d.id for d in devs]}; inputs "
        + ", ".join(f"{n}x{x.shape[1]} {x.dtype}" for n, (x, _) in
                    cases.items()))
    refs = {n: np.asarray(ref_fn(jax.device_put(x, one)), np.float32)
            for n, (x, _) in cases.items()}
    for schedule in schedulers.available_schedulers():
        t0 = time.perf_counter()
        for n, (x, tol) in cases.items():
            out = pf.device_parallel_for(fn, x, mesh=mesh, schedule=schedule)
            np.testing.assert_allclose(np.asarray(out, np.float32), refs[n],
                                       **tol, err_msg=f"{schedule} n={n}")
            b = pf._device_block_size(schedule, n, 4, None, None)
            want = pf.block_cyclic_assignment(n, b, 4)
            got = np.asarray(pf.device_parallel_for(owner, x, mesh=mesh,
                                                    schedule=schedule))
            if not (got == want).all():
                raise AssertionError(f"{schedule} n={n}: rows ran on "
                                     f"workers other than the layout's")
        c_s, c_n = clock.lap()
        log(f"[smoke timing] {schedule}: {time.perf_counter() - t0:.3f}s "
            f"incl. compile {c_s:.3f}s ({c_n} programs); matches vmap on "
            f"one chip for n in {list(cases)}")
    log(f"peak_bytes_in_use per device="
        f"{[peak_bytes(d) for d in devs]}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: serve qwen2.5-3b; 4: device_parallel_for "
                         "across a 4-chip mesh, and nothing else")
    args = ap.parse_args()

    import jax
    import jax.monitoring

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    log(f"jax {jax.__version__}; device {dev.device_kind} x "
        f"{len(jax.devices())}; compile cache {cache_dir}")
    clock = CompileClock(jax.monitoring)
    if args.chips == 4:
        parallel_for_phase(jax, clock)
    else:
        serve_phases(jax, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
