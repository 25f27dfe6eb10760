"""Serving launcher: load (or init) a model and run batched generation,
or drive the continuous-batching engine over a mixed-length workload.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduced \
        --batch 4 --prompt-len 16 --tokens 32
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduced \
        --requests 16 --tokens 24 --schedule hierarchical --slots 4
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b \
        --requests 8 --prompt-len 1024 --tokens 32 --cache paged

Parameters and the KV cache are bfloat16.  Weights are random (seed 0)
unless ``--ckpt-dir`` names a checkpoint.  With ``--requests``, the
process exits non-zero when any request ends failed or shed.
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import numpy as np

from repro.checkpoint import checkpoint as ckpt
from repro.configs import get_config
from repro.configs.inputs import make_dummy_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.serve.engine import Engine, ServeConfig

DTYPE = "bfloat16"


def init_model(arch: str, *, reduced: bool = False, seed: int = 0):
    """(model, params) in bfloat16.  ``Model.init`` runs under ``jax.jit``,
    so every weight is made on the device in its storage dtype."""
    cfg = get_config(arch).with_dtype(DTYPE)
    if reduced:
        cfg = cfg.reduced()
    model = Model(cfg)
    return model, jax.jit(model.init)(jax.random.PRNGKey(seed))


def make_prompts(vocab_size: int, n: int, prompt_len: int,
                 seed: int = 0) -> list:
    """``n`` prompts of ``prompt_len // 8`` to ``prompt_len`` random tokens."""
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab_size, int(l)).astype(np.int32)
            for l in rng.randint(max(2, prompt_len // 8), prompt_len + 1, n)]


def serve_max_len(prompt_len: int, tokens: int, page_size: int = 16) -> int:
    """Cache length for prompts up to ``prompt_len`` plus ``tokens`` new
    ones: the enclosing power of two, in whole pages."""
    need = prompt_len + tokens + 1
    max_len = 1 << (need - 1).bit_length()
    return -(-max_len // page_size) * page_size


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--ckpt-dir", default=None)
    # continuous-serving options (--requests > 0 switches to serve())
    ap.add_argument("--requests", type=int, default=0,
                    help="serve N mixed-length requests through the "
                         "continuous engine instead of one generate()")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--schedule", default="faa",
                    help="admission policy (any registered scheduler)")
    ap.add_argument("--mode", default="continuous",
                    choices=("continuous", "rounds"))
    ap.add_argument("--cache", default="contiguous",
                    choices=("contiguous", "paged"),
                    help="KV layout: per-slot max_len rows, or a page "
                         "pool with per-slot page tables + prefix reuse")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged cache only); 0 "
                         "resolves the tuned page size from the tuning db")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="page pool size; default matches the contiguous "
                         "byte budget (slots * max_len / page_size)")
    ap.add_argument("--kv-dtype", default=None,
                    help="quantized KV cache storage, e.g. int8 or "
                         "float8_e4m3fn (default: the compute dtype)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    model, params = init_model(args.arch, reduced=args.reduced)
    cfg = model.cfg
    if args.ckpt_dir:
        tree, step = ckpt.restore(args.ckpt_dir, like={"params": params})
        params = tree["params"]
        print(f"loaded checkpoint step {step}")

    if args.requests > 0:
        eng = Engine(model, params, ServeConfig(
            max_len=serve_max_len(args.prompt_len, args.tokens,
                                  args.page_size or 16),
            temperature=args.temperature, slots=args.slots,
            cache_dtype=DTYPE,
            refill_schedule=args.schedule, mode=args.mode,
            cache=args.cache, page_size=args.page_size or None,
            num_pages=args.num_pages, kv_dtype=args.kv_dtype))
        prompts = make_prompts(cfg.vocab_size, args.requests,
                               args.prompt_len)
        outs = eng.serve(prompts, args.tokens)
        rep = eng.last_report
        print(f"served {len(outs)} requests x <= {args.tokens} tokens "
              f"[{args.mode}/{args.schedule}] in {rep.wall_s:.2f}s")
        for k, v in rep.as_row().items():
            print(f"  {k:24s} {v}")
        return 1 if rep.failed_requests or rep.shed_requests else 0

    eng = Engine(model, params, ServeConfig(
        max_len=args.prompt_len + args.tokens + 1,
        temperature=args.temperature, cache_dtype=DTYPE))
    batch = make_dummy_batch(cfg, args.batch, args.prompt_len)
    t0 = time.time()
    out = eng.generate(batch, args.tokens)
    dt = time.time() - t0
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({out.size / dt:.1f} tok/s incl. compile)")
    print("sample:", out[0][:16])
    return 0


if __name__ == "__main__":
    sys.exit(main())
