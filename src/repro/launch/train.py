"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --reduced \
        --steps 200 --batch 8 --seq 128

--reduced runs the CPU-scale config (the full configs are for the dry-run /
real pods).  The launcher trains on the default device with no mesh; the
sharded step (``repro.distributed`` policy over ``launch.mesh``) is driven
by the dry-run and the distributed tests, not by this entry point.
"""

from __future__ import annotations

import argparse

import jax

from repro.configs import get_config
from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.train.optimizer import AdamWConfig
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=None,
                    help="grad-accumulation count; default: the calibrated "
                         "TuningContext picks it (autotune.microbatch_count)")
    ap.add_argument("--grad-compression", default=None)
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--host-threads", type=int, default=4)
    ap.add_argument("--calibrate", action="store_true",
                    help="run the fast online FAA-cost calibration first "
                         "(persists results/calibration.json)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.calibrate:
        from repro.core import runtime
        ctx = runtime.calibrate(fast=True)
        print(f"[calibrate] {ctx.source}: {ctx.n_points} points, "
              f"fit loss {ctx.fit_loss:.1f}")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = Model(cfg)
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.batch,
                          host_threads=args.host_threads)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                          total_steps=args.steps)
    tr = Trainer(model, opt_cfg, data_cfg,
                 TrainerConfig(total_steps=args.steps,
                               ckpt_every=args.ckpt_every,
                               ckpt_dir=args.ckpt_dir,
                               microbatches=args.microbatches,
                               grad_compression=args.grad_compression))
    out = tr.run()
    print(f"done at step {out['final_step']}; "
          f"final loss {out['history'][-1][1] if out['history'] else 'n/a'}")


if __name__ == "__main__":
    main()
