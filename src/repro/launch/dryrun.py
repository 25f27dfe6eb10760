import os
# 512 virtual devices of the CPU backend, asked for by name: on a machine
# with a TPU the default backend would otherwise be the chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
mesh, extract memory/cost analyses and the collective schedule, and persist
one JSON record per cell.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-2b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
Records land in results/dryrun/<arch>__<shape>__<mesh>.json and are skipped
if already present (resumable).
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import REGISTRY, SHAPES, applicable_shapes, get_config
from repro.configs.inputs import input_specs
from repro.distributed import params as psh
from repro.distributed.sharding import ShardingPolicy, policy
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import Roofline, model_flops_for, parse_hlo
from repro.models import Model
from repro.train import optimizer as opt_mod
from repro.train.train_step import (make_decode_step, make_prefill_step,
                                    make_train_step)

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _local_bytes(tree, shardings) -> float:
    """Static per-device bytes of a sharded pytree (params/opt/cache)."""
    total = 0.0
    for leaf, sh in zip(jax.tree.leaves(tree),
                        jax.tree.leaves(shardings, is_leaf=lambda x: isinstance(
                            x, jax.sharding.Sharding))):
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        denom = 1
        spec = sh.spec
        for dim, axes in enumerate(spec):
            if axes is None:
                continue
            names = axes if isinstance(axes, tuple) else (axes,)
            for name in names:
                denom *= sh.mesh.shape[name]
        total += n * jnp.dtype(leaf.dtype).itemsize / denom
    return total


def build_cell(arch: str, shape_name: str, multi_pod: bool,
               microbatches: int = 1, grad_compression=None,
               overrides=None, seq_parallel: bool = False,
               layout: str = "tp", cache_layout: str = None):
    """Returns (jitted_fn, example_args, static_bytes, meta).

    overrides: dataclasses.replace kwargs on the ModelConfig (hillclimb
    knobs: moe_dispatch_groups, remat_policy, capacity_factor, ...).
    seq_parallel: sequence-parallel activation sharding policy.
    layout: "tp" (FSDP+TP) | "fsdp" (pure ZeRO-3, no TP)."""
    import dataclasses as _dc
    cfg = get_config(arch).with_dtype("bfloat16")
    if overrides:
        cfg = _dc.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = Model(cfg)
    cache_layout = cache_layout or layout
    pol = ShardingPolicy(mesh, multi_pod=multi_pod,
                         seq_parallel=seq_parallel,
                         fsdp_pure=(layout == "fsdp"),
                         decode_seq_shard=(cache_layout == "seq"))

    key = jax.random.PRNGKey(0)
    abstract_params = jax.eval_shape(model.init, key)
    p_sh = psh.param_shardings(abstract_params, mesh, layout=layout)
    batch_abs = input_specs(cfg, shape)
    b_sh = psh.batch_shardings(batch_abs, mesh, layout=layout)

    if shape.kind == "train":
        opt_cfg = opt_mod.AdamWConfig()
        abstract_opt = jax.eval_shape(
            lambda p: opt_mod.init_state(p, opt_cfg), abstract_params)
        o_sh = psh.tree_shardings(abstract_opt, mesh,
                                  psh.RULESETS[layout])
        step = make_train_step(model, opt_cfg, microbatches=microbatches,
                               grad_compression=grad_compression,
                               grad_shardings=p_sh)
        jitted = jax.jit(step,
                         in_shardings=(p_sh, o_sh, b_sh),
                         out_shardings=(p_sh, o_sh, None))
        args = (abstract_params, abstract_opt, batch_abs)
        static = _local_bytes(abstract_params, p_sh) + _local_bytes(
            abstract_opt, o_sh)
    elif shape.kind == "prefill":
        step = make_prefill_step(model, max_len=shape.seq_len)
        abstract_cache = jax.eval_shape(
            lambda: model.init_cache(
                shape.global_batch, shape.seq_len, jnp.bfloat16,
                enc_len=(shape.seq_len // cfg.encoder_downsample
                         if cfg.family == "encdec" else None)))
        c_sh = psh.cache_shardings(abstract_cache, mesh,
                                   layout=cache_layout)
        jitted = jax.jit(step, in_shardings=(p_sh, b_sh),
                         out_shardings=(None, c_sh))
        args = (abstract_params, batch_abs)
        static = _local_bytes(abstract_params, p_sh) + _local_bytes(
            abstract_cache, c_sh)
    else:  # decode
        step = make_decode_step(model)
        abstract_cache = jax.eval_shape(
            lambda: model.init_cache(
                shape.global_batch, shape.seq_len, jnp.bfloat16,
                enc_len=(shape.seq_len // cfg.encoder_downsample
                         if cfg.family == "encdec" else None)))
        c_sh = psh.cache_shardings(abstract_cache, mesh,
                                   layout=cache_layout)
        jitted = jax.jit(step, in_shardings=(p_sh, b_sh["tokens"], c_sh),
                         out_shardings=(None, c_sh))
        args = (abstract_params, batch_abs["tokens"], abstract_cache)
        static = _local_bytes(abstract_params, p_sh) + _local_bytes(
            abstract_cache, c_sh)

    meta = {"arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "kind": shape.kind, "chips": int(np.prod(list(mesh.shape.values()))),
            "static_bytes_per_device": static}
    return jitted, args, mesh, pol, cfg, shape, meta


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             *, microbatches: int = 1, grad_compression=None,
             overrides=None, seq_parallel: bool = False, layout: str = "tp",
             cache_layout: str = None,
             tag: str = "", verbose: bool = True) -> dict:
    t0 = time.time()
    jitted, args, mesh, pol, cfg, shape, meta = build_cell(
        arch, shape_name, multi_pod, microbatches, grad_compression,
        overrides=overrides, seq_parallel=seq_parallel, layout=layout,
        cache_layout=cache_layout)
    with policy(pol):
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    try:
        mem = compiled.memory_analysis()
        mem_d = {k: getattr(mem, k) for k in (
            "temp_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)}
    except Exception as e:  # CPU backend may not implement it
        mem_d = {"error": str(e)[:200]}
    try:
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        xla_flops = float(cost.get("flops", 0.0))
        xla_bytes = float(cost.get("bytes accessed", 0.0))
    except Exception as e:
        xla_flops, xla_bytes = 0.0, 0.0

    hlo = compiled.as_text()
    stats = parse_hlo(hlo)
    chips = meta["chips"]
    rl = Roofline(
        flops=stats.flops, hbm_bytes=stats.ideal_bytes,
        collective_bytes=stats.ideal_collective_bytes, chips=chips,
        model_flops=model_flops_for(cfg, shape),
        hbm_bytes_pessimistic=stats.hbm_bytes)

    record = {
        **meta,
        "ok": True,
        "tag": tag,
        "t_lower_s": t_lower,
        "t_compile_s": t_compile,
        "memory_analysis": mem_d,
        "xla_cost_analysis": {"flops": xla_flops,
                              "bytes_accessed": xla_bytes},
        "roofline": rl.to_dict(),
        "collectives": {
            "bytes_by_kind": stats.coll_bytes_by_kind,
            "count_by_kind": stats.coll_count_by_kind,
            "raw_total": stats.collective_bytes,
            "top": stats.top_collectives,
        },
        "top_dots": stats.top_dots,
        "hlo_bytes": len(hlo),
    }
    if verbose:
        print(f"[{arch} x {shape_name} x {meta['mesh']}]"
              f" lower={t_lower:.1f}s compile={t_compile:.1f}s"
              f" flops/dev={stats.flops:.3e} bytes/dev={stats.hbm_bytes:.3e}"
              f" coll/dev={stats.collective_bytes:.3e}"
              f" bottleneck={rl.bottleneck}"
              f" frac={rl.roofline_fraction:.3f}")
    return record


def cell_path(arch, shape_name, mesh_name, tag="") -> Path:
    sfx = f"__{tag}" if tag else ""
    return RESULTS / f"{arch}__{shape_name}__{mesh_name}{sfx}.json"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", default=None)
    args = ap.parse_args()

    RESULTS.mkdir(parents=True, exist_ok=True)
    cells = []
    archs = list(REGISTRY) if (args.all or not args.arch) else [args.arch]
    for arch in archs:
        cfg = get_config(arch)
        shapes = ([args.shape] if args.shape else applicable_shapes(cfg))
        for sh in shapes:
            meshes = {"single": [False], "multi": [True],
                      "both": [False, True]}[args.mesh]
            for mp in meshes:
                cells.append((arch, sh, mp))

    done, failed = 0, 0
    for arch, sh, mp in cells:
        mesh_name = "2x16x16" if mp else "16x16"
        out = cell_path(arch, sh, mesh_name, args.tag)
        if out.exists() and not args.force:
            print(f"skip (cached): {out.name}")
            continue
        try:
            rec = run_cell(arch, sh, mp, microbatches=args.microbatches,
                           grad_compression=args.grad_compression,
                           tag=args.tag)
            done += 1
        except Exception as e:
            traceback.print_exc()
            rec = {"arch": arch, "shape": sh, "mesh": mesh_name, "ok": False,
                   "tag": args.tag, "error": f"{type(e).__name__}: {e}"[:500]}
            failed += 1
        out.write_text(json.dumps(rec, indent=1, default=float))
    print(f"dry-run complete: {done} ok, {failed} failed")


if __name__ == "__main__":
    main()
