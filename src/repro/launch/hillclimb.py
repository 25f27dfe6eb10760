import os
# 512 virtual devices of the CPU backend, asked for by name: on a machine
# with a TPU the default backend would otherwise be the chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Perf hillclimb driver: run tagged dry-run variants of one cell.

    PYTHONPATH=src python -m repro.launch.hillclimb \
        --arch qwen2.5-32b --shape prefill_32k --variant sp

Variants are named knob bundles (hypothesis -> change); records land next
to the baselines as <arch>__<shape>__16x16__<tag>.json for EXPERIMENTS.md
§Perf before/after comparison.
"""

import argparse
import json
import traceback

from repro.launch import dryrun

VARIANTS = {
    # sequence-parallel activations (Korthikanti-style SP on the model axis)
    "sp": dict(seq_parallel=True),
    # remat keeps matmul outputs (less recompute, more activation memory)
    "dots": dict(overrides={"remat_policy": "dots"}),
    "sp_dots": dict(seq_parallel=True, overrides={"remat_policy": "dots"}),
    # bf16 gradient all-reduce compression
    "gc": dict(grad_compression="bf16"),
    "sp_gc": dict(seq_parallel=True, grad_compression="bf16"),
    "sp_dots_gc": dict(seq_parallel=True, grad_compression="bf16",
                       overrides={"remat_policy": "dots"}),
    # hierarchical (core-group) MoE dispatch: per-shard claim counters
    "moegrp16": dict(overrides={"moe_dispatch_groups": 16}),
    "moegrp256": dict(overrides={"moe_dispatch_groups": 256}),
    "sp_moegrp16": dict(seq_parallel=True,
                        overrides={"moe_dispatch_groups": 16}),
    "sp_moegrp256": dict(seq_parallel=True,
                         overrides={"moe_dispatch_groups": 256}),
    "sp_moegrp256_dots": dict(
        seq_parallel=True,
        overrides={"moe_dispatch_groups": 256, "remat_policy": "dots"}),
    # gradient-accumulation microbatching (collective/compute overlap)
    "mb2": dict(microbatches=2),
    "mb4": dict(microbatches=4),
    "sp_mb4": dict(seq_parallel=True, microbatches=4),
    # pure-FSDP (ZeRO-3) layout: no TP, no per-layer activation all-reduces
    "fsdp": dict(layout="fsdp"),
    "fsdp_dots": dict(layout="fsdp", overrides={"remat_policy": "dots"}),
    "fsdp_gc": dict(layout="fsdp", grad_compression="bf16"),
    # shard_map MoE: all_to_all dispatch with per-shard (core-group) claiming
    "moeshard": dict(overrides={"moe_impl": "sharded"}),
    "moeshard_dots": dict(overrides={"moe_impl": "sharded",
                                     "remat_policy": "dots"}),
    "sp_moeshard": dict(seq_parallel=True,
                        overrides={"moe_impl": "sharded"}),
    # ZeRO-3 + Ulysses-style sequence sharding on the model axis
    "fsdp_sp": dict(layout="fsdp", seq_parallel=True),
    # ZeRO-3 + shard_map MoE combined (experts stay EP in the fsdp ruleset)
    "fsdp_moeshard": dict(layout="fsdp", overrides={"moe_impl": "sharded"}),
    "fsdp_moeshard_dots": dict(layout="fsdp",
                               overrides={"moe_impl": "sharded",
                                          "remat_policy": "dots"}),
    # kvblk: forced sharding constraint on stacked KV blocks (REFUTED,
    # reverted — kept for the record)
    "kvblk": dict(),
    # kvseq: sequence-sharded KV cache + shard_map flash-decode with
    # partial-softmax combine (the principled decode fix)
    "kvseq": dict(cache_layout="seq"),
    # bigger flash chunk: fewer accumulator round-trips (memory term)
    "sp_bk8k": dict(seq_parallel=True, overrides={"attn_block_k": 8192}),
    "sp_bk16k": dict(seq_parallel=True, overrides={"attn_block_k": 16384}),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", required=True, choices=list(VARIANTS))
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    multi = args.mesh == "multi"
    mesh_name = "2x16x16" if multi else "16x16"
    out = dryrun.cell_path(args.arch, args.shape, mesh_name, args.variant)
    if out.exists() and not args.force:
        print(f"cached: {out.name}")
        return
    kw = VARIANTS[args.variant]
    try:
        rec = dryrun.run_cell(args.arch, args.shape, multi,
                              tag=args.variant, **kw)
    except Exception as e:
        traceback.print_exc()
        rec = {"arch": args.arch, "shape": args.shape, "mesh": mesh_name,
               "ok": False, "tag": args.variant,
               "error": f"{type(e).__name__}: {e}"[:500]}
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(rec, indent=1, default=float))


if __name__ == "__main__":
    main()
