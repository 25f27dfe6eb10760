"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload qwen2.5-3b.chat --seed 7 --seconds 30 \
        --trace 0

(``python3 -m bench.run`` works the same.)  Runs from the root of a
checkout, on the machine that holds the chips: it exits with code 2 and
prints no result where JAX finds no TPU, or fewer chips than the cell
asks for.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the end-to-end
metrics, or with ``--trace 1`` the per-layer ones), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: every number compared
with its limit, which the last lines of standard error repeat.

``--control int8`` (or ``fp8``, or both, comma-separated) puts the
reference in that precision in the program's place for the check, with
the program's own readings beside it; ``--control kv-int8`` serves with
the program's own int8 KV cache instead.  ``--readings`` takes a list of
seeds and prints one line of check readings per seed from one process.
Neither is part of a benchmark run.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default="",
                    help="comma-separated: int8, fp8 (the reference in "
                         "that precision, the first standing in for the "
                         "program) or kv-int8 (the program's int8 KV cache)")
    ap.add_argument("--readings", default="",
                    help="comma-separated seeds: check readings only")
    args = ap.parse_args(argv)

    # committed code alone decides: no calibration or tuning database
    # left in the working tree may steer the run
    os.environ["REPRO_CALIBRATION"] = "off"
    os.environ["REPRO_TUNING"] = "off"
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)

    from bench import harness, modeldef, traffic

    bench = harness.benchmark()
    work = {w["name"]: w for w in bench["workloads"]}.get(args.workload)
    if work is None:
        print(f"unknown workload {args.workload!r}", file=sys.stderr)
        return 2

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < work["chips"]:
        print(f"bench: needs {work['chips']} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform!r} device(s); nothing was run",
              file=sys.stderr)
        return 2
    harness.log(f"JAX and the chip up at {time.monotonic() - T_PROCESS:.3f} s")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    controls = [c for c in args.control.split(",") if c]
    kw = dict(
        cfg=modeldef.load_config(work["config"]),
        mix=traffic.load_mix(work["traffic"]),
        cell=harness.load_json(harness.HERE / "cells"
                               / f"{args.workload}.json"),
        metrics=harness.cell_metrics(bench, args.workload, bool(args.trace)),
        seconds=args.seconds, trace=bool(args.trace), t_process=T_PROCESS,
        controls=[c for c in controls if c != "kv-int8"],
        kv_dtype="int8" if "kv-int8" in controls else None)
    if args.readings:
        for s in [int(x) for x in args.readings.split(",")]:
            res = harness.run_cell(seed=s, **kw)
            print(json.dumps({"seed": s, "checks": res["checks"],
                              "attempted": res["attempted"]}), flush=True)
        return 0
    harness.print_result(harness.run_cell(seed=args.seed, **kw))
    return 0


if __name__ == "__main__":
    sys.exit(main())
