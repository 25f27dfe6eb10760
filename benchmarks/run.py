"""Benchmark harness — one function per paper table (+ device/roofline
extras).  Prints CSV rows and writes results/benchmarks/<table>.csv.

    PYTHONPATH=src python -m benchmarks.run             # everything
    PYTHONPATH=src python -m benchmarks.run --only sim  # one suite
    PYTHONPATH=src python -m benchmarks.run --quick     # CI smoke subset

``--quick`` runs each suite's ``QUICK`` list (falling back to ``ALL``
where a suite has no cheap subset) — the CI job that keeps these scripts
from rotting.
"""

from __future__ import annotations

import argparse
import csv
import sys
import time
from collections import defaultdict
from pathlib import Path

RESULTS = Path(__file__).resolve().parents[1] / "results" / "benchmarks"


def run_suite(name: str, fns) -> list[dict]:
    rows = []
    for fn in fns:
        t0 = time.time()
        out = fn()
        dt = time.time() - t0
        print(f"# {name}.{fn.__name__}: {len(out)} rows in {dt:.1f}s",
              file=sys.stderr)
        rows.extend(out)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="sim | cost | taskflow | sched | serve | paged "
                         "| roofline | calib | kautotune | quant | chaos "
                         "| spec")
    ap.add_argument("--quick", action="store_true",
                    help="run each suite's QUICK subset (CI smoke)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (calibration_sweep, chaos_sweep,
                            cost_model_bench, dryrun_summary,
                            kernel_autotune_sweep, quant_sweep,
                            scheduler_sweep, serve_admission_sweep,
                            serve_paged_sweep, sim_tables,
                            spec_sweep, taskflow_compare)

    mods = {
        "sim": sim_tables,
        "cost": cost_model_bench,
        "taskflow": taskflow_compare,
        "sched": scheduler_sweep,
        "serve": serve_admission_sweep,
        "paged": serve_paged_sweep,
        "roofline": dryrun_summary,
        "calib": calibration_sweep,
        "kautotune": kernel_autotune_sweep,
        "quant": quant_sweep,
        "chaos": chaos_sweep,
        "spec": spec_sweep,
    }
    suites = {name: (getattr(m, "QUICK", m.ALL) if args.quick else m.ALL)
              for name, m in mods.items()}
    if args.only:
        suites = {args.only: suites[args.only]}

    all_rows = []
    for name, fns in suites.items():
        all_rows += run_suite(name, fns)

    # group rows by table name, write one csv per table, print everything
    RESULTS.mkdir(parents=True, exist_ok=True)
    by_table = defaultdict(list)
    for row in all_rows:
        by_table[row.get("table", "misc")].append(row)
    for table, rows in by_table.items():
        keys = sorted({k for r in rows for k in r if k != "table"},
                      key=lambda k: (k != "block_size", k))
        path = RESULTS / f"{table}.csv"
        with open(path, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=["table"] + keys,
                               extrasaction="ignore")
            w.writeheader()
            w.writerows(rows)
        for r in rows:
            print(",".join(str(r.get(k, "")) for k in ["table"] + keys))
    print(f"# wrote {len(by_table)} tables to {RESULTS}", file=sys.stderr)


if __name__ == "__main__":
    main()
