"""Benchmark driver — one section per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--only tableX,...] [--fast]``
prints CSV sections and writes them to benchmarks/artifacts/results/.
Roofline reads the dry-run JSONs if present.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import common as C


def all_benchmarks():
    from benchmarks import (table1_accuracy, table2_efficiency,
                            table3_ablation, table5_dag_validity,
                            table6_threshold_sweep, table7_planner,
                            table8_pair_swap, fig3_offload,
                            fig5_plan_quality, exposure_bench,
                            kernels_bench, roofline, serve_throughput)
    return {
        "table1": table1_accuracy,
        "table2": table2_efficiency,
        "table3": table3_ablation,
        "table5": table5_dag_validity,
        "table6": table6_threshold_sweep,
        "table7": table7_planner,
        "table8": table8_pair_swap,
        "fig3": fig3_offload,
        "fig5": fig5_plan_quality,
        "exposure": exposure_bench,
        "kernels": kernels_bench,
        "roofline": roofline,
        "serve": serve_throughput,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--fast", action="store_true",
                    help="fewer queries/seeds for smoke runs")
    args = ap.parse_args()
    if args.fast:
        C.N_QUERIES = 60
        C.N_SEEDS = 1

    benches = all_benchmarks()
    names = args.only.split(",") if args.only else list(benches)
    outdir = os.path.join(os.path.dirname(__file__), "artifacts", "results")
    os.makedirs(outdir, exist_ok=True)

    failures = 0
    for name in names:
        mod = benches[name]
        t0 = time.time()
        try:
            header, rows = mod.run()
        except Exception:
            print(f"\n# {name} FAILED\n{traceback.format_exc()[-1500:]}")
            failures += 1
            continue
        C.print_csv(f"{name} ({time.time() - t0:.1f}s)", header, rows)
        with open(os.path.join(outdir, f"{name}.csv"), "w") as f:
            f.write(",".join(header) + "\n")
            for r in rows:
                f.write(",".join(C._fmt(x) for x in r) + "\n")
    if failures:
        raise SystemExit(f"{failures} benchmark(s) failed")


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
