"""Readings that a cell's correctness limit is set from, on the chip.

    python3 -m chipbench.control --workload <name> --seeds 1,2,3 --seconds <s> [--controls fp8,int8]

In one process (set-up is long), for each seed: one run of the cell at
its own size and load, whose served tokens are compared with the float32
reference as every benchmark run does (the program's reading), and with
the reference computed at each lower precision put in the program's
place (the control's reading: at every position of the same prompts and
tokens, the gap of the token the lower precision puts first), judged
against the cell's limit by the same check as a run, so that a sound
limit reads ``correct: false`` for each control. Prints one JSON line
per seed. Benchmark runs never read the controls.
"""
from __future__ import annotations

import argparse
import json
import sys

from chipbench import registry
from chipbench import run as R


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="python3 -m chipbench.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", default="fp8,int8")
    args = ap.parse_args(argv)
    cell = registry.load_cell(args.workload)
    import jax
    if jax.default_backend() != "tpu":
        R.fail(2, f"needs a TPU backend; JAX found {jax.default_backend()!r}")
    _, device = R.device_info()
    peaks = registry.peaks(device["kind"])
    sys.path.insert(0, str(registry.ROOT / "src"))
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    controls = tuple(c for c in args.controls.split(",") if c)
    for s in (int(x) for x in args.seeds.split(",")):
        out = R.run_cell(cell, s, args.seconds, False, device, peaks,
                         controls)
        print(json.dumps({"workload": args.workload, "seed": s,
                          "correct": out["correct"],
                          "program": out["check"]["max_logit_gap"]["value"],
                          "limit": out["check"]["max_logit_gap"]["limit"],
                          "controls": out["controls"],
                          "metrics": {k: v["value"] for k, v in
                                      out["metrics"].items()},
                          "attempted": out["attempted"],
                          "failed": out["failed"]}), flush=True)


if __name__ == "__main__":
    main()
