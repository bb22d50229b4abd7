"""Find a cell's knee: run it at several fixed rates, one process per rate.

    python3 -m chipbench.sweep --workload <name> --rates 0.5,1,2 --seed <n> --seconds <s>

Each rate runs in a copy of the benchmark under a temporary directory
whose traffic file carries that rate (the program is linked, not copied,
so the compile cache is shared). Prints one JSON line per rate with the
run's end-to-end metrics, its failures and how long the fleet took to
drain after the window closed. The rate a cell runs at is then written
into its traffic file by hand, with the sweep recorded in ``PERF.md``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
from pathlib import Path

from chipbench import registry


def run_at(workload: str, rate: float, seed: int, seconds: float,
           timeout: float) -> dict:
    bench = registry.load_benchmark()
    w = {x["name"]: x for x in bench["workloads"]}[workload]
    with tempfile.TemporaryDirectory(prefix="chipbench-sweep-") as tmp:
        root = Path(tmp)
        shutil.copy(registry.ROOT / "BENCHMARK.json", root)
        shutil.copytree(registry.PKG, root / "chipbench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        os.symlink(registry.ROOT / "src", root / "src")
        path = root / "chipbench" / "traffic" / f"{w['traffic']}.json"
        mix = json.loads(path.read_text())
        mix["rate"] = rate
        path.write_text(json.dumps(mix))
        p = subprocess.run(
            [sys.executable, "-m", "chipbench.run", "--workload", workload,
             "--seed", str(seed), "--seconds", str(seconds), "--trace", "0"],
            cwd=root, capture_output=True, text=True, timeout=timeout)
    info = {}
    for line in p.stderr.splitlines():
        if line.startswith("chipbench: info "):
            info = json.loads(line[len("chipbench: info "):])
    last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else "{}"
    out = json.loads(last) if last.startswith("{") else {}
    return {"workload": workload, "rate": rate, "rc": p.returncode,
            "correct": out.get("correct"), "attempted": out.get("attempted"),
            "failed": out.get("failed"),
            "metrics": {k: v["value"]
                        for k, v in out.get("metrics", {}).items()},
            "drain_s": info.get("drain_s"),
            "compiles_in_window": info.get("compiles_in_window"),
            "stderr_tail": p.stderr[-600:] if p.returncode else ""}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="python3 -m chipbench.sweep")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--timeout", type=float, default=900.0)
    args = ap.parse_args(argv)
    for r in (float(x) for x in args.rates.split(",")):
        print(json.dumps(run_at(args.workload, r, args.seed, args.seconds,
                                args.timeout)), flush=True)


if __name__ == "__main__":
    main()
