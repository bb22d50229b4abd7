"""A throwaway benchmark at CPU sizes, written into a temporary directory:
``BENCHMARK.json`` plus config, traffic, cell, metric and family files of
its own, found by the registry exactly as the real ones are."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from chipbench import registry

SMALL = {"num_hidden_layers": 2, "hidden_size": 128,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
         "intermediate_size": 256}
TO_PROGRAM = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
              "num_attention_heads": "n_heads",
              "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
              "intermediate_size": "d_ff", "vocab_size": "vocab_size"}


def small_model(config_name: str, vocab: int = 512) -> dict:
    """A real config file cut to CPU size (the same cut in the file's
    stated sizes and in the program's overrides)."""
    model = json.loads((registry.PKG / "configs" /
                        f"{config_name}.json").read_text())
    model.update(SMALL, vocab_size=vocab)
    over = dict(model.get("overrides", {}))
    over.update({TO_PROGRAM[k]: model[k] for k in TO_PROGRAM})
    model["overrides"] = over
    return model


FLEET_MIX = {
    "entry": "fleet", "arrival": "poisson", "rate": 3.0,
    "preroll_s": 0.5, "drain_s": 120.0, "trace_seed": 0,
    "benchmarks": ["gpqa", "mmlu_pro", "aime24", "livebench_reasoning"],
    "warm_queries": 2,
}
FLEET_CELL = {
    "deployment": {"edge_slots": 2, "cloud_slots": 2, "cloud_replicas": 2,
                   "max_len": 256, "prefill_chunk": 32, "prefix_reuse": True,
                   "edge_concurrency": 1, "price_out": 3.2e-05,
                   "tau0": 0.35, "k_max": 0.04, "max_inflight": 4,
                   "router_queries": 16, "router_epochs": 2},
    "warm_edge": [], "warm_cloud": [],
    "trace": {"offset_s": 0.2, "seconds": 0.5},
    "correct": {"limit": 0.02, "sample_tokens": 60, "max_requests": 6},
}


ENGINE_MIX = {
    "entry": "engine", "arrival": "poisson", "rate": 6.0, "burst": 4,
    "preroll_s": 0.5, "drain_s": 120.0, "trace_seed": 0,
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8,
               "max": 64},
    "answer": {"dist": "lognormal", "median": 16, "sigma": 0.6, "min": 4,
               "max": 48},
}
ENGINE_CELL = {
    "deployment": {"slots": 4, "max_len": 128, "prefill_chunk": 16,
                   "prefix_reuse": True},
    "warm": [],
    "trace": {"offset_s": 0.2, "seconds": 0.5},
    "correct": {"limit": 0.02, "sample_tokens": 60, "max_requests": 6},
}


def write(root: Path, cells, metrics=("engine_step_ms",
                                      "compiles_in_window")) -> Path:
    """Lay out a benchmark under ``root``; ``cells`` is a list of
    (workload, config, model dict, traffic name, mix dict, cell dict).
    Per-layer metric readers, and each model's family where ``root``
    holds none of that name yet, are copied from the real ones."""
    pkg = root / "chipbench"
    for sub in ("configs", "traffic", "cells", "metrics", "families"):
        (pkg / sub).mkdir(parents=True, exist_ok=True)
    bench = {"command": ["python3", "-m", "chipbench.run"],
             "paths": ["chipbench"], "run_seconds": 2, "configs": [],
             "workloads": [], "end_to_end": [
                 {"name": "itl_p99_ms", "unit": "ms", "better": "lower",
                  "bound": 0.1, "source": "host_clock"},
                 {"name": "tokens_per_s", "unit": "tokens/s",
                  "better": "higher", "bound": 0.1, "source": "host_clock",
                  "workloads": [c[0] for c in cells
                                if c[4]["entry"] == "engine"]},
                 {"name": "setup_s", "unit": "s", "better": "lower",
                  "bound": 0.25, "source": "host_clock"}],
             "per_layer": []}
    seen = set()
    for workload, config, model, mix_name, mix, cell in cells:
        fam = pkg / "families" / f"{model['family']}.py"
        if not fam.exists():
            shutil.copy(registry.PKG / "families" / fam.name, fam)
        if config not in seen:
            seen.add(config)
            f = f"chipbench/configs/{config}.json"
            (root / f).write_text(json.dumps(model))
            bench["configs"].append({"name": config, "source": "test",
                                     "file": f, "reduced": [],
                                     "why": "test"})
        (pkg / "traffic" / f"{mix_name}.json").write_text(json.dumps(mix))
        (pkg / "cells" / f"{workload}.json").write_text(json.dumps(cell))
        bench["workloads"].append({"name": workload, "config": config,
                                   "traffic": mix_name, "chips": 1,
                                   "why": "test"})
    for m in metrics:
        shutil.copy(registry.PKG / "metrics" / f"{m}.py",
                    pkg / "metrics" / f"{m}.py")
        bench["per_layer"].append({"name": m, "unit": "ms",
                                   "better": "lower",
                                   "source": "program_span",
                                   "layer": "engine",
                                   "moves": "itl_p99_ms"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


def cpu_device() -> "tuple[dict, dict]":
    """A device line and peaks for CPU runs of the harness in tests."""
    import jax
    d = jax.devices()[0]
    return ({"platform": d.platform, "kind": d.device_kind,
             "count": len(jax.devices())},
            {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11})
