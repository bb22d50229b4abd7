"""The harness is driven by data: a throwaway configuration, traffic mix,
cell and per-layer metric, added as files beside copies of the real ones,
are found and run with no edit to the harness. Also: ``run.py`` refuses
any backend but the TPU, and the real ``BENCHMARK.json`` keeps to the
shape the harness reads."""
from __future__ import annotations

import json
import os
import subprocess
import sys

from chipbench import registry
from chipbench import run as R
from chipbench.tests import tiny

NEW_METRIC = '''
"""Tokens seen in the window, per engine step that ended in it."""


def read(r):
    steps = sum(1 for _ in r.recorder.steps_in(*r.window))
    return r.recorder.tokens_in(*r.window) / steps if steps else None
'''


def test_new_cell_and_metric_need_only_new_files(tmp_path):
    model = tiny.small_model("qwen2-1.5b", vocab=384)
    mix = dict(tiny.FLEET_MIX, rate=4.0)
    tiny.write(tmp_path, [("tiny.new", "tiny-new-model", model, "new-mix",
                           mix, tiny.FLEET_CELL)])
    (tmp_path / "chipbench" / "metrics" / "tokens_per_step.py").write_text(
        NEW_METRIC)
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "tokens_per_step", "unit": "tokens",
                               "better": "higher", "source": "program_span",
                               "layer": "engine", "moves": "itl_p99_ms",
                               "workloads": ["tiny.new"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = registry.load_cell("tiny.new", tmp_path, tmp_path / "chipbench")
    assert cell.model["vocab_size"] == 384 and cell.traffic["rate"] == 4.0
    assert [m.name for m in cell.per_layer] == [
        "engine_step_ms", "compiles_in_window", "tokens_per_step"]
    dev, peaks = tiny.cpu_device()
    out = R.run_cell(cell, 3, 2.0, True, dev, peaks)
    assert out["correct"] is True
    assert out["metrics"]["tokens_per_step"]["value"] > 0
    assert out["metrics"]["engine_step_ms"]["unit"] == "ms"
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_run_refuses_a_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "qwen2-1.5b.fleet-mix", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=registry.ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr and "cpu" in p.stderr


def test_benchmark_file_names_every_file_it_needs():
    bench = registry.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = {w["name"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        cell = registry.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in [m.name for m in cell.end_to_end]
        assert cell.traffic["entry"] == "fleet"
        assert 0 < cell.cell["correct"]["limit"]
    for m in bench["per_layer"]:
        assert set(m.get("workloads", names)) <= names
        assert (registry.PKG / "metrics" / f"{m['name']}.py").exists()
    for c in bench["configs"]:
        assert (registry.ROOT / c["file"]).exists()
    assert all(0.01 <= m["bound"] <= 0.25 for m in bench["end_to_end"])
