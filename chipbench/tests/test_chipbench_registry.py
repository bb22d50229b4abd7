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
        assert (registry.PKG / f"drive_{cell.traffic['entry']}.py").exists()
        assert 0 < cell.cell["correct"]["limit"]
    for m in bench["per_layer"]:
        assert set(m.get("workloads", names)) <= names
        assert (registry.PKG / "metrics" / f"{m['name']}.py").exists()
        # every cell a per-layer metric reads reports what it moves
        for w in m.get("workloads", names):
            assert m["moves"] in [e.name for e in
                                  registry.load_cell(w).end_to_end]
    for c in bench["configs"]:
        assert (registry.ROOT / c["file"]).exists()
        model = json.loads((registry.ROOT / c["file"]).read_text())
        fam = registry.load_family(model["family"])
        assert all(callable(getattr(fam, a)) for a in registry.FAMILY_API
                   if a != "Q_BLOCK") and fam.Q_BLOCK > 0
        assert fam.sizes(model).vocab == model["vocab_size"]
    assert all(0.01 <= m["bound"] <= 0.25 for m in bench["end_to_end"])


def test_each_entry_gives_the_shapes_its_cells_rehearse():
    """``rehearse.py`` takes every engine shape from the cell's entry:
    the fleet's edge engine and cloud replicas, the engine entry's one
    engine with its burst prefilling every slot against the longest
    prompt's lines."""
    from chipbench import rehearse
    for w in registry.load_benchmark()["workloads"]:
        cell = registry.load_cell(w["name"])
        d = cell.cell["deployment"]
        shapes = rehearse.shapes_of(cell)
        for label, slots, max_len, chunk, copies, g, kv in shapes:
            assert max_len == d["max_len"] and chunk == d["prefill_chunk"]
            assert 0 < g <= slots and chunk <= kv <= max_len
        if cell.traffic["entry"] == "engine":
            [(_, slots, _, _, copies, g, kv)] = shapes
            assert (slots, copies, g) == (d["slots"], 1, d["slots"])
            assert kv >= cell.traffic["prompt"]["max"] > kv // 2
        else:
            assert [(s[0], s[1], s[4]) for s in shapes] == [
                ("edge", d["edge_slots"], 1),
                ("cloud", d["cloud_slots"], d["cloud_replicas"])]


OWN_FAMILY = '''
"""A family of the test's own: the dense family's weights and layout,
served and checked through functions of this module, which count their
calls."""
from chipbench import registry

_dense = registry.load_family("dense")
Q_BLOCK = _dense.Q_BLOCK
CALLS = []
sizes = _dense.sizes
canonical = _dense.canonical
program_params = _dense.program_params


def served_config(model):
    CALLS.append("served_config")
    return _dense.served_config(model)


def logits_at(sz, w, ids, want, mode):
    CALLS.append("logits_at")
    return _dense.logits_at(sz, w, ids, want, mode)
'''


def _tree_digest() -> dict:
    import hashlib
    files = [registry.ROOT / "BENCHMARK.json"] + sorted(
        p for p in registry.PKG.rglob("*")
        if p.is_file() and "__pycache__" not in p.parts)
    return {str(p): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in files}


def test_new_family_runs_through_both_entries_from_new_files(tmp_path):
    """A configuration of a family the benchmark has never had, with its
    own ``families/<family>.py``, runs a fleet cell and an engine cell;
    every file of the benchmark stays as it was."""
    before = _tree_digest()
    (tmp_path / "chipbench" / "families").mkdir(parents=True)
    (tmp_path / "chipbench" / "families" / "counted.py").write_text(
        OWN_FAMILY)
    model = dict(tiny.small_model("internlm2-1.8b", vocab=448),
                 family="counted")
    tiny.write(tmp_path, [
        ("tiny.fleet", "tiny-counted", model, "fmix", tiny.FLEET_MIX,
         tiny.FLEET_CELL),
        ("tiny.engine", "tiny-counted", model, "emix", tiny.ENGINE_MIX,
         tiny.ENGINE_CELL)])
    dev, peaks = tiny.cpu_device()
    for name in ("tiny.fleet", "tiny.engine"):
        cell = registry.load_cell(name, tmp_path, tmp_path / "chipbench")
        assert cell.family.__file__.startswith(str(tmp_path))
        out = R.run_cell(cell, 2**32 + 11, 2.0, False, dev, peaks)
        assert out["correct"] is True, (name, out["check"])
        assert {"served_config", "logits_at"} <= set(cell.family.CALLS)
    assert _tree_digest() == before
