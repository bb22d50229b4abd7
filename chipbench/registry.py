"""Find a cell's files by the names in ``BENCHMARK.json``.

A later change adds a cell or a per-layer metric by adding files under
``configs/``, ``traffic/``, ``cells/`` and ``metrics/`` and entries in
``BENCHMARK.json``; nothing here names a cell, a mix or a metric.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

PKG = Path(__file__).resolve().parent
ROOT = PKG.parent


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    layer: Optional[str] = None
    moves: Optional[str] = None
    workloads: Optional[List[str]] = None
    reader: Optional[Callable] = None

    def applies_to(self, workload: str) -> bool:
        return self.workloads is None or workload in self.workloads


@dataclass
class Cell:
    """Everything one run of one workload needs."""

    name: str
    chips: int
    config_name: str
    traffic_name: str
    model: dict              # configs/<config>.json
    traffic: dict            # traffic/<mix>.json
    cell: dict               # cells/<workload>.json
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)


def load_benchmark(root: Path = ROOT) -> dict:
    return _load_json(root / "BENCHMARK.json")


def load_reader(name: str, metrics_dir: Path = PKG / "metrics") -> Callable:
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    path = metrics_dir / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(f"no reader for per-layer metric {name!r}: "
                                f"{path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _metric(entry: dict) -> Metric:
    return Metric(name=entry["name"], unit=entry["unit"],
                  better=entry["better"], source=entry["source"],
                  layer=entry.get("layer"), moves=entry.get("moves"),
                  workloads=entry.get("workloads"))


def load_cell(workload: str, root: Path = ROOT,
              pkg: Path = PKG) -> Cell:
    bench = load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(by_name)}")
    w = by_name[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = configs[w["config"]]
    cell = Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"], traffic_name=w["traffic"],
                model=_load_json(root / conf["file"]),
                traffic=_load_json(pkg / "traffic" / f"{w['traffic']}.json"),
                cell=_load_json(pkg / "cells" / f"{workload}.json"))
    cell.end_to_end = [m for m in map(_metric, bench["end_to_end"])
                       if m.applies_to(workload)]
    for entry in bench["per_layer"]:
        m = _metric(entry)
        if m.applies_to(workload):
            m.reader = load_reader(m.name, pkg / "metrics")
            cell.per_layer.append(m)
    return cell


def peaks(device_kind: str, pkg: Path = PKG) -> Dict[str, float]:
    """The chip's published peaks; an unknown device is an error."""
    table = _load_json(pkg / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table['devices'])})")
    return table["devices"][device_kind]
