"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

- a configuration: ``perfbench/configs/<config>.json``, whose
  ``family`` names its family module, ``perfbench/families/<family>.py``
  (everything the benchmark knows of a model's layout: the keys that fix
  its math, its shipped weights, the program's serving model, and the
  operations its steps and kernels need), and whose ``reference`` names
  its plain reference, ``perfbench/references/<reference>.py``;
- a traffic mix: ``perfbench/traffic/<traffic>.json``;
- an end-to-end metric: ``perfbench/e2e/<name>.py``;
- a per-layer metric: ``perfbench/metrics/<name>.py``.

Each metric file defines ``read(run) -> float | None``: ``None`` when it
finds nothing to read, and the metric is then left out of the line. A new
cell, mix, metric or model family is new files and a new entry in
``BENCHMARK.json``; no existing file changes.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from types import ModuleType
from typing import Dict, Optional


class Registry:
    def __init__(self, root: pathlib.Path):
        self.root = pathlib.Path(root)
        self.bench_dir = self.root / "perfbench"
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (have: "
                       f"{[w['name'] for w in self.spec['workloads']]})")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads(
            (self.bench_dir / "traffic" / f"{name}.json").read_text())

    def family(self, name: str) -> ModuleType:
        return _load(self.bench_dir / "families" / f"{name}.py")

    def reference(self, name: str) -> ModuleType:
        return _load(self.bench_dir / "references" / f"{name}.py")

    def metrics(self, kind: str, workload: str) -> Dict[str, dict]:
        """The metrics of ``kind`` ("end_to_end" | "per_layer") that apply
        to ``workload``: listed for it, or listed for no cell."""
        return {m["name"]: m for m in self.spec[kind]
                if workload in m.get("workloads", [workload])}

    def reader(self, kind: str, name: str) -> ModuleType:
        sub = "e2e" if kind == "end_to_end" else "metrics"
        return _load(self.bench_dir / sub / f"{name}.py")


def _load(path: pathlib.Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        "perfbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_all(reg: Registry, kind: str, workload: str, run) -> Dict[str, dict]:
    """``{name: {"value", "unit"}}`` of every metric of ``kind`` whose
    reader finds something."""
    out: Dict[str, dict] = {}
    for name, m in reg.metrics(kind, workload).items():
        value: Optional[float] = reg.reader(kind, name).read(run)
        if value is not None:
            out[name] = {"value": float(value), "unit": m["unit"]}
    return out
