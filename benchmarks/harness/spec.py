"""Find a cell's files by the names ``BENCHMARK.json`` gives them."""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Spec:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.bench = _load(os.path.join(root, "BENCHMARK.json"))
        self.dir = os.path.join(root, self.bench["paths"][0])
        self._modules: dict = {}

    def cell(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{[w['name'] for w in self.bench['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.bench["configs"]:
            if c["name"] == name:
                return _load(os.path.join(self.root, c["file"]))
        raise KeyError(name)

    def traffic(self, name: str) -> dict:
        return _load(os.path.join(self.dir, "traffic", name + ".json"))

    def metrics_of(self, cell: str, kind: str) -> list[dict]:
        """The cell's metrics of ``kind`` (``end_to_end``/``per_layer``):
        those that list it, or list no cells at all."""
        return [m for m in self.bench[kind]
                if cell in m.get("workloads", [cell])]

    def metric_file(self, name: str) -> dict:
        return _load(os.path.join(self.dir, "metrics", name + ".json"))

    def _module(self, kind: str, name: str):
        """``benchmarks/<kind>/<name>.py``, loaded once by its path."""
        key = f"benchmarks.{kind}.{name}"
        if key not in self._modules:
            mod_spec = importlib.util.spec_from_file_location(
                key, os.path.join(self.dir, kind, name + ".py"))
            mod = importlib.util.module_from_spec(mod_spec)
            mod_spec.loader.exec_module(mod)
            self._modules[key] = mod
        return self._modules[key]

    def reader(self, name: str):
        return self._module("readers", name).read

    def family(self, name: str):
        """What a configuration's ``"family"`` names: the module that
        holds everything about a model the harness does not know."""
        return self._module("families", name)
