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

    def reader(self, name: str):
        path = os.path.join(self.dir, "readers", name + ".py")
        mod_spec = importlib.util.spec_from_file_location(
            f"benchmarks.readers.{name}", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read
