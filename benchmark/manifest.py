"""Finding a cell's files by the names in `BENCHMARK.json`. Whatever belongs
to one configuration, one traffic mix or one per-layer metric sits in a file
of its own; a later PR adds a cell or a metric by adding files and entries
and edits nothing that is here."""
from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


class Manifest:
    def __init__(self, root: str = ROOT, bench_dir: str | None = None):
        self.root = root
        self.data = _load(os.path.join(root, "BENCHMARK.json"))
        self.bench_dir = bench_dir or os.path.join(root, "benchmark")
        self._readers: dict = {}      # reader file -> loaded module

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                       f"{[w['name'] for w in self.data['workloads']]}")

    def config(self, cell: dict) -> dict:
        for c in self.data["configs"]:
            if c["name"] == cell["config"]:
                return _load(os.path.join(self.root, c["file"]))
        raise KeyError(f"no configuration {cell['config']!r}")

    def mix(self, cell: dict) -> dict:
        return _load(os.path.join(self.bench_dir, "traffic",
                                  cell["traffic"] + ".json"))

    def _reported(self, metric: dict, cell_name: str) -> bool:
        return "workloads" not in metric or cell_name in metric["workloads"]

    def end_to_end(self, cell_name: str) -> list[dict]:
        return [m for m in self.data["end_to_end"]
                if self._reported(m, cell_name)]

    def per_layer(self, cell_name: str) -> list[dict]:
        """The per-layer metrics of a cell: those that list it, and those
        that list no cell and move an end-to-end metric it reports."""
        e2e = {m["name"] for m in self.end_to_end(cell_name)}
        return [m for m in self.data["per_layer"]
                if (cell_name in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def reader(self, metric_name: str):
        """(function, arguments) of a per-layer metric's reader, from
        `benchmark/metrics/<name>.json`."""
        spec = _load(os.path.join(self.bench_dir, "metrics",
                                  metric_name + ".json"))
        mod, fn = spec["reader"].split(":")
        path = os.path.join(self.bench_dir, "readers", mod + ".py")
        if path not in self._readers:
            sp = importlib.util.spec_from_file_location(
                "benchmark.readers." + mod, path)
            module = importlib.util.module_from_spec(sp)
            sp.loader.exec_module(module)
            self._readers[path] = module
        return getattr(self._readers[path], fn), spec.get("args", {})
