"""Finding a cell's files by the names in `BENCHMARK.json`. Whatever belongs
to one configuration, one model family, one traffic mix or one per-layer
metric sits in a file of its own; a later PR adds a cell, a metric or an
architecture by adding files and entries and edits nothing that is here."""
from __future__ import annotations

import importlib.util
import json
import os
import types

FAMILY_PARTS = ("weights", "reference", "counts", "program")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


class Manifest:
    def __init__(self, root: str = ROOT, bench_dir: str | None = None):
        self.root = root
        self.data = _load(os.path.join(root, "BENCHMARK.json"))
        self.bench_dir = bench_dir or os.path.join(root, "benchmark")
        self._modules: dict = {}      # reader or family file -> module
        self._families: dict = {}     # family -> its four modules

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

    def _module(self, name: str, path: str):
        """The file at ``path``, loaded once (by path, so that a scratch
        root's files are its own)."""
        if path not in self._modules:
            sp = importlib.util.spec_from_file_location(name, path)
            module = importlib.util.module_from_spec(sp)
            sp.loader.exec_module(module)
            self._modules[path] = module
        return self._modules[path]

    def reader(self, metric_name: str):
        """(function, arguments) of a per-layer metric's reader, from
        `benchmark/metrics/<name>.json`."""
        spec = _load(os.path.join(self.bench_dir, "metrics",
                                  metric_name + ".json"))
        mod, fn = spec["reader"].split(":")
        path = os.path.join(self.bench_dir, "readers", mod + ".py")
        return (getattr(self._module("benchmark.readers." + mod, path), fn),
                spec.get("args", {}))

    def family(self, cfg: dict):
        """What depends on the architecture of ``cfg``, found by its
        `family` key: the modules `weights` (`make_weights`), `reference`
        (`logits_at`), `counts` (`decode_step_work`, `prefill_work`, ...)
        and `program` (`build`, `derive`) of
        `benchmark/families/<family>/`, and the family's `name`."""
        name = cfg["family"]
        if name not in self._families:
            top = os.path.join(self.bench_dir, "families", name)
            paths = {p: os.path.join(top, p + ".py") for p in FAMILY_PARTS}
            missing = [p + ".py" for p, path in paths.items()
                       if not os.path.isfile(path)]
            if missing:
                raise FileNotFoundError(
                    f"configuration {cfg.get('name')!r} is of family "
                    f"{name!r}: looked in {top} for "
                    f"{[p + '.py' for p in FAMILY_PARTS]}, found no "
                    f"{missing}")
            self._families[name] = types.SimpleNamespace(name=name, **{
                p: self._module(f"benchmark.families.{name}.{p}", path)
                for p, path in paths.items()})
        return self._families[name]
