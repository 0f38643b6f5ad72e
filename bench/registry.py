"""Finds every piece of the benchmark by its name, under one root.

A cell is ``bench/workloads/<cell>.json``; it names its configuration
(``bench/configs/<config>.json``) and its traffic mix
(``bench/traffic/<traffic>.json``).  A per-layer metric is the module
``bench/metrics/<metric>.py`` with a ``read(run)`` function; the metrics a
cell reports, and their units, come from ``BENCHMARK.json``.  A model
family is a module of ``bench/families/`` whose ``MODEL_TYPES`` names the
``model_type`` values of the configurations it serves.  Adding a cell, a
configuration, a mix, a metric or a family is adding files and entries:
nothing here lists them.
"""
from __future__ import annotations

import importlib.util
import json
import os
from types import ModuleType
from typing import Any, Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class Registry:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.bench = os.path.join(root, "bench")
        self._families: Dict[str, ModuleType] = {}

    def _json(self, *parts: str) -> Dict[str, Any]:
        path = os.path.join(self.bench, *parts)
        if not os.path.isfile(path):
            raise KeyError(f"no such benchmark file: {path}")
        with open(path) as f:
            return json.load(f)

    def benchmark(self) -> Dict[str, Any]:
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            return json.load(f)

    def cell(self, name: str) -> Dict[str, Any]:
        c = self._json("workloads", f"{name}.json")
        if c.get("name") != name:
            raise ValueError(
                f"workload file {name}.json names {c.get('name')!r}")
        return c

    def config(self, name: str) -> Dict[str, Any]:
        return self._json("configs", f"{name}.json")

    def mix(self, name: str) -> Dict[str, Any]:
        return self._json("traffic", f"{name}.json")

    def peaks(self) -> Dict[str, Any]:
        return self._json("peaks.json")

    def cells(self) -> List[str]:
        d = os.path.join(self.bench, "workloads")
        return sorted(f[:-5] for f in os.listdir(d) if f.endswith(".json"))

    def metric_reader(self, name: str) -> Callable[[Any], Any]:
        path = os.path.join(self.bench, "metrics", f"{name}.py")
        if not os.path.isfile(path):
            raise KeyError(f"no reader for per-layer metric {name!r}: {path}")
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    def family(self, cfg: Dict[str, Any]) -> ModuleType:
        """The one module of ``bench/families/`` that claims the
        configuration's ``model_type``: the weights, the program's tree,
        the reference model and the counts of that architecture."""
        d = os.path.join(self.bench, "families")
        paths = sorted(os.path.join(d, f) for f in os.listdir(d)
                       if f.endswith(".py") and not f.startswith("_"))
        mt = cfg.get("model_type")
        hits = [p for p in paths
                if mt in getattr(self._family_module(p), "MODEL_TYPES", ())]
        if len(hits) != 1:
            raise KeyError(
                f"model_type {mt!r} of configuration {cfg.get('name')!r} is "
                f"claimed by {len(hits)} family modules, not one "
                f"({[os.path.basename(p) for p in hits]}); looked at "
                f"{paths}")
        return self._family_module(hits[0])

    def _family_module(self, path: str) -> ModuleType:
        if path not in self._families:
            stem = os.path.basename(path)[:-3]
            spec = importlib.util.spec_from_file_location(
                f"bench_family_{stem.replace('.', '_').replace('-', '_')}",
                path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._families[path] = mod
        return self._families[path]

    @staticmethod
    def applies(metric: Dict[str, Any], cell: str) -> bool:
        return "workloads" not in metric or cell in metric["workloads"]

    def end_to_end(self, cell: str) -> List[Dict[str, Any]]:
        return [m for m in self.benchmark()["end_to_end"]
                if self.applies(m, cell)]

    def per_layer(self, cell: str) -> List[Dict[str, Any]]:
        return [m for m in self.benchmark()["per_layer"]
                if self.applies(m, cell)]

    def chips(self, cell: str) -> int:
        for w in self.benchmark()["workloads"]:
            if w["name"] == cell:
                return int(w["chips"])
        return int(self.cell(cell).get("chips", 1))
