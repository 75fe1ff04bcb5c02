"""The benchmark's data: ``BENCHMARK.json`` and the files it names.

A cell ``<name>`` is ``workloads/<name>.json`` (its traffic and the limits
of its correctness check), its configuration is ``configs/<config>.json``
(sizes as run; ``job`` names ``jobs/<job>.py``), and a per-layer metric
``<metric>`` is read by ``metrics/<metric>.py``.  Nothing in this module
knows any cell, configuration or metric by name.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from types import ModuleType
from typing import List

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: str) -> ModuleType:
    name = "bench_" + re.sub(r"\W", "_", os.path.relpath(path, BENCH))
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Spec:
    def __init__(self, benchmark: dict, bench_dir: str = BENCH):
        self.b = benchmark
        self.dir = bench_dir

    @classmethod
    def load(cls, root: str = ROOT) -> "Spec":
        return cls(_load_json(os.path.join(root, "BENCHMARK.json")),
                   os.path.join(root, "bench"))

    def cell(self, name: str) -> dict:
        for w in self.b["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def workload_file(self, name: str) -> dict:
        if not _NAME.match(name):
            raise ValueError(f"bad workload name {name!r}")
        return _load_json(os.path.join(self.dir, "workloads",
                                       name + ".json"))

    def config_file(self, name: str) -> dict:
        for c in self.b["configs"]:
            if c["name"] == name:
                return _load_json(os.path.join(os.path.dirname(self.dir),
                                               c["file"]))
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def job(self, job: str) -> ModuleType:
        return _module(os.path.join(self.dir, "jobs", job + ".py"))

    def reference(self, ref: str) -> ModuleType:
        return _module(os.path.join(self.dir, "reference", ref + ".py"))

    @staticmethod
    def _applies(metric: dict, cell: str, cell_e2e: List[str]) -> bool:
        if "workloads" in metric:
            return cell in metric["workloads"]
        return metric["moves"] in cell_e2e

    def end_to_end(self, cell: str) -> List[dict]:
        return [m for m in self.b["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]]

    def per_layer(self, cell: str) -> List[dict]:
        e2e = [m["name"] for m in self.end_to_end(cell)]
        return [m for m in self.b["per_layer"]
                if self._applies(m, cell, e2e)]

    def reader(self, metric: str) -> ModuleType:
        if not _NAME.match(metric):
            raise ValueError(f"bad metric name {metric!r}")
        return _module(os.path.join(self.dir, "metrics", metric + ".py"))
