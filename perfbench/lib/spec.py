"""BENCHMARK.json and the files it names, found by name.

A cell names a configuration and a traffic mix; the configuration's
file is listed in BENCHMARK.json, the traffic mix is
``traffic/<traffic>.json`` and each per-layer metric is a reader
module ``metrics/<name>.py`` (dots in a name become underscores).
Adding a cell or a metric adds files and entries, never edits these.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    pass


def _load_json(path):
    with open(path) as f:
        return json.load(f)


class Spec:
    def __init__(self, root: str = ROOT):
        self.root = root
        path = os.path.join(root, "BENCHMARK.json")
        if not os.path.exists(path):
            raise SpecError(f"no BENCHMARK.json in {root}")
        self.data = _load_json(path)
        self.configs = {c["name"]: c for c in self.data["configs"]}
        self.workloads = {w["name"]: w for w in self.data["workloads"]}

    def cell(self, name: str) -> dict:
        if name not in self.workloads:
            raise SpecError(f"unknown workload {name!r}; BENCHMARK.json "
                            f"has {sorted(self.workloads)}")
        return self.workloads[name]

    def config(self, cell: dict) -> dict:
        entry = self.configs[cell["config"]]
        return _load_json(os.path.join(self.root, entry["file"]))

    def traffic(self, cell: dict) -> dict:
        return _load_json(os.path.join(BENCH_DIR, "traffic",
                                       cell["traffic"] + ".json"))

    def end_to_end(self, cell: dict) -> list:
        return [m for m in self.data["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])]

    def per_layer(self, cell: dict) -> list:
        return [m for m in self.data["per_layer"]
                if cell["name"] in m.get("workloads", [cell["name"]])]


def reader(metric: dict):
    """The per-layer metric's reader module, checked against its entry:
    the module states the layer and the end-to-end metric it moves."""
    name = metric["name"]
    path = os.path.join(BENCH_DIR, "metrics",
                        name.replace(".", "_").replace("-", "_") + ".py")
    if not os.path.exists(path):
        raise SpecError(f"per-layer metric {name!r} has no reader {path}")
    modspec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(modspec)
    modspec.loader.exec_module(mod)
    for key in ("layer", "moves", "unit", "source"):
        if getattr(mod, key.upper()) != metric[key]:
            raise SpecError(f"metrics/{name}: {key.upper()} = "
                            f"{getattr(mod, key.upper())!r} but "
                            f"BENCHMARK.json says {metric[key]!r}")
    return mod
