"""Finds everything a cell needs by name, from BENCHMARK.json and the data
files beside it, so that a new configuration, traffic mix, limit or
per-layer metric is a new file and an entry, never an edit of code:

    BENCHMARK.json                  cells, configurations, metrics
    bench/configs/<file>.json       a configuration as it is run
    bench/traffic/<traffic>.json    a traffic mix
    bench/limits/<cell>.json        the limits `correct` is held to
    bench/metrics/<metric>.py       a per-layer metric's reader
    bench/reference/<family>.py     a plain reference, named by the config
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    root: str

    def metric_module(self, name: str):
        path = os.path.join(self.root, "bench", "metrics", f"{name}.py")
        spec = importlib.util.spec_from_file_location(f"bench.metrics.{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def reference(self):
        family = self.config["reference"]
        path = os.path.join(self.root, "bench", "reference", f"{family}.py")
        spec = importlib.util.spec_from_file_location(f"bench.reference.{family}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(root, "bench", "traffic", f"{w['traffic']}.json"))
    limits = _load_json(os.path.join(root, "bench", "limits", f"{name}.json"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name) and m["moves"] in reported]
    return Cell(name=name, chips=w["chips"], config=config, traffic=traffic, limits=limits,
                end_to_end=e2e, per_layer=per_layer, root=root)
