"""Find a cell, its configuration, its traffic and its metrics by name.

``BENCHMARK.json`` at the root of the checkout lists them; everything that
belongs to one of them is a file of its own:

* a configuration: the JSON file its entry names (sizes, skew, guarantees,
  and the reference it is checked against);
* a traffic mix: ``bench/traffic/<traffic>.json``, read by
  :mod:`harness.traffic` (the queries, their parameters, and the
  comparison's limits);
* a metric: ``bench/metrics/<name>.py``, whose ``read(run)`` returns the
  number, or None where the run has nothing to read.

A cell, a configuration or a metric is added by adding its files and its
entry, without editing a file that is there.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

from .traffic import Traffic

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    kind: str                     # "end_to_end" or "per_layer"
    path: str

    def read(self, run):
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + self.name.replace(".", "_").replace("-", "_"),
            self.path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read(run)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: Traffic
    chips: int
    metrics: tuple[Metric, ...]


class Bench:
    """``BENCHMARK.json`` of the checkout at ``root``."""

    def __init__(self, root: str):
        self.root = root
        self.dir = os.path.join(root, "bench")
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)

    def cell(self, name: str) -> Cell:
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; there "
                           f"are {sorted(cells)}")
        w = cells[name]
        conf = {c["name"]: c for c in self.spec["configs"]}[w["config"]]
        with open(os.path.join(self.root, conf["file"])) as f:
            config = json.load(f)
        traffic = Traffic.load(os.path.join(
            self.dir, "traffic", f"{w['traffic']}.json"))
        metrics = tuple(
            Metric(m["name"], m["unit"], kind,
                   os.path.join(self.dir, "metrics", f"{m['name']}.py"))
            for kind in ("end_to_end", "per_layer")
            for m in self.spec[kind]
            if name in m.get("workloads", [name]))
        return Cell(name, config, traffic, int(w["chips"]), metrics)
