"""Finds what a cell is made of by the names in BENCHMARK.json.

- a configuration: ``configs/<config>.json`` (the deployment as run) and
  ``configs/<config>.py`` (its plain reference and control);
- a traffic mix: ``traffic/<traffic>.json``;
- a metric: ``metrics/<metric>.py``, whose ``read(run)`` returns a number
  or None when the run has nothing for it to read.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_module(path: str):
    name = "bench_" + re.sub(r"\W", "_", os.path.relpath(path, BENCH_DIR))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


class Cell:
    """One workload of BENCHMARK.json with its files loaded."""

    def __init__(self, bench: dict, name: str, workload: dict = None):
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload is None and name not in cells:
            raise SystemExit(f"unknown workload {name!r}; known: "
                             f"{sorted(cells)}")
        self.bench = bench
        self.name = name
        self.workload = workload or cells[name]
        self.chips = int(self.workload["chips"])
        cfg = self.workload["config"]
        self.config = load_json(os.path.join(BENCH_DIR, "configs",
                                             cfg + ".json"))
        self.reference = _load_module(os.path.join(BENCH_DIR, "configs",
                                                   cfg + ".py"))
        self.traffic = load_json(os.path.join(
            BENCH_DIR, "traffic", self.workload["traffic"] + ".json"))

    def metrics(self, trace: bool) -> list:
        """The metric entries this cell reports: end-to-end ones in an
        untraced run, per-layer ones in a traced run."""
        group = self.bench["per_layer" if trace else "end_to_end"]
        return [m for m in group
                if self.name in m.get("workloads", [self.name])]


def reader(metric_name: str):
    return _load_module(os.path.join(BENCH_DIR, "metrics",
                                     metric_name + ".py")).read
