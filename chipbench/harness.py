"""Finds what ``BENCHMARK.json`` names: a cell's configuration, traffic,
limits, driver and per-layer metric readers, each in a file of its own.

- ``configs/<config>.json``: the model's sizes, its source and the
  program's architecture that runs them (the ``file`` in BENCHMARK.json).
- ``traffic/<traffic>.json``: the mix, one general driver's parameters;
  its ``kind`` picks the driver ``drivers/<kind>.py``.
- ``limits/<cell>.json``: the limit of each number that decides
  ``correct``, with the readings it was set from.
- ``metrics/<metric>.py``: one reader per per-layer metric, a
  ``read(ctx)`` that returns a number, or None where it finds nothing.

A later cell, configuration or metric is therefore new files and entries,
never an edit of these.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.isfile(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Benchmark:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.spec = _json(os.path.join(root, "BENCHMARK.json"))

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return _json(os.path.join(self.root, c["file"]))
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return _json(os.path.join(HERE, "traffic", f"{name}.json"))

    def limits(self, cell: str) -> dict:
        return _json(os.path.join(HERE, "limits", f"{cell}.json"))["limits"]

    def driver(self, kind: str):
        return load_module(os.path.join(HERE, "drivers", f"{kind}.py"),
                           f"chipbench_driver_{kind}")

    def metrics(self, cell: str, trace: bool) -> List[dict]:
        """The cell's metrics: its end-to-end ones without tracing, its
        per-layer ones with."""
        group = self.spec["per_layer" if trace else "end_to_end"]
        return [m for m in group if cell in m.get("workloads", [cell])]

    @staticmethod
    def reader(metric: str):
        return load_module(os.path.join(HERE, "metrics", f"{metric}.py"),
                           f"chipbench_metric_{metric}")
