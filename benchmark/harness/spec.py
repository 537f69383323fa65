"""Finds everything a run needs from names: ``BENCHMARK.json`` at the root
names the cells, configurations and metrics; each has a file of its own under
``benchmark/`` that this module loads by that name. Nothing here knows a
cell's, a configuration's or a metric's name."""

from __future__ import annotations

import importlib
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Spec:
    def __init__(self, root: Path = ROOT):
        self.root = root
        self.bench = _load_json(root / "BENCHMARK.json")

    def cell(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"BENCHMARK.json has no workload {name!r}; it has "
                       f"{[w['name'] for w in self.bench['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.bench["configs"]:
            if c["name"] == name:
                return _load_json(self.root / c["file"])
        raise KeyError(f"BENCHMARK.json has no config {name!r}")

    def workload_file(self, cell_name: str) -> dict:
        return _load_json(BENCH_DIR / "workloads" / f"{cell_name}.json")

    def metrics(self, group: str, cell_name: str) -> list[dict]:
        """The metrics of ``end_to_end`` or ``per_layer`` that this cell
        reports: those without a ``workloads`` key, or that list the cell."""
        return [m for m in self.bench[group]
                if "workloads" not in m or cell_name in m["workloads"]]

    def metric_file(self, metric_name: str) -> dict:
        return _load_json(BENCH_DIR / "metrics" / f"{metric_name}.json")


def load_driver(name: str):
    return importlib.import_module(f"benchmark.drivers.{name}")


def load_reader(name: str):
    return importlib.import_module(f"benchmark.readers.{name}")
