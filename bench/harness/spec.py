"""Finds a cell's configuration, traffic mix and metric readers by name.

``BENCHMARK.json`` at the checkout's root names everything; the files
live under its first path: ``configs/`` (named in the configuration's
entry), ``traffic/<mix>.json`` and ``metrics/<metric>.py``.  Adding a
cell, a mix or a metric is adding files and entries, never editing this.
"""
from __future__ import annotations

import importlib.util
import json
import os


class SpecError(SystemExit):
    """BENCHMARK.json or a file it names is missing or malformed."""


def _read_json(path: str) -> dict:
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, ValueError) as e:
        raise SpecError(f"cannot read {path}: {e}") from e


class Bench:
    """``BENCHMARK.json`` at ``root`` and the files it names."""

    def __init__(self, root: str):
        self.root = root
        self.doc = _read_json(os.path.join(root, "BENCHMARK.json"))
        self.home = os.path.join(root, self.doc["paths"][0])

    def cell(self, name: str) -> dict:
        for c in self.doc["workloads"]:
            if c["name"] == name:
                return c
        raise SpecError(f"no cell {name!r} in BENCHMARK.json; cells: "
                        f"{[c['name'] for c in self.doc['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return _read_json(os.path.join(self.root, c["file"]))
        raise SpecError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return _read_json(os.path.join(self.home, "traffic", f"{name}.json"))

    def reference(self, cfg: dict) -> dict:
        return _read_json(os.path.join(self.root, cfg["reference"]))

    def metrics(self, cell: str, trace: bool) -> list:
        """The cell's end-to-end metrics, or with ``trace`` its per-layer
        metrics: those that list the cell, and, where a metric lists no
        cells, every cell (per-layer: every cell that reports the
        end-to-end metric it moves)."""
        e2e = [m for m in self.doc["end_to_end"]
               if cell in m.get("workloads", [cell])]
        if not trace:
            return e2e
        moved = {m["name"] for m in e2e}
        return [m for m in self.doc["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in moved)]

    def reader(self, metric: str):
        """The ``read(record)`` function of ``metrics/<metric>.py``."""
        path = os.path.join(self.home, "metrics", f"{metric}.py")
        if not os.path.exists(path):
            raise SpecError(f"metric {metric!r} has no reader at {path}")
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
