"""Find everything a run needs by name, from ``BENCHMARK.json`` and data files.

A cell (``workloads`` entry) names a configuration and a traffic mix; the
configuration's ``file`` names its schema.  Each of these lives in a file of
its own, so a later change adds a configuration, a mix or a per-layer metric
by adding files and ``BENCHMARK.json`` entries, and edits nothing here:

* ``<bench>/configs/<config>.json``  — the deployment (sizes, guarantees);
* ``<bench>/schemas/<schema>.py``    — loader, transaction generator and
  consistency rule of one schema (YCSB, TPC-C);
* ``<bench>/traffic/<traffic>.json`` — arrivals, cut bounds, key choice;
* ``<bench>/metrics/<metric>.py``    — one per-layer metric's reader,
  ``read(run) -> float | None``;
* ``<bench>/peaks.json``             — the chip's published peaks.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict, List


class SpecError(ValueError):
    """The benchmark's description is missing something a run needs."""


def _load_json(path: str) -> Dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def _module(path: str, name: str):
    if not os.path.exists(path):
        raise SpecError(f"missing file {path}")
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


class Bench:
    """``BENCHMARK.json`` at ``root`` and the data files it names."""

    def __init__(self, root: str):
        self.root = root
        self.bench_dir = os.path.join(root, "bench")
        self.doc = _load_json(os.path.join(root, "BENCHMARK.json"))

    def cell(self, name: str) -> Dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise SpecError(f"no workload named {name!r} in BENCHMARK.json")

    def config(self, name: str) -> Dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                cfg = _load_json(os.path.join(self.root, c["file"]))
                cfg.setdefault("name", name)
                return cfg
        raise SpecError(f"no config named {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> Dict:
        return _load_json(os.path.join(self.bench_dir, "traffic", f"{name}.json"))

    def schema(self, name: str):
        return _module(os.path.join(self.bench_dir, "schemas", f"{name}.py"),
                       f"bench_schema_{name}")

    def peaks(self, device_kind: str) -> Dict:
        table = _load_json(os.path.join(self.bench_dir, "peaks.json"))
        if device_kind not in table["devices"]:
            raise SpecError(f"no peaks for device kind {device_kind!r} in "
                            "peaks.json")
        return table["devices"][device_kind]

    def reader(self, metric: str) -> Callable:
        path = os.path.join(self.bench_dir, "metrics", f"{metric}.py")
        return _module(path, "bench_metric_" + metric.replace(".", "_")).read

    def end_to_end(self, cell: str) -> List[Dict]:
        return [m for m in self.doc["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]]

    def per_layer(self, cell: str) -> List[Dict]:
        """Per-layer metrics reported in ``cell``: those that list it, and
        those without a list whose ``moves`` metric the cell reports."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        out = []
        for m in self.doc["per_layer"]:
            if "workloads" in m:
                if cell in m["workloads"]:
                    out.append(m)
            elif m["moves"] in e2e:
                out.append(m)
        return out
