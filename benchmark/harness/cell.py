"""A cell and its files, found by the names in BENCHMARK.json.

Nothing here knows a configuration, a traffic mix, a query or a metric
by name: a later PR adds a ``workloads`` entry and new files, and edits
no file that is there (benchmark/README.md lists the files).
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

#: the checkout this file lies in
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(root: str, kind: str, name: str):
    """The module ``<root>/benchmark/<kind>/<name>.py``, loaded by path
    so that a checkout, an export and a test's copy each get their own."""
    path = os.path.join(root, "benchmark", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}", path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"benchmark: no {kind} file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict            # benchmark/configs/<config>.json
    traffic: dict           # benchmark/traffic/<suite>/<traffic>.json
    end_to_end: tuple       # BENCHMARK.json entries this cell reports
    per_layer: tuple

    @property
    def suite(self) -> str:
        return self.config["suite"]

    @property
    def dataset(self) -> str:
        """Configurations of one suite and scale share their data."""
        return f"{self.suite}-sf{self.config['scale_factor']:g}"


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"benchmark: no workload {name!r} in BENCHMARK.json")
    conf_entry = next(c for c in bench["configs"]
                      if c["name"] == entry["config"])
    config = _read_json(os.path.join(root, conf_entry["file"]))
    traffic = _read_json(os.path.join(
        root, "benchmark", "traffic", config["suite"],
        entry["traffic"] + ".json"))
    if config["chips"] != entry["chips"]:
        raise ValueError(f"benchmark: {name}: chips {entry['chips']} in "
                         f"BENCHMARK.json, {config['chips']} in its config")

    def mine(metrics):
        return tuple(m for m in metrics
                     if "workloads" not in m or name in m["workloads"])
    return Cell(name=name, chips=entry["chips"], config=config,
                traffic=traffic,
                end_to_end=mine(bench["end_to_end"]),
                per_layer=mine(bench["per_layer"]))
