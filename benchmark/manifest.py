"""Where the harness finds things: BENCHMARK.json at the root of the
checkout, and under ``benchmark/`` one file per configuration, traffic mix,
cell, driver kind and per-layer metric, each found by its name."""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


class Cell:
    """One entry of ``workloads`` with its configuration, traffic and limits."""

    def __init__(self, manifest: dict, name: str, here: str = HERE):
        entry = next((w for w in manifest["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.chips = int(entry["chips"])
        self.config_name = entry["config"]
        self.traffic_name = entry["traffic"]
        conf = next(c for c in manifest["configs"]
                    if c["name"] == entry["config"])
        self.config = _load(os.path.join(os.path.dirname(here), conf["file"]))
        self.traffic = _load(os.path.join(here, "traffic",
                                          entry["traffic"] + ".json"))
        #: per-cell limits of `correct` (benchmark/workloads/<cell>.json)
        self.limits = _load(os.path.join(here, "workloads",
                                         name + ".json"))["limits"]
        self.driver = self.traffic["driver"]


def load_driver(kind: str):
    return importlib.import_module(f"benchmark.drivers.{kind}")


def load_reader(metric: str):
    """The reader of one per-layer metric: ``benchmark/layer_metrics/<name>.py``
    with dots in the metric's name written as underscores."""
    return importlib.import_module(
        "benchmark.layer_metrics." + metric.replace(".", "_").replace("-", "_"))


def peaks_for(device_kind: str, here: str = HERE) -> dict:
    table = _load(os.path.join(here, "peaks.json"))
    if device_kind not in table or device_kind.startswith("_"):
        raise LookupError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json; add "
            "its published peaks with their source before measuring on it")
    return table[device_kind]
