"""Finding a cell's files by the names in ``BENCHMARK.json``.

The harness holds no cell's, configuration's, traffic mix's or metric's
name: a workload entry names its configuration and its traffic mix,
a configuration entry names its file, a traffic mix is
``traffic/<name>.json``, a per-layer metric is
``layer_metrics/<name>.py`` and a builder ``builders/<name>.py``.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]   # the metric entries this cell reports
    per_layer: List[Dict[str, Any]]


def _read_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> Dict[str, Any]:
    return _read_json(os.path.join(ROOT, "BENCHMARK.json"))


def _reported_by(metrics: List[Dict[str, Any]], cell: str):
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, rehearse: bool = False) -> Cell:
    bench = load_benchmark()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(
            f"no workload {name!r} in BENCHMARK.json; it has "
            f"{sorted(by_name)}")
    entry = by_name[name]
    config_entry = next(
        c for c in bench["configs"] if c["name"] == entry["config"])
    config = _read_json(os.path.join(ROOT, config_entry["file"]))
    traffic = _read_json(
        os.path.join(HERE, "traffic", entry["traffic"] + ".json"))
    if rehearse:
        # the same files at the toy size each names for itself
        config = {**config, **config["rehearsal"]}
        traffic = {**traffic, **traffic["rehearsal"]}
    return Cell(
        name=name, chips=entry["chips"], config=config, traffic=traffic,
        end_to_end=_reported_by(bench["end_to_end"], name),
        per_layer=_reported_by(bench["per_layer"], name))


def load_builder(config: Dict[str, Any]):
    return importlib.import_module(f"benchmark.builders.{config['builder']}")


# the key of BENCHMARK.json a metric is listed under -> where its reader is
METRIC_DIRS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def load_metric(kind: str, name: str):
    return importlib.import_module(
        f"benchmark.{METRIC_DIRS[kind]}.{name}")
