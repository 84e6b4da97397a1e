"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one cell or one per-layer
metric is a file of its own under the benchmark's directory; a later PR
adds files and manifest entries and edits nothing that is there.
"""

from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")


def load_json(path: str) -> Dict[str, Any]:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def resolve(dotted: str) -> Any:
    """``package.module:attribute`` to the object it names."""
    module, _, attr = dotted.partition(":")
    obj = importlib.import_module(module)
    return getattr(obj, attr) if attr else obj


@dataclass
class Metric:
    """One per-layer metric: its manifest entry and its reader."""

    name: str
    unit: str
    reader: Callable[..., Optional[float]]
    args: Dict[str, Any] = field(default_factory=dict)


@dataclass
class Cell:
    """One cell with everything a run of it needs."""

    name: str
    chips: int
    workload: Dict[str, Any]       # the cell's own file
    traffic: Dict[str, Any]        # the traffic mix's file
    config: Dict[str, Any]         # the configuration's file
    reference: Any                 # the configuration's plain reference
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Metric]


def load_metric(entry: Dict[str, Any], metrics_dir: str) -> Metric:
    spec = load_json(os.path.join(metrics_dir, entry["name"] + ".json"))
    return Metric(name=entry["name"], unit=entry["unit"],
                  reader=resolve(spec["reader"]),
                  args=dict(spec.get("args", {})))


def _reported(metric: Dict[str, Any], cell: str, reports: List[str]) -> bool:
    """A metric with a ``workloads`` key is reported in those cells; one
    without is reported wherever the metric it moves is (``moves``), or,
    for an end-to-end metric, in every cell."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reports


def load_cell(name: str, *, manifest_path: str = MANIFEST,
              bench_dir: str = BENCH_DIR) -> Cell:
    manifest = load_json(manifest_path)
    root = os.path.dirname(os.path.abspath(manifest_path))
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in manifest["workloads"])
        raise KeyError(f"no workload {name!r} in the manifest; it has {known}")
    config_entry = next(c for c in manifest["configs"]
                        if c["name"] == entry["config"])
    config = load_json(os.path.join(root, config_entry["file"]))
    workload = load_json(os.path.join(bench_dir, "workloads", name + ".json"))
    end_to_end = [m for m in manifest["end_to_end"]
                  if _reported(m, name, [])]
    reports = [m["name"] for m in end_to_end]
    per_layer = [load_metric(m, os.path.join(bench_dir, "metrics"))
                 for m in manifest["per_layer"]
                 if _reported(m, name, reports)]
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     entry["traffic"] + ".json"))
    return Cell(name=name, chips=int(entry["chips"]), workload=workload,
                traffic=traffic, config=config, reference=resolve(config["reference"]),
                end_to_end=end_to_end, per_layer=per_layer)
