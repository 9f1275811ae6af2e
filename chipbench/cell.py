"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by name:

- ``configs[].file``: the configuration as run (published sizes,
  ``reduced``, ``assumed``, ``family``);
- ``chipbench/traffic/<traffic>.json``: the traffic mix and deployment;
  its ``kind`` names the module ``chipbench/<kind>.py`` that runs it
  (``serve``), whose ``run(cell, seed, seconds, trace, t_process, peaks,
  log, control)`` returns the pieces of the result line: ``record``,
  ``setup_s``, ``end_to_end``, ``attempted``, ``failed``, ``memory``,
  ``compiles_in_window``, ``breakdown``, ``correct`` and ``checks``;
- ``chipbench/metrics/<metric>.py``: a per-layer metric's reader, with
  ``read(record) -> float | None``;
- ``chipbench/reference/<family>.py`` and ``chipbench/costs/<family>.py``:
  a family's plain reference and its operation and byte counts;
- ``chipbench/peaks.json``: the chips' peaks, keyed by ``device_kind``.

So a later cell or metric is new files plus new entries, and no file
that exists changes.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
from dataclasses import dataclass
from types import ModuleType
from typing import Any, Dict, List

BENCH_DIR = "chipbench"


@dataclass
class Metric:
    name: str
    unit: str
    spec: Dict[str, Any]


@dataclass
class Cell:
    root: str
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Metric]
    per_layer: List[Metric]

    @property
    def family(self) -> str:
        return self.config["family"]

    def kind(self) -> ModuleType:
        return importlib.import_module(f"{BENCH_DIR}.{self.traffic['kind']}")

    def reference(self) -> ModuleType:
        return importlib.import_module(f"{BENCH_DIR}.reference.{self.family}")

    def costs(self) -> ModuleType:
        return importlib.import_module(f"{BENCH_DIR}.costs.{self.family}")

    def reader(self, metric: str) -> ModuleType:
        path = os.path.join(self.root, BENCH_DIR, "metrics", metric + ".py")
        spec = importlib.util.spec_from_file_location(
            f"{BENCH_DIR}_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], workload: str,
             end_to_end: Dict[str, Dict[str, Any]]) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    moves = metric.get("moves")
    if moves is not None:                    # per-layer: where its metric is
        return _applies(end_to_end[moves], workload, end_to_end)
    return True


def load(root: str, workload: str) -> Cell:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        names = [w["name"] for w in bench["workloads"]]
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json: {names}")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = _load_json(os.path.join(root, conf["file"]))
    traffic = _load_json(os.path.join(root, BENCH_DIR, "traffic",
                                      entry["traffic"] + ".json"))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    return Cell(
        root=root, name=workload, chips=int(entry["chips"]), config=config,
        traffic=traffic,
        end_to_end=[Metric(m["name"], m["unit"], m)
                    for m in bench["end_to_end"] if _applies(m, workload, e2e)],
        per_layer=[Metric(m["name"], m["unit"], m)
                   for m in bench["per_layer"] if _applies(m, workload, e2e)])


def peaks(root: str, device_kind: str) -> Dict[str, Any]:
    """The chip's peaks; a device missing from the table is an error."""
    table = _load_json(os.path.join(root, BENCH_DIR, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{BENCH_DIR}/peaks.json: {sorted(table['devices'])}")
    return table["devices"][device_kind]
