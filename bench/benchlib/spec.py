"""Everything the harness runs is found by name, from files of its own.

- ``BENCHMARK.json`` (the checkout's root) lists configurations, cells and
  metrics;
- ``bench/configs/<config>.json``: a configuration as it is run;
- ``bench/workloads/<cell>.json``: the ByzSGD settings of a cell and the
  limits of its check;
- ``bench/traffic/<traffic>.json``: the parameters of a traffic mix;
- ``bench/metrics/<metric>.py``: the reader of one per-layer metric;
- ``bench/reference/<name>.py``: the plain reference a configuration names.

A later cell, configuration or metric is a new file and a new entry; no file
here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from types import ModuleType

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_module(path: str) -> ModuleType:
    """Import a file by path (metric names hold dots, so not by name)."""
    name = "bench_" + os.path.relpath(path, BENCH).replace(os.sep, "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with the files it names, loaded."""
    name: str
    chips: int
    config_name: str
    config: dict          # bench/configs/<config>.json
    traffic_name: str
    traffic: dict         # bench/traffic/<traffic>.json
    settings: dict        # bench/workloads/<cell>.json
    end_to_end: tuple     # the cell's end-to-end metric entries
    per_layer: tuple      # the cell's per-layer metric entries

    @property
    def reference(self) -> ModuleType:
        return load_module(os.path.join(BENCH, "reference",
                                        self.config["reference"] + ".py"))


def _for_cell(metric: dict, cell: str, reports: set[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in reports if "moves" in metric else True


def load_cell(name: str, benchmark: dict | None = None) -> Cell:
    bm = benchmark if benchmark is not None else _json(
        os.path.join(ROOT, "BENCHMARK.json"))
    entry = next((w for w in bm["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in bm['workloads']]}")
    conf = next(c for c in bm["configs"] if c["name"] == entry["config"])
    e2e = tuple(m for m in bm["end_to_end"] if _for_cell(m, name, set()))
    reports = {m["name"] for m in e2e}
    per_layer = tuple(m for m in bm["per_layer"]
                      if _for_cell(m, name, reports))
    return Cell(
        name=name, chips=int(entry["chips"]), config_name=conf["name"],
        config=_json(os.path.join(ROOT, conf["file"])),
        traffic_name=entry["traffic"],
        traffic=_json(os.path.join(BENCH, "traffic",
                                   entry["traffic"] + ".json")),
        settings=_json(os.path.join(BENCH, "workloads", name + ".json")),
        end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str) -> ModuleType:
    return load_module(os.path.join(BENCH, "metrics", name + ".py"))
