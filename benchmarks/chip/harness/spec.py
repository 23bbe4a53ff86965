"""Resolve a cell of ``BENCHMARK.json`` to the files that define it.

Everything is found by name: a workload names a configuration and a traffic
mix; the configuration entry names its file; the traffic file names its
driver (``drivers/<driver>.py``); every per-layer metric is a reader at
``metrics/<name>.py``; a cell's correctness limits are at
``limits/<workload>.json``.  Adding a cell, a mix, a configuration or a
metric adds files and entries and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import List, Optional

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict            # the configuration file's contents
    traffic_name: str
    traffic: dict           # the traffic file's contents
    driver: str             # drivers/<driver>.py
    end_to_end: List[dict]  # BENCHMARK.json metric entries this cell reports
    per_layer: List[dict]
    limits: dict            # limits/<workload>.json (may be empty)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def bench_path(root: str = ROOT) -> str:
    return os.path.join(root, "BENCHMARK.json")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, root: str = ROOT,
            bench: Optional[dict] = None) -> Cell:
    """The cell called ``workload``, with every file it names loaded."""
    bench = load_json(bench_path(root)) if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    centry = configs[w["config"]]
    config = load_json(os.path.join(root, centry["file"]))
    traffic = load_json(traffic_path(w["traffic"]))
    lim = limits_path(workload)
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=w["config"],
        config=config, traffic_name=w["traffic"], traffic=traffic,
        driver=traffic["driver"],
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, workload)],
        limits=load_json(lim) if os.path.exists(lim) else {})


def traffic_path(name: str) -> str:
    return os.path.join(HERE, "traffic", f"{name}.json")


def driver_path(name: str) -> str:
    return os.path.join(HERE, "drivers", f"{name}.py")


def metric_path(name: str) -> str:
    return os.path.join(HERE, "metrics", f"{name}.py")


def limits_path(workload: str) -> str:
    return os.path.join(HERE, "limits", f"{workload}.json")


def load_module(path: str, name: str):
    """Import a file by path (metric file names carry dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_driver(name: str):
    return load_module(driver_path(name), f"bench_driver_{name}")


def load_reader(metric: str):
    """``read(rec) -> float | None`` of the per-layer metric ``metric``."""
    safe = metric.replace(".", "_").replace("-", "_")
    return load_module(metric_path(metric), f"bench_metric_{safe}").read

