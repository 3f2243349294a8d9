"""Find a cell's parts by name.

``BENCHMARK.json`` lists the cells; each names a configuration and a traffic
mix.  Everything that belongs to one of them lives in a file of its own,
found here by name, so a later cell, mix or metric is added by adding files:

* ``bench/configs/<config>.json``  — the deployment's sizes, as run;
* ``bench/traffic/<mix>.json``     — the traffic mix's parameters;
* ``bench/limits/<cell>.json``     — the limits the correctness check holds;
* ``bench/engines/<engine>.py``    — how the window drives an engine;
* ``bench/references/<name>.py``   — the plain reference of a configuration;
* ``bench/metrics/<metric>.py``    — the reader of one per-layer metric.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import os
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``BENCHMARK.json``'s ``workloads`` with its files read."""
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _read_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT,
              bench_dir: Optional[str] = None) -> Cell:
    """The cell ``name`` with its configuration, traffic and limits.

    Raises ``KeyError`` for a cell ``BENCHMARK.json`` does not list."""
    bench_dir = bench_dir or os.path.join(root, "bench")
    spec = load_benchmark(root)
    entries = {w["name"]: w for w in spec["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(entries)}")
    w = entries[name]
    config = _read_json(os.path.join(bench_dir, "configs", w["config"] + ".json"))
    traffic = _read_json(os.path.join(bench_dir, "traffic", w["traffic"] + ".json"))
    limits = _read_json(os.path.join(bench_dir, "limits", name + ".json"))
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        limits={k: float(v["limit"]) for k, v in limits.items()},
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)])


def module(kind: str, name: str):
    """``bench.<kind>.<name>``: an engine, reference or metric reader."""
    return importlib.import_module(f"bench.{kind}.{name}")
