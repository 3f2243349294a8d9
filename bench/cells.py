"""Find a cell's parts by name.

``BENCHMARK.json`` lists the cells; each names a configuration and a traffic
mix.  Everything that belongs to one of them lives in a file of its own,
found here by name, so a later cell, mix, metric or client model is added
by adding files and entries, with no edit to a file that is there:

* ``bench/configs/<config>.json``  — the deployment's sizes, as run;
* ``bench/traffic/<mix>.json``     — the traffic mix's parameters;
* ``bench/limits/<cell>.json``     — the limits the correctness check holds;
* ``bench/engines/<engine>.py``    — how the window drives an engine;
* ``bench/references/<name>.py``   — the plain reference of a configuration;
* ``bench/metrics/<metric>.py``    — the reader of one per-layer metric.

What each kind of file provides:

* a reference module (named by the configuration's ``reference``):
  ``run_trial(cfg, traffic, plan, strategy, seed, precision="float32",
  ties=True)``, the trial's per-round ``accuracy``, ``loss`` and
  ``num_selected``, one trajectory for each way float32 may break a
  selection tie, and with ``precision=CONTROL_PRECISION`` the control:
  ``CONTROL_PRECISION``, a module constant, is the precision one step
  below the configuration's (bfloat16 for float32, int8 or fp8 for
  bfloat16); ``selected_samples(cfg, traffic, plan, strategy, seed)``, the
  valid samples the selected clients hold over the trial's rounds;
  ``num_params(cfg)``, the f32 parameters one client reports (the size of
  what aggregation reads); ``trial_train_flops(cfg, traffic, plan,
  strategy, seed)``, the forward + backward FLOPs the selected clients'
  local training requires, from shapes;
* an engine module (named by the configuration's ``engine`` where it
  names one, else by the mix's, so that a model and a mix stay
  independent files): a class ``Engine(cfg,
  traffic, plans)`` with ``setup(warm)``, ``call(i, seeds)`` (the (S, R, T)
  ``accuracy``, ``loss``, ``num_selected`` and ``mask_sum`` on the host),
  ``plan(i, r)``, ``free()``, and ``spans``, ``memory`` and ``compiled``.
  ``bench/engines/sim.py``'s ``Engine`` sets the client model up in three
  methods, ``workload()``, ``dataset()`` and ``check_model(ds)``: an
  engine for another model subclasses it and overrides those;
* a metric module: ``read(ctx)``, a number, or None where it finds nothing
  to read (``bench/run.py`` says what ``ctx`` holds).

Configuration keys that the shared files read: the generator
(``bench/traffic_gen.py``) ``num_clients``, ``num_classes`` (the label
space: image classes, or the domains of a language model's text),
``samples_per_client``, ``majority_per_client`` (case plans) and
``samples_min`` (ragged sizes, where given); the harness and the sim
engine ``reference``, ``engine`` (where given), ``workload``,
``clients_per_round``, ``local_epochs``, ``batch_size``, ``lr``,
``optimizer``, ``server_lr`` and ``eval_n_per_class``; the readers ``clients_per_round``, ``num_classes``,
``model_scopes`` (``bench/scopes.py``: the scopes the model names, each
split into its ``:fwd`` and ``:bwd`` passes) and ``model_scopes_whole``
(those of them read whole, such as an optimizer's update).  Every other
key is the reference's and the engine's own.
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


def engine(config: Dict[str, Any], traffic: Dict[str, Any]):
    """The ``Engine`` class that drives the cell: the configuration's
    ``engine`` where it names one (an engine that sets its model up), else
    the traffic mix's."""
    return module("engines", config.get("engine", traffic["engine"])).Engine
