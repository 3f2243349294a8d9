"""FL training-loop front-end: ``run_fl`` — rounds × (materialize → select →
train → aggregate → evaluate).  This is the end-to-end single-trial driver;
it is a thin shim over the declarative experiment surface
(repro.fl.experiment), with ``engine`` naming a registered runner:

* ``engine="sim"`` (default) — the compiled simulator (repro.fl.sim): the
  round loop is a device-resident lax.scan, one jit for the whole trial.
* ``engine="host"`` — ``run_fl_host`` below: the compiled engine's round
  step, compiled once and called per round from Python.  The sim and host
  engines are two drivers of one step; tests/test_fl_sim.py pins that they
  agree.
* ``engine="sharded"`` — the SPMD pod-scale round (one mesh slice per
  client; see repro.fl.experiment._engine_sharded for its constraints).

All engines use the identical fold_in key tree, so trajectories agree within
float tolerance.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import record_memory_analysis, span
from .round import resolve_aggregator
from .sim import _ones_avail, _split_telemetry, make_round_step
from .workloads import Workload, frozen_base


@dataclasses.dataclass
class FLHistory:
    """One trial's trajectories.  For clustered aggregation families
    (``Aggregator.n_clusters > 1``) ``accuracy``/``loss`` are the
    valid-population-weighted mixture over the per-cluster models, and the
    per-cluster detail rides in the optional fields: ``cluster_accuracy`` /
    ``cluster_loss`` are (rounds, n_clusters) and ``cluster_assign`` is the
    (rounds, N) round k-means assignment."""
    accuracy: List[float]
    loss: List[float]
    num_selected: List[float]
    wall_s: float
    cluster_accuracy: Optional[List[List[float]]] = None
    cluster_loss: Optional[List[List[float]]] = None
    cluster_assign: Optional[List[List[int]]] = None
    # AOT compile time of the round step, excluded from wall_s, and the
    # per-round in-graph metric series (name → (rounds, …) stacks) when
    # telemetry is on.
    compile_s: float = 0.0
    telemetry: Optional[Dict[str, Any]] = None

    @property
    def final_accuracy(self) -> float:
        return self.accuracy[-1]

    def summary(self) -> Dict[str, float]:
        return {"final_accuracy": self.accuracy[-1], "final_loss": self.loss[-1],
                "rounds": len(self.accuracy), "wall_s": self.wall_s}


def run_fl(plan: np.ndarray, fl_cfg, *, strategy: Optional[str] = None,
           aggregation: Optional[str] = None, rounds: Optional[int] = None,
           ds=None, seed: Optional[int] = None,
           verbose: bool = False, engine: str = "sim",
           avail: Optional[np.ndarray] = None,
           eval_n_per_class: int = 50, workload: str = "cnn") -> FLHistory:
    """Run FL over a non-IID label plan.  Returns history.

    Thin shim over the declarative surface (repro.fl.experiment): the plan
    becomes a single explicit-plan ScenarioSpec and ``engine`` picks the
    runner from the engine registry ("sim" compiled grid, "host" per-round loop,
    "sharded" SPMD).  ``workload`` names the registered client workload
    (repro.fl.workloads) — "cnn" (the paper model, default) or any other
    registered bundle such as "lm"."""
    from . import experiment
    scenario = experiment.ScenarioSpec.from_plan("scenario", plan, avail=avail)
    spec = experiment.ExperimentSpec(
        scenarios=(scenario,),
        strategies=(strategy or fl_cfg.selection,),
        seeds=(fl_cfg.seed if seed is None else seed,),
        engine=engine, fl=fl_cfg, aggregation=aggregation, rounds=rounds,
        eval_n_per_class=eval_n_per_class, workload=workload)
    res = experiment.run(spec, ds=ds)
    traj = res.trajectory(scenario.name, spec.strategies[0], spec.seeds[0])
    cl = res.meta.get("clustered")
    c_kw = {}
    if cl is not None:
        c_kw = {  # the (scenario, strategy, seed) = (0, 0, 0) cell's detail
            "cluster_accuracy": np.asarray(cl["cluster_accuracy"],
                                           np.float32)[0, 0, 0].tolist(),
            "cluster_loss": np.asarray(cl["cluster_loss"],
                                       np.float32)[0, 0, 0].tolist(),
            "cluster_assign": np.asarray(cl["cluster_assign"],
                                         np.int32)[0, 0, 0].tolist()}
    hist = FLHistory([float(a) for a in traj["accuracy"]],
                     [float(l) for l in traj["loss"]],
                     [float(s) for s in traj["num_selected"]],
                     res.wall_s + res.compile_s, **c_kw)
    if verbose:
        for t, (a, l, s) in enumerate(zip(hist.accuracy, hist.loss,
                                          hist.num_selected)):
            print(f"  round {t + 1:3d}/{len(hist.accuracy)}: acc={a:.4f} "
                  f"loss={l:.4f} selected={s:.0f}")
    return hist


def run_fl_host(plan: np.ndarray, fl_cfg, *, strategy: Optional[str] = None,
                aggregation: Optional[str] = None, rounds: Optional[int] = None,
                ds=None, seed: Optional[int] = None,
                verbose: bool = False, eval_n_per_class: int = 50,
                workload: "str | Workload" = "cnn",
                telemetry: Sequence[str] = (),
                adversary: Optional[dict] = None,
                adv: Optional[np.ndarray] = None) -> FLHistory:
    """Host-driven loop: the compiled engine's round step
    (repro.fl.sim.make_round_step), compiled once and called per round from
    Python, with the trajectories pulled to the host after every round.

    ``plan`` is the composed (T, N, n) plan, so availability is all ones
    and the strategy universe is ``(strategy,)``.  The step is AOT-compiled
    under a ``repro.obs`` compile span before the first round, so
    ``FLHistory.compile_s`` is real and ``wall_s`` excludes it.
    ``telemetry`` names registered round metrics (or ``("auto",)``); the
    series land in ``FLHistory.telemetry[name]`` as (rounds, …) stacks.
    ``adversary`` + ``adv`` (the (N,) byzantine mask) enable the
    engine-level attack behaviors, as in :func:`repro.fl.sim.make_trial_fn`.
    """
    name = strategy or fl_cfg.selection
    seed = fl_cfg.seed if seed is None else seed
    # `is None`, not falsy-or: rounds=0 is a zero-round dry-run (empty
    # history), not a request for the full schedule.
    rounds = fl_cfg.global_epochs if rounds is None else rounds
    rs = make_round_step(fl_cfg, ds, aggregation=aggregation,
                         eval_n_per_class=eval_n_per_class, strategies=(name,),
                         workload=workload, telemetry=telemetry,
                         adversary=adversary)
    if rs.attacked and adv is None:
        raise ValueError("adversary behaviors requested but no (N,) adv "
                         "byzantine mask passed")
    key = jax.random.PRNGKey(seed)
    # Built under jit, as inside the sim trial: op by op, the init rounds
    # differently, and the two engines would start from other bits.
    carry = jax.jit(rs.init, static_argnums=1)(key, plan.shape)
    inputs = (key, jnp.asarray(plan, jnp.int32), jnp.int32(0),
              _ones_avail(plan),
              jnp.asarray(adv, jnp.float32) if rs.attacked else None,
              frozen_base(rs.workload, ds))
    compile_s = 0.0
    if rounds:
        with span("compile", engine="host", what="round") as sp:
            step = jax.jit(rs.step).lower(carry, jnp.int32(0),
                                          *inputs).compile()
        compile_s = sp.duration_s
        record_memory_analysis("host:round", step)
    ys = []
    t0 = time.perf_counter()
    for t in range(rounds):
        carry, out = step(carry, jnp.int32(t), *inputs)
        main, tel = _split_telemetry(jax.device_get(out))
        ns, ms = float(main[2]), float(main[3])
        assert ns == ms, (
            f"round {t}: selection budget violated — trained {ns} clients but "
            f"mask selects {ms}; a strategy's mask escaped its budget window")
        ys.append((main, tel))
        if verbose:
            print(f"  round {t + 1:3d}/{rounds}: acc={float(main[0]):.4f} "
                  f"loss={float(main[1]):.4f} selected={ns:.0f}")
    wall_s = time.perf_counter() - t0

    def column(i, dtype=np.float32):
        return [np.asarray(main[i], dtype).tolist() for main, _ in ys]
    clustered = resolve_aggregator(aggregation, fl_cfg).clustered
    return FLHistory(column(0), column(1), column(2), wall_s,
                     cluster_accuracy=column(4) if clustered else None,
                     cluster_loss=column(5) if clustered else None,
                     cluster_assign=column(6, np.int32) if clustered else None,
                     compile_s=compile_s,
                     telemetry={n: np.stack([tl[n] for _, tl in ys])
                                for n in ys[0][1]}
                     if ys and ys[0][1] else None)


def success_rate(histories: List[FLHistory], threshold: float = 0.2) -> float:
    """Paper Table II: fraction of trials whose final accuracy > threshold."""
    return float(np.mean([h.final_accuracy > threshold for h in histories]))
