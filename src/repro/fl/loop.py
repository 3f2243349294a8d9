"""FL training-loop front-end: ``run_fl`` — rounds × (materialize → select →
train → aggregate → evaluate).  This is the end-to-end single-trial driver;
it is a thin shim over the declarative experiment surface
(repro.fl.experiment), with ``engine`` naming a registered runner:

* ``engine="sim"`` (default) — the compiled simulator (repro.fl.sim): the
  round loop is a device-resident lax.scan, one jit for the whole trial.
* ``engine="host"`` — the legacy per-round host loop (``run_fl_host`` below),
  kept as the parity oracle (tests/test_fl_sim.py) and the baseline the
  BENCH_sim_grid speedup is measured against.
* ``engine="sharded"`` — the SPMD pod-scale round (one mesh slice per
  client; see repro.fl.experiment._engine_sharded for its constraints).

All engines use the identical fold_in key tree, so trajectories agree within
float tolerance.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import plan_round
from repro.data import client_batches
from repro.obs import (make_collector, phase, record_memory_analysis,
                       resolve_metrics, resolve_telemetry_request, span)
from .round import (make_fl_round, resolve_adversary, resolve_aggregator,
                    stack_global_params)
from .workloads import Workload, get_workload

Array = jax.Array
PyTree = Any


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
    # AOT round/eval compile time, excluded from wall_s (the host engine's
    # half of the wall_s/compile_s honesty fix), and the per-round in-graph
    # metric series (name → (rounds, …) lists) when telemetry is on.
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
    runner from the engine registry ("sim" compiled grid, "host" legacy loop,
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
    """Legacy host-driven loop: one jitted round per step, eval on host.

    The parity oracle generalizes over the same workload registry as the
    compiled engine, so host≡sim trajectory pins hold per workload.

    The round and eval functions are AOT-compiled on the first round under a
    ``repro.obs`` compile span, so ``FLHistory.compile_s`` is real and
    ``wall_s`` excludes it (the engines' wall-clock numbers are comparable).
    ``telemetry`` names registered round metrics (or ``("auto",)``) evaluated
    on the round's device arrays; the series land in
    ``FLHistory.telemetry[name]`` as (rounds, …) stacks.

    ``adversary`` + ``adv`` (the (N,) byzantine mask) enable the engine-level
    attack behaviors, matching the compiled engine exactly
    (repro.fl.sim.make_trial_fn): byzantine clients poison their reported
    deltas and/or train from a τ-rounds-old global kept in a host-side
    window — the oracle half of the attacked-run host≡sim parity pins."""
    wl = get_workload(workload)
    ds = wl.dataset(ds)
    seed = fl_cfg.seed if seed is None else seed
    # `is None`, not falsy-or: rounds=0 is a zero-round dry-run (empty
    # history), not a request for the full schedule.
    rounds = fl_cfg.global_epochs if rounds is None else rounds
    agg = resolve_aggregator(aggregation, fl_cfg)
    poison_scale, tau = resolve_adversary(adversary)
    attacked = poison_scale is not None or tau > 0
    if attacked and adv is None:
        raise ValueError("adversary behaviors requested but no (N,) adv "
                         "byzantine mask passed")
    key = jax.random.PRNGKey(seed)
    params = wl.init(jax.random.fold_in(key, 1), ds)
    if agg.clustered:
        params = stack_global_params(params, agg.n_clusters)
    # Metrics resolve BEFORE the round builds: the delta_outlier series needs
    # the round to compute per-client update norms (a round-shape static).
    avail_keys = ["hists", "mask", "num_classes", "params_old", "params_new"]
    if agg.clustered:
        avail_keys += ["assign", "n_clusters", "centroids", "prev_centroids"]
    else:
        avail_keys += ["client_update_norms"]
    metrics = resolve_metrics(resolve_telemetry_request(telemetry), avail_keys)
    needs_norms = not agg.clustered and any(
        "client_update_norms" in m.requires for m in metrics)
    fl_round = make_fl_round(wl.make_loss(ds), fl_cfg, strategy, agg,
                             poison_scale=poison_scale, with_stale=tau > 0,
                             want_client_norms=needs_norms)
    eval_batch = wl.eval_set(ds, eval_n_per_class)
    eval_fn = wl.make_eval(ds)
    if agg.clustered:
        # Per-cluster eval + the valid-population mixture — the same f32 jnp
        # ops as the compiled simulator's scan body, so host≡sim parity holds
        # for the mixture exactly as it does for the single-model trajectory.
        @jax.jit
        def eval_jit(p, w):
            with phase("eval"):
                l_c, m_c = jax.vmap(lambda q: eval_fn(q, eval_batch))(p)
                tot = jnp.maximum(w.sum(), 1.0)
                return ((l_c * w).sum() / tot,
                        {"accuracy": (m_c["accuracy"] * w).sum() / tot},
                        m_c["accuracy"], l_c)
    else:
        @jax.jit
        def eval_jit(p):
            with phase("eval"):
                return eval_fn(p, eval_batch)

    hist_acc, hist_loss, hist_sel = [], [], []
    c_acc, c_loss, c_assign = [], [], []
    tel: Dict[str, List[np.ndarray]] = {}
    compile_s = 0.0
    round_exec = eval_exec = collector = prev_cent = None
    adv_dev = jnp.asarray(adv, jnp.float32) if attacked else None
    # stale_update window: θ_{t−τ}..θ_t, so [0] is the byzantine training
    # base (θ₀ while the run is younger than τ) — the host-side mirror of
    # the compiled engine's scan-carried ring.
    past = deque([params], maxlen=tau + 1) if tau else None
    t0 = time.time()
    for t in range(rounds):
        kt = jax.random.fold_in(key, 1000 + t)
        data = wl.materialize(ds, plan_round(plan, t),
                              jax.random.fold_in(kt, 0))
        batches = client_batches(data, fl_cfg.batch_size, wl.batch_keys)
        key_t = jax.random.fold_in(kt, 1)
        extra_args = ()
        if attacked:
            extra_args = (adv_dev, past[0] if tau else None)
        if round_exec is None:
            # AOT-compile once so compile_s is accounted (not folded into
            # wall_s) — round shapes are static across rounds.
            with span("compile", engine="host", what="round") as sp:
                round_exec = fl_round.lower(params, batches, data["hists"],
                                            key_t, *extra_args).compile()
            compile_s += sp.duration_s
            record_memory_analysis("host:round", round_exec)
        params_old = params
        params, info = round_exec(params, batches, data["hists"], key_t,
                                  *extra_args)
        if tau:
            past.append(params)
        if agg.clustered:
            if eval_exec is None:
                with span("compile", engine="host", what="eval") as sp:
                    eval_exec = eval_jit.lower(
                        params, info["cluster_weights"]).compile()
                compile_s += sp.duration_s
            loss, m, acc_c, loss_c = eval_exec(params, info["cluster_weights"])
            c_acc.append(np.asarray(acc_c, np.float32).tolist())
            c_loss.append(np.asarray(loss_c, np.float32).tolist())
            c_assign.append(np.asarray(info["cluster_assign"],
                                       np.int32).tolist())
        else:
            if eval_exec is None:
                with span("compile", engine="host", what="eval") as sp:
                    eval_exec = eval_jit.lower(params).compile()
                compile_s += sp.duration_s
            loss, m = eval_exec(params)
        ns, ms = float(info["num_selected"]), float(info["mask_sum"])
        assert ns == ms, (
            f"round {t}: selection budget violated — trained {ns} clients but "
            f"mask selects {ms}; a strategy's mask escaped its budget window")
        if metrics:
            if collector is None:
                statics = {"num_classes": int(data["hists"].shape[1]),
                           "n_clusters": agg.n_clusters}
                collector = jax.jit(make_collector(metrics, statics))
                if agg.clustered:
                    prev_cent = jnp.zeros_like(info["cluster_centroids"])
            dyn = {"hists": data["hists"], "mask": info["mask"],
                   "params_old": params_old, "params_new": params}
            if needs_norms:
                dyn["client_update_norms"] = info["client_update_norms"]
            if agg.clustered:
                dyn.update(assign=info["cluster_assign"],
                           centroids=info["cluster_centroids"],
                           prev_centroids=prev_cent)
                prev_cent = info["cluster_centroids"]
            for name, v in collector(dyn).items():
                tel.setdefault(name, []).append(np.asarray(v))
        hist_acc.append(float(m["accuracy"]))
        hist_loss.append(float(loss))
        hist_sel.append(float(info["num_selected"]))
        if verbose:
            print(f"  round {t + 1:3d}/{rounds}: acc={hist_acc[-1]:.4f} "
                  f"loss={hist_loss[-1]:.4f} selected={hist_sel[-1]:.0f}")
    wall_s = time.time() - t0 - compile_s
    return FLHistory(hist_acc, hist_loss, hist_sel, wall_s,
                     cluster_accuracy=c_acc if agg.clustered else None,
                     cluster_loss=c_loss if agg.clustered else None,
                     cluster_assign=c_assign if agg.clustered else None,
                     compile_s=compile_s,
                     telemetry={n: np.stack(v) for n, v in tel.items()}
                     if tel else None)


def success_rate(histories: List[FLHistory], threshold: float = 0.2) -> float:
    """Paper Table II: fraction of trials whose final accuracy > threshold."""
    return float(np.mean([h.final_accuracy > threshold for h in histories]))
