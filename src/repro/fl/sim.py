"""Compiled multi-trial FL simulation engine: the whole experiment is ONE
XLA program.

One FL round is defined once, here: :func:`make_round_step` builds the
round step (materialize → select → budget gather → local training,
reduction and server update → eval → metric state) and the carry it
threads.  :func:`make_trial_fn` scans that step (``jax.lax.scan``) over the
rounds of one trial, and the grid ``jax.vmap``-s the trial over seeds ×
strategies × cases; selection strategies become a traced stack+index
dispatch (a batchable axis over the requested strategy set).  One compile,
zero host round-trips, the full grid in a single device launch:

    plans = stack_case_plans(CASES, cfg, seed0=0)          # (K, T, N, n)
    res = run_grid(plans, cfg, strategies=("random", "labelwise"),
                   seeds=range(5))                         # one compiled call
    res.accuracy            # (K, S, R, rounds) f32

The host engine (repro.fl.loop.run_fl_host) compiles the same step and
calls it once per round from Python, so the two engines differ only in who
drives the rounds; tests/test_fl_sim.py pins that they agree.

Scenario transforms compose: plans may carry −1 padding from
``quantity_skew`` / ``apply_availability`` (repro.core.noniid), and
``avail`` threads a (T, N) availability mask into selection on-device —
an unavailable client reports an empty histogram and cannot be selected.

The engine is workload-agnostic: what each client trains (the paper CNN, an
LM over domain-skewed token streams, …) comes from the workload registry
(repro.fl.workloads) — ``workload=`` names a registered bundle whose traced
init/materialize/loss/eval compile into the scan body.  This module contains
no model- or dataset-specific code.

The scan body's non-training hot path — per-client histograms (inside the
workload's ``materialize``) and the FedAvg/FedSGD reduction (inside
``client_update_step``) — compiles through the backend compute dispatch
(repro.kernels.dispatch): Pallas kernels on TPU, the parity-pinned XLA
references on CPU, decided at trace time so the compiled grid contains
exactly one implementation.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (STRATEGIES, cluster_counts, kmeans_cluster,
                        registered_strategies, selection_budget, strategy_id)
from repro.data import client_batches
from repro.obs import (collect_metrics, phase, record_memory_analysis,
                       resolve_metrics, resolve_telemetry_request, span)
from repro.optim import get_optimizer
from .round import (client_update_step, clustered_update_step,
                    resolve_adversary, resolve_aggregator,
                    stack_global_params)
from .workloads import Workload, frozen_base, get_workload, with_frozen

Array = jax.Array
PyTree = Any


def __getattr__(name: str):
    # ENGINE_STRATEGIES (the pre-registry frozen tuple) is now a live view of
    # the append-only registry (repro.core.selection.register_strategy):
    # builtin ids 0..6 are unchanged, registered extensions append.  Kept as a
    # module attribute for back-compat; prefer registered_strategies().
    if name == "ENGINE_STRATEGIES":
        return registered_strategies()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


@dataclasses.dataclass
class GridResult:
    """Stacked trajectories from one compiled grid.

    Leading axes follow the call: (*grid_axes, rounds) where grid_axes is
    (cases, strategies, seeds) for run_grid, or () for simulate.

    Clustered aggregation families fill the optional per-cluster fields:
    ``accuracy``/``loss`` become the valid-population-weighted mixture over
    the n_clusters models, ``cluster_accuracy``/``cluster_loss`` carry the
    (*grid_axes, rounds, n_clusters) per-model trajectories and
    ``cluster_assign`` the (*grid_axes, rounds, N) round k-means assignment.
    """
    accuracy: np.ndarray
    loss: np.ndarray
    num_selected: np.ndarray
    wall_s: float
    compile_s: float = 0.0
    cluster_accuracy: Optional[np.ndarray] = None
    cluster_loss: Optional[np.ndarray] = None
    cluster_assign: Optional[np.ndarray] = None
    # In-graph metric series (repro.obs registry): name → (*grid_axes,
    # rounds, …) arrays, collected inside the scan when telemetry was
    # requested; None otherwise (the compiled program is then unchanged).
    telemetry: Optional[Dict[str, np.ndarray]] = None

    @property
    def final_accuracy(self) -> np.ndarray:
        return self.accuracy[..., -1]

    def success_rate(self, threshold: float = 0.2, axis: int = -1) -> np.ndarray:
        """Paper Table II: fraction of seed-axis trials with final acc > τ.

        On a single-trial result (simulate()) there is no trial axis to
        average over; the 0/1 success indicator is returned instead."""
        success = self.accuracy[..., -1] > threshold
        if success.ndim == 0:
            return success.astype(np.float64)
        return success.mean(axis=axis)


def _select(sid: Array, key: Array, hists: Array, n_sel: int,
            universe: Sequence[str]):
    """Traced strategy dispatch → (mask, scores, order, budget).

    Every strategy in ``universe`` is computed unconditionally (each is
    sub-millisecond math on an (N, C) histogram) and the requested one is
    gathered by ``sid`` — an index into ``universe``, NOT a global
    strategy_id.  Deliberately stack+index rather than ``lax.switch``: under
    a batched ``sid`` a switch lowers to run-all-branches-and-select anyway,
    and the branch-free form keeps the scan body a single straight-line
    graph.  The universe is the *requested* strategy set, so the compiled
    program only pays for the strategies the grid actually runs; a
    single-entry universe compiles to a direct call.

    ``budget`` is the STATIC gather width — the max of the universe's
    declared ``SelectionResult.budget``s (the compiled program is shared
    across the strategy axis, so it must size training for the widest
    strategy; narrower strategies' extra slots are dead, mask 0).  A universe
    containing ``full`` therefore sizes training for the whole population."""
    n_clients = hists.shape[0]
    if len(universe) == 1:
        r = STRATEGIES[universe[0]](key, hists, n_sel)
        return r.mask, r.scores, r.order, selection_budget(r, n_sel, n_clients)
    rs = [STRATEGIES[n](key, hists, n_sel) for n in universe]
    budget = max(selection_budget(r, n_sel, n_clients) for r in rs)
    masks = jnp.stack([r.mask for r in rs])
    scores = jnp.stack([r.scores for r in rs])
    orders = jnp.stack([r.order for r in rs])
    return masks[sid], scores[sid], orders[sid], budget


class RoundStep(NamedTuple):
    """One FL round as a pure function of device arrays, built once from the
    trace-time statics (:func:`make_round_step`).

    ``init(key, plan_shape)`` builds the round's carry: the model (the
    ``(n_clusters, *params)`` stack for a clustered family), with last
    round's centroids beside it where a metric reads them, or the
    stale-update ring where the adversary asks for one.  ``step(carry, t,
    key, plan, sid, avail, adv, frozen) -> (carry, ys)`` runs round ``t``;
    ``ys`` is the trajectory tuple ``(acc, loss, nsel, msum)`` (seven
    entries for a clustered family, see :func:`make_trial_fn`), paired with
    the ``{name: value}`` metric dict when telemetry resolved metrics.

    The sim engine scans ``step`` inside one compiled trial; the host engine
    (repro.fl.loop.run_fl_host) compiles it once and calls it per round."""
    init: Callable
    step: Callable
    attacked: bool
    workload: Workload


def make_round_step(fl_cfg, ds=None, *,
                    aggregation: Optional[str] = None,
                    eval_n_per_class: int = 50,
                    strategies: Optional[Sequence[str]] = None,
                    workload: "str | Workload" = "cnn",
                    telemetry: Sequence[str] = (),
                    adversary: Optional[dict] = None) -> RoundStep:
    """The one FL round every single-device driver runs: materialize (with
    the availability weighting) → select (the strategy universe's traced
    dispatch and the validity gate) → gather the strategy's budget → local
    training, reduction and server update (``client_update_step``, or
    ``clustered_update_step`` after the round's k-means) → eval → the
    metric state.  Arguments as :func:`make_trial_fn`'s, which scans it."""
    wl = get_workload(workload)
    ds = wl.dataset(ds)
    has_frozen = wl.frozen is not None
    universe = (tuple(strategies) if strategies is not None
                else registered_strategies())
    for name in universe:
        strategy_id(name)  # validate early: unknown names raise here
    agg = resolve_aggregator(aggregation, fl_cfg)
    poison_scale, tau = resolve_adversary(adversary)
    attacked = poison_scale is not None or tau > 0
    if attacked and agg.clustered:
        raise ValueError(
            "engine-level adversary behaviors (poison/stale_update) are not "
            "defined for clustered aggregation families; use the plan-level "
            "label_flip transform or a single-global-model aggregator")
    if tau > 0 and agg.base == "fedsgd":
        raise ValueError(
            "stale_update needs a stale TRAINING base; the fedsgd family "
            "reports one gradient at the current global, so the behavior is "
            "undefined for it")
    if has_frozen and agg.clustered:
        raise ValueError(
            f"workload {wl.name!r} has a frozen part; clustered aggregation "
            "families train whole per-cluster models and do not run it")
    n_sel = fl_cfg.clients_per_round
    opt = get_optimizer(fl_cfg.optimizer, fl_cfg.lr)
    loss_fn = wl.make_loss(ds)
    eval_batch = wl.eval_set(ds, eval_n_per_class)
    eval_fn = wl.make_eval(ds)
    avail_keys = ["hists", "mask", "num_classes", "params_old", "params_new"]
    if agg.clustered:
        avail_keys += ["assign", "n_clusters", "centroids", "prev_centroids"]
    else:
        avail_keys += ["client_update_norms"] + list(wl.counters)
    metrics = resolve_metrics(resolve_telemetry_request(telemetry), avail_keys)
    # Only clustered centroid-drift needs last round's centroids in the
    # carry; everything else observes the current round alone.
    needs_prev = agg.clustered and any(
        "prev_centroids" in m.requires for m in metrics)
    # Per-client update norms are computed only when a resolved metric asks
    # (the delta_outlier z-scores) — same gating rule as needs_prev, so
    # telemetry off keeps the round bit-identical.
    needs_norms = not agg.clustered and any(
        "client_update_norms" in m.requires for m in metrics)

    def init(key: Array, plan_shape: Sequence[int]):
        params = wl.init(jax.random.fold_in(key, 1), ds)
        if agg.clustered:
            params = stack_global_params(params, agg.n_clusters)
        if needs_prev:
            # (M, C) zeros for round 0 — C via a shape-only materialize probe
            # (trace-time, no FLOPs).
            probe = jax.eval_shape(
                lambda p: wl.materialize(ds, p, jax.random.PRNGKey(0)),
                jax.ShapeDtypeStruct(tuple(plan_shape[1:]), jnp.int32))
            return (params, jnp.zeros(
                (agg.n_clusters, probe["hists"].shape[1]), jnp.float32))
        if tau:
            # stale_update ring: slot j holds the newest θ_{t'} with
            # t' ≡ j (mod τ+1); every slot starts at θ₀ so reads before
            # round τ see the init (a client can never be staler than the
            # run is old).
            return (params, jax.tree_util.tree_map(
                lambda p: jnp.broadcast_to(p[None], (tau + 1,) + p.shape),
                params))
        return params

    def step(carry, t: Array, key: Array, plan: Array, sid: Array,
             avail: Array, adv: Optional[Array] = None,
             frozen: Optional[PyTree] = None):
        t_static = plan.shape[0]
        prev_cent = ring = None
        if needs_prev:
            params, prev_cent = carry
        elif tau:
            params, ring = carry
        else:
            params = carry
        with phase("materialize"):
            kt = jax.random.fold_in(key, 1000 + t)
            plan_t = jax.lax.dynamic_index_in_dim(plan, t % t_static, 0,
                                                  keepdims=False)
            avail_t = jax.lax.dynamic_index_in_dim(
                avail, t % avail.shape[0], 0, keepdims=False)
            data = wl.materialize(ds, plan_t, jax.random.fold_in(kt, 0))
            # Availability is applied ONCE, here: a dark client reports an
            # empty histogram, so every registry strategy's validity gate
            # excludes it.
            hists = data["hists"] * avail_t[:, None]
            batches = client_batches(data, fl_cfg.batch_size, wl.batch_keys)
        with phase("select"):
            mask, scores, order, budget = _select(
                sid, jax.random.fold_in(kt, 1), hists, n_sel, universe)
            # Enforce the registry validity contract engine-side: a client
            # with an empty (possibly availability-zeroed) histogram is
            # never live, even under a strategy whose own gate forgot it —
            # here the plan may be intact (mask-mode avail), so the dark
            # client's data is real and training it would silently leak
            # influence.
            mask = mask * (hists.sum(-1) > 0)
            idx = order[:budget]      # the strategy's static gather width
            live = mask[idx]
            data_sel = jax.tree_util.tree_map(lambda x: x[idx], batches)

        def emit(new_params, main, cent=None, assign=None, norms=None,
                 counts=None):
            # Metric collection is additive: the trajectory tuple is
            # untouched, the series ride alongside as a second ys leaf.
            if needs_prev:
                new_carry = (new_params, cent)
            elif tau:
                new_carry = (new_params, ring)
            else:
                new_carry = new_params
            if not metrics:
                return new_carry, main
            state = {"hists": hists, "mask": mask,
                     "num_classes": hists.shape[1],
                     "params_old": params, "params_new": new_params}
            if agg.clustered:
                state.update(assign=assign, n_clusters=agg.n_clusters,
                             centroids=cent, prev_centroids=prev_cent)
            if needs_norms:
                state["client_update_norms"] = norms
            state.update(counts or {})
            return new_carry, (main, collect_metrics(metrics, state))

        if agg.clustered:
            with phase("cluster"):
                assign, cent = kmeans_cluster(hists, agg.n_clusters,
                                              n_iters=agg.kmeans_iters)
            new_params, m = clustered_update_step(
                params, assign[idx], data_sel, live, loss_fn, opt, fl_cfg,
                agg)
            with phase("eval"):
                loss_c, ev_m = jax.vmap(
                    lambda p: eval_fn(p, eval_batch))(new_params)
                acc_c = ev_m["accuracy"]
                # The scalar trajectory is the mixture over per-cluster
                # models, weighted by each cluster's VALID population (every
                # client the round could have trained, not just the selected
                # ones) — a single comparable number against the one-model
                # baseline.
                valid = (hists.sum(-1) > 0).astype(jnp.float32)
                w = cluster_counts(assign, agg.n_clusters, weights=valid)
                tot = jnp.maximum(w.sum(), 1.0)
            return emit(new_params,
                        ((acc_c * w).sum() / tot, (loss_c * w).sum() / tot,
                         live.sum(), mask.sum(), acc_c, loss_c, assign),
                        cent=cent, assign=assign)
        stale = None
        if tau:
            # Write θ_t into its ring slot FIRST (so τ=0 degenerates to
            # reading the current params), then read θ_{t−τ} (θ₀ before
            # round τ — every unwritten slot still holds the init).
            ring = jax.tree_util.tree_map(
                lambda r, p: jax.lax.dynamic_update_index_in_dim(
                    r, p, t % (tau + 1), 0), ring, params)
            stale = jax.tree_util.tree_map(
                lambda r: jax.lax.dynamic_index_in_dim(
                    r, jnp.mod(t - tau, tau + 1), 0, keepdims=False),
                ring)
        new_params, m = client_update_step(
            params, data_sel, live, loss_fn, opt, fl_cfg, agg,
            adv=adv[idx] if attacked else None,
            poison_scale=poison_scale, stale_params=stale,
            want_client_norms=needs_norms, frozen=frozen,
            counters=wl.counters, map_clients=wl.map_clients)
        norms = None
        if needs_norms:
            norms = (jnp.zeros(hists.shape[0], jnp.float32)
                     .at[idx].set(m["update_norm"] * live))
        counts = {c: _slot_counts(m, c, live, params, data_sel, frozen)
                  for c in wl.counters}

        with phase("eval"):
            ev_loss, ev_m = eval_fn(new_params, with_frozen(eval_batch, frozen))
        return emit(new_params, (ev_m["accuracy"], ev_loss, live.sum(),
                                 mask.sum()), norms=norms, counts=counts)

    def _slot_counts(m, name, live, params, data_sel, frozen):
        """Counter ``name`` per selected slot, zero for a dead slot.  The
        round step reports every counter of the workload; a step put in its
        place that reports none (a test's stand-in that leaves the state
        unchanged) reads zeros."""
        if name in m:
            return m[name] * live.reshape((-1,) + (1,) * (m[name].ndim - 1))
        batch = jax.tree_util.tree_map(lambda x: x[0, 0], data_sel)
        one = jax.eval_shape(
            lambda p, b: loss_fn(p, with_frozen(b, frozen))[1][name],
            params, batch)
        return jnp.zeros(live.shape + one.shape, jnp.float32)

    return RoundStep(init, step, attacked, wl)


def make_trial_fn(fl_cfg, ds=None, *,
                  aggregation: Optional[str] = None,
                  rounds: Optional[int] = None,
                  eval_n_per_class: int = 50,
                  strategies: Optional[Sequence[str]] = None,
                  workload: "str | Workload" = "cnn",
                  telemetry: Sequence[str] = (),
                  adversary: Optional[dict] = None):
    """Build ``trial(plan, sid, seed, avail) -> (acc, loss, nsel, msum)`` —
    one FL trial as a pure jit/vmap-able function of device arrays.

    plan: (T, N, n_max) int32 (−1 pad); sid: scalar int32 index into
    ``strategies`` (default: every registered strategy, in stable-id order —
    note that universe includes ``full``, so training is sized for the whole
    population; pass the strategies you actually run); seed: scalar int32;
    avail: (T, N) f32 availability (pass all-ones for the no-dropout
    scenario).  Returns four (rounds,) f32 trajectories: accuracy, loss,
    clients trained (``live.sum()``), and the selection mask sum — the last
    two must be equal (the budget invariant; ``simulate``/``grid_arrays``
    assert it after execution).

    ``workload`` names a registered client workload (repro.fl.workloads) — or
    is a Workload instance — whose traced init/materialize/loss/eval fns are
    compiled into the scan body; this engine contains no workload-specific
    code.  ``ds`` overrides the workload's default dataset.

    ``aggregation`` resolves through the aggregator registry
    (repro.core.aggregation).  A clustered family extends the return to
    seven trajectories: the scalar accuracy/loss become the
    valid-population-weighted mixture over the per-cluster models, followed
    by (rounds, n_clusters) per-cluster accuracy/loss and the (rounds, N)
    round k-means assignment.

    ``telemetry`` names registered round metrics (repro.obs; ``("auto",)``
    expands to every applicable builtin, empty falls back to the
    ``REPRO_TELEMETRY`` env var).  With metrics resolved the trial returns
    ``(trajectories, {name: (rounds, …)})`` — the metric series ride the
    same scan ys — and with none resolved the returned function (and the
    compiled program) is exactly the telemetry-free one.

    ``adversary`` (see :func:`resolve_adversary`) enables the engine-level
    byzantine behaviors: with a non-empty ``behaviors`` set, the trial takes
    a trailing ``adv`` argument — the (N,) 0/1 per-client byzantine mask
    (``repro.core.adversary_mask``) — and byzantine clients ``poison`` their
    reported updates (``scale``·delta) and/or train from a ``tau``-rounds-old
    global (``stale_update``; the scan carry gains a (τ+1)-deep parameter
    ring, reading θ₀ for t < τ).  Behaviors are rejected for clustered
    families.  No behaviors → the 4-argument trial, program unchanged.

    A workload with a frozen part (repro.fl.workloads) makes the trial take
    its base as the sixth argument, ``trial(plan, sid, seed, avail, adv,
    frozen)`` (``adv`` None without an adversary): an input shared by every
    trial and client, which a grid passes unbatched (``in_axes=None``).  The
    carried model state is the trainable tree.  The workload's
    ``counters`` become round state of the same names, (budget, …) per
    selected slot, for the telemetry metrics that read them.
    """
    rs = make_round_step(fl_cfg, ds, aggregation=aggregation,
                         eval_n_per_class=eval_n_per_class,
                         strategies=strategies, workload=workload,
                         telemetry=telemetry, adversary=adversary)
    # `is None`, not falsy-or: rounds=0 is a legitimate zero-round dry-run
    # (empty trajectories), not a request for the full schedule.
    num_rounds = fl_cfg.global_epochs if rounds is None else rounds

    def trial(plan: Array, sid: Array, seed: Array, avail: Array,
              adv: Optional[Array] = None, frozen: Optional[PyTree] = None):
        if rs.attacked and adv is None:
            raise ValueError("adversary behaviors requested at trial build "
                             "time need the (N,) adv mask as a 5th argument")
        if rs.workload.frozen is not None and frozen is None:
            raise ValueError(f"workload {rs.workload.name!r} has a frozen "
                             "part: the trial needs its base as the 6th "
                             "argument")
        key = jax.random.PRNGKey(seed)
        _, traj = jax.lax.scan(
            lambda carry, t: rs.step(carry, t, key, plan, sid, avail, adv,
                                     frozen),
            rs.init(key, plan.shape), jnp.arange(num_rounds))
        return traj

    @functools.wraps(trial)
    def traced_trial(*args, **kwargs):
        # The trial runs only under jit/vmap, so this span is its Python
        # tracing: once per lowering.
        with span("trace:trial"):
            return trial(*args, **kwargs)

    return traced_trial


def _ones_avail(plan: np.ndarray) -> jnp.ndarray:
    return jnp.ones(plan.shape[:2], jnp.float32)


def _cluster_fields(out: tuple) -> dict:
    """GridResult kwargs for a trial fn's clustered tail (empty when the
    aggregation family is single-model and the tuple has just 4 entries)."""
    if len(out) <= 4:
        return {}
    return {"cluster_accuracy": np.asarray(out[4]),
            "cluster_loss": np.asarray(out[5]),
            "cluster_assign": np.asarray(out[6])}


def _split_telemetry(out):
    """Split a trial fn's output into (trajectory tuple, telemetry dict or
    None).  With metrics resolved the ys are ``(main, {name: series})``;
    without, the plain trajectory tuple (len 4 or 7)."""
    if isinstance(out, tuple) and len(out) == 2 and isinstance(out[1], dict):
        main, tel = out
        return main, {n: np.asarray(v) for n, v in tel.items()}
    return out, None


def _assert_budget_invariant(nsel, msum) -> None:
    """num_selected == mask.sum(): every mask-selected client was inside the
    gathered budget window and therefore actually trained."""
    nsel, msum = np.asarray(nsel), np.asarray(msum)
    assert np.array_equal(nsel, msum), (
        "selection budget violated: clients trained per round "
        f"{nsel.tolist()} != mask.sum() {msum.tolist()}; a strategy's mask "
        "escaped its declared budget window")


def simulate(plan: np.ndarray, fl_cfg, *, strategy: Optional[str] = None,
             aggregation: Optional[str] = None, rounds: Optional[int] = None,
             ds=None, seed: Optional[int] = None,
             avail: Optional[np.ndarray] = None,
             eval_n_per_class: int = 50,
             workload: "str | Workload" = "cnn",
             telemetry: Sequence[str] = (),
             adversary: Optional[dict] = None,
             adv: Optional[np.ndarray] = None) -> GridResult:
    """One FL trial through the compiled engine (host-loop-compatible knobs).

    ``adversary`` + ``adv`` (the (N,) byzantine mask) enable the engine-level
    attack behaviors — see :func:`make_trial_fn`."""
    import time
    name = strategy or fl_cfg.selection
    trial = make_trial_fn(fl_cfg, ds, aggregation=aggregation, rounds=rounds,
                          eval_n_per_class=eval_n_per_class,
                          strategies=(name,), workload=workload,
                          telemetry=telemetry, adversary=adversary)
    sid = jnp.int32(0)      # single-entry universe → direct call inside
    seed = fl_cfg.seed if seed is None else seed
    av = (jnp.asarray(avail, jnp.float32) if avail is not None
          else _ones_avail(plan))
    args = (jnp.asarray(plan, jnp.int32), sid, jnp.int32(seed), av)
    if adv is not None:
        args += (jnp.asarray(adv, jnp.float32),)
    frozen = frozen_base(workload, ds)
    if frozen is not None:
        args += (None,) * (5 - len(args)) + (frozen,)
    fn = jax.jit(trial)
    with span("compile", engine="sim", what="trial") as sp:
        compiled = fn.lower(*args).compile()
    record_memory_analysis("sim:trial", compiled)
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    t2 = time.perf_counter()
    out, tel = _split_telemetry(out)
    acc, loss, nsel, msum = out[:4]
    _assert_budget_invariant(nsel, msum)
    return GridResult(np.asarray(acc), np.asarray(loss), np.asarray(nsel),
                      wall_s=t2 - t1, compile_s=sp.duration_s, telemetry=tel,
                      **_cluster_fields(out))


def run_grid(plans: np.ndarray, fl_cfg, *, strategies: Sequence[str],
             seeds: Sequence[int], aggregation: Optional[str] = None,
             rounds: Optional[int] = None, ds=None,
             avail: Optional[np.ndarray] = None,
             eval_n_per_class: int = 50,
             workload: str = "cnn") -> GridResult:
    """The whole grid — cases × strategies × seeds — as ONE compiled program.

    Thin shim over the declarative experiment surface: the raw plan stack
    becomes one explicit-plan ScenarioSpec per case and the grid runs through
    ``repro.fl.experiment.run`` (engine="sim"), which calls back into
    :func:`grid_arrays` below — the actual compiled primitive.

    plans: (K, T, N, n_max) int32 stacked label plans (all cases must share
    T/N/n_max — pad with −1 to the common n_max), or (K, R, T, N, n_max) to
    give every seed its own plan draw (the paper's per-trial re-partition).
    avail: optional (T, N) or (K, T, N) availability masks.  Returns
    trajectories with leading axes (K, len(strategies), len(seeds)).
    """
    from . import experiment
    plans = np.asarray(plans)
    seeds = list(seeds)
    if plans.ndim not in (4, 5):
        raise ValueError(f"plans must be (K[, R], T, N, n); got {plans.shape}")
    if avail is not None:
        avail = np.asarray(avail)
        if avail.ndim == 2:
            avail = np.broadcast_to(avail[None],
                                    (plans.shape[0],) + avail.shape)
    scenarios = tuple(
        experiment.ScenarioSpec.from_plan(
            f"case{k}", plans[k],
            avail=None if avail is None else avail[k])
        for k in range(plans.shape[0]))
    spec = experiment.ExperimentSpec(
        scenarios=scenarios, strategies=tuple(strategies), seeds=tuple(seeds),
        engine="sim", fl=fl_cfg, aggregation=aggregation, rounds=rounds,
        eval_n_per_class=eval_n_per_class, workload=workload)
    res = experiment.run(spec, ds=ds)
    cl = res.meta.get("clustered")
    extra = {} if cl is None else {
        "cluster_accuracy": np.asarray(cl["cluster_accuracy"], np.float32),
        "cluster_loss": np.asarray(cl["cluster_loss"], np.float32),
        "cluster_assign": np.asarray(cl["cluster_assign"], np.int32)}
    return GridResult(res.accuracy, res.loss, res.num_selected,
                      wall_s=res.wall_s, compile_s=res.compile_s, **extra)


def grid_arrays(plans: np.ndarray, fl_cfg, *, strategies: Sequence[str],
                seeds: Sequence[int], aggregation: Optional[str] = None,
                rounds: Optional[int] = None,
                ds=None,
                avail: Optional[np.ndarray] = None,
                eval_n_per_class: int = 50,
                workload: "str | Workload" = "cnn",
                telemetry: Sequence[str] = (),
                adversary: Optional[dict] = None,
                adv: Optional[np.ndarray] = None) -> GridResult:
    """Compiled grid primitive on raw device arrays (the "sim" engine body):
    vmap(trial) over seeds × strategies × cases, one lower+compile+launch.
    Prefer ``run_grid`` / ``experiment.run`` — this is their backend.

    ``adversary`` + ``adv`` — the (R, N) PER-SEED byzantine masks (the mask
    is part of the seed's random draw, like a per-seed plan) — enable the
    engine-level attack behaviors; see :func:`make_trial_fn`."""
    import time
    plans = np.asarray(plans)
    seeds = list(seeds)          # consume a one-shot iterable exactly once
    per_seed = plans.ndim == 5
    if plans.ndim not in (4, 5):
        raise ValueError(f"plans must be (K[, R], T, N, n); got {plans.shape}")
    if per_seed and plans.shape[1] != len(seeds):
        raise ValueError(f"per-seed plans axis 1 ({plans.shape[1]}) must match "
                         f"len(seeds) ({len(seeds)})")
    strategies = tuple(strategies)
    trial = make_trial_fn(fl_cfg, ds, aggregation=aggregation, rounds=rounds,
                          eval_n_per_class=eval_n_per_class,
                          strategies=strategies, workload=workload,
                          telemetry=telemetry, adversary=adversary)
    # sids index the requested universe (the compiled program only contains
    # these strategies); position i of the output's strategy axis is
    # strategies[i].
    sids = jnp.arange(len(strategies), dtype=jnp.int32)
    seed_arr = jnp.asarray(seeds, jnp.int32)
    tn = plans.shape[-3:-1]                              # (T, N)
    if avail is None:
        av = jnp.ones((plans.shape[0],) + tn, jnp.float32)
    else:
        av = jnp.asarray(avail, jnp.float32)
        if av.ndim == 2:
            av = jnp.broadcast_to(av[None], (plans.shape[0],) + av.shape)

    # seeds / strategies / cases vmap nest; the optional per-seed adv mask
    # batches with the seed axis only (same mask for every case/strategy).
    seed_axes = (0 if per_seed else None, None, 0, None)
    strat_axes = (None, 0, None, None)
    case_axes = (0, None, None, 0)
    args = (jnp.asarray(plans, jnp.int32), sids, seed_arr, av)
    if adv is not None:
        adv = jnp.asarray(adv, jnp.float32)
        if adv.ndim != 2 or adv.shape[0] != len(seeds):
            raise ValueError(f"adv must be (len(seeds), N); got {adv.shape}")
        seed_axes += (0,)
        strat_axes += (None,)
        case_axes += (None,)
        args += (adv,)
    # A frozen base is one input of the grid, unbatched at every level.
    frozen = frozen_base(workload, ds)
    if frozen is not None:
        pad = 6 - len(args)
        args += (None,) * (pad - 1) + (frozen,)
        seed_axes += (None,) * pad
        strat_axes += (None,) * pad
        case_axes += (None,) * pad
    f = jax.vmap(trial, in_axes=seed_axes)               # seeds
    f = jax.vmap(f, in_axes=strat_axes)                  # strategies
    f = jax.vmap(f, in_axes=case_axes)                   # cases
    fn = jax.jit(f)
    with span("compile", engine="sim", what="grid") as sp:
        compiled = fn.lower(*args).compile()
    record_memory_analysis("sim:grid", compiled)
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    t2 = time.perf_counter()
    out, tel = _split_telemetry(out)
    acc, loss, nsel, msum = out[:4]
    _assert_budget_invariant(nsel, msum)
    return GridResult(np.asarray(acc), np.asarray(loss), np.asarray(nsel),
                      wall_s=t2 - t1, compile_s=sp.duration_s, telemetry=tel,
                      **_cluster_fields(out))


def stack_case_plans(cases: Sequence[str], fl_cfg, *, seed0: int = 0,
                     rounds: Optional[int] = None,
                     samples_per_client: Optional[int] = None,
                     majority: Optional[int] = None,
                     num_classes: int = 10) -> np.ndarray:
    """(K, T, N, n) stacked §III case plans sharing one shape — run_grid food."""
    from repro.core import case_label_plan, SAMPLES_PER_CLIENT
    spc = samples_per_client or SAMPLES_PER_CLIENT
    maj = majority if majority is not None else int(spc * 200 / 290)
    t = fl_cfg.global_epochs if rounds is None else rounds
    return np.stack([
        case_label_plan(c, seed=seed0, num_rounds=t,
                        num_clients=fl_cfg.num_clients, num_classes=num_classes,
                        samples_per_client=spc, majority=maj)
        for c in cases])
