"""Population-scale FL: hierarchical two-tier rounds + async FedBuff engine.

Every pre-existing engine materializes the full (T, N, n) plan and the dense
(N, C) histogram matrix on every shard — fine at the paper's N ≈ 20–128,
impossible at cross-device scale (10⁵–10⁶ clients).  This module is the
population-scale subsystem: E edge aggregators each own an N/E-client BLOCK,
and both data movement and statistics are restructured so nothing dense in N
ever exists on a shard.

Three layers, bottom up:

* **Block-streamed selection** (:func:`streamed_selection`) — a ``lax.scan``
  over client blocks.  Each step builds ONE block's (Bs, C) histograms from
  its labels, scores it with the registered strategy, and folds the block
  into a running top-``budget`` candidate carry via
  :func:`repro.core.selection.topk_by_score` plus the block-reducible label
  statistics of :func:`repro.core.label_stats.partial_label_statistics`.
  The carry is O(budget + C); the dense (N, C) matrix is never built, yet
  the merged top-k is BIT-IDENTICAL to a dense ``topn_mask`` over all N
  clients (same lexicographic (−score, id) order — pinned by
  tests/test_population.py).

  Strategy contract: the scores must be BLOCK-SEPARABLE — client i's score a
  row-wise function of its own histogram — which holds for every builtin
  except ``labelwise_priority`` (its area-index offset depends on the whole
  population's label union; the hier/async engines reject it) and ``random``
  (shape-dependent uniform draw: the block path folds a per-block key, so
  the stream differs from ``sim``'s single (N,) draw — same distribution,
  documented, not parity-pinned).

* **Hierarchical two-tier engine** (``engine="hier"``) — per round: streamed
  block selection (phase A, labels only — no client payload data), then
  local training of ONLY the selected ``budget`` clients and a two-level
  reduction ``Σ_e Σ_{i∈e} w·x / Σ_e Σ_{i∈e} w``
  (:func:`repro.core.aggregation.two_tier_weighted_mean`) — algebraically a
  reassociation of flat FedAvg/FedSGD, so the trajectory matches ``sim`` to
  ≤1e-5 at small N (the acceptance pin).  In this registry mode the round
  payload is materialized with ``sim``'s exact key (JAX PRNG array draws
  are shape-dependent, so bit-parity REQUIRES the dense draw); the
  chunked id-keyed path below is the population-scale surface.

* **Async FedBuff engine** (``engine="async"``) — the first engine where
  rounds overlap.  The server keeps a bounded buffer of K staleness-tagged
  block updates and a ring of the last ``tau_max + 1`` parameter versions;
  an arriving block trained from the version ``τ`` steps stale and enters
  the buffer with weight ``n_block · 1/(1+τ)^α`` (FedBuff, Nguyen et al.);
  every K-th arrival the buffer's staleness-weighted mean is applied and a
  new version pushed.  The arrival schedule — which block arrives when, and
  how stale — is DETERMINISTIC, derived from the scenario's availability
  transform (:func:`derive_arrival_schedule`): a block's delay is its dark
  fraction scaled to ``tau_max``.  Fully-available scenarios degenerate to
  ``τ = 0``, where ``async`` with ``buffer_k = num_blocks`` equals flat
  FedAvg exactly (the async≡sim pin).

* **Population-scale direct surface** (:func:`make_population_round`) — the
  10⁵–10⁶-client path: the plan itself is PROCEDURAL (``plan_fn(key, ids)``
  generates any block's label rows from global client ids) and only the
  selected ``budget`` clients' payload is ever materialized
  (:func:`repro.fl.workloads.materialize_rows` — id-keyed, so any block
  partition yields identical per-client data).  Per-shard memory is
  O(block_size + budget), flat in N; ``benchmarks/population.py`` records
  the sweep to 10⁶ synthetic clients.

Engine knobs ride in ``ExperimentSpec.engine_options`` (a JSON-able dict):
``num_blocks`` (both), ``buffer_k``/``alpha``/``tau_max`` (async).  Both
engines reject clustered aggregation families and custom ``reduce``
overrides — the two-tier reduction IS the aggregation rule here, like the
sharded engine's delta-psum.
"""
from __future__ import annotations

import math
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (STRATEGIES, get_aggregator, interpolate,
                        merge_label_statistics, partial_label_statistics,
                        selection_budget, topk_by_score, two_tier_weighted_mean)
from repro.core.selection import NEG_INF
from repro.data import client_batches
from repro.kernels.dispatch import client_histograms, masked_weighted_mean
from repro.obs import (collect_metrics, phase, record_memory_analysis,
                       resolve_metrics, resolve_telemetry_request, span)
from repro.optim import apply_updates, get_optimizer
from .client import local_gradient, local_train
from .workloads import Workload, get_workload, materialize_rows

Array = jax.Array
PyTree = Any

# Override denylist: names here are rejected by the block engines without
# consulting the analyzer.  Since the gate below became a VERIFIED property
# (repro.analysis.separability classifies the strategy's jaxpr), this set is
# only an escape hatch for names the maintainers want refused regardless of
# what the classifier concludes (labelwise_priority's area index offsets
# every score by the population-wide label-union count q, which differs per
# block — the classifier agrees, but the pin here keeps the error message
# stable and the rejection analyzer-independent).
NON_BLOCK_SEPARABLE = frozenset({"labelwise_priority"})

# Opt-out allowlist: extension-strategy names whose authors vouch for block
# separability, skipping the jaxpr classification — for row-wise strategies
# whose jaxpr defeats the static pass (e.g. opaque custom_call primitives).
ASSUME_BLOCK_SEPARABLE: set = set()

# (name, id(fn), num_classes) -> SeparabilityVerdict.  id(fn) keys the cache
# to the registered callable, so overwrite-registrations re-classify.
_SEPARABILITY_CACHE: Dict[Tuple[str, int, int], Any] = {}


def _block_separability(strategy: str, num_classes: int):
    fn = STRATEGIES[strategy]
    key = (strategy, id(fn), int(num_classes))
    if key not in _SEPARABILITY_CACHE:
        from repro.analysis.separability import classify_strategy
        _SEPARABILITY_CACHE[key] = classify_strategy(
            fn, num_clients=32, num_classes=int(num_classes), name=strategy)
    return _SEPARABILITY_CACHE[key]


def _check_block_separable(strategy: str, engine: str,
                           num_classes: int) -> None:
    """Reject ``strategy`` if its scores are not a row-wise function of the
    client's own histogram row — denylist override first, then the verified
    jaxpr classification (cached per (name, callable, num_classes))."""
    if strategy in NON_BLOCK_SEPARABLE:
        raise ValueError(
            f"strategy {strategy!r} is not block-separable (its score "
            "depends on population-wide statistics, not just the client's "
            f"own histogram) and cannot run on engine={engine!r}; use "
            "'coverage' (identical ordering, row-wise scores) or run on "
            "engine='sim'")
    if strategy in ASSUME_BLOCK_SEPARABLE or strategy not in STRATEGIES:
        return  # vouched for / unknown name (raises later at get_strategy)
    verdict = _block_separability(strategy, num_classes)
    if not verdict.separable:
        why = "; ".join(verdict.reasons) or verdict.summary()
        raise ValueError(
            f"strategy {strategy!r} is not block-separable per the jaxpr "
            f"classification ({why}) and cannot run on engine={engine!r}; "
            "run it on engine='sim' or 'host', or add the name to "
            "repro.fl.population.ASSUME_BLOCK_SEPARABLE to vouch for it")


def default_num_blocks(num_clients: int) -> int:
    """Default edge-aggregator count: the largest divisor of N that is
    ≤ ⌈√N⌉ — balanced two-tier fan-in (≈√N blocks of ≈√N clients)."""
    cap = max(1, math.isqrt(num_clients))
    return max(d for d in range(1, cap + 1) if num_clients % d == 0)


def _check_block_engine(agg, strategies: Sequence[str], engine: str,
                        num_classes: int = 10) -> None:
    if agg.clustered:
        raise ValueError(
            f"engine={engine!r} aggregates through the two-tier block "
            "reduction; clustered families (per-cluster global models) are "
            "not supported — run them on engine='sim' or 'host'")
    if agg.reduce is not None:
        raise ValueError(
            f"engine={engine!r} aggregates through the two-tier block "
            "reduction; a custom Aggregator.reduce override is not "
            "supported — run it on engine='sim' or 'host'")
    for s in strategies:
        _check_block_separable(s, engine, num_classes)


def _resolve_blocks(num_clients: int, options: Dict[str, Any]) -> Tuple[int, int]:
    """(num_blocks, block_size) from engine_options, validated."""
    e = int(options.get("num_blocks", default_num_blocks(num_clients)))
    if e < 1 or num_clients % e:
        raise ValueError(
            f"num_blocks ({e}) must be a positive divisor of num_clients "
            f"({num_clients}) — every edge aggregator owns an equal block")
    return e, num_clients // e


def _static_budget(strategy: str, num_clients: int, num_classes: int,
                   n_select: int) -> int:
    """The strategy's STATIC gather width, resolved from a dummy call.

    Every builtin's declared budget is a shape-only fact (``_clamped`` /
    the population size), so one call on a zeros histogram matrix pins it
    without touching real data."""
    r = STRATEGIES[strategy](jax.random.PRNGKey(0),
                             jnp.zeros((num_clients, num_classes)), n_select)
    return selection_budget(r, n_select, num_clients)


# ---------------------------------------------------------------------------
# Phase A: streamed block selection — top-k-of-N from block partials
# ---------------------------------------------------------------------------

def streamed_selection(labels_for_block: Callable[[Array, Array], Array],
                       avail_for_block: Callable[[Array], Array],
                       *, num_blocks: int, block_size: int, num_classes: int,
                       strategy: str, key: Array, budget: int):
    """Global top-``budget`` selection via a ``lax.scan`` over client blocks.

    ``labels_for_block(b, ids_b) -> (block_size, n)`` yields one block's
    label rows (a dynamic slice of a resident plan, or a procedural
    ``plan_fn`` at population scale); ``avail_for_block(b) -> (block_size,)``
    its availability column.  Each step forms the block's (Bs, C) histograms,
    scores them by calling the registered strategy with ``n_select =
    block_size`` (which makes ``mask ≡ the strategy's validity gate`` — all
    ranks clear the threshold — recovering (scores, valid) rows without a
    dense call), applies the engine-side empty-histogram gate, and merges
    into the running top-``budget`` carry through
    :func:`~repro.core.selection.topk_by_score`.

    Returns ``(ids, live, scores, stats)``: the (budget,) global client ids
    in canonical dense-``topn_mask`` order, their 0/1 live flags and masked
    scores, and the merged :func:`partial_label_statistics` dict.  Carry and
    outputs are O(budget + C) — nothing dense in N."""
    select = STRATEGIES[strategy]
    num_clients = num_blocks * block_size

    init = (jnp.full((budget,), NEG_INF, jnp.float32),
            jnp.full((budget,), num_clients, jnp.int32),
            jnp.zeros((budget,), bool),
            {"hist_sum": jnp.zeros((num_classes,), jnp.float32),
             "n_valid": jnp.zeros((), jnp.float32),
             "present": jnp.zeros((num_classes,), bool)})

    def block_step(carry, b):
        top_scores, top_ids, top_live, stats = carry
        ids_b = b * block_size + jnp.arange(block_size, dtype=jnp.int32)
        labels = labels_for_block(b, ids_b)
        valid_rows = labels >= 0
        hists = client_histograms(jnp.where(valid_rows, labels, 0),
                                  num_classes, valid_rows)
        hists = hists * avail_for_block(b)[:, None]
        # n_select = block_size ⇒ every rank clears the threshold ⇒ the
        # returned mask IS the strategy's validity gate; scores are the
        # same row-wise values a dense call would produce (block-separable
        # strategies only — enforced at engine setup).
        r = select(jax.random.fold_in(key, b), hists, block_size)
        live_b = (r.mask > 0) & (hists.sum(-1) > 0)
        cand = (jnp.concatenate([top_scores, r.scores.astype(jnp.float32)]),
                jnp.concatenate([top_ids, ids_b]),
                jnp.concatenate([top_live, live_b]))
        merged = topk_by_score(*cand, budget)
        stats = merge_label_statistics(stats, partial_label_statistics(hists))
        return (merged[0], merged[1], merged[2], stats), None

    (scores, ids, live, stats), _ = jax.lax.scan(
        block_step, init, jnp.arange(num_blocks, dtype=jnp.int32))
    return ids, live, scores, stats


# ---------------------------------------------------------------------------
# Hierarchical two-tier engine (engine="hier")
# ---------------------------------------------------------------------------

def make_hier_trial_fn(fl_cfg, ds=None, *, strategy: str,
                       aggregation: Optional[str] = None,
                       rounds: Optional[int] = None,
                       eval_n_per_class: int = 50,
                       workload: "str | Workload" = "cnn",
                       num_blocks: Optional[int] = None,
                       telemetry: Sequence[str] = ()):
    """Build ``trial(plan, seed, avail) -> (acc, loss, nsel, msum)`` — one
    hierarchical FL trial, jit-able, mirroring ``sim``'s key-derivation tree
    (same fold_in constants) so the two engines see identical randomness.

    Per round: phase A streams blocks through :func:`streamed_selection`
    (labels → block histograms → merged global top-k; the dense (N, C)
    matrix never exists), phase B materializes the round payload with
    ``sim``'s exact key, gathers ONLY the selected ``budget`` clients,
    trains them, and reduces through the two-tier block partial sums.  The
    (budget,) selected set is bit-identical to ``sim``'s ``order[:budget]``
    (topk_by_score ≡ topn_mask order) and the two-tier mean is a
    reassociation of the flat mean, so trajectories agree to ≤1e-5."""
    wl = get_workload(workload)
    ds = wl.dataset(ds)
    agg = get_aggregator(aggregation or fl_cfg.aggregation)
    n_clients = fl_cfg.num_clients
    n_classes = wl.num_classes(ds)
    _check_block_engine(agg, (strategy,), "hier", num_classes=n_classes)
    e_blocks, block_size = _resolve_blocks(
        n_clients, {} if num_blocks is None else {"num_blocks": num_blocks})
    budget = _static_budget(strategy, n_clients, n_classes,
                            fl_cfg.clients_per_round)
    num_rounds = fl_cfg.global_epochs if rounds is None else rounds
    opt = get_optimizer(fl_cfg.optimizer, fl_cfg.lr)
    loss_fn = wl.make_loss(ds)
    eval_batch = wl.eval_set(ds, eval_n_per_class)
    eval_fn = wl.make_eval(ds)
    metrics = resolve_metrics(
        resolve_telemetry_request(telemetry),
        ("hists", "mask", "num_classes", "params_old", "params_new"))

    def trial(plan: Array, seed: Array, avail: Array):
        t_static = plan.shape[0]
        key = jax.random.PRNGKey(seed)
        params = wl.init(jax.random.fold_in(key, 1), ds)

        def round_body(params, t):
            kt = jax.random.fold_in(key, 1000 + t)
            plan_t = jax.lax.dynamic_index_in_dim(plan, t % t_static, 0,
                                                  keepdims=False)
            avail_t = jax.lax.dynamic_index_in_dim(avail, t % avail.shape[0],
                                                   0, keepdims=False)
            with phase("select"):
                ids, live_b, _, _ = streamed_selection(
                    lambda b, _ids: jax.lax.dynamic_slice_in_dim(
                        plan_t, b * block_size, block_size, 0),
                    lambda b: jax.lax.dynamic_slice_in_dim(
                        avail_t, b * block_size, block_size, 0),
                    num_blocks=e_blocks, block_size=block_size,
                    num_classes=n_classes, strategy=strategy,
                    key=jax.random.fold_in(kt, 1), budget=budget)
                live = live_b.astype(jnp.float32)
            with phase("materialize"):
                # Registry-mode payload: sim's exact materialize key — the
                # only way to bit-match its shape-dependent PRNG draws (see
                # module docstring); phase A above still never built dense
                # statistics.
                data = wl.materialize(ds, plan_t, jax.random.fold_in(kt, 0))
                batches = client_batches(data, fl_cfg.batch_size,
                                         wl.batch_keys)
                data_sel = jax.tree_util.tree_map(lambda x: x[ids], batches)
                sizes = data_sel["valid"].reshape(budget, -1).sum(-1).astype(
                    jnp.float32)
                block_ids = ids // block_size
            with phase("train"):
                # fedsgd clients report gradients, fedavg clients weights.
                if agg.base == "fedsgd":
                    updates, _ = jax.vmap(
                        lambda b: local_gradient(params, b, loss_fn))(data_sel)
                else:
                    updates, _ = jax.vmap(
                        lambda b: local_train(params, opt, b, loss_fn,
                                              fl_cfg.local_epochs))(data_sel)
            with phase("aggregate"):
                mean = two_tier_weighted_mean(updates, live, sizes, block_ids,
                                              e_blocks)
                if agg.base == "fedsgd":
                    new_params = apply_updates(
                        params,
                        jax.tree_util.tree_map(lambda g: -fl_cfg.lr * g, mean))
                else:
                    new_params = interpolate(params, mean, fl_cfg.server_lr)
                any_live = live.sum() > 0
                new_params = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(any_live, new, old),
                    new_params, params)
            with phase("eval"):
                ev_loss, ev_m = eval_fn(new_params, eval_batch)
            main = (ev_m["accuracy"], ev_loss, live.sum(), live.sum())
            if metrics:
                # Rebuild the dense (N,) selection mask from the streamed
                # top-k: the init sentinel id (= num_clients) scatters out
                # of bounds and is dropped.
                mask = jnp.zeros((n_clients,), jnp.float32).at[ids].add(
                    live, mode="drop")
                state = {"hists": data["hists"] * avail_t[:, None],
                         "mask": mask, "num_classes": n_classes,
                         "params_old": params, "params_new": new_params}
                return new_params, (main, collect_metrics(metrics, state))
            return new_params, main

        _, traj = jax.lax.scan(round_body, params, jnp.arange(num_rounds))
        return traj

    trial.budget = budget
    trial.num_blocks = e_blocks
    trial.block_size = block_size
    return trial


# ---------------------------------------------------------------------------
# Async FedBuff engine (engine="async")
# ---------------------------------------------------------------------------

def staleness_weight(tau: Array, alpha: float) -> Array:
    """FedBuff's polynomial staleness discount: ``1 / (1 + τ)^α``."""
    return (1.0 + tau.astype(jnp.float32)) ** (-float(alpha))


def derive_arrival_schedule(plan: np.ndarray, avail: Optional[np.ndarray],
                            *, rounds: int, num_blocks: int, block_size: int,
                            buffer_k: int, tau_max: int
                            ) -> Tuple[np.ndarray, np.ndarray]:
    """Deterministic (rounds, K) arrival schedule from the availability
    transform: ``blocks[t, j]`` is the block arriving as the j-th buffered
    update of server version t (round-robin, so ``buffer_k = num_blocks``
    hears every edge once per version), and ``delays[t, j]`` its staleness —
    the block's dark-client fraction at dispatch scaled to ``tau_max`` and
    rounded.  Mask-mode availability reads the (T_a, N) mask; compose-mode
    (or no transform) reads darkness off the plan itself (a dark client's
    round row is all −1).  No availability ⇒ all delays 0 — the degenerate
    schedule under which ``async`` ≡ flat FedAvg."""
    t_idx = np.arange(rounds)
    blocks = (t_idx[:, None] * buffer_k
              + np.arange(buffer_k)[None, :]) % num_blocks
    if tau_max <= 0:
        return blocks.astype(np.int32), np.zeros_like(blocks, np.int32)
    if avail is not None:
        a = np.asarray(avail, np.float32)[t_idx % avail.shape[0]]
    else:
        p = np.asarray(plan)
        p = p[t_idx % p.shape[0]]
        a = 1.0 - (p < 0).all(axis=-1).astype(np.float32)   # (rounds, N)
    dark = 1.0 - a.reshape(rounds, num_blocks, block_size).mean(-1)
    delays = np.rint(tau_max * dark[t_idx[:, None], blocks])
    return (blocks.astype(np.int32),
            np.clip(delays, 0, tau_max).astype(np.int32))


def make_async_trial_fn(fl_cfg, ds=None, *, strategy: str,
                        aggregation: Optional[str] = None,
                        rounds: Optional[int] = None,
                        eval_n_per_class: int = 50,
                        workload: "str | Workload" = "cnn",
                        num_blocks: Optional[int] = None,
                        buffer_k: Optional[int] = None, alpha: float = 0.5,
                        tau_max: int = 2,
                        schedule: Optional[Tuple[np.ndarray,
                                                 np.ndarray]] = None,
                        telemetry: Sequence[str] = ()):
    """Build ``trial(plan, seed, avail) -> (acc, loss, nsel)`` — one async
    FedBuff trial: rounds OVERLAP through a ring of the last ``tau_max + 1``
    parameter versions.

    Server version t buffers ``buffer_k`` staleness-tagged block arrivals
    (the deterministic :func:`derive_arrival_schedule`); arrival j trains
    its block's locally-selected clients from the ring entry ``τ_j``
    versions stale and contributes its block-weighted update delta with the
    FedBuff discount ``n_e / (1+τ_j)^α``; after the K-th arrival the
    buffer's weighted mean is applied (``θ ← θ + η·Σ wΔ / Σ w``) and the new
    version pushed into the ring.  With all-zero delays and ``buffer_k =
    num_blocks`` every version hears every block fresh — flat FedAvg exactly
    (the async≡sim degenerate pin in tests/test_population.py)."""
    wl = get_workload(workload)
    ds = wl.dataset(ds)
    agg = get_aggregator(aggregation or fl_cfg.aggregation)
    n_clients = fl_cfg.num_clients
    n_classes = wl.num_classes(ds)
    _check_block_engine(agg, (strategy,), "async", num_classes=n_classes)
    e_blocks, block_size = _resolve_blocks(
        n_clients, {} if num_blocks is None else {"num_blocks": num_blocks})
    k_buf = e_blocks if buffer_k is None else int(buffer_k)
    if k_buf < 1:
        raise ValueError(f"buffer_k must be >= 1; got {k_buf}")
    if tau_max < 0:
        raise ValueError(f"tau_max must be >= 0; got {tau_max}")
    ring_len = int(tau_max) + 1
    num_rounds = fl_cfg.global_epochs if rounds is None else rounds
    # Block-local selection: each edge asks its own clients_per_round (capped
    # by the block), so K round-robin arrivals ≈ one flat round's budget.
    select = STRATEGIES[strategy]
    blk_budget = _static_budget(strategy, block_size, n_classes,
                                min(fl_cfg.clients_per_round, block_size))
    opt = get_optimizer(fl_cfg.optimizer, fl_cfg.lr)
    loss_fn = wl.make_loss(ds)
    eval_batch = wl.eval_set(ds, eval_n_per_class)
    eval_fn = wl.make_eval(ds)
    if schedule is None:
        raise ValueError("make_async_trial_fn needs the host-derived arrival "
                         "schedule (derive_arrival_schedule)")
    sched_blocks = jnp.asarray(schedule[0], jnp.int32)     # (rounds, K)
    sched_delays = jnp.asarray(schedule[1], jnp.int32)
    if sched_blocks.shape != (num_rounds, k_buf):
        raise ValueError(f"schedule shape {sched_blocks.shape} != "
                         f"(rounds, buffer_k) ({num_rounds}, {k_buf})")
    server_lr = fl_cfg.server_lr if agg.base == "fedavg" else 1.0
    metrics = resolve_metrics(
        resolve_telemetry_request(telemetry),
        ("hists", "mask", "num_classes", "params_old", "params_new",
         "staleness_delays", "tau_max"))

    def trial(plan: Array, seed: Array, avail: Array):
        t_static = plan.shape[0]
        key = jax.random.PRNGKey(seed)
        params0 = wl.init(jax.random.fold_in(key, 1), ds)
        # Version ring: every slot starts at θ₀, so a clamped stale read
        # before version τ exists is exactly θ₀.
        ring = jax.tree_util.tree_map(
            lambda p: jnp.broadcast_to(p[None], (ring_len,) + p.shape).astype(
                p.dtype), params0)

        def window_body(ring, t):
            kt = jax.random.fold_in(key, 1000 + t)
            plan_t = jax.lax.dynamic_index_in_dim(plan, t % t_static, 0,
                                                  keepdims=False)
            avail_t = jax.lax.dynamic_index_in_dim(avail, t % avail.shape[0],
                                                   0, keepdims=False)
            with phase("materialize"):
                data = wl.materialize(ds, plan_t, jax.random.fold_in(kt, 0))
                hists = data["hists"] * avail_t[:, None]
                batches = client_batches(data, fl_cfg.batch_size,
                                         wl.batch_keys)
            theta_t = jax.tree_util.tree_map(lambda r: r[t % ring_len], ring)
            blocks_t = jax.lax.dynamic_index_in_dim(sched_blocks, t, 0,
                                                    keepdims=False)
            delays_t = jax.lax.dynamic_index_in_dim(sched_delays, t, 0,
                                                    keepdims=False)
            zero_buf = (jax.tree_util.tree_map(
                            lambda r: jnp.zeros(r.shape[1:], jnp.float32),
                            ring),
                        jnp.zeros((), jnp.float32),
                        jnp.zeros((), jnp.float32))
            if metrics:
                # Telemetry-only carry leaf: the dense selection mask
                # accumulated across the window's K arrivals.
                zero_buf = zero_buf + (jnp.zeros((n_clients,), jnp.float32),)

            def arrival(buf, j):
                if metrics:
                    buf_num, buf_den, n_live, sel_mask = buf
                else:
                    buf_num, buf_den, n_live = buf
                e = blocks_t[j]
                tau = jnp.minimum(delays_t[j], t).astype(jnp.int32)
                theta_stale = jax.tree_util.tree_map(
                    lambda r: jax.lax.dynamic_index_in_dim(
                        r, (t - tau) % ring_len, 0, keepdims=False), ring)
                with phase("select"):
                    hists_e = jax.lax.dynamic_slice_in_dim(
                        hists, e * block_size, block_size, 0)
                    r = select(
                        jax.random.fold_in(jax.random.fold_in(kt, 1), j),
                        hists_e, blk_budget)
                    mask = r.mask * (hists_e.sum(-1) > 0)
                    idx_local = r.order[:blk_budget]
                    live = mask[idx_local]
                    idx = e * block_size + idx_local
                    data_sel = jax.tree_util.tree_map(lambda x: x[idx],
                                                      batches)
                    sizes = data_sel["valid"].reshape(blk_budget, -1).sum(-1)\
                        .astype(jnp.float32)
                with phase("train"):
                    if agg.base == "fedsgd":
                        updates, _ = jax.vmap(
                            lambda b: local_gradient(theta_stale, b,
                                                     loss_fn))(data_sel)
                    else:
                        updates, _ = jax.vmap(
                            lambda b: local_train(theta_stale, opt, b,
                                                  loss_fn,
                                                  fl_cfg.local_epochs)
                        )(data_sel)
                with phase("aggregate"):
                    mean = masked_weighted_mean(updates, live, sizes)
                    if agg.base == "fedsgd":
                        delta = jax.tree_util.tree_map(
                            lambda g: -fl_cfg.lr * g.astype(jnp.float32),
                            mean)
                    else:
                        delta = jax.tree_util.tree_map(
                            lambda a, s: a.astype(jnp.float32)
                            - s.astype(jnp.float32), mean, theta_stale)
                    # Block weight: live data size; an empty block (count=0)
                    # contributes exactly zero to both numerator and
                    # denominator.
                    w = (live * sizes).sum() * staleness_weight(tau, alpha)
                    buf_num = jax.tree_util.tree_map(
                        lambda acc, d: acc + w * d, buf_num, delta)
                if metrics:
                    sel_mask = sel_mask.at[idx].add(live)
                    return (buf_num, buf_den + w, n_live + live.sum(),
                            sel_mask), None
                return (buf_num, buf_den + w, n_live + live.sum()), None

            buf_out, _ = jax.lax.scan(arrival, zero_buf, jnp.arange(k_buf))
            if metrics:
                buf_num, buf_den, n_live, sel_mask = buf_out
            else:
                buf_num, buf_den, n_live = buf_out
            with phase("aggregate"):
                denom = jnp.maximum(buf_den, 1e-12)
                theta_new = jax.tree_util.tree_map(
                    lambda p, acc: (p + server_lr * (acc / denom)).astype(
                        p.dtype), theta_t, buf_num)
                theta_new = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(buf_den > 0, new, old),
                    theta_new, theta_t)
                ring = jax.tree_util.tree_map(
                    lambda r, n: jax.lax.dynamic_update_index_in_dim(
                        r, n, (t + 1) % ring_len, 0), ring, theta_new)
            with phase("eval"):
                ev_loss, ev_m = eval_fn(theta_new, eval_batch)
            main = (ev_m["accuracy"], ev_loss, n_live)
            if metrics:
                state = {"hists": hists,
                         # A block arriving twice in one window re-adds its
                         # live clients; the mask is membership, so clamp.
                         "mask": jnp.minimum(sel_mask, 1.0),
                         "num_classes": n_classes,
                         "params_old": theta_t, "params_new": theta_new,
                         "staleness_delays": jnp.minimum(
                             delays_t, t).astype(jnp.int32),
                         "tau_max": int(tau_max)}
                return ring, (main, collect_metrics(metrics, state))
            return ring, main

        _, traj = jax.lax.scan(window_body, ring, jnp.arange(num_rounds))
        return traj

    trial.num_blocks = e_blocks
    trial.block_size = block_size
    trial.block_budget = blk_budget
    trial.buffer_k = k_buf
    return trial


# ---------------------------------------------------------------------------
# Engine registry bodies (registered by repro.fl.experiment)
# ---------------------------------------------------------------------------

def _ones_avail(plan: np.ndarray) -> jnp.ndarray:
    return jnp.ones(plan.shape[:2], jnp.float32)


def _run_cells(spec, lowered, make_trial, out_width: int,
               engine_label: str = "population"):
    """Shared grid driver: one AOT lower+compile per (scenario, strategy)
    cell — seeds share the compiled program (the seed is an argument) — and
    per-seed execution, accumulating wall/compile seconds.

    A trial fn with telemetry resolved returns ``(trajectories, {name:
    (rounds, …)})``; the metric series are stacked into (K, S, R, rounds, …)
    arrays and returned as the fourth element (None without telemetry)."""
    k_n, s_n, r_n = len(lowered), len(spec.strategies), len(spec.seeds)
    t_n = spec.num_rounds
    out = [np.zeros((k_n, s_n, r_n, t_n), np.float32)
           for _ in range(out_width)]
    tel: Dict[str, np.ndarray] = {}
    wall = compile_s = 0.0
    for k, low in enumerate(lowered):
        av = (jnp.asarray(low.avail, jnp.float32) if low.avail is not None
              else _ones_avail(low.plan[0] if low.per_seed else low.plan))
        for s, strat in enumerate(spec.strategies):
            fn = jax.jit(make_trial(strat, low))
            compiled = None
            for r, seed in enumerate(spec.seeds):
                plan = low.plan[r] if low.per_seed else low.plan
                args = (jnp.asarray(plan, jnp.int32), jnp.int32(seed), av)
                if compiled is None:
                    with span("compile", engine=engine_label,
                              what=f"{low.name}:{strat}") as sp:
                        compiled = fn.lower(*args).compile()
                    compile_s += sp.duration_s
                    record_memory_analysis(
                        f"{engine_label}:{low.name}:{strat}", compiled)
                t0 = time.perf_counter()
                traj = jax.block_until_ready(compiled(*args))
                wall += time.perf_counter() - t0
                if (isinstance(traj, tuple) and len(traj) == 2
                        and isinstance(traj[1], dict)):
                    traj, mvals = traj
                    for name, v in mvals.items():
                        v = np.asarray(v, np.float32)
                        if name not in tel:
                            tel[name] = np.zeros((k_n, s_n, r_n) + v.shape,
                                                 np.float32)
                        tel[name][k, s, r] = v
                for i in range(out_width):
                    out[i][k, s, r] = np.asarray(traj[i], np.float32)
    return out, wall, compile_s, tel or None


def run_engine_hier(spec, lowered, ds):
    """The ``engine="hier"`` registry body — see :func:`make_hier_trial_fn`."""
    opts = dict(getattr(spec, "engine_options", None) or {})
    agg = get_aggregator(spec.aggregation or spec.fl.aggregation)
    wl = get_workload(spec.workload)
    _check_block_engine(agg, spec.strategies, "hier",
                        num_classes=wl.num_classes(wl.dataset(ds)))
    e_blocks, block_size = _resolve_blocks(spec.fl.num_clients, opts)
    trials: Dict[str, Any] = {}

    def make_trial(strat, low):
        if strat not in trials:
            trials[strat] = make_hier_trial_fn(
                spec.fl, ds, strategy=strat, aggregation=spec.aggregation,
                rounds=spec.rounds, eval_n_per_class=spec.eval_n_per_class,
                workload=spec.workload, num_blocks=e_blocks,
                telemetry=getattr(spec, "telemetry", ()))
        return trials[strat]

    (acc, loss, nsel, _msum), wall, compile_s, tel = _run_cells(
        spec, lowered, make_trial, 4, engine_label="hier")
    meta = {"population": {
        "mode": "hier", "num_blocks": e_blocks, "block_size": block_size,
        "budgets": {s: t.budget for s, t in trials.items()}}}
    if tel:
        meta["_telemetry_series"] = tel
    return acc, loss, nsel, wall, compile_s, meta


def run_engine_async(spec, lowered, ds):
    """The ``engine="async"`` registry body — see
    :func:`make_async_trial_fn`."""
    opts = dict(getattr(spec, "engine_options", None) or {})
    agg = get_aggregator(spec.aggregation or spec.fl.aggregation)
    wl = get_workload(spec.workload)
    _check_block_engine(agg, spec.strategies, "async",
                        num_classes=wl.num_classes(wl.dataset(ds)))
    e_blocks, block_size = _resolve_blocks(spec.fl.num_clients, opts)
    k_buf = int(opts.get("buffer_k", e_blocks))
    alpha = float(opts.get("alpha", 0.5))
    tau_max = int(opts.get("tau_max", 2))
    t_n = spec.num_rounds
    schedules = {}
    for low in lowered:
        plan0 = low.plan[0] if low.per_seed else low.plan
        schedules[low.name] = derive_arrival_schedule(
            plan0, low.avail, rounds=t_n, num_blocks=e_blocks,
            block_size=block_size, buffer_k=k_buf, tau_max=tau_max)
    trials: Dict[Tuple[str, str], Any] = {}

    def make_trial(strat, low):
        cell = (strat, low.name)
        if cell not in trials:
            trials[cell] = make_async_trial_fn(
                spec.fl, ds, strategy=strat, aggregation=spec.aggregation,
                rounds=spec.rounds, eval_n_per_class=spec.eval_n_per_class,
                workload=spec.workload, num_blocks=e_blocks, buffer_k=k_buf,
                alpha=alpha, tau_max=tau_max, schedule=schedules[low.name],
                telemetry=getattr(spec, "telemetry", ()))
        return trials[cell]

    (acc, loss, nsel), wall, compile_s, tel = _run_cells(
        spec, lowered, make_trial, 3, engine_label="async")
    delays = np.stack([schedules[low.name][1] for low in lowered])
    meta = {"population": {
        "mode": "async", "num_blocks": e_blocks, "block_size": block_size,
        "buffer_k": k_buf, "alpha": alpha, "tau_max": tau_max,
        "staleness_weight": "1/(1+tau)^alpha",
        "delay_mean": float(delays.mean()), "delay_max": int(delays.max())}}
    if tel:
        meta["_telemetry_series"] = tel
    return acc, loss, nsel, wall, compile_s, meta


# ---------------------------------------------------------------------------
# Population-scale direct surface: procedural plans, O(budget) materialize
# ---------------------------------------------------------------------------

def synthetic_population_plan(num_classes: int = 10,
                              samples_per_client: int = 8,
                              majority_frac: float = 0.75
                              ) -> Callable[[Array, Array], Array]:
    """A procedural case1b-flavoured plan: ``plan_fn(key, ids) -> (B, n)``.

    Client i's row is a pure function of ``(key, i)`` (per-id fold_in): a
    majority label for ``majority_frac`` of its samples, uniform fill for
    the tail — the §III majority-bias structure without ever materializing
    an (N, n) array.  Any block partition of ``ids`` yields identical rows,
    which is the id-keyed stability the chunked engine path requires."""
    n = samples_per_client
    n_major = int(round(majority_frac * n))

    def plan_fn(key: Array, ids: Array) -> Array:
        def one(i):
            k = jax.random.fold_in(key, i)
            maj = jax.random.randint(jax.random.fold_in(k, 0), (), 0,
                                     num_classes)
            tail = jax.random.randint(jax.random.fold_in(k, 1), (n,), 0,
                                      num_classes)
            return jnp.where(jnp.arange(n) < n_major, maj,
                             tail).astype(jnp.int32)
        return jax.vmap(one)(jnp.asarray(ids, jnp.int32))

    return plan_fn


def make_population_round(*, plan_fn: Callable[[Array, Array], Array],
                          num_clients: int, block_size: int,
                          strategy: str = "labelwise", budget: int,
                          workload: "str | Workload" = "cnn", ds=None,
                          batch_size: int = 8, local_epochs: int = 1,
                          lr: float = 1e-3, server_lr: float = 1.0,
                          optimizer: str = "sgd"):
    """One population-scale FedAvg round as a jit-able
    ``round(params, key_t) -> (new_params, info)``.

    Phase A scans ``num_clients / block_size`` blocks of the PROCEDURAL plan
    (labels regenerated per block from global client ids — the (N, n) plan
    never exists), merging the global top-``budget`` candidates and the
    block-reducible label statistics.  Phase B regenerates ONLY the selected
    clients' label rows (id-keyed ⇒ identical to the scanned values),
    materializes their payload through the workload's chunked
    :func:`~repro.fl.workloads.materialize_rows` hook, trains them, and
    applies the two-tier reduction.  Peak memory is O(block_size·n +
    budget·payload) — flat in N, which is what BENCH_population's compiled
    ``memory_analysis`` sweep records up to N = 10⁶."""
    if num_clients % block_size:
        raise ValueError(f"block_size ({block_size}) must divide num_clients "
                         f"({num_clients})")
    wl = get_workload(workload)
    ds = wl.dataset(ds)
    n_classes = wl.num_classes(ds)
    _check_block_separable(strategy, "population", n_classes)
    e_blocks = num_clients // block_size
    budget = max(1, min(int(budget), num_clients))
    opt = get_optimizer(optimizer, lr)
    loss_fn = wl.make_loss(ds)

    def round_fn(params: PyTree, key_t: Array):
        kp = jax.random.fold_in(key_t, 0)      # plan stream
        kd = jax.random.fold_in(key_t, 1)      # payload stream
        ks = jax.random.fold_in(key_t, 2)      # strategy stream
        with phase("select"):
            ids, live_b, scores, stats = streamed_selection(
                lambda b, ids_b: plan_fn(kp, ids_b),
                lambda b: jnp.ones((block_size,), jnp.float32),
                num_blocks=e_blocks, block_size=block_size,
                num_classes=n_classes, strategy=strategy, key=ks,
                budget=budget)
            live = live_b.astype(jnp.float32)
        with phase("materialize"):
            labels_sel = plan_fn(kp, ids)      # id-keyed ⇒ same rows as scan
            data = materialize_rows(wl, ds, labels_sel, kd, ids)
            batches = client_batches(data, batch_size, wl.batch_keys)
            sizes = data["valid"].reshape(budget, -1).sum(-1).astype(
                jnp.float32)
        with phase("train"):
            trained, _ = jax.vmap(
                lambda b: local_train(params, opt, b, loss_fn,
                                      local_epochs))(batches)
        with phase("aggregate"):
            # Two-tier reduction over the edges that actually own a selected
            # client: at most ``budget`` of the N/block_size edges are
            # touched, so remap their block ids into a dense ≤budget rank
            # space before forming partials — empty edges ship nothing, the
            # reassociated sum is unchanged, and the (num_edges, |θ|)
            # partial tree stays O(budget·|θ|) instead of O(N/block_size·|θ|).
            owner = ids // block_size
            uniq = jnp.unique(owner, size=budget, fill_value=e_blocks)
            agg_p = two_tier_weighted_mean(trained, live, sizes,
                                           jnp.searchsorted(uniq, owner),
                                           budget)
            new_params = interpolate(params, agg_p, server_lr)
            any_live = live.sum() > 0
            new_params = jax.tree_util.tree_map(
                lambda new, old: jnp.where(any_live, new, old), new_params,
                params)
        info = {"selected": ids, "live": live, "scores": scores,
                "num_selected": live.sum(), "hist_sum": stats["hist_sum"],
                "n_valid": stats["n_valid"],
                "union_coverage": stats["present"].sum()}
        return new_params, info

    round_fn.num_blocks = e_blocks
    round_fn.block_size = block_size
    round_fn.budget = budget
    return round_fn
