"""The round math of one FL round (paper Algorithm 1): local training,
reduction and server update of the selected clients.

Flow per round T:
  1. every client reports its label histogram → σ²(L_i) scalars (cheap),
  2. the strategy ranks clients and the server picks order[:budget] (Eq. 3) —
     the budget is the STRATEGY's static slot count (SelectionResult.budget,
     default clients_per_round), so "full" really trains every valid client
     and a wide registered strategy is never truncated,
  3. ONLY those budget clients run local training (vmap over the gathered
     subset — unselected clients spend zero FLOPs, matching §V's saving),
  4. masked weighted aggregation (FedAvg Eq. 1 / Algorithm-1 uniform mean),
  5. server interpolates and broadcasts.

Steps 1–2 and eval live in the round step of repro.fl.sim
(``make_round_step``), which every single-device driver runs.  Budget
invariant (asserted by the sim and host drivers after execution): every
mask-selected client sits inside the gathered window, so
``num_selected == mask.sum()``.

``aggregation='fedsgd'`` switches clients to single-gradient reporting with a
server-side SGD step (the paper's FedSGD baseline).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import Aggregator, get_aggregator, interpolate
from repro.kernels.dispatch import masked_weighted_mean
from repro.obs import phase
from repro.optim import apply_updates
from .client import local_train, local_gradient
from .workloads import with_frozen

Array = jax.Array
PyTree = Any


def resolve_aggregator(agg: "str | Aggregator | None", fl_cfg) -> Aggregator:
    """Name (or None → ``fl_cfg.aggregation``) → registered Aggregator.

    The trace-time resolution every engine shares: the returned family's
    ``base``/``n_clusters``/``reduce`` are static Python facts that pick the
    compiled round's shape."""
    if isinstance(agg, Aggregator):
        return agg
    return get_aggregator(agg or fl_cfg.aggregation)


def resolve_adversary(adversary: "dict | None"):
    """Normalize an adversary behavior dict into the engines' trace-time
    statics ``(poison_scale, tau)``.

    ``adversary`` keys (all optional): ``behaviors`` — a subset of
    ``{"poison", "stale_update"}`` (empty → no engine-level behavior; the
    plan-level ``label_flip`` attack rides the transform stack instead);
    ``scale`` — the poison delta multiplier (default −1.0, the sign-flip
    attack); ``tau`` — how many rounds stale a ``stale_update`` client's
    training base is (default 1).  Returns ``(None, 0)`` for no/empty
    adversary — the value every engine treats as compile-the-old-program."""
    cfg = dict(adversary or {})
    behaviors = tuple(cfg.get("behaviors", ()))
    unknown = set(behaviors) - {"poison", "stale_update"}
    if unknown:
        raise ValueError(f"unknown adversary behaviors {sorted(unknown)}; "
                         "have ['poison', 'stale_update'] (label_flip is a "
                         "plan-level transform, not an engine behavior)")
    poison_scale = (float(cfg.get("scale", -1.0))
                    if "poison" in behaviors else None)
    tau = int(cfg.get("tau", 1)) if "stale_update" in behaviors else 0
    if tau < 0:
        raise ValueError(f"adversary tau must be >= 0; got {tau}")
    return poison_scale, tau


def _reduce_fn(agg: Aggregator):
    """The family's masked weighted reduction: a registered override, or the
    backend compute dispatch (resolved HERE, not in repro.core.aggregation —
    the dispatch module imports core.aggregation, so the registry stores
    ``None`` and the engines' round math closes the cycle-free direction)."""
    return agg.reduce if agg.reduce is not None else masked_weighted_mean


def stack_global_params(params: PyTree, n_clusters: int) -> PyTree:
    """Replicate one global model into the (n_clusters, *params) stacked
    pytree clustered families carry — every cluster starts from the SAME
    init, which is also what makes the sharded engine's per-cluster delta
    mean algebraically equal to aggregate-then-interpolate."""
    return jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p[None], (n_clusters,) + p.shape), params)


def _slot_bcast(v: Array, leaf: Array) -> Array:
    """Broadcast a (S,) per-slot vector against a (S, ...) stacked leaf."""
    return v.reshape(v.shape + (1,) * (leaf.ndim - 1))


def _over_clients(f, xs, map_clients: bool):
    """``f`` over the leading (client) axis of ``xs``: side by side
    (``vmap``), or one client after another (``lax.map``)."""
    if map_clients:
        return jax.lax.map(lambda x: f(*x), xs)
    return jax.vmap(f)(*xs)


def client_update_step(global_params: PyTree, data_sel: Dict[str, Array],
                       live: Array, loss_fn, opt, fl_cfg,
                       agg_kind: "str | Aggregator", *,
                       adv: Array | None = None,
                       poison_scale: float | None = None,
                       stale_params: PyTree | None = None,
                       want_client_norms: bool = False,
                       frozen: PyTree | None = None,
                       counters: Tuple[str, ...] = (),
                       map_clients: bool = False
                       ) -> Tuple[PyTree, Dict[str, Array]]:
    """Local training + masked aggregation + server update for the selected
    client subset — the round math of the shared round step
    (repro.fl.sim.make_round_step) and of the hier engine's rounds.

    Workload-agnostic: ``loss_fn`` and the ``data_sel`` payload come from the
    workload registry (repro.fl.workloads); the only leaf this round math
    names is ``"valid"`` — the per-sample validity mask every workload's
    materializer must emit — whose per-client sums are the FedAvg n_i
    weights.  data_sel: leaves (n_sel, n_batches, batch_size, ...); live:
    (n_sel,) 0/1.  Returns (new_global_params, per-client metrics).

    ``agg_kind`` is an aggregator name (repro.core.aggregation registry) or a
    resolved :class:`Aggregator`.  The family's reduction defaults to the
    backend compute dispatch (repro.kernels.dispatch.masked_weighted_mean):
    the fused Pallas weighted-agg kernel on TPU, ``masked_mean`` — the
    parity-pinned reference — on CPU; a registered ``reduce`` override
    (robust aggregation) slots in here without engine edits.

    Adversary hooks (all default-off — the defaults compile the EXACT
    pre-adversary program, the bit-identity every parity pin rests on):

    * ``adv`` — (n_sel,) 0/1 per-slot byzantine mask (``adversary_mask``
      gathered through ``order[:budget]``); required by the two behaviors.
    * ``poison_scale`` — byzantine slots report ``base + scale·(θ' − base)``
      instead of θ' (``scale=−1`` is the sign-flip attack; fedsgd scales the
      reported gradient, the same statement with base ≡ 0).
    * ``stale_params`` — byzantine slots run local training from this
      τ-rounds-old global tree instead of the current one (the stale_update
      systems fault; honest slots always train from ``global_params``).
    * ``want_client_norms`` — adds ``m["update_norm"]``, the (n_sel,) ℓ₂
      norm of each slot's AS-REPORTED update (post-poison — the
      attack-visible signal the delta_outlier telemetry metric consumes).

    A workload with a frozen part (repro.fl.workloads) passes its base as
    ``frozen``: the loss sees it in each batch, unbatched, while everything
    this step trains, reports, reduces and updates is ``global_params``, the
    trainable tree alone.  ``counters`` are the loss aux entries each
    client's local training sums (``m[name]``, per slot);
    ``map_clients`` trains the slots one after another.  The defaults
    compile the program without them.
    """
    agg = resolve_aggregator(agg_kind, fl_cfg)
    if agg.clustered:
        raise ValueError(
            "client_update_step is the single-global-model round; clustered "
            "families go through clustered_update_step (the engines branch "
            "on Aggregator.clustered at trace time)")
    if (poison_scale is not None or stale_params is not None) and adv is None:
        raise ValueError("poison_scale/stale_params need the per-slot adv "
                         "mask to know which clients misbehave")
    reduce = _reduce_fn(agg)
    n_sel = live.shape[0]
    sizes = data_sel["valid"].reshape(n_sel, -1).sum(-1).astype(jnp.float32)
    if frozen is not None:
        raw_loss = loss_fn

        def loss_fn(p, b):
            return raw_loss(p, with_frozen(b, frozen))

    def _as_reported(updates: PyTree, base: PyTree | None) -> PyTree:
        """Apply the poison behavior: byzantine slots report base +
        scale·(update − base); base=None means the zero tree (gradients)."""
        if poison_scale is None:
            return updates
        s = float(poison_scale)
        a = adv.astype(jnp.float32)

        def one(u: Array, b: Array | None) -> Array:
            flip = b + s * (u - b) if b is not None else s * u
            return jnp.where(_slot_bcast(a, u) > 0, flip.astype(u.dtype), u)

        if base is None:
            return jax.tree_util.tree_map(lambda u: one(u, None), updates)
        return jax.tree_util.tree_map(one, updates, base)

    def _norms(updates: PyTree, base: PyTree | None) -> Array:
        sq = sum(((u - (0 if b is None else b)).astype(jnp.float32) ** 2)
                 .reshape(n_sel, -1).sum(-1)
                 for u, b in zip(jax.tree_util.tree_leaves(updates),
                                 jax.tree_util.tree_leaves(base)
                                 if base is not None else
                                 [None] * len(
                                     jax.tree_util.tree_leaves(updates))))
        return jnp.sqrt(sq)

    # fedsgd clients report gradients, fedavg clients trained weights.
    with phase("train"):
        if agg.base == "fedsgd":
            updates, m = _over_clients(
                lambda b: local_gradient(global_params, b, loss_fn, counters),
                (data_sel,), map_clients)
            updates = _as_reported(updates, None)
            if want_client_norms:
                m = dict(m, update_norm=_norms(updates, None))
        else:
            if stale_params is None:
                updates, m = _over_clients(
                    lambda b: local_train(global_params, opt, b, loss_fn,
                                          fl_cfg.local_epochs, counters),
                    (data_sel,), map_clients)
                base = global_params
            else:
                # Per-slot training base: byzantine slots start from the
                # stale global, honest slots from the current one.
                a_bool = adv > 0
                base = jax.tree_util.tree_map(
                    lambda g, st: jnp.where(
                        _slot_bcast(a_bool, g[None]),
                        jnp.broadcast_to(st, (n_sel,) + st.shape),
                        jnp.broadcast_to(g, (n_sel,) + g.shape)),
                    global_params, stale_params)
                updates, m = _over_clients(
                    lambda p, b: local_train(p, opt, b, loss_fn,
                                             fl_cfg.local_epochs, counters),
                    (base, data_sel), map_clients)
            updates = _as_reported(
                updates,
                base if stale_params is not None else
                jax.tree_util.tree_map(
                    lambda g: jnp.broadcast_to(g, (n_sel,) + g.shape),
                    global_params) if poison_scale is not None else None)
            if want_client_norms:
                nb = (base if stale_params is not None else
                      jax.tree_util.tree_map(
                          lambda g: jnp.broadcast_to(g, (n_sel,) + g.shape),
                          global_params))
                m = dict(m, update_norm=_norms(updates, nb))

    with phase("aggregate"):
        mean = reduce(updates, live, sizes)
        if agg.base == "fedsgd":
            new_params = apply_updates(
                global_params,
                jax.tree_util.tree_map(lambda g: -fl_cfg.lr * g, mean))
        else:
            new_params = interpolate(global_params, mean, fl_cfg.server_lr)
        # Algorithm 1's count=0 degradation: an empty selection must leave
        # the global params untouched (the ε-denominator mean would zero
        # them).
        any_live = live.sum() > 0
        new_params = jax.tree_util.tree_map(
            lambda new, old: jnp.where(any_live, new, old),
            new_params, global_params)
    return new_params, m


def clustered_update_step(global_stack: PyTree, cluster_sel: Array,
                          data_sel: Dict[str, Array], live: Array,
                          loss_fn, opt, fl_cfg, agg: Aggregator
                          ) -> Tuple[PyTree, Dict[str, Array]]:
    """The clustered round math: per-cluster global models, run by the
    shared round step (repro.fl.sim.make_round_step; the sharded round reaches
    the same numbers through its delta-psum form — Σw(θ'−θ_c)/Σw = θ̄_c − θ_c
    because every cluster member trains from the same θ_c).

    ``global_stack`` leaves are (n_clusters, ...); ``cluster_sel`` is the
    (n_sel,) int32 cluster id of each gathered training slot (``assign[idx]``
    from :func:`repro.core.clustering.kmeans_cluster`).  Each slot trains
    from ITS cluster's model; each cluster then reduces ONLY its own live
    slots (membership × live mask) through the family's reduction and applies
    the base rule's server update.  A cluster with no live member this round
    keeps its model bit-identically (the per-cluster count=0 guard —
    Algorithm 1's degradation, per model)."""
    reduce = _reduce_fn(agg)
    m_clusters = agg.n_clusters
    n_sel = live.shape[0]
    sizes = data_sel["valid"].reshape(n_sel, -1).sum(-1).astype(jnp.float32)
    params_sel = jax.tree_util.tree_map(lambda g: g[cluster_sel], global_stack)
    # (M, n_sel) per-cluster live masks: slot s enters cluster c's reduction
    # iff it is live AND assigned to c.
    member = (cluster_sel[None, :] == jnp.arange(m_clusters)[:, None])
    live_mc = member.astype(live.dtype) * live[None, :]

    with phase("train"):
        if agg.base == "fedsgd":
            updates, m = jax.vmap(
                lambda p, b: local_gradient(p, b, loss_fn))(params_sel,
                                                            data_sel)
        else:
            updates, m = jax.vmap(
                lambda p, b: local_train(p, opt, b, loss_fn,
                                         fl_cfg.local_epochs)
            )(params_sel, data_sel)

    def update_one(g_c, live_c):
        mean = reduce(updates, live_c, sizes)
        if agg.base == "fedsgd":
            return apply_updates(
                g_c, jax.tree_util.tree_map(lambda g: -fl_cfg.lr * g, mean))
        return interpolate(g_c, mean, fl_cfg.server_lr)

    with phase("aggregate"):
        new_stack = jax.vmap(update_one)(global_stack, live_mc)
        any_live_c = live_mc.sum(-1) > 0                       # (M,)
        new_stack = jax.tree_util.tree_map(
            lambda new, old: jnp.where(
                any_live_c.reshape((m_clusters,) + (1,) * (new.ndim - 1)),
                new, old),
            new_stack, global_stack)
    return new_stack, m
