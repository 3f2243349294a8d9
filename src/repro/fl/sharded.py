"""Pod-scale FL: the paper's round as ONE SPMD program over the mesh — with
the training phase GATHER-BASED, so only the selected budget of clients
spends FLOPs.

Mapping (DESIGN.md §2, revised): the mesh's client axis (``pod`` on the
production mesh) carries a *block* of clients per slice — ``num_clients``
need not equal the device count; each of the G groups holds C = N/G clients.
Each round:

  1. every group computes its C clients' label histograms locally through
     the backend compute dispatch (repro.kernels.dispatch) — the Pallas
     label_hist kernel on TPU, the bincount-shaped XLA reference on CPU/GPU
     (an unavailable client's histogram is zeroed — the single availability
     application every engine shares),
  2. all-gathers the (N, C_classes) histogram matrix — Algorithm 1's
     "transmit statistics to server" step: N small integer vectors, not N
     models, preserving the paper's cheap-server-side cost.  (The paper's
     labelwise strategy needs only the σ² scalars; gathering the histograms
     instead is what lets ANY registered strategy run in-shard.)
  3. every shard deterministically computes the same SelectionResult through
     the strategy registry (repro.core.selection) — mask, order, and the
     strategy's STATIC training budget B,
  4. **exchange**: the batch shards of ``order[:B_pad]`` (B padded up to a
     multiple of G so the sub-round stays SPMD-even) move so each group
     holds exactly B_pad/G selected clients' data; local training runs
     vmapped over those slots ONLY — unselected clients spend ZERO training
     FLOPs instead of being masked out of the reduction.  Realized FLOP
     sparsity is 1 − B_pad/N per round (the wrapper exposes it statically as
     ``round_fn.flop_sparsity``).  ``exchange="a2a"`` (default) is the O(B)
     selected-shard exchange (core.aggregation.exchange_selected_shards):
     selection is replicated, so every shard computes the same static-budget
     slot routing and ONE psum_scatter moves only the B_pad selected shards
     — ring bytes (G−1)/G·B_pad versus the O(N) full-batch all-gather's
     (G−1)/G·N.  ``exchange="allgather"`` keeps the all-gather path as the
     measured baseline; both are bit-identical (one owner per slot).
  5. **scatter**: the trained slots' parameter deltas enter a weighted psum
     pair (live mask × n_i weights, FedAvg Eq. 1) whose result is replicated
     to every shard — the server broadcast, fused into the same collective.
     Deltas (not params) are reduced, so a bf16 ``agg_dtype`` halves the
     cross-pod all-reduce bytes; the in-shard slot reduction routes through
     the compute dispatch (fused Pallas weighted-agg kernel on TPU).

``mode="masked"`` keeps the legacy masked-psum round (every client trains,
the mask zeroes unselected contributions) as the comparison baseline.

Numerics match the shared round step (repro.fl.sim): identical histograms →
identical registry selection (same tie-breaking), identical ``local_step``
math, and the weighted delta mean equals fedavg-then-interpolate
algebraically, so host/sim/sharded trajectories agree to float tolerance
(pinned by tests/test_experiment.py).

The round is workload-agnostic by construction: ``local_step``,
``params_pspec`` and ``batch_pspec`` describe whatever pytree the client
trains — the sharded engine (repro.fl.experiment._engine_sharded) derives
all three from the workload registry (repro.fl.workloads), so registered LM
clients shard and train through the same collective schedule as the CNN.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.clustering import cluster_counts, kmeans_cluster
from repro.core.selection import SelectFn, get_strategy, selection_budget
from repro.core.aggregation import (exchange_selected_shards,
                                    gather_client_shards, interpolate,
                                    psum_weighted_mean)
from repro.kernels.dispatch import client_histograms, weighted_sum_tree
from repro.obs import phase
from .round import _slot_bcast

Array = jax.Array
PyTree = Any


def shard_map(f, mesh, in_specs, out_specs):
    # check_vma=False: the replicated outputs (mask/scores) come from an
    # all_gather whose replication the static checker cannot infer.
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


def _static_budget(select_fn: SelectFn, n_select: int, num_clients: int,
                   num_classes: int) -> int:
    """Trace the strategy on abstract histograms to read its STATIC budget
    (SelectionResult.budget) at build time — the gather width B."""
    box: Dict[str, int] = {}

    def probe(key, hists):
        r = select_fn(key, hists, n_select)
        box["budget"] = selection_budget(r, n_select, num_clients)
        return r.mask

    jax.eval_shape(probe, jax.ShapeDtypeStruct((2,), jnp.uint32),
                   jax.ShapeDtypeStruct((num_clients, num_classes),
                                        jnp.float32))
    return box["budget"]


def make_sharded_fl_round(mesh: Mesh, client_axis: str,
                          local_step: Callable[[PyTree, Dict[str, Array]], PyTree],
                          n_select: int, num_classes: int,
                          params_pspec: PyTree, batch_pspec: PyTree,
                          agg_dtype=None, with_availability: bool = False,
                          num_clients: Optional[int] = None,
                          strategy: Union[str, SelectFn] = "labelwise",
                          server_lr: float = 1.0,
                          mode: str = "gather",
                          exchange: str = "a2a",
                          n_clusters: int = 1,
                          kmeans_iters: int = 4,
                          reduce_fn: Optional[Callable] = None,
                          poison_scale: Optional[float] = None,
                          with_stale: bool = False) -> Callable:
    """Build the SPMD FL round.

    ``local_step(params, batch) -> params`` is ONE client's local training
    (already pjit-sharded *within* the client group over the remaining axes);
    batch leaves carry no client axis — the round vmaps it over each group's
    gathered training slots.  ``params_pspec``/``batch_pspec`` are
    PartitionSpecs WITHOUT the client axis (intra-group sharding); the batch
    gains a leading client-sharded axis here.

    ``num_clients`` (default: one client per mesh slice) must be a multiple
    of the client-axis size; each group then holds num_clients/G clients.
    ``strategy`` is a registered strategy name or a raw SelectFn — its STATIC
    ``SelectionResult.budget`` (default ``n_select``) fixes the gather width;
    ``full`` budgets the whole population and so degenerates to training
    everyone.  ``server_lr`` is the server interpolation rate (θ ← θ + η_s·Δ̄).

    ``mode="gather"`` (default) trains only the ``order[:B_pad]`` gathered
    slots (B padded to a multiple of G); ``mode="masked"`` is the legacy
    every-client-trains masked-psum baseline.  Both share selection and the
    weighted-delta scatter, so they are numerically interchangeable.

    ``exchange`` picks how the selected batch shards move in ``mode=
    "gather"``: ``"a2a"`` (default) the O(B) selected-shard exchange — one
    psum_scatter over the replicated slot routing moves only the B_pad
    selected clients' shards; ``"allgather"`` the O(N) full-round-batch
    all-gather baseline.  The two are BIT-IDENTICAL (every training slot has
    exactly one owning shard), pinned by the sharded subprocess parity test;
    :func:`exchange_bytes_per_device` gives the analytic ring-byte cost of
    each.

    ``n_clusters > 1`` is the CLUSTERED round (Aggregator families such as
    ``clustered_fedavg``): ``params`` leaves carry a leading (n_clusters,)
    axis (replicated — :func:`repro.fl.round.stack_global_params` builds the
    initial stack), every shard computes the same deterministic
    ``kmeans_cluster`` assignment from the replicated histogram matrix, each
    gathered slot trains from ITS cluster's model, and the weighted-delta
    psum runs once per cluster over membership-masked weights.  Because all
    of cluster c's members start from the same θ_c, the per-cluster delta
    mean equals the other engines' aggregate-then-interpolate algebraically;
    a cluster with no live member gets an exact-zero delta (ε denominator)
    and keeps its model.  ``info`` gains the replicated ``cluster_assign``
    (N,) and ``cluster_weights`` (n_clusters,) valid-population mixture
    weights.

    ``with_availability=True`` adds a trailing ``avail`` argument — a (N,)
    0/1 per-client availability vector (repro.core.noniid.availability_plan
    row), sharded over the client axis.  An unavailable client's histogram is
    zeroed, so every registry strategy's validity gate excludes it — the same
    single availability application the compiled simulator uses.

    ``reduce_fn`` switches the scatter phase from the weighted delta-psum
    collective to the GATHER-REDUCE form robust aggregation needs: the
    ``slots`` per-shard deltas are all-gathered to the replicated
    (B_pad, ...) stack, ``reduce_fn(trained, live, sizes)`` (a registered
    ``Aggregator.reduce`` — median/trimmed_mean/krum) runs replicated on
    every shard over ``trained = params + delta``, and the server
    interpolation finishes as usual.  The reduction must mask dead slots
    itself (every robust builtin does) — the padded ``B_pad − B`` slots
    arrive dead, exactly like a short selection.  Because the builtins are
    translation-equivariant, reduce-the-trained ≡ reduce-the-delta, so the
    gather path matches the host/sim robust trajectories the same way the
    psum pair matches fedavg.  Requires ``mode="gather"`` and a non-clustered
    family.

    Adversary statics (mirror of :func:`repro.fl.round.client_update_step`,
    both default-off → the identical pre-adversary program): ``poison_scale``
    and/or ``with_stale=True`` extend the signature with a replicated (N,)
    0/1 ``adv`` byzantine-mask argument (and, for ``with_stale``, a
    ``stale_params`` tree sharded like ``params``): byzantine slots train
    from the stale tree and report ``base + scale·(θ' − base)``, honest
    slots are untouched.  Not defined for clustered families.

    Returned signature: ``round_fn(params, batch, labels, valid, key
    [, avail][, adv][, stale_params]) -> (new_params, info)`` with ``key``
    the round's selection PRNG key (replicated; used by stochastic
    strategies such as ``random``).  The wrapper exposes the static facts:
    ``round_fn.budget`` (B), ``round_fn.trained_per_round`` (clients that
    spend FLOPs: B_pad gathered, N masked) and ``round_fn.flop_sparsity``
    (1 − trained/N).
    """
    if mode not in ("gather", "masked"):
        raise ValueError(f"mode must be 'gather' or 'masked'; got {mode!r}")
    if exchange not in ("a2a", "allgather"):
        raise ValueError(f"exchange must be 'a2a' or 'allgather'; "
                         f"got {exchange!r}")
    attacked = poison_scale is not None or with_stale
    if reduce_fn is not None or attacked:
        if n_clusters > 1:
            raise ValueError(
                "custom reduce overrides and engine-level adversary "
                "behaviors are single-global-model features; clustered "
                "families keep the per-cluster delta-psum pair")
        if reduce_fn is not None and mode != "gather":
            raise ValueError(
                "reduce_fn needs mode='gather' — the masked round's deltas "
                "are laid out in client-id order, not selection order")
    n_groups = mesh.shape[client_axis]
    n_clients = n_groups if num_clients is None else int(num_clients)
    if n_clients % n_groups:
        raise ValueError(
            f"num_clients ({n_clients}) must be a multiple of the client-axis "
            f"size ({n_groups}) so every group holds the same client block")
    per_group = n_clients // n_groups
    select_fn = get_strategy(strategy) if isinstance(strategy, str) else strategy

    budget = _static_budget(select_fn, n_select, n_clients, num_classes)
    slots = max(1, -(-budget // n_groups))       # selected clients per group
    budget_padded = slots * n_groups             # static gather width ≤ N
    trained_per_round = budget_padded if mode == "gather" else n_clients

    def round_fn(params: PyTree, batch: Dict[str, Array], labels: Array,
                 valid: Array, key: Array, *extras: Any
                 ) -> Tuple[PyTree, Dict[str, Array]]:
        # Trailing args appear in build-static order: [avail][, adv]
        # [, stale_params] — unpack by the same statics that built in_specs.
        rest = list(extras)
        avail = rest.pop(0) if with_availability else None
        adv = rest.pop(0) if attacked else None
        stale_params = rest.pop(0) if with_stale else None
        # labels/valid: (num_clients, n_i) sharded over the client axis →
        # per-shard (per_group, n_i); batch leaves likewise (per_group, ...).
        with phase("materialize"):
            hist = client_histograms(jnp.where(valid, labels, 0), num_classes,
                                     valid)
            if avail is not None:
                hist = hist * avail[:, None].astype(hist.dtype)  # dark → empty
        with phase("select"):
            hists_all = jax.lax.all_gather(hist, client_axis,
                                           tiled=True)       # (N, C)
            sel = select_fn(key, hists_all, n_select)  # replicated everywhere
            sizes = hists_all.sum(-1)                  # n_i (valid counts)
            g = jax.lax.axis_index(client_axis)

            if mode == "gather":
                # Re-shard: the top-B_pad selected clients' batch shards move
                # so each group trains exactly `slots` of them — the other
                # N − B_pad clients spend zero training FLOPs.
                my_slots = jax.lax.dynamic_slice_in_dim(
                    sel.order[:budget_padded], g * slots, slots)
                if exchange == "a2a":
                    my_batch = exchange_selected_shards(
                        batch, sel.order[:budget_padded], client_axis,
                        num_groups=n_groups, per_group=per_group)
                else:
                    my_batch = jax.tree_util.tree_map(
                        lambda x: x[my_slots],
                        gather_client_shards(batch, client_axis))
            else:
                my_slots = g * per_group + jnp.arange(per_group,
                                                      dtype=jnp.int32)
                my_batch = batch
            live = sel.mask[my_slots]           # 0 on dead/padded slots

        dt = agg_dtype or jnp.float32
        if n_clusters > 1:
            with phase("cluster"):
                # Replicated, deterministic — every shard computes the
                # identical assignment from the identical all-gathered
                # histogram matrix.
                assign, cent = kmeans_cluster(hists_all, n_clusters,
                                              n_iters=kmeans_iters)
            with phase("train"):
                cl_my = assign[my_slots]                   # (slots,)
                params_slot = jax.tree_util.tree_map(
                    lambda g: g[cl_my], params)            # each slot's θ_c
                new_local = jax.vmap(local_step)(params_slot, my_batch)
            with phase("aggregate"):
                delta = jax.tree_util.tree_map(
                    lambda a, b: (a.astype(jnp.float32)
                                  - b.astype(jnp.float32)).astype(dt),
                    new_local, params_slot)
                w = live * sizes[my_slots]
                member = (cl_my[None, :] == jnp.arange(n_clusters)[:, None])
                w_mc = member.astype(w.dtype) * w[None, :]  # (M, slots)
                # One weighted delta-psum per cluster (vmapped over the
                # membership-masked weight rows); a memberless cluster's
                # numerator is exactly zero, so its model survives unchanged.
                agg_delta = jax.vmap(
                    lambda wc: psum_weighted_mean(delta, wc, client_axis,
                                                  local_sum=weighted_sum_tree)
                )(w_mc)
                new_global = jax.tree_util.tree_map(
                    lambda p, d: (p.astype(jnp.float32)
                                  + server_lr * d).astype(p.dtype),
                    params, agg_delta)
            valid_all = (hists_all.sum(-1) > 0).astype(jnp.float32)
            info = {"mask": sel.mask, "num_selected": sel.mask.sum(),
                    "scores": sel.scores, "cluster_assign": assign,
                    "cluster_centroids": cent,
                    "cluster_weights": cluster_counts(assign, n_clusters,
                                                      weights=valid_all)}
            return new_global, info

        n_slots = live.shape[0]
        with phase("train"):
            if with_stale:
                # Byzantine slots train from the τ-rounds-old global tree
                # the caller carries; honest slots from the current one —
                # the same per-slot base jnp.where client_update_step builds.
                a_bool = adv[my_slots] > 0
                base = jax.tree_util.tree_map(
                    lambda gp, st: jnp.where(
                        _slot_bcast(a_bool, gp[None]),
                        jnp.broadcast_to(st, (n_slots,) + st.shape),
                        jnp.broadcast_to(gp, (n_slots,) + gp.shape)),
                    params, stale_params)
                new_local = jax.vmap(local_step)(base, my_batch)
            else:
                base = None
                new_local = jax.vmap(local_step, in_axes=(None, 0))(
                    params, my_batch)
            if poison_scale is not None:
                # Byzantine slots report base + s·(θ' − base) — with the
                # fedsgd local_step (θ − lr·∇) and base = θ this is exactly
                # client_update_step's scaled-gradient report, so one statement
                # covers both families.
                s = float(poison_scale)
                a = adv[my_slots].astype(jnp.float32)
                pb = base if base is not None else jax.tree_util.tree_map(
                    lambda gp: jnp.broadcast_to(gp, (n_slots,) + gp.shape),
                    params)
                new_local = jax.tree_util.tree_map(
                    lambda u, b: jnp.where(
                        _slot_bcast(a, u) > 0,
                        (b + s * (u - b)).astype(u.dtype), u),
                    new_local, pb)
        info = {"mask": sel.mask, "num_selected": sel.mask.sum(),
                "scores": sel.scores}
        with phase("aggregate"):
            # Aggregating DELTAS (not params) tolerates low precision: bf16
            # halves the cross-pod all-reduce bytes (§Perf, FL-round lever).
            delta = jax.tree_util.tree_map(
                lambda a, b: (a.astype(jnp.float32)
                              - b.astype(jnp.float32)).astype(dt),
                new_local, params)
            if reduce_fn is not None:
                # GATHER-REDUCE: all-gather the B_pad selected deltas (still
                # the compact delta form — bf16 agg_dtype halves these bytes
                # too), rebuild the trained stack and run the robust
                # reduction replicated on every shard; dead/padded slots are
                # masked by the reduction itself.  live/sizes come from the
                # replicated selection, so no second collective is needed.
                order_b = sel.order[:budget_padded]
                delta_all = gather_client_shards(delta, client_axis)
                trained = jax.tree_util.tree_map(
                    lambda p, d: p.astype(jnp.float32)
                    + d.astype(jnp.float32), params, delta_all)
                live_all = sel.mask[order_b]
                agg_p = reduce_fn(trained, live_all, sizes[order_b])
                new_global = interpolate(params, agg_p, server_lr)
                any_live = live_all.sum() > 0
                new_global = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(any_live, new, old),
                    new_global, params)
            else:
                # The in-shard Σ_s w·Δ slot reduction routes through the
                # compute dispatch (fused Pallas kernel on TPU, plain XLA
                # elsewhere); the psum pair then finishes the replicated
                # mean.
                agg_delta = psum_weighted_mean(delta, live * sizes[my_slots],
                                               client_axis,
                                               local_sum=weighted_sum_tree)
                new_global = jax.tree_util.tree_map(
                    lambda p, d: (p.astype(jnp.float32)
                                  + server_lr * d).astype(p.dtype),
                    params, agg_delta)
        return new_global, info

    def add_client_axis(spec):
        return P(*((client_axis,) + tuple(spec)))

    batch_specs = jax.tree_util.tree_map(
        add_client_axis, batch_pspec,
        is_leaf=lambda x: isinstance(x, P))
    lv_spec = P(client_axis)
    out_info_spec = {"mask": P(), "num_selected": P(), "scores": P()}
    if n_clusters > 1:   # replicated clustering facts join the info pytree
        out_info_spec.update({"cluster_assign": P(), "cluster_weights": P(),
                              "cluster_centroids": P()})

    in_specs = (params_pspec, batch_specs, lv_spec, lv_spec, P())
    if with_availability:
        in_specs = in_specs + (lv_spec,)
    if attacked:
        # The (N,) byzantine mask is replicated — every shard indexes its own
        # my_slots out of the full mask, exactly like the replicated order.
        in_specs = in_specs + (P(),)
    if with_stale:
        in_specs = in_specs + (params_pspec,)
    # jit the mapped round: eager shard_map re-lowers on every call, which
    # would make each round pay compile time — jit compiles once per shape.
    mapped = jax.jit(shard_map(round_fn, mesh, in_specs=in_specs,
                               out_specs=(params_pspec, out_info_spec)))

    @functools.wraps(mapped)
    def wrapper(*args):
        return mapped(*args)

    wrapper.budget = budget
    wrapper.budget_padded = budget_padded
    wrapper.trained_per_round = trained_per_round
    wrapper.flop_sparsity = 1.0 - trained_per_round / n_clients
    wrapper.mode = mode
    wrapper.exchange = exchange if mode == "gather" else None
    wrapper.n_clusters = n_clusters
    return wrapper


def exchange_bytes_per_device(batch: Dict[str, Array], num_clients: int,
                              budget_padded: int, num_groups: int,
                              exchange: str) -> int:
    """Analytic per-device ring bytes of the gather-phase batch exchange.

    ``batch`` leaves carry the (num_clients, ...) client axis; a client's
    shard is ``prod(shape[1:]) · itemsize`` bytes per leaf (bool leaves ride
    the a2a psum_scatter as int8 — also 1 byte, so the modes' per-client
    bytes agree).  On a ring, ``allgather`` receives the other groups'
    ``N − N/G`` client shards; ``a2a`` (reduce-scatter over the B_pad slot
    routing) moves ``B_pad − B_pad/G`` shards — O(B) instead of O(N)."""
    if exchange not in ("a2a", "allgather"):
        raise ValueError(f"exchange must be 'a2a' or 'allgather'; "
                         f"got {exchange!r}")
    per_client = 0
    for leaf in jax.tree_util.tree_leaves(batch):
        n_elems = 1
        for d in leaf.shape[1:]:
            n_elems *= int(d)
        per_client += n_elems * jnp.dtype(leaf.dtype).itemsize
    rows = num_clients if exchange == "allgather" else budget_padded
    return (rows - rows // num_groups) * per_client
