"""In-graph metrics registry — the observability counterpart of the
strategy / workload / aggregator registries.

A *metric* is a traced observer of one engine round: ``fn(round_state) ->
scalar or small array`` in pure JAX ops, compiled INTO the engines' round
bodies (the simulator's ``lax.scan``, the population engines' window scans)
or evaluated on the round's device arrays in the host-looped engines — never
through a host callback.  ``round_state`` is a plain dict the engine
assembles per round; every entry is either a traced array or a static Python
int (shapes):

==================  =======================================================
``hists``           (N, C) f32 per-client label histograms, availability
                    already applied (a dark client's row is zero)
``mask``            (N,) f32 0/1 selection mask after the validity gate
``num_classes``     static int C
``params_old``      the global parameter pytree entering the round
``params_new``      the pytree leaving it (clustered families: the
                    (n_clusters, …) stacked tree)
``assign``          (N,) int32 round k-means assignment  (clustered only)
``n_clusters``      static int M                         (clustered only)
``centroids``       (M, C) round k-means centroids       (clustered only)
``prev_centroids``  (M, C) previous round's centroids — ZEROS on the first
                    round, so round-0 "drift" is the distance from the
                    origin (documented, deterministic on every engine)
``staleness_delays`` (K,) int32 effective staleness of each buffered
                    arrival                              (async only)
``tau_max``         static int                           (async only)
``client_update_norms`` (N,) f32 ℓ₂ norm of each client's AS-REPORTED
                    update this round (post-poison — the attack-visible
                    signal), zero for unselected clients (single-global-
                    model families on sim/host; computed only when a
                    resolved metric asks, so telemetry-off programs are
                    bit-identical)
``expert_load``     (budget, layers, held + 1) f32: per selected slot, the
                    tokens its local training routed to each held expert
                    of each layer, then the held assignments dropped (a
                    workload's ``counters``; zero for a dead slot)
``expert_rows_run`` (budget, layers) f32: per selected slot, the rows each
                    layer's expert dispatch ran over its local training
                    (a workload's ``counters``; zero for a dead slot)
==================  =======================================================

A metric declares ``requires`` — the state keys it reads; an engine collects
exactly the requested metrics whose requirements it can satisfy (the resolved
set is a trace-time static, so telemetry-off compiles the identical program).
Registration follows the strategy-registry contract: append-only stable ids
(:func:`metric_id` positions never remap), ``overwrite=True`` keeps the id,
and ``check=True`` runs the jaxpr contract pass (repro.analysis A301/A302 +
the shared A005/A006 forbidden-primitive scan) at registration time.

Metrics are requested per experiment via ``ExperimentSpec.telemetry`` —
metric names, or ``("auto",)`` for every builtin the engine can satisfy —
or globally via ``REPRO_TELEMETRY`` (``1``/``all``/``auto``, a comma list of
names, or ``0``/``off``; the spec field wins when non-empty).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Dict, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array

ENV_TELEMETRY = "REPRO_TELEMETRY"

# Base result axes every series shares; a metric's own trailing axes append.
BASE_AXES = ("scenario", "strategy", "seed", "round")


@dataclasses.dataclass(frozen=True)
class Metric:
    """One registered round metric.

    ``fn(round_state) -> Array`` must be traceable pure JAX ops over the
    state entries named in ``requires`` (arrays) — static ints may also be
    read for shapes.  ``axes`` labels the trailing dims of the returned
    array (``()`` for a scalar)."""
    name: str
    fn: Callable[[Mapping[str, Any]], Array]
    requires: Tuple[str, ...] = ()
    axes: Tuple[str, ...] = ()


_METRICS: Dict[str, Metric] = {}
_METRIC_IDS: list = []          # append-only ledger: position = stable id


def register_metric(name: str, fn: Callable, *, requires: Sequence[str] = (),
                    axes: Sequence[str] = (), overwrite: bool = False,
                    check: bool = False) -> Metric:
    """Register a round metric under ``name``.

    Same open-registry contract as strategies: ids are append-only
    (``overwrite=True`` replaces the callable but keeps the id), and
    ``check=True`` raises :class:`repro.analysis.ContractError` if the fn
    violates the metric contract (untraceable, oversized output, forbidden
    primitives)."""
    if not name or not isinstance(name, str):
        raise ValueError(f"metric name must be a non-empty str; got {name!r}")
    if name in _METRICS and not overwrite:
        raise ValueError(f"metric {name!r} already registered")
    if not callable(fn):
        raise TypeError(f"metric {name!r} must be callable; got {type(fn)}")
    m = Metric(name=name, fn=fn, requires=tuple(requires), axes=tuple(axes))
    if check:
        from repro.analysis import assert_metric_contract
        assert_metric_contract(name, m)
    _METRICS[name] = m
    if name not in _METRIC_IDS:
        _METRIC_IDS.append(name)
    return m


def registered_metrics() -> Tuple[str, ...]:
    """Registered metric names in stable-id order."""
    return tuple(_METRIC_IDS)


def metric_id(name: str) -> int:
    """The append-only stable id of ``name`` (position in the ledger)."""
    try:
        return _METRIC_IDS.index(name)
    except ValueError:
        raise KeyError(f"unknown metric {name!r}; have "
                       f"{registered_metrics()}") from None


def get_metric(name: str) -> Metric:
    if name not in _METRICS:
        raise KeyError(f"unknown metric {name!r}; have "
                       f"{registered_metrics()}")
    return _METRICS[name]


def metrics_registry() -> Dict[str, Metric]:
    """Live name → Metric view (the analysis layer iterates it)."""
    return _METRICS


# ---------------------------------------------------------------------------
# Request resolution
# ---------------------------------------------------------------------------

def resolve_telemetry_request(spec_telemetry: Sequence[str] = ()
                              ) -> Tuple[str, ...]:
    """The effective metric request: the spec's own ``telemetry`` tuple when
    non-empty, else the ``REPRO_TELEMETRY`` env var (``0``/``off``/unset →
    no telemetry; ``1``/``on``/``all``/``auto`` → every applicable builtin;
    otherwise a comma list of metric names)."""
    if spec_telemetry:
        return tuple(spec_telemetry)
    raw = os.environ.get(ENV_TELEMETRY, "").strip()
    if not raw or raw.lower() in ("0", "off", "false", "none"):
        return ()
    if raw.lower() in ("1", "on", "all", "auto", "true"):
        return ("auto",)
    return tuple(n.strip() for n in raw.split(",") if n.strip())


def resolve_metrics(names: Sequence[str], available: Sequence[str]
                    ) -> Tuple[Metric, ...]:
    """The metrics an engine will actually collect: the requested ``names``
    (``"auto"`` expands to every registered metric) filtered to those whose
    ``requires`` the engine's ``available`` state keys satisfy.  Unknown
    names raise (also enforced earlier, at ``spec.validate()``); a known but
    inapplicable metric (e.g. ``staleness_hist`` on the sim engine) is
    silently skipped — applicability is an engine fact, not an error."""
    avail = set(available)
    want: list = []
    for n in names:
        if n == "auto":
            for reg in _METRIC_IDS:
                if reg not in want:
                    want.append(reg)
        elif n not in want:
            get_metric(n)
            want.append(n)
    return tuple(m for m in (get_metric(n) for n in want)
                 if set(m.requires) <= avail)


def collect_metrics(metrics: Sequence[Metric], state: Mapping[str, Any]
                    ) -> Dict[str, Array]:
    """Evaluate ``metrics`` over one round's state dict → name → f32 array.
    Pure traced ops — callable inside a scan body or under jit."""
    return {m.name: jnp.asarray(m.fn(state), jnp.float32) for m in metrics}


def make_collector(metrics: Sequence[Metric],
                   static_state: Mapping[str, Any] = ()) -> Callable:
    """A jit-friendly collector: statics (num_classes, n_clusters, tau_max)
    ride the closure so the dynamic state dict holds only arrays."""
    statics = dict(static_state or {})
    metrics = tuple(metrics)

    def collect(dyn: Mapping[str, Array]) -> Dict[str, Array]:
        return collect_metrics(metrics, {**statics, **dyn})

    return collect


# ---------------------------------------------------------------------------
# Builtin metrics (stable ids 0..5 — append-only, like strategy ids)
# ---------------------------------------------------------------------------

def _selection_entropy(state: Mapping[str, Any]) -> Array:
    """Shannon entropy (nats) of the selected set's pooled label pdf — the
    paper's uniformity signal; 0 when nothing is selected, collapsing toward
    0 when the selected clients concentrate on few classes."""
    h = (state["hists"] * state["mask"][:, None]).sum(0)
    p = h / jnp.maximum(h.sum(), 1e-9)
    return -(p * jnp.log(jnp.maximum(p, 1e-12))).sum()


def _selected_label_hist(state: Mapping[str, Any]) -> Array:
    """(C,) pooled label counts over the selected clients."""
    return (state["hists"] * state["mask"][:, None]).sum(0)


def _update_norm(state: Mapping[str, Any]) -> Array:
    """Global-model update norm ‖Δθ‖₂ over every leaf (clustered families:
    over the whole stacked tree)."""
    sq = sum(((n.astype(jnp.float32) - o.astype(jnp.float32)) ** 2).sum()
             for n, o in zip(jax.tree_util.tree_leaves(state["params_new"]),
                             jax.tree_util.tree_leaves(state["params_old"])))
    return jnp.sqrt(sq)


def _cluster_occupancy(state: Mapping[str, Any]) -> Array:
    """(M,) valid-client population per k-means cluster — a persistent zero
    row is the "cluster starved" failure the report layer flags."""
    assign = state["assign"]
    m = state["n_clusters"]
    valid = (state["hists"].sum(-1) > 0).astype(jnp.float32)
    member = assign[None, :] == jnp.arange(m)[:, None]
    return (member.astype(jnp.float32) * valid[None, :]).sum(-1)


def _centroid_drift(state: Mapping[str, Any]) -> Array:
    """Mean per-cluster L2 distance between this round's and the previous
    round's centroids (round 0 measures from the zero state — see module
    docstring)."""
    d = state["centroids"] - state["prev_centroids"]
    return jnp.sqrt((d ** 2).sum(-1)).mean()


def _staleness_hist(state: Mapping[str, Any]) -> Array:
    """(tau_max + 1,) count of buffered arrivals at each staleness level."""
    tau = state["staleness_delays"]
    w = int(state["tau_max"]) + 1
    onehot = tau[:, None] == jnp.arange(w, dtype=tau.dtype)[None, :]
    return onehot.astype(jnp.float32).sum(0)


def _delta_outlier(state: Mapping[str, Any]) -> Array:
    """(N,) z-scores of each SELECTED client's as-reported update norm
    against the round's selected-set mean/std — the byzantine fingerprint: a
    poisoned (scale·Δ) or stale report sits |z| σs away from the honest
    cluster.  Unselected clients read exactly 0; an all-equal round (e.g.
    one selected client) reads 0 via the ε-guarded std."""
    norms = state["client_update_norms"]
    m = state["mask"]
    cnt = jnp.maximum(m.sum(), 1.0)
    mean = (norms * m).sum() / cnt
    var = (((norms - mean) ** 2) * m).sum() / cnt
    return (norms - mean) / jnp.sqrt(var + 1e-12) * m


def _expert_load(state: Mapping[str, Any]) -> Array:
    """(budget, layers, held experts + 1): the round's per-client expert
    load, as the workload's loss counted it over local training."""
    return state["expert_load"]


def _expert_rows_run(state: Mapping[str, Any]) -> Array:
    """(budget, layers): the rows each layer's expert dispatch ran over the
    round's local training, per client, as the workload's loss counted
    them."""
    return state["expert_rows_run"]


register_metric("selection_entropy", _selection_entropy,
                requires=("hists", "mask"))
register_metric("selected_label_hist", _selected_label_hist,
                requires=("hists", "mask"), axes=("class",))
register_metric("update_norm", _update_norm,
                requires=("params_old", "params_new"))
register_metric("cluster_occupancy", _cluster_occupancy,
                requires=("hists", "assign", "n_clusters"), axes=("cluster",))
register_metric("centroid_drift", _centroid_drift,
                requires=("centroids", "prev_centroids"))
register_metric("staleness_hist", _staleness_hist,
                requires=("staleness_delays", "tau_max"), axes=("staleness",))
register_metric("delta_outlier", _delta_outlier,
                requires=("client_update_norms", "mask"), axes=("client",))
register_metric("expert_load", _expert_load, requires=("expert_load",),
                axes=("slot", "layer", "expert"))
register_metric("expert_rows_run", _expert_rows_run,
                requires=("expert_rows_run",), axes=("slot", "layer"))
