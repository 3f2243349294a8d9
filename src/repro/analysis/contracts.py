"""Jaxpr-level contract verification for the registry axes.

Every registered strategy / workload / aggregator must compile into the
engines' traced round bodies, which means its contract — documented prose in
``repro.core.selection`` / ``repro.fl.workloads`` / ``repro.core.aggregation``
— is checkable *abstractly*, before anything compiles: ``jax.eval_shape`` /
``jax.make_jaxpr`` run the callable over shape/dtype placeholders, so schema
violations, host-side tracer concretization (``if traced_bool:``), forbidden
primitives (callbacks, ``debug_print``, constant-seeded PRNG) and
block-separability all surface here as structured
:class:`~repro.analysis.diagnostics.Diagnostic` findings instead of a stack
trace buried in a ``lax.scan`` trace at compile time.

Three entry points:

* ``check_strategy`` / ``check_workload`` / ``check_aggregator`` — one
  registry entry each, returning :class:`Findings`;
* ``check_spec(spec)`` — exactly the entries an :class:`ExperimentSpec`
  resolves, at the spec's own shapes (``ExperimentSpec.validate(deep=True)``
  raises :class:`ContractError` when this finds errors);
* ``check_registries()`` — every registered entry at canonical shapes (the
  ``python -m repro.analysis`` contract layer).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Iterator

import jax
import jax.numpy as jnp

from .diagnostics import ContractError, Findings
from .separability import classify_strategy

# Host-side concretization of traced values: the error family jax raises
# when a traced body branches on (or converts) an abstract value.
TRACE_ERRORS = (jax.errors.ConcretizationTypeError,
                jax.errors.TracerArrayConversionError,
                jax.errors.TracerBoolConversionError,
                jax.errors.TracerIntegerConversionError)

# Primitives that must not appear in a registry callable's traced body:
# callbacks punch through the compiled round (host sync every scan step) and
# debug prints are side effects the engines never expect.
FORBIDDEN_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
})

# `random_seed` inside a traced body means a PRNG key was built from a
# constant — the same draw every round/trace, never what a strategy or
# materializer wants (engines hand every callable an already-folded key).
CONST_SEEDED_PRNG = frozenset({"random_seed"})


def _iter_primitives(closed) -> Iterator[str]:
    """All primitive names in a ClosedJaxpr, recursing into sub-jaxprs."""
    from jax.extend import core as jex

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn.primitive.name
            for val in eqn.params.values():
                if isinstance(val, jex.ClosedJaxpr):
                    yield from walk(val.jaxpr)
                elif isinstance(val, jex.Jaxpr):
                    yield from walk(val)
                elif isinstance(val, (tuple, list)):
                    for v in val:
                        if isinstance(v, jex.ClosedJaxpr):
                            yield from walk(v.jaxpr)
                        elif isinstance(v, jex.Jaxpr):
                            yield from walk(v)

    yield from walk(closed.jaxpr)


def _scan_forbidden(closed, kind: str, name: str, where: str,
                    out: Findings) -> None:
    seen: Dict[str, int] = {}
    for prim in _iter_primitives(closed):
        if prim in FORBIDDEN_PRIMITIVES or prim in CONST_SEEDED_PRNG:
            seen[prim] = seen.get(prim, 0) + 1
    for prim, count in sorted(seen.items()):
        if prim in CONST_SEEDED_PRNG:
            out.add("A006", "error", kind, name,
                    f"constant-seeded PRNG in traced {where} "
                    f"({prim} ×{count}): keys must come from the engine's "
                    "folded key argument, never jax.random.PRNGKey(const)",
                    primitive=prim, count=count, where=where)
        else:
            out.add("A005", "error", kind, name,
                    f"forbidden primitive {prim!r} ×{count} in traced "
                    f"{where}: callbacks/debug prints cannot ride in the "
                    "engines' compiled round bodies",
                    primitive=prim, count=count, where=where)


def _trace_diag(out: Findings, e: Exception, *, kind: str, name: str,
                where: str) -> None:
    """Fold a trace-time exception into one structured diagnostic."""
    first_line = str(e).strip().split("\n")[0]
    if isinstance(e, TRACE_ERRORS):
        out.add("A001" if kind == "strategy" else "A102", "error", kind, name,
                f"{where} concretizes a traced value host-side "
                f"({type(e).__name__}): {first_line}",
                where=where, error=type(e).__name__)
    else:
        out.add("A002" if kind == "strategy" else "A102", "error", kind, name,
                f"{where} raised under abstract evaluation "
                f"({type(e).__name__}): {first_line}",
                where=where, error=type(e).__name__)


# ---------------------------------------------------------------------------
# Strategy contract
# ---------------------------------------------------------------------------

def check_strategy(name: str, fn: Callable, *, num_clients: int = 16,
                   num_classes: int = 10, n_select: int = 8,
                   separability: bool = True) -> Findings:
    """Verify one selection strategy against the ``register_strategy``
    contract: traceable, SelectionResult schema (mask/scores/order shapes and
    dtypes, static-int budget), no forbidden primitives, plus the
    block-separability classification (reported as info — engines that need
    the property enforce it; ``sim``/``host``/``sharded`` don't)."""
    out = Findings()
    budget_cell: list = []

    def wrapper(key, hists):
        r = fn(key, hists, n_select)
        budget_cell.append(getattr(r, "budget", "MISSING"))
        return (getattr(r, "mask", None), getattr(r, "scores", None),
                getattr(r, "order", None))

    try:
        closed = jax.make_jaxpr(wrapper)(
            jax.ShapeDtypeStruct((2,), jnp.uint32),
            jax.ShapeDtypeStruct((num_clients, num_classes), jnp.float32))
    except Exception as e:
        _trace_diag(out, e, kind="strategy", name=name,
                    where=f"fn(key, hists[{num_clients},{num_classes}], "
                          f"{n_select})")
        return out

    avals = list(closed.out_avals)
    fields = ("mask", "scores", "order")
    want = {"mask": ((num_clients,), jnp.float32),
            "scores": ((num_clients,), jnp.float32),
            "order": ((num_clients,), jnp.int32)}
    if len(avals) != 3:
        out.add("A003", "error", "strategy", name,
                f"fn must return SelectionResult(mask, scores, order, budget);"
                f" traced output has {len(avals)} array leaves",
                leaves=len(avals))
        return out
    for field, aval in zip(fields, avals):
        shape, dtype = want[field]
        got_shape = tuple(getattr(aval, "shape", ()))
        got_dtype = getattr(aval, "dtype", None)
        if got_shape != shape or got_dtype != dtype:
            out.add("A003", "error", "strategy", name,
                    f"SelectionResult.{field} must be {dtype.__name__}"
                    f"{list(shape)}; got "
                    f"{getattr(got_dtype, 'name', got_dtype)}"
                    f"{list(got_shape)}",
                    field=field, want_shape=list(shape),
                    want_dtype=dtype.__name__,
                    got_shape=list(got_shape),
                    got_dtype=str(got_dtype))
    budget = budget_cell[0] if budget_cell else "MISSING"
    if budget is not None and (isinstance(budget, bool)
                               or not isinstance(budget, int)):
        out.add("A004", "error", "strategy", name,
                "SelectionResult.budget must be a static Python int or None "
                f"(the engines' gather width is a trace-time shape); got "
                f"{type(budget).__name__}",
                budget_type=type(budget).__name__)
    _scan_forbidden(closed, "strategy", name, "strategy body", out)

    if separability:
        v = classify_strategy(fn, num_clients=max(8, min(num_clients, 64)),
                              num_classes=num_classes, name=name)
        out.add("A007", "info", "strategy", name,
                f"block-separability: {'separable' if v.separable else 'NOT separable'}"
                f" (scores={v.scores_dep}, mask_probe={v.mask_consistent})",
                separable=v.separable, scores_dep=v.scores_dep,
                mask_consistent=v.mask_consistent,
                reasons=list(v.reasons))
    return out


# ---------------------------------------------------------------------------
# Workload contract
# ---------------------------------------------------------------------------

def check_workload(name: str, wl, *, ds: Any = None, num_clients: int = 8,
                   plan_n: int = 6) -> Findings:
    """Verify one workload bundle: ``materialize`` schema (``labels`` /
    ``valid`` / ``hists`` + declared ``batch_keys``, histogram width =
    ``num_classes``), traceable init/loss, and eval metrics containing
    ``"accuracy"``."""
    out = Findings()
    try:
        ds = wl.dataset(ds)
        num_classes = int(wl.num_classes(ds))
    except Exception as e:
        _trace_diag(out, e, kind="workload", name=name,
                    where="make_dataset/num_classes")
        return out

    plan_sds = jax.ShapeDtypeStruct((num_clients, plan_n), jnp.int32)
    key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)

    # -- materialize schema (eval_shape keeps the dict structure) -----------
    mat = None
    try:
        mat = jax.eval_shape(lambda p, k: wl.materialize(ds, p, k),
                             plan_sds, key_sds)
    except Exception as e:
        _trace_diag(out, e, kind="workload", name=name,
                    where=f"materialize(ds, plan[{num_clients},{plan_n}], key)")
    if mat is not None:
        if not isinstance(mat, dict):
            out.add("A101", "error", "workload", name,
                    f"materialize must return a dict; got {type(mat).__name__}")
            mat = None
    if mat is not None:
        want = {"labels": ((num_clients, plan_n), jnp.int32),
                "valid": ((num_clients, plan_n), jnp.bool_),
                "hists": ((num_clients, num_classes), jnp.float32)}
        for k, (shape, dtype) in want.items():
            if k not in mat:
                out.add("A101", "error", "workload", name,
                        f"materialize output is missing required key {k!r} "
                        f"(contract: labels/valid/hists + batch_keys)",
                        missing_key=k, have=sorted(mat))
                continue
            got = mat[k]
            if tuple(got.shape) != shape or got.dtype != dtype:
                out.add("A101", "error", "workload", name,
                        f"materialize[{k!r}] must be {dtype.__name__}"
                        f"{list(shape)}; got {got.dtype}{list(got.shape)}",
                        key=k, want_shape=list(shape),
                        got_shape=list(got.shape), got_dtype=str(got.dtype))
        for k in wl.batch_keys:
            if k not in mat:
                out.add("A101", "error", "workload", name,
                        f"declared batch_keys entry {k!r} is absent from the "
                        "materialize output", missing_key=k)
            elif tuple(mat[k].shape[:2]) != (num_clients, plan_n):
                out.add("A101", "error", "workload", name,
                        f"batch_keys leaf {k!r} must lead with "
                        f"(N, n_max) = ({num_clients}, {plan_n}); got "
                        f"{list(mat[k].shape)}",
                        key=k, got_shape=list(mat[k].shape))

    # -- forbidden primitives in the materializer ---------------------------
    try:
        closed = jax.make_jaxpr(lambda p, k: wl.materialize(ds, p, k))(
            plan_sds, key_sds)
        _scan_forbidden(closed, "workload", name, "materialize", out)
    except Exception:
        pass  # already diagnosed above

    # -- init / loss / eval -------------------------------------------------
    params = None
    try:
        params = jax.eval_shape(lambda k: wl.init(k, ds), key_sds)
    except Exception as e:
        _trace_diag(out, e, kind="workload", name=name, where="init(key, ds)")
    if params is not None and mat is not None and not out.errors():
        batch = {k: jax.ShapeDtypeStruct(tuple(mat[k].shape[1:]),
                                         mat[k].dtype)
                 for k in wl.batch_keys}
        try:
            loss_out = jax.eval_shape(wl.make_loss(ds), params, batch)
            if tuple(loss_out[0].shape) != ():
                out.add("A102", "error", "workload", name,
                        "make_loss(ds)(params, batch) must return a scalar "
                        f"loss first; got shape {list(loss_out[0].shape)}")
        except Exception as e:
            _trace_diag(out, e, kind="workload", name=name,
                        where="make_loss(ds)(params, one-client batch)")
    if params is not None:
        try:
            eval_batch = wl.eval_set(ds, 2)
            _, metrics = jax.eval_shape(wl.make_eval(ds), params, eval_batch)
            if not isinstance(metrics, dict) or "accuracy" not in metrics:
                have = sorted(metrics) if isinstance(metrics, dict) else \
                    type(metrics).__name__
                out.add("A103", "error", "workload", name,
                        'make_eval metrics must contain "accuracy" (the '
                        f"trajectory every engine records); got {have}",
                        have=have)
        except Exception as e:
            _trace_diag(out, e, kind="workload", name=name,
                        where="make_eval(ds)(params, eval_set(ds, 2))")
    return out


# ---------------------------------------------------------------------------
# Aggregator contract
# ---------------------------------------------------------------------------

def check_aggregator(name: str, agg, *, params: Any = None,
                     num_slots: int = 5) -> Findings:
    """Verify one aggregation family.  Builtin reductions (``reduce=None``)
    resolve to the parity-pinned backend dispatch and need no trace; a custom
    ``reduce`` must map ``(stacked, live, sizes) -> tree`` preserving the
    per-client tree structure, shapes and dtypes."""
    out = Findings()
    if agg.reduce is None:
        return out
    if params is None:
        params = {"w": jax.ShapeDtypeStruct((4, 3), jnp.float32),
                  "b": jax.ShapeDtypeStruct((3,), jnp.float32)}
    stacked = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct((num_slots,) + tuple(p.shape),
                                       p.dtype), params)
    live = jax.ShapeDtypeStruct((num_slots,), jnp.float32)
    sizes = jax.ShapeDtypeStruct((num_slots,), jnp.float32)
    try:
        got = jax.eval_shape(agg.reduce, stacked, live, sizes)
    except Exception as e:
        first_line = str(e).strip().split("\n")[0]
        code = "A202"
        sev_where = ("reduce(stacked, live, sizes) "
                     f"({type(e).__name__}): {first_line}")
        if isinstance(e, TRACE_ERRORS):
            out.add(code, "error", "aggregator", name,
                    f"custom reduce concretizes a traced value host-side — "
                    + sev_where, error=type(e).__name__)
        else:
            out.add(code, "error", "aggregator", name,
                    "custom reduce raised under abstract evaluation — "
                    + sev_where, error=type(e).__name__)
        return out
    want_td = jax.tree_util.tree_structure(params)
    got_td = jax.tree_util.tree_structure(got)
    if want_td != got_td:
        out.add("A201", "error", "aggregator", name,
                "custom reduce must return the per-client tree structure "
                f"{want_td}; got {got_td}")
        return out
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(params),
                            jax.tree_util.tree_leaves(got)):
        if tuple(w.shape) != tuple(g.shape) or w.dtype != g.dtype:
            leaf = jax.tree_util.keystr(path)
            out.add("A201", "error", "aggregator", name,
                    f"custom reduce leaf {leaf} must be "
                    f"{w.dtype}{list(w.shape)}; got {g.dtype}{list(g.shape)}",
                    leaf=leaf, want_shape=list(w.shape),
                    got_shape=list(g.shape))
    try:
        closed = jax.make_jaxpr(agg.reduce)(stacked, live, sizes)
        _scan_forbidden(closed, "aggregator", name, "reduce", out)
    except Exception:
        pass
    return out


# ---------------------------------------------------------------------------
# Metric contract (repro.obs registry)
# ---------------------------------------------------------------------------

# Metric series ride every engine's scan ys (one slot per round per grid
# cell); anything bigger than this is a trajectory, not a metric.
MAX_METRIC_ELEMS = 4096


def _metric_state(num_clients: int, num_classes: int, n_clusters: int,
                  buffer_k: int):
    """The canonical abstract round-state: the superset of every engine's
    documented keys (repro.obs.registry) at small shapes — dynamic
    ShapeDtypeStruct leaves plus the static ints."""
    params = {"w": jax.ShapeDtypeStruct((3, 2), jnp.float32),
              "b": jax.ShapeDtypeStruct((2,), jnp.float32)}
    cent = jax.ShapeDtypeStruct((n_clusters, num_classes), jnp.float32)
    dyn = {
        "hists": jax.ShapeDtypeStruct((num_clients, num_classes),
                                      jnp.float32),
        "mask": jax.ShapeDtypeStruct((num_clients,), jnp.float32),
        "params_old": params, "params_new": params,
        "assign": jax.ShapeDtypeStruct((num_clients,), jnp.int32),
        "centroids": cent, "prev_centroids": cent,
        "staleness_delays": jax.ShapeDtypeStruct((buffer_k,), jnp.int32),
        "client_update_norms": jax.ShapeDtypeStruct((num_clients,),
                                                    jnp.float32),
        # (slots, layers, held experts + 1): a workload counter
        "expert_load": jax.ShapeDtypeStruct((4, 2, 3), jnp.float32),
        "expert_rows_run": jax.ShapeDtypeStruct((4, 2), jnp.float32),
    }
    return dyn


def check_metric(name: str, metric: Any = None, *, num_clients: int = 16,
                 num_classes: int = 10, n_clusters: int = 4,
                 buffer_k: int = 4, tau_max: int = 2) -> Findings:
    """Verify one round metric (repro.obs registry) against its contract:
    ``fn(round_state)`` traceable over the canonical abstract state (A301),
    returning exactly one small array whose rank matches the declared
    trailing ``axes`` (A302), with no forbidden primitives in the traced
    body (the shared A005/A006 scan) — metrics compile INTO the engines'
    scan bodies, so a callback here would host-sync every round."""
    from repro.obs import get_metric
    out = Findings()
    if metric is None:
        metric = get_metric(name)
    dyn = _metric_state(num_clients, num_classes, n_clusters, buffer_k)
    statics = {"num_classes": num_classes, "n_clusters": n_clusters,
               "tau_max": tau_max}

    try:
        closed = jax.make_jaxpr(
            lambda d: metric.fn({**statics, **d}))(dyn)
    except Exception as e:
        first_line = str(e).strip().split("\n")[0]
        verb = ("concretizes a traced value host-side"
                if isinstance(e, TRACE_ERRORS)
                else "raised under abstract evaluation")
        out.add("A301", "error", "metric", name,
                f"metric fn {verb} over the canonical round state "
                f"({type(e).__name__}): {first_line}",
                error=type(e).__name__)
        return out

    avals = list(closed.out_avals)
    if len(avals) != 1:
        out.add("A302", "error", "metric", name,
                "metric fn must return one array (scalar or small vector); "
                f"traced output has {len(avals)} array leaves",
                leaves=len(avals))
    else:
        shape = tuple(int(d) for d in avals[0].shape)
        size = 1
        for d in shape:
            size *= d
        if size > MAX_METRIC_ELEMS:
            out.add("A302", "error", "metric", name,
                    f"metric output {list(shape)} has {size} elements "
                    f"(> {MAX_METRIC_ELEMS}); series ride every engine's "
                    "scan ys per round per grid cell and must stay small",
                    shape=list(shape), size=size)
        if len(shape) != len(metric.axes):
            out.add("A302", "error", "metric", name,
                    f"metric output rank {len(shape)} does not match the "
                    f"declared trailing axes {list(metric.axes)}",
                    shape=list(shape), axes=list(metric.axes))
    _scan_forbidden(closed, "metric", name, "metric body", out)
    return out


# ---------------------------------------------------------------------------
# Spec-level and registry-wide drivers
# ---------------------------------------------------------------------------

def check_spec(spec, *, ds: Any = None) -> Findings:
    """Run the jaxpr passes on exactly the registry entries ``spec``
    resolves, at the spec's own shapes — the ``validate(deep=True)``
    backend."""
    from repro.core.aggregation import get_aggregator
    from repro.core.selection import STRATEGIES
    from repro.fl.workloads import get_workload

    out = Findings()
    wl = get_workload(spec.workload)
    out.extend(check_workload(wl.name, wl, ds=ds,
                              num_clients=min(int(spec.fl.num_clients), 8)))
    try:
        resolved_ds = wl.dataset(ds)
        num_classes = int(wl.num_classes(resolved_ds))
    except Exception:
        num_classes = 10      # already diagnosed by check_workload
    for s in spec.strategies:
        out.extend(check_strategy(
            s, STRATEGIES[s],
            num_clients=max(2, min(int(spec.fl.num_clients), 64)),
            num_classes=num_classes,
            n_select=max(1, min(int(spec.fl.clients_per_round),
                                int(spec.fl.num_clients)))))
    agg_name = spec.aggregation or spec.fl.aggregation
    agg = get_aggregator(agg_name)
    params = None
    if agg.reduce is not None:
        try:
            params = wl.param_shapes(wl.dataset(ds))
        except Exception:
            params = None
        out.extend(check_aggregator(agg_name, agg, params=params))
    # Requested round metrics trace at the spec's own client count; "auto"
    # expands to every registered metric (the engines would resolve it the
    # same way).
    tel = tuple(getattr(spec, "telemetry", ()))
    if tel:
        from repro.obs import registered_metrics
        names = registered_metrics() if "auto" in tel else \
            tuple(dict.fromkeys(n for n in tel if n != "auto"))
        for mname in names:
            out.extend(check_metric(
                mname, num_clients=max(2, min(int(spec.fl.num_clients), 64)),
                num_classes=num_classes))
    return out


def check_registries() -> Findings:
    """Contract passes over EVERY registered strategy, workload and
    aggregator at canonical shapes — the ``python -m repro.analysis``
    contract layer.  Importing the experiment/workload modules first is what
    populates the registries with their import-time extensions."""
    import repro.fl.experiment  # noqa: F401  (registers engines + extensions)
    from repro.core.aggregation import AGGREGATORS
    from repro.core.selection import STRATEGIES
    from repro.fl.workloads import _WORKLOADS
    from repro.obs import metrics_registry

    out = Findings()
    for name, fn in STRATEGIES.items():
        out.extend(check_strategy(name, fn))
    for name, wl in _WORKLOADS.items():
        out.extend(check_workload(name, wl))
    for name, agg in AGGREGATORS.items():
        out.extend(check_aggregator(name, agg))
    for name, m in metrics_registry().items():
        out.extend(check_metric(name, m))
    return out


def assert_strategy_contract(name: str, fn: Callable, **kw: Any) -> None:
    """Raise :class:`ContractError` if ``fn`` violates the strategy
    contract — the ``register_strategy(..., check=True)`` hook."""
    findings = check_strategy(name, fn, **kw)
    if findings.errors():
        raise ContractError(findings)


def assert_workload_contract(name: str, wl, **kw: Any) -> None:
    """Raise :class:`ContractError` on a bad workload bundle — the
    ``register_workload(..., check=True)`` hook."""
    findings = check_workload(name, wl, **kw)
    if findings.errors():
        raise ContractError(findings)


def assert_aggregator_contract(name: str, agg, **kw: Any) -> None:
    """Raise :class:`ContractError` on a bad aggregation family — the
    ``register_aggregator(..., check=True)`` hook."""
    findings = check_aggregator(name, agg, **kw)
    if findings.errors():
        raise ContractError(findings)


def assert_metric_contract(name: str, metric: Any = None, **kw: Any) -> None:
    """Raise :class:`ContractError` on a bad round metric — the
    ``register_metric(..., check=True)`` hook (repro.obs)."""
    findings = check_metric(name, metric, **kw)
    if findings.errors():
        raise ContractError(findings)
