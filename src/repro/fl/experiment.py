"""Declarative experiment API: scenario specs × strategy registry × one
``run`` surface.

The paper's claims live in grids — six non-IID cases × selection strategies ×
seeds (§III, Tables I/II) — and before this module every entry point
(``run_fl``, ``run_fl_host``, ``simulate``, ``run_grid``) re-declared
overlapping kwargs while scenario transforms were hand-composed at each
call-site.  Here the whole experiment is DATA:

    spec = ExperimentSpec(
        scenarios=tuple(ScenarioSpec.from_case(c, per_seed_plans=True)
                        for c in CASES),
        strategies=("random", "labelwise", "kl"),
        seeds=tuple(range(5)),
        engine="sim")                       # or "host" / "sharded"
    res = run(spec)                         # one labeled ExperimentResult
    res.table1(); res.success_rate()        # paper renderers
    res.to_json()                           # round-trips via from_json

Five orthogonal registries make every axis pluggable without engine edits:

* **workloads** — ``repro.fl.workloads.register_workload(name, Workload)``:
  what each client trains ("cnn" — the paper model — or "lm" — a micro
  transformer over domain-skewed token streams — out of the box); every
  engine resolves ``spec.workload`` and compiles the bundle's traced
  init/materialize/loss/eval fns, so a new model family needs no engine
  edits.
* **strategies** — ``repro.core.selection.register_strategy(name, fn)``; the
  registered callable compiles straight into the simulator's traced
  stack+index dispatch (repro.fl.sim._select) and ids are append-only, so
  saved grid indices never remap.  ``select_dirichlet_uniformity`` below is
  registered purely through that public API as proof.
* **aggregators** — ``repro.core.aggregation.register_aggregator(name,
  agg)``: the server-side family (``fedavg``/``fedsgd``, their
  ``clustered_*`` per-cluster multi-global-model forms, or a registered
  robust reduction); ``spec.aggregation`` resolves it by name in every
  engine, clustered families report per-cluster trajectories + round
  k-means assignments in ``meta["clustered"]``, and ids are append-only
  like strategies.
* **transforms** — ``register_transform(kind, fn)``; a ScenarioSpec carries an
  *ordered* list of TransformSpecs (availability dropout, quantity skew, …)
  that lower onto the base plan host-side before the arrays enter a device.
* **engines** — ``register_engine(name, fn)``: "sim" (the compiled vmapped
  grid, one XLA program), "host" (the same round step called per round
  from Python), "sharded" (the gather-based SPMD pod-scale round: clients in
  equal blocks per mesh slice, any registered strategy, training FLOPs
  scale with the selection budget), "hier" (hierarchical two-tier rounds:
  block-streamed selection + edge/global reduction — repro.fl.population;
  matches "sim" to ≤1e-5), and "async" (the FedBuff buffered-asynchronous
  engine: overlapping rounds, staleness-weighted block updates).  Engine
  knobs (``num_blocks``, ``buffer_k``, ``alpha``, ``tau_max``) ride in
  ``ExperimentSpec.engine_options``.

``run_fl`` and ``run_grid`` are now thin shims over this surface.
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Any, Callable, Dict, Mapping, Optional, Sequence, Tuple

import numpy as np

from repro.configs.paper_cnn import FLConfig
from repro.core import (CASES, SAMPLES_PER_CLIENT, SelectionResult, STRATEGIES,
                        adversary_mask, apply_availability, availability_plan,
                        bias_mix_plan, case_label_plan, dirichlet_plan,
                        flip_labels, get_aggregator, get_strategy,
                        quantity_skew, register_strategy, topn_mask)

# ---------------------------------------------------------------------------
# Transform registry: kind -> lowering fn(plan, avail, seed, **params)
# ---------------------------------------------------------------------------
# A lowering consumes the host-side (T, N, n) plan plus the accumulated
# (T_a, N) availability mask (or None) and returns the transformed pair.
TransformFn = Callable[..., Tuple[np.ndarray, Optional[np.ndarray]]]

_TRANSFORMS: Dict[str, TransformFn] = {}


def register_transform(kind: str, fn: TransformFn, *,
                       overwrite: bool = False) -> TransformFn:
    """Register a scenario transform lowering under ``kind``."""
    if not kind or not isinstance(kind, str):
        raise ValueError(f"transform kind must be a non-empty str; got {kind!r}")
    if kind in _TRANSFORMS and not overwrite:
        raise ValueError(f"transform {kind!r} already registered")
    if not callable(fn):
        raise TypeError(f"transform {kind!r} must be callable; got {type(fn)}")
    _TRANSFORMS[kind] = fn
    return fn


def registered_transforms() -> Tuple[str, ...]:
    return tuple(_TRANSFORMS)


def _lower_availability(plan: np.ndarray, avail: Optional[np.ndarray],
                        seed: int, *, p_drop: float, min_available: int = 1,
                        rounds: int, mode: str = "compose"):
    """Per-round client dropout over the full experiment horizon.

    mode="compose" (default) folds the mask into the plan (dark clients'
    labels → −1) so every engine sees the same arrays; mode="mask" carries a
    device-side (T, N) mask instead, which the compiled engine threads into
    selection (the plan stays intact — identical selected-set semantics,
    pinned by tests/test_fl_sim.py::test_composed_plan_equivalent)."""
    mask = availability_plan(seed, rounds, plan.shape[1], p_drop,
                             min_available=min_available)
    if mode == "compose":
        return apply_availability(plan, mask), avail
    if mode != "mask":
        raise ValueError(f"availability mode must be 'compose' or 'mask'; "
                         f"got {mode!r}")
    m = mask.astype(np.float32)
    avail = m if avail is None else (avail * m)
    return plan, avail


def _lower_quantity_skew(plan: np.ndarray, avail: Optional[np.ndarray],
                         seed: int, *, n_min: int = 30,
                         n_max: Optional[int] = None, rounds: int):
    del rounds
    return quantity_skew(plan, seed, n_min=n_min, n_max=n_max), avail


def _lower_label_flip(plan: np.ndarray, avail: Optional[np.ndarray],
                      seed: int, *, frac: float, num_classes: int = 10,
                      rounds: int):
    """Plan-level byzantine label poisoning: a fixed ``adversary_mask(frac)``
    client subset reports the inverted label ℓ → C−1−ℓ for every sample in
    every round (−1 padding untouched).  Purely a data transform, so it
    composes with availability/quantity_skew in stack order and runs
    identically on every engine — the adversary subset is drawn from the
    scenario's deterministic transform seed schedule unless the spec pins an
    explicit ``seed``."""
    del rounds
    adv = adversary_mask(seed, plan.shape[1], frac)
    return flip_labels(plan, adv, num_classes=num_classes), avail


register_transform("availability", _lower_availability)
register_transform("quantity_skew", _lower_quantity_skew)
register_transform("label_flip", _lower_label_flip)


@dataclasses.dataclass(frozen=True, eq=False)
class TransformSpec:
    """One step of a scenario's ordered transform stack.

    ``params`` may carry an explicit ``seed``; otherwise the transform draws
    its randomness from the scenario's deterministic seed schedule (seed0 +
    per-seed offset + a per-position stride), so the same spec always lowers
    to the same arrays."""
    kind: str
    params: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_dict(self) -> Dict[str, Any]:
        return {"kind": self.kind, "params": dict(self.params)}

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "TransformSpec":
        return cls(kind=d["kind"], params=dict(d.get("params", {})))


def availability(p_drop: float, **params: Any) -> TransformSpec:
    """Sugar: TransformSpec("availability", p_drop=...)."""
    return TransformSpec("availability", {"p_drop": p_drop, **params})


def quantity(n_min: int = 30, n_max: Optional[int] = None,
             **params: Any) -> TransformSpec:
    """Sugar: TransformSpec("quantity_skew", n_min=..., n_max=...)."""
    return TransformSpec("quantity_skew",
                         {"n_min": n_min, "n_max": n_max, **params})


def label_flip(frac: float, **params: Any) -> TransformSpec:
    """Sugar: TransformSpec("label_flip", frac=...)."""
    return TransformSpec("label_flip", {"frac": frac, **params})


# ---------------------------------------------------------------------------
# Scenario specs
# ---------------------------------------------------------------------------

_SOURCES = ("case", "bias_mix", "dirichlet", "plan")

# Stride between consecutive transforms' derived seeds (any prime far from
# the fold_in constants the engines use keeps the streams disjoint).
_TRANSFORM_SEED_STRIDE = 7919

# Offset for the spec-level adversary mask's derived seed (per experiment
# seed s the mask seed is s + stride) — a different prime keeps the byzantine
# draw disjoint from both the transform streams and the engines' fold_ins.
_ADVERSARY_SEED_STRIDE = 104729

# The ExperimentSpec.adversary dict's accepted keys (see the field docstring).
_ADVERSARY_KEYS = frozenset({"frac", "behaviors", "scale", "tau", "seed"})


def _jsonable_adversary(adv: Mapping[str, Any]) -> Dict[str, Any]:
    """JSON-able copy of an adversary dict (behaviors tuple → list)."""
    out = dict(adv)
    if "behaviors" in out:
        out["behaviors"] = list(out["behaviors"])
    return out


@dataclasses.dataclass(frozen=True, eq=False)
class ScenarioSpec:
    """One data scenario: a plan *source* plus an ordered transform stack.

    Sources:
        case      — one of the seven §III cases (params: samples_per_client,
                    majority, num_classes); horizon = the experiment's rounds
        bias_mix  — Figs. 6–7 partitioner (params: p_bias, n_min, n_max,
                    num_rounds, num_classes); static (T=1) by default
        dirichlet — Dirichlet(α) label skew (params: alpha,
                    samples_per_client, num_classes); static (T=1)
        plan      — an explicit (T, N, n) int32 array, or (R, T, N, n) for
                    per-seed draws

    ``per_seed_plans=True`` re-draws the source per experiment seed (the
    paper's per-trial re-partition): seed s gets ``seed0 + s`` as its source
    seed, so ``seeds=range(R), seed0=0`` reproduces the benchmarks' historic
    ``case_label_plan(case, seed=trial)`` stacking exactly.
    """
    name: str
    source: str = "case"
    case: Optional[str] = None
    params: Dict[str, Any] = dataclasses.field(default_factory=dict)
    transforms: Tuple[TransformSpec, ...] = ()
    seed0: int = 0
    per_seed_plans: bool = False
    plan: Optional[np.ndarray] = None
    avail: Optional[np.ndarray] = None

    # -- constructors -------------------------------------------------------
    @classmethod
    def from_case(cls, case: str, *, name: Optional[str] = None,
                  transforms: Sequence[TransformSpec] = (), seed0: int = 0,
                  per_seed_plans: bool = False, **params: Any) -> "ScenarioSpec":
        if case not in CASES:
            raise ValueError(f"unknown case {case!r}; have {CASES}")
        return cls(name=name or case, source="case", case=case,
                   params=dict(params), transforms=tuple(transforms),
                   seed0=seed0, per_seed_plans=per_seed_plans)

    @classmethod
    def from_bias_mix(cls, p_bias: float, *, name: Optional[str] = None,
                      transforms: Sequence[TransformSpec] = (), seed0: int = 0,
                      per_seed_plans: bool = False, **params: Any) -> "ScenarioSpec":
        return cls(name=name or f"bias{p_bias}", source="bias_mix",
                   params={"p_bias": p_bias, **params},
                   transforms=tuple(transforms), seed0=seed0,
                   per_seed_plans=per_seed_plans)

    @classmethod
    def from_dirichlet(cls, alpha: float, *, name: Optional[str] = None,
                       transforms: Sequence[TransformSpec] = (), seed0: int = 0,
                       per_seed_plans: bool = False, **params: Any) -> "ScenarioSpec":
        return cls(name=name or f"dirichlet{alpha}", source="dirichlet",
                   params={"alpha": alpha, **params},
                   transforms=tuple(transforms), seed0=seed0,
                   per_seed_plans=per_seed_plans)

    @classmethod
    def from_plan(cls, name: str, plan: np.ndarray, *,
                  avail: Optional[np.ndarray] = None,
                  transforms: Sequence[TransformSpec] = (),
                  seed0: int = 0) -> "ScenarioSpec":
        plan = np.asarray(plan, np.int32)
        if plan.ndim not in (3, 4):
            raise ValueError(f"explicit plan must be (T, N, n) or "
                             f"(R, T, N, n); got {plan.shape}")
        return cls(name=name, source="plan", plan=plan,
                   avail=None if avail is None else np.asarray(avail),
                   transforms=tuple(transforms), seed0=seed0,
                   per_seed_plans=plan.ndim == 4)

    # -- lowering -----------------------------------------------------------
    def _base_plan(self, fl_cfg, seed: int, rounds: int) -> np.ndarray:
        p = self.params
        if self.source == "case":
            spc = p.get("samples_per_client", SAMPLES_PER_CLIENT)
            return case_label_plan(
                self.case, seed=seed, num_rounds=rounds,
                num_clients=fl_cfg.num_clients,
                num_classes=p.get("num_classes", 10), samples_per_client=spc,
                majority=p.get("majority", int(spc * 200 / 290)))
        if self.source == "bias_mix":
            return bias_mix_plan(
                seed, fl_cfg.num_clients, p_bias=p["p_bias"],
                num_classes=p.get("num_classes", 10),
                n_min=p.get("n_min", 30), n_max=p.get("n_max", 270),
                num_rounds=p.get("num_rounds", 1))
        if self.source == "dirichlet":
            return dirichlet_plan(
                seed, fl_cfg.num_clients, alpha=p["alpha"],
                num_classes=p.get("num_classes", 10),
                samples_per_client=p.get("samples_per_client",
                                         SAMPLES_PER_CLIENT))
        raise ValueError(f"unknown scenario source {self.source!r}; "
                         f"have {_SOURCES}")

    def _lower_one(self, fl_cfg, seed_offset: int, rounds: int
                   ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        if self.source == "plan":
            plan = np.asarray(self.plan, np.int32)
            if plan.ndim == 4:
                plan = plan[seed_offset]
        else:
            plan = self._base_plan(fl_cfg, self.seed0 + seed_offset, rounds)
        avail = (None if self.avail is None
                 else np.asarray(self.avail, np.float32))
        for ti, t in enumerate(self.transforms):
            fn = _TRANSFORMS.get(t.kind)
            if fn is None:
                raise KeyError(f"unknown transform {t.kind!r}; have "
                               f"{registered_transforms()}")
            params = dict(t.params)
            seed = params.pop("seed", None)
            if seed is None:
                seed = (self.seed0 + seed_offset
                        + _TRANSFORM_SEED_STRIDE * (ti + 1))
            plan, avail = fn(plan, avail, seed, rounds=rounds, **params)
        return plan, avail

    def lower(self, fl_cfg, seeds: Sequence[int], rounds: int
              ) -> "LoweredScenario":
        """Materialize the spec into host arrays: (T, N, n) — or
        (R, T, N, n) when per-seed — plus an optional (T, N) device mask."""
        if self.per_seed_plans:
            if self.source == "plan" and self.plan.shape[0] != len(seeds):
                raise ValueError(
                    f"scenario {self.name!r}: per-seed plans axis 0 "
                    f"({self.plan.shape[0]}) must match len(seeds) "
                    f"({len(seeds)})")
            pairs = [self._lower_one(fl_cfg, (s if self.source != "plan"
                                              else i), rounds)
                     for i, s in enumerate(seeds)]
            plans = np.stack([p for p, _ in pairs])
            avails = [a for _, a in pairs]
            if any(a is not None for a in avails):
                if any(a is None for a in avails):
                    raise ValueError(
                        f"scenario {self.name!r}: mask-mode transforms must "
                        "apply to every per-seed draw or none")
                # One (T, N) mask per grid cell is the engine contract;
                # per-seed masks must agree (use an explicit seed to pin).
                first = avails[0]
                for a in avails[1:]:
                    if not np.array_equal(first, a):
                        raise ValueError(
                            f"scenario {self.name!r}: per-seed availability "
                            "masks diverge; pin them with an explicit "
                            "transform seed or use mode='compose'")
                return LoweredScenario(self.name, plans, first, True)
            return LoweredScenario(self.name, plans, None, True)
        if self.source == "plan" and np.asarray(self.plan).ndim == 4:
            raise ValueError(f"scenario {self.name!r}: (R, T, N, n) plans "
                             "imply per_seed_plans=True")
        plan, avail = self._lower_one(fl_cfg, 0, rounds)
        return LoweredScenario(self.name, plan, avail, False)

    # -- serialization ------------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name, "source": self.source, "case": self.case,
            "params": dict(self.params),
            "transforms": [t.to_dict() for t in self.transforms],
            "seed0": self.seed0, "per_seed_plans": self.per_seed_plans,
            "plan": None if self.plan is None else np.asarray(self.plan).tolist(),
            "avail": None if self.avail is None else np.asarray(self.avail).tolist(),
        }

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "ScenarioSpec":
        return cls(
            name=d["name"], source=d.get("source", "case"),
            case=d.get("case"), params=dict(d.get("params", {})),
            transforms=tuple(TransformSpec.from_dict(t)
                             for t in d.get("transforms", ())),
            seed0=d.get("seed0", 0),
            per_seed_plans=d.get("per_seed_plans", False),
            plan=(None if d.get("plan") is None
                  else np.asarray(d["plan"], np.int32)),
            avail=(None if d.get("avail") is None
                   else np.asarray(d["avail"], np.float32)))


@dataclasses.dataclass(frozen=True)
class LoweredScenario:
    """A ScenarioSpec lowered to arrays, ready for any engine."""
    name: str
    plan: np.ndarray                      # (T, N, n) or (R, T, N, n)
    avail: Optional[np.ndarray]           # (T_a, N) float mask or None
    per_seed: bool

    def composed_plan(self, seed_index: int) -> np.ndarray:
        """(T, N, n) plan for one grid cell with any device-mask availability
        folded in — what mask-free engines (host loop) consume."""
        plan = self.plan[seed_index] if self.per_seed else self.plan
        if self.avail is not None:
            plan = apply_availability(plan, self.avail.astype(bool))
        return plan


# ---------------------------------------------------------------------------
# Experiment spec + result
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class ExperimentSpec:
    """The full grid: scenarios × strategies × seeds × aggregation × engine
    × workload (the registered client model family — repro.fl.workloads)."""
    scenarios: Tuple[ScenarioSpec, ...]
    strategies: Tuple[str, ...] = ("labelwise",)
    seeds: Tuple[int, ...] = (0,)
    engine: str = "sim"
    fl: Any = dataclasses.field(default_factory=FLConfig)
    aggregation: Optional[str] = None
    rounds: Optional[int] = None
    eval_n_per_class: int = 50
    workload: str = "cnn"
    # Engine-specific knobs (JSON-able): the population engines read
    # num_blocks (hier/async) and buffer_k / alpha / tau_max (async).
    # Each engine declares its accepted keys at register_engine(); validate()
    # rejects keys outside that set (engines registered without a declaration
    # accept anything).
    engine_options: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # Requested round metrics (repro.obs registry): metric names, or
    # ("auto",) for every builtin the engine can satisfy.  Empty falls back
    # to the REPRO_TELEMETRY env var; with neither set the engines compile
    # the identical telemetry-free program (trajectories are bit-identical).
    telemetry: Tuple[str, ...] = ()
    # Engine-level byzantine adversary (JSON-able; empty = off, compiling the
    # identical pre-adversary program).  Keys: ``frac`` — byzantine client
    # fraction (adversary_mask draw); ``behaviors`` — subset of
    # {"poison", "stale_update"} (the plan-level label_flip attack is a
    # scenario TRANSFORM, not a behavior); ``scale`` — poison delta
    # multiplier (default −1.0, sign-flip); ``tau`` — stale_update staleness
    # in rounds (default 1); ``seed`` — pin one mask across all experiment
    # seeds (default: per-seed masks from s + _ADVERSARY_SEED_STRIDE).
    # Supported on sim/host/sharded with single-global-model families.
    adversary: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def num_rounds(self) -> int:
        return self.fl.global_epochs if self.rounds is None else self.rounds

    def validate(self, deep: bool = False, ds=None) -> None:
        """Fail-fast spec checks, all pre-compile.

        The default pass is name/shape-level: unknown strategy / engine /
        aggregator / workload / transform names and undeclared
        ``engine_options`` keys raise here.  ``deep=True`` additionally runs
        the jaxpr contract passes (repro.analysis) over exactly this spec's
        resolved registry entries and raises
        :class:`repro.analysis.ContractError` with structured diagnostics if
        any entry would break mid-compile inside an engine."""
        if not self.scenarios:
            raise ValueError("spec needs at least one scenario")
        names = [s.name for s in self.scenarios]
        if len(set(names)) != len(names):
            raise ValueError(f"scenario names must be unique; got {names}")
        for sc in self.scenarios:
            for t in sc.transforms:
                if t.kind not in _TRANSFORMS:
                    raise KeyError(
                        f"scenario {sc.name!r}: unknown transform kind "
                        f"{t.kind!r}; have {registered_transforms()}")
        if not self.strategies:
            raise ValueError("spec needs at least one strategy")
        for s in self.strategies:
            get_strategy(s)          # unknown names raise here, pre-compile
        if not self.seeds:
            raise ValueError("spec needs at least one seed")
        if self.engine not in _ENGINES:
            raise KeyError(f"unknown engine {self.engine!r}; have "
                           f"{engines()}")
        accepted = _ENGINE_OPTION_KEYS.get(self.engine)
        if accepted is not None:
            unknown = sorted(set(self.engine_options) - set(accepted))
            if unknown:
                raise ValueError(
                    f"engine {self.engine!r} does not accept engine_options "
                    f"key(s) {unknown}; it declares "
                    f"{sorted(accepted) or '(no options)'}")
        # Unknown aggregation families raise here, pre-compile — the same
        # fail-fast contract as strategies/engines/workloads.
        agg = get_aggregator(self.aggregation or self.fl.aggregation)
        if self.adversary:
            unknown = sorted(set(self.adversary) - _ADVERSARY_KEYS)
            if unknown:
                raise ValueError(
                    f"unknown adversary key(s) {unknown}; have "
                    f"{sorted(_ADVERSARY_KEYS)}")
            frac = float(self.adversary.get("frac", 0.0))
            if not 0.0 <= frac <= 1.0:
                raise ValueError(
                    f"adversary frac must be in [0, 1]; got {frac}")
            from .round import resolve_adversary
            poison_scale, tau = resolve_adversary(self.adversary)
            if poison_scale is not None or tau > 0:
                if agg.clustered:
                    raise ValueError(
                        "engine-level adversary behaviors (poison/"
                        "stale_update) are not defined for clustered "
                        "aggregation families; use the plan-level label_flip "
                        "transform or a single-global-model aggregator")
                if tau > 0 and agg.base == "fedsgd":
                    raise ValueError(
                        "stale_update needs a stale TRAINING base; the "
                        "fedsgd family reports one gradient at the current "
                        "global, so the behavior is undefined for it")
                if self.engine in ("hier", "async"):
                    raise ValueError(
                        f"engine {self.engine!r} does not support "
                        "engine-level adversary behaviors (poison/"
                        "stale_update); run on sim/host/sharded, or attack "
                        "the plan with the label_flip transform")
        from .workloads import get_workload
        get_workload(self.workload)  # unknown workloads raise pre-compile
        from repro.obs import get_metric
        for m in self.telemetry:
            if m != "auto":
                get_metric(m)        # unknown metric names raise pre-compile
        if deep:
            from repro.analysis import ContractError, check_spec
            findings = check_spec(self, ds=ds)
            if findings.errors():
                raise ContractError(findings)

    def adversary_masks(self) -> Optional[np.ndarray]:
        """The (R, N) per-seed 0/1 byzantine masks this spec's adversary
        draws — the SAME schedule on every engine, so an attacked run is as
        reproducible as a clean one.  Experiment seed ``seeds[i]`` gets mask
        seed ``seeds[i] + _ADVERSARY_SEED_STRIDE`` unless the adversary dict
        pins an explicit ``seed`` (then every row is that one draw).  None
        when the spec has no adversary."""
        if not self.adversary:
            return None
        frac = float(self.adversary.get("frac", 0.0))
        base = self.adversary.get("seed")
        return np.stack([
            adversary_mask(int(base) if base is not None
                           else int(s) + _ADVERSARY_SEED_STRIDE,
                           self.fl.num_clients, frac)
            for s in self.seeds])

    def to_dict(self) -> Dict[str, Any]:
        return {
            "scenarios": [s.to_dict() for s in self.scenarios],
            "strategies": list(self.strategies), "seeds": list(self.seeds),
            "engine": self.engine, "fl": dataclasses.asdict(self.fl),
            "aggregation": self.aggregation, "rounds": self.rounds,
            "eval_n_per_class": self.eval_n_per_class,
            "workload": self.workload,
            "engine_options": dict(self.engine_options),
            "telemetry": list(self.telemetry),
            "adversary": _jsonable_adversary(self.adversary),
        }

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "ExperimentSpec":
        return cls(
            scenarios=tuple(ScenarioSpec.from_dict(s) for s in d["scenarios"]),
            strategies=tuple(d.get("strategies", ("labelwise",))),
            seeds=tuple(d.get("seeds", (0,))),
            engine=d.get("engine", "sim"),
            fl=FLConfig(**d["fl"]) if "fl" in d else FLConfig(),
            aggregation=d.get("aggregation"), rounds=d.get("rounds"),
            eval_n_per_class=d.get("eval_n_per_class", 50),
            workload=d.get("workload", "cnn"),
            engine_options=dict(d.get("engine_options", {})),
            telemetry=tuple(d.get("telemetry", ())),
            adversary=dict(d.get("adversary") or {}))


@dataclasses.dataclass
class ExperimentResult:
    """Labeled grid trajectories: axes (scenario, strategy, seed, round).

    ``meta`` carries engine-specific, JSON-able side facts — e.g. the sharded
    engine's realized FLOP sparsity per strategy (``meta["sharded"]``)."""
    scenarios: Tuple[str, ...]
    strategies: Tuple[str, ...]
    seeds: Tuple[int, ...]
    accuracy: np.ndarray        # (K, S, R, T) f32
    loss: np.ndarray
    num_selected: np.ndarray
    engine: str = "sim"
    wall_s: float = 0.0
    compile_s: float = 0.0
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)

    AXES = ("scenario", "strategy", "seed", "round")

    def __post_init__(self):
        want = (len(self.scenarios), len(self.strategies), len(self.seeds))
        for name in ("accuracy", "loss", "num_selected"):
            arr = np.asarray(getattr(self, name))
            if arr.shape[:3] != want:
                raise ValueError(f"{name} leading axes {arr.shape[:3]} != "
                                 f"(scenarios, strategies, seeds) {want}")
            setattr(self, name, arr)

    # -- label-based access -------------------------------------------------
    def _idx(self, axis_labels: Sequence[Any], label: Any, axis: str) -> int:
        try:
            return list(axis_labels).index(label)
        except ValueError:
            raise KeyError(f"unknown {axis} {label!r}; have "
                           f"{tuple(axis_labels)}") from None

    def trajectory(self, scenario: str, strategy: str,
                   seed: Optional[int] = None) -> Dict[str, np.ndarray]:
        """The (rounds,) trajectories of one grid cell (or a (R, rounds)
        block when ``seed`` is omitted)."""
        k = self._idx(self.scenarios, scenario, "scenario")
        s = self._idx(self.strategies, strategy, "strategy")
        sl = (k, s) if seed is None else (k, s, self._idx(self.seeds, seed,
                                                          "seed"))
        return {"accuracy": self.accuracy[sl], "loss": self.loss[sl],
                "num_selected": self.num_selected[sl]}

    @property
    def final_accuracy(self) -> np.ndarray:
        return self.accuracy[..., -1]

    def cluster_trajectories(self) -> Optional[Dict[str, np.ndarray]]:
        """Clustered-family detail from ``meta["clustered"]`` as arrays:
        ``accuracy``/``loss`` (K, S, R, T, n_clusters) per-cluster-model
        trajectories and ``assign`` (K, S, R, T, N) round k-means
        assignments.  ``None`` for single-model aggregation families."""
        cl = self.meta.get("clustered")
        if cl is None:
            return None
        return {"n_clusters": int(cl["n_clusters"]),
                "accuracy": np.asarray(cl["cluster_accuracy"], np.float32),
                "loss": np.asarray(cl["cluster_loss"], np.float32),
                "assign": np.asarray(cl["cluster_assign"], np.int32)}

    def telemetry(self) -> Optional[Dict[str, np.ndarray]]:
        """The round-metric series from the versioned ``meta["telemetry"]``
        envelope as float64 arrays, ``{name: (K, S, R, rounds, …)}`` —
        leading axes follow ``AXES``, trailing axes are the metric's own
        (``Metric.axes``).  ``None`` when the run collected no metrics."""
        env = self.meta.get("telemetry")
        if not env or not env.get("series"):
            return None
        from repro.obs import series_arrays
        return series_arrays(env)

    def success_rate(self, threshold: float = 0.2) -> np.ndarray:
        """Paper Table II: fraction of seeds with final accuracy > τ; (K, S)."""
        return (self.final_accuracy > threshold).mean(axis=-1)

    # -- paper renderers ----------------------------------------------------
    def table1(self) -> Dict[str, Dict[str, Dict[str, float]]]:
        """Table-I data: scenario → strategy → final acc mean/std + loss."""
        out: Dict[str, Dict[str, Dict[str, float]]] = {}
        for k, sc in enumerate(self.scenarios):
            out[sc] = {}
            for s, st in enumerate(self.strategies):
                fa = self.final_accuracy[k, s]
                out[sc][st] = {"acc_mean": float(fa.mean()),
                               "acc_std": float(fa.std()),
                               "loss_mean": float(self.loss[k, s, :, -1].mean())}
        return out

    def table2(self, threshold: float = 0.2) -> Dict[str, Dict[str, float]]:
        """Table-II data: scenario → strategy → train success rate."""
        sr = self.success_rate(threshold)
        return {sc: {st: float(sr[k, s])
                     for s, st in enumerate(self.strategies)}
                for k, sc in enumerate(self.scenarios)}

    def _render(self, cell: Callable[[int, int], str], title: str) -> str:
        w = max(10, *(len(s) for s in self.strategies)) + 2
        head = f"{'scenario':12s}" + "".join(f"{s:>{w}s}"
                                             for s in self.strategies)
        rows = [f"# {title}", head]
        for k, sc in enumerate(self.scenarios):
            rows.append(f"{sc:12s}" + "".join(f"{cell(k, s):>{w}s}"
                                              for s in range(len(self.strategies))))
        return "\n".join(rows)

    def render_table1(self) -> str:
        fa = self.final_accuracy
        return self._render(
            lambda k, s: f"{fa[k, s].mean():.3f}±{fa[k, s].std():.3f}",
            f"Table I — final accuracy over {len(self.seeds)} seed(s), "
            f"engine={self.engine}")

    def render_table2(self, threshold: float = 0.2) -> str:
        sr = self.success_rate(threshold)
        return self._render(lambda k, s: f"{sr[k, s]:.2f}",
                            f"Table II — success rate (acc > {threshold})")

    # -- serialization ------------------------------------------------------
    def to_json(self, **json_kw: Any) -> str:
        return json.dumps({
            "axes": list(self.AXES),
            "scenarios": list(self.scenarios),
            "strategies": list(self.strategies),
            "seeds": [int(s) for s in self.seeds],
            "engine": self.engine,
            "wall_s": self.wall_s, "compile_s": self.compile_s,
            "meta": self.meta,
            "accuracy": self.accuracy.tolist(),
            "loss": self.loss.tolist(),
            "num_selected": self.num_selected.tolist(),
        }, **json_kw)

    @classmethod
    def from_json(cls, s: str) -> "ExperimentResult":
        d = json.loads(s)
        return cls(
            scenarios=tuple(d["scenarios"]), strategies=tuple(d["strategies"]),
            seeds=tuple(d["seeds"]),
            accuracy=np.asarray(d["accuracy"], np.float32),
            loss=np.asarray(d["loss"], np.float32),
            num_selected=np.asarray(d["num_selected"], np.float32),
            engine=d.get("engine", "sim"), wall_s=d.get("wall_s", 0.0),
            compile_s=d.get("compile_s", 0.0), meta=d.get("meta", {}))


# ---------------------------------------------------------------------------
# Engine registry
# ---------------------------------------------------------------------------
# An engine consumes (spec, lowered_scenarios, ds) and returns
# (accuracy, loss, num_selected) arrays shaped (K, S, R, rounds) plus
# (wall_s, compile_s) and optionally a trailing JSON-able meta dict
# (surfaced as ExperimentResult.meta).
EngineFn = Callable[..., Tuple[np.ndarray, np.ndarray, np.ndarray, float, float]]

_ENGINES: Dict[str, EngineFn] = {}

# Engine name -> the engine_options keys it consumes, or None for
# "accepts anything" (extension engines registered without a declaration
# keep the old ignore-unknown-keys behaviour).  validate() rejects keys
# outside the declared set pre-compile.
_ENGINE_OPTION_KEYS: Dict[str, Optional[Tuple[str, ...]]] = {}


def register_engine(name: str, fn: EngineFn, *, overwrite: bool = False,
                    option_keys: Optional[Sequence[str]] = None) -> EngineFn:
    """Register an execution engine under ``name`` (see module docstring).

    ``option_keys`` declares the ``ExperimentSpec.engine_options`` keys this
    engine consumes; ``validate()`` rejects any key outside that set.  Leave
    it ``None`` to accept arbitrary options (no validation)."""
    if not name or not isinstance(name, str):
        raise ValueError(f"engine name must be a non-empty str; got {name!r}")
    if name in _ENGINES and not overwrite:
        raise ValueError(f"engine {name!r} already registered")
    if not callable(fn):
        raise TypeError(f"engine {name!r} must be callable; got {type(fn)}")
    _ENGINES[name] = fn
    _ENGINE_OPTION_KEYS[name] = (None if option_keys is None
                                 else tuple(option_keys))
    return fn


def engines() -> Tuple[str, ...]:
    return tuple(_ENGINES)


def engine_option_keys(name: str) -> Optional[Tuple[str, ...]]:
    """The declared engine_options keys for ``name`` (None = accepts any)."""
    if name not in _ENGINES:
        raise KeyError(f"unknown engine {name!r}; have {engines()}")
    return _ENGINE_OPTION_KEYS.get(name)


def _clustered_meta(c_acc: np.ndarray, c_loss: np.ndarray,
                    c_assign: np.ndarray) -> Dict[str, Any]:
    """The engines' shared JSON-able clustered side-channel: per-cluster
    trajectories (K, S, R, T, n_clusters) and round k-means assignments
    (K, S, R, T, N), as nested lists so ``ExperimentResult.to_json``
    round-trips them exactly."""
    c_acc = np.asarray(c_acc, np.float32)
    return {"clustered": {
        "n_clusters": int(c_acc.shape[-1]),
        "axes": ["scenario", "strategy", "seed", "round", "cluster"],
        "assign_axes": ["scenario", "strategy", "seed", "round", "client"],
        "cluster_accuracy": c_acc.tolist(),
        "cluster_loss": np.asarray(c_loss, np.float32).tolist(),
        "cluster_assign": np.asarray(c_assign, np.int32).tolist()}}


def _engine_sim(spec: ExperimentSpec, lowered: Sequence[LoweredScenario], ds):
    """Compiled vmapped grid: the whole experiment is ONE XLA program."""
    from .sim import grid_arrays
    shapes = {low.plan.shape[-3:] for low in lowered}
    if len(shapes) != 1:
        raise ValueError(
            "engine='sim' stacks every scenario into one compiled grid, so "
            "all lowered plans must share (T, N, n); got "
            f"{ {low.name: low.plan.shape for low in lowered} } — pad plans "
            "to a common n_max or split into separate specs")
    per_seed = any(low.per_seed for low in lowered)
    r = len(spec.seeds)

    def cell(low: LoweredScenario) -> np.ndarray:
        if low.per_seed:
            return low.plan
        if per_seed:        # tile static scenarios onto the per-seed axis
            return np.broadcast_to(low.plan[None],
                                   (r,) + low.plan.shape)
        return low.plan

    plans = np.stack([cell(low) for low in lowered])
    avail = None
    if any(low.avail is not None for low in lowered):
        a_shapes = {low.avail.shape for low in lowered
                    if low.avail is not None}
        if len(a_shapes) != 1:
            raise ValueError("engine='sim' stacks availability masks on the "
                             f"scenario axis; shapes must agree, got {a_shapes}")
        (t_a, n_a), = a_shapes
        avail = np.ones((len(lowered), t_a, n_a), np.float32)
        for k, low in enumerate(lowered):
            if low.avail is not None:
                avail[k] = low.avail
    res = grid_arrays(plans, spec.fl, strategies=spec.strategies,
                      seeds=spec.seeds, aggregation=spec.aggregation,
                      rounds=spec.rounds, ds=ds, avail=avail,
                      eval_n_per_class=spec.eval_n_per_class,
                      workload=spec.workload, telemetry=spec.telemetry,
                      adversary=spec.adversary or None,
                      adv=spec.adversary_masks())
    meta: Dict[str, Any] = {}
    if res.cluster_accuracy is not None:
        meta.update(_clustered_meta(res.cluster_accuracy, res.cluster_loss,
                                    res.cluster_assign))
    if res.telemetry:
        # The compiled grid stacks the scan's metric ys under the case →
        # strategy → seed vmap nest, so each series is already
        # (K, S, R, rounds, …); run() folds it into the envelope.
        meta["_telemetry_series"] = res.telemetry
    if meta:
        return (res.accuracy, res.loss, res.num_selected, res.wall_s,
                res.compile_s, meta)
    return res.accuracy, res.loss, res.num_selected, res.wall_s, res.compile_s


def _engine_host(spec: ExperimentSpec, lowered: Sequence[LoweredScenario], ds):
    """The sim engine's round step driven per round from Python, one grid
    cell at a time."""
    from .loop import run_fl_host
    agg = get_aggregator(spec.aggregation or spec.fl.aggregation)
    adv_masks = spec.adversary_masks()
    k_n, s_n, r_n = len(lowered), len(spec.strategies), len(spec.seeds)
    t_n = spec.num_rounds
    acc = np.zeros((k_n, s_n, r_n, t_n), np.float32)
    loss = np.zeros_like(acc)
    nsel = np.zeros_like(acc)
    c_acc = c_loss = c_assign = None
    if agg.clustered:
        c_acc = np.zeros((k_n, s_n, r_n, t_n, agg.n_clusters), np.float32)
        c_loss = np.zeros_like(c_acc)
        c_assign = np.zeros((k_n, s_n, r_n, t_n, spec.fl.num_clients),
                            np.int32)
    compile_s = 0.0
    tel: Dict[str, np.ndarray] = {}
    t0 = time.perf_counter()
    for k, low in enumerate(lowered):
        for r, seed in enumerate(spec.seeds):
            plan = low.composed_plan(r)
            for s, strat in enumerate(spec.strategies):
                h = run_fl_host(plan, spec.fl, strategy=strat,
                                aggregation=spec.aggregation,
                                rounds=spec.rounds, ds=ds, seed=seed,
                                eval_n_per_class=spec.eval_n_per_class,
                                workload=spec.workload,
                                telemetry=spec.telemetry,
                                adversary=spec.adversary or None,
                                adv=None if adv_masks is None
                                else adv_masks[r])
                compile_s += h.compile_s
                acc[k, s, r] = h.accuracy
                loss[k, s, r] = h.loss
                nsel[k, s, r] = h.num_selected
                if agg.clustered:
                    c_acc[k, s, r] = h.cluster_accuracy
                    c_loss[k, s, r] = h.cluster_loss
                    c_assign[k, s, r] = h.cluster_assign
                for name, v in (h.telemetry or {}).items():
                    v = np.asarray(v, np.float32)
                    if name not in tel:
                        tel[name] = np.zeros((k_n, s_n, r_n) + v.shape,
                                             np.float32)
                    tel[name][k, s, r] = v
    # Per-cell AOT compiles are accounted separately (satellite of the
    # wall_s/compile_s honesty fix): wall is pure execution time.
    wall = time.perf_counter() - t0 - compile_s
    meta: Dict[str, Any] = {}
    if agg.clustered:
        meta.update(_clustered_meta(c_acc, c_loss, c_assign))
    if tel:
        meta["_telemetry_series"] = tel
    return acc, loss, nsel, wall, compile_s, meta


def _engine_sharded(spec: ExperimentSpec, lowered: Sequence[LoweredScenario],
                    ds):
    """Pod-scale SPMD: the gather-based client-parallel round — selection is
    an all-gather of per-client histograms through the strategy registry,
    training runs only on the ``order[:budget]`` gathered client shards, and
    the weighted delta psum scatters the aggregate back.

    Any registered strategy and any registered ``base`` aggregation family —
    fedavg/fedsgd and their clustered multi-global-model forms — are
    supported (each strategy compiles its own round with its own static
    budget).  A registered ``Aggregator.reduce`` override (the robust
    median/trimmed_mean/krum builtins) switches the scatter phase from the
    weighted delta-psum collective to the gather-reduce form: the B_pad
    selected deltas are all-gathered and the reduction runs replicated on
    every shard (see ``make_sharded_fl_round``'s ``reduce_fn``); clustered
    families keep the per-cluster psum pair and reject overrides.  The
    spec-level adversary (``poison``/``stale_update`` + the per-seed
    byzantine masks) threads through the same round arguments the host loop
    uses, so attacked sharded runs stay parity-pinned.  Clients are
    distributed over the mesh in equal blocks: the client axis takes the
    largest device count dividing ``fl.num_clients`` (one client per slice
    when there are enough devices; emulate more with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).  Realized FLOP
    sparsity per strategy (1 − trained/N) is reported in the result's
    ``meta["sharded"]``.

    Workload-agnostic: ``spec.workload`` resolves the client model family —
    its ``param_shapes`` metadata sizes the replicated parameter
    PartitionSpec tree and its static ``batch_keys`` size the client-sharded
    batch specs, so the round trains whichever pytree the workload declares.

    The gather phase uses the O(B) selected-shard exchange by default
    (``exchange="a2a"``, bit-identical to the all-gather baseline); set
    ``REPRO_SHARDED_EXCHANGE=allgather`` to measure the O(N) path.  The
    chosen exchange is reported in ``meta["sharded"]["exchange"]``."""
    import os
    from collections import deque

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.data import client_batches
    from repro.obs import (make_collector, phase, resolve_metrics,
                           resolve_telemetry_request)
    from repro.optim import get_optimizer
    from .client import local_gradient, local_train
    from .round import resolve_adversary, stack_global_params
    from .sharded import exchange_bytes_per_device, make_sharded_fl_round
    from .workloads import get_workload, reject_frozen

    cfg = spec.fl
    agg = get_aggregator(spec.aggregation or cfg.aggregation)
    poison_scale, tau = resolve_adversary(spec.adversary)
    attacked = poison_scale is not None or tau > 0
    adv_masks = spec.adversary_masks() if attacked else None
    n_clients = cfg.num_clients
    ndev = jax.device_count()
    groups = (n_clients if ndev >= n_clients else
              max(g for g in range(1, ndev + 1) if n_clients % g == 0))

    wl = get_workload(spec.workload)
    reject_frozen(wl, "sharded")
    ds = wl.dataset(ds)
    mesh = jax.make_mesh((groups,), ("clients",))
    opt = get_optimizer(cfg.optimizer, cfg.lr)
    eval_batch = wl.eval_set(ds, spec.eval_n_per_class)
    eval_fn = wl.make_eval(ds)
    @jax.jit
    def eval_jit(p):
        with phase("eval"):
            return eval_fn(p, eval_batch)
    if agg.clustered:
        # Per-cluster eval + the valid-population mixture, the same f32 jnp
        # ops as the other engines' clustered eval.
        @jax.jit
        def eval_mix_jit(p, w):
            with phase("eval"):
                l_c, m_c = jax.vmap(lambda q: eval_fn(q, eval_batch))(p)
                tot = jnp.maximum(w.sum(), 1.0)
                return ((l_c * w).sum() / tot,
                        (m_c["accuracy"] * w).sum() / tot,
                        m_c["accuracy"], l_c)
    loss_fn = wl.make_loss(ds)

    if agg.base == "fedavg":
        server_lr = cfg.server_lr

        def local_step(params, batch):   # batch: ONE client, no client axis
            return local_train(params, opt, batch, loss_fn,
                               cfg.local_epochs)[0]
    else:
        server_lr = 1.0                  # fedsgd has no server interpolation

        def local_step(params, batch):
            # Client delta −lr·∇ makes the weighted delta mean ≡ the engines'
            # aggregate-gradients-then-step FedSGD update.
            g, _ = local_gradient(params, batch, loss_fn)
            return jax.tree_util.tree_map(
                lambda p, gr: p - cfg.lr * gr, params, g)

    k_n, s_n, r_n = len(lowered), len(spec.strategies), len(spec.seeds)
    t_n = spec.num_rounds
    acc = np.zeros((k_n, s_n, r_n, t_n), np.float32)
    loss = np.zeros_like(acc)
    nsel = np.zeros_like(acc)
    c_acc = c_loss = c_assign = None
    if agg.clustered:
        c_acc = np.zeros((k_n, s_n, r_n, t_n, agg.n_clusters), np.float32)
        c_loss = np.zeros_like(c_acc)
        c_assign = np.zeros((k_n, s_n, r_n, t_n, n_clients), np.int32)
    t0 = time.perf_counter()
    # The workload's static shape metadata: params replicated across the
    # client mesh axis, one client-sharded PartitionSpec per batch leaf.
    pspec = jax.tree_util.tree_map(lambda _: P(), wl.param_shapes(ds))
    exchange = os.environ.get("REPRO_SHARDED_EXCHANGE", "a2a")
    round_fns = {
        strat: make_sharded_fl_round(
            mesh, "clients", local_step, n_select=cfg.clients_per_round,
            num_classes=wl.num_classes(ds), params_pspec=pspec,
            batch_pspec={k: P() for k in wl.batch_keys},
            num_clients=n_clients, strategy=strat, server_lr=server_lr,
            exchange=exchange, n_clusters=agg.n_clusters,
            kmeans_iters=agg.kmeans_iters, reduce_fn=agg.reduce,
            poison_scale=poison_scale, with_stale=tau > 0)
        for strat in spec.strategies}
    avail_keys = ["hists", "mask", "num_classes", "params_old", "params_new"]
    if agg.clustered:
        avail_keys += ["assign", "n_clusters", "centroids", "prev_centroids"]
    metrics = resolve_metrics(
        resolve_telemetry_request(spec.telemetry), avail_keys)
    collector = None
    if metrics:
        collector = jax.jit(make_collector(
            metrics, {"num_classes": wl.num_classes(ds),
                      "n_clusters": agg.n_clusters}))
    tel: Dict[str, np.ndarray] = {}
    xbytes: Optional[Dict[str, int]] = None
    for k, low in enumerate(lowered):
        for r, seed in enumerate(spec.seeds):
            plan = low.composed_plan(r)
            key = jax.random.PRNGKey(int(seed))
            init = wl.init(jax.random.fold_in(key, 1), ds)
            if agg.clustered:
                init = stack_global_params(init, agg.n_clusters)
            params = {strat: init for strat in spec.strategies}
            prev_cent = {strat: None for strat in spec.strategies}
            adv_dev = (jnp.asarray(adv_masks[r], jnp.float32)
                       if attacked else None)
            # stale_update window: past[strat][0] is θ_{t−τ} (θ₀ early).
            past = ({strat: deque([init], maxlen=tau + 1)
                     for strat in spec.strategies} if tau else None)
            for t in range(t_n):
                # Round data and keys depend only on (scenario, seed, round)
                # — materialize once and step every strategy's own params.
                kt = jax.random.fold_in(key, 1000 + t)
                data = wl.materialize(ds, plan[t % plan.shape[0]],
                                      jax.random.fold_in(kt, 0))
                batches = client_batches(data, cfg.batch_size, wl.batch_keys)
                if xbytes is None:
                    xbytes = {strat: exchange_bytes_per_device(
                                  batches, n_clients, fn.budget_padded,
                                  groups, exchange)
                              for strat, fn in round_fns.items()
                              if fn.exchange is not None}
                k_sel = jax.random.fold_in(kt, 1)
                for s, strat in enumerate(spec.strategies):
                    params_old = params[strat]
                    args = (params[strat], batches, data["labels"],
                            data["valid"], k_sel)
                    if attacked:
                        args += (adv_dev,)
                    if tau:
                        args += (past[strat][0],)
                    params[strat], info = round_fns[strat](*args)
                    if tau:
                        past[strat].append(params[strat])
                    if collector is not None:
                        dyn = {"hists": data["hists"], "mask": info["mask"],
                               "params_old": params_old,
                               "params_new": params[strat]}
                        if agg.clustered:
                            cent = info["cluster_centroids"]
                            prev = (prev_cent[strat]
                                    if prev_cent[strat] is not None
                                    else jnp.zeros_like(cent))
                            dyn.update(assign=info["cluster_assign"],
                                       centroids=cent, prev_centroids=prev)
                            prev_cent[strat] = cent
                        for name, v in collector(dyn).items():
                            v = np.asarray(v, np.float32)
                            if name not in tel:
                                tel[name] = np.zeros(
                                    (k_n, s_n, r_n, t_n) + v.shape,
                                    np.float32)
                            tel[name][k, s, r, t] = v
                    if agg.clustered:
                        l, a, acc_c, loss_c = eval_mix_jit(
                            params[strat], info["cluster_weights"])
                        acc[k, s, r, t] = float(a)
                        loss[k, s, r, t] = float(l)
                        c_acc[k, s, r, t] = np.asarray(acc_c, np.float32)
                        c_loss[k, s, r, t] = np.asarray(loss_c, np.float32)
                        c_assign[k, s, r, t] = np.asarray(
                            info["cluster_assign"], np.int32)
                    else:
                        l, m = eval_jit(params[strat])
                        acc[k, s, r, t] = float(m["accuracy"])
                        loss[k, s, r, t] = float(l)
                    nsel[k, s, r, t] = float(info["num_selected"])
    meta = {"sharded": {
        "groups": groups, "clients": n_clients,
        "clients_per_group": n_clients // groups, "exchange": exchange,
        "n_clusters": agg.n_clusters,
        "reduce": "gather" if agg.reduce is not None else "psum",
        "strategies": {
            strat: {"budget": fn.budget,
                    "trained_per_round": fn.trained_per_round,
                    "flop_sparsity": fn.flop_sparsity,
                    # Analytic per-device ring bytes of the gather-phase
                    # batch exchange (None when no round ran).
                    "exchange_bytes_per_device":
                        None if xbytes is None else xbytes.get(strat)}
            for strat, fn in round_fns.items()}}}
    if agg.clustered:
        meta.update(_clustered_meta(c_acc, c_loss, c_assign))
    if tel:
        meta["_telemetry_series"] = tel
    return acc, loss, nsel, time.perf_counter() - t0, 0.0, meta


def _engine_hier(spec: ExperimentSpec, lowered: Sequence[LoweredScenario], ds):
    """Hierarchical two-tier population engine — repro.fl.population."""
    from .population import run_engine_hier
    return run_engine_hier(spec, lowered, ds)


def _engine_async(spec: ExperimentSpec, lowered: Sequence[LoweredScenario],
                  ds):
    """Async FedBuff population engine — repro.fl.population."""
    from .population import run_engine_async
    return run_engine_async(spec, lowered, ds)


register_engine("sim", _engine_sim, option_keys=())
register_engine("host", _engine_host, option_keys=())
register_engine("sharded", _engine_sharded, option_keys=())
register_engine("hier", _engine_hier, option_keys=("num_blocks",))
register_engine("async", _engine_async,
                option_keys=("num_blocks", "buffer_k", "alpha", "tau_max"))


# ---------------------------------------------------------------------------
# The one run surface
# ---------------------------------------------------------------------------

def run(spec: ExperimentSpec, *, ds=None) -> ExperimentResult:
    """Execute a declarative experiment spec and return the labeled result.

    Lowers every ScenarioSpec (source + ordered transforms) to arrays once,
    dispatches through the engine registry, and labels the output axes
    (scenario, strategy, seed, round).

    Observability: each stage runs under a ``repro.obs`` trace span (and the
    engine call under ``obs.profiler``, which also wraps it in
    ``jax.profiler.trace`` when ``REPRO_TRACE_DIR`` is set); the engine's raw
    metric series (``meta["_telemetry_series"]``) are folded into the
    versioned ``meta["telemetry"]`` envelope together with the engine's
    side facts, the span summary, and any compiled-module memory analyses.
    The old per-engine keys (``meta["sharded"]`` / ``meta["population"]`` /
    ``meta["clustered"]``) are kept as aliases of the envelope's
    ``engine_facts``."""
    from repro.obs import (build_envelope, memory_snapshots, profiler, span,
                           span_summary, write_trace)
    with span("validate", engine=spec.engine):
        spec.validate()
    with span("lower_scenarios", engine=spec.engine):
        lowered = [s.lower(spec.fl, spec.seeds, spec.num_rounds)
                   for s in spec.scenarios]
    engine = _ENGINES[spec.engine]
    n_mem = len(memory_snapshots())
    with profiler(spec.engine):
        out = engine(spec, lowered, ds)
    acc, loss, nsel, wall_s, compile_s = out[:5]
    meta = dict(out[5]) if len(out) > 5 else {}
    series = meta.pop("_telemetry_series", None)
    facts = {k: meta[k] for k in ("sharded", "population", "clustered")
             if k in meta}
    meta["telemetry"] = build_envelope(
        spec.engine, series=series, engine_facts=facts or None,
        spans=span_summary(),
        memory_analysis=memory_snapshots()[n_mem:] or None)
    write_trace()          # no-op unless REPRO_TRACE_DIR is set
    return ExperimentResult(
        scenarios=tuple(s.name for s in spec.scenarios),
        strategies=tuple(spec.strategies), seeds=tuple(spec.seeds),
        accuracy=np.asarray(acc), loss=np.asarray(loss),
        num_selected=np.asarray(nsel), engine=spec.engine,
        wall_s=wall_s, compile_s=compile_s, meta=meta)


# ---------------------------------------------------------------------------
# A beyond-paper strategy registered purely through the public API — proof
# that the registry reaches the compiled engine without touching sim.py.
# ---------------------------------------------------------------------------

def select_dirichlet_uniformity(key, hists, n_select) -> SelectionResult:
    """Dirichlet-posterior expected entropy of p(L_i).

    Treat each client's histogram h as multinomial counts with a uniform
    Dirichlet(1) prior → posterior Dirichlet(α = h + 1), and rank clients by
    the posterior-expected Shannon entropy

        E[−Σ_c p_c log p_c] = Σ_c (α_c/α₀)(ψ(α₀+1) − ψ(α_c+1)).

    Unlike the plug-in ``entropy``/``kl`` scores this is sample-size aware:
    a 3-sample "uniform" histogram shrinks toward the prior and cannot outrank
    a 300-sample genuinely uniform client, so it trades off §IV-C uniformity
    against histogram evidence."""
    del key
    import jax.numpy as jnp
    from jax.scipy.special import digamma

    alpha = jnp.asarray(hists, jnp.float32) + 1.0
    a0 = alpha.sum(-1, keepdims=True)
    scores = ((alpha / a0) * (digamma(a0 + 1.0) - digamma(alpha + 1.0))).sum(-1)
    valid = jnp.asarray(hists).sum(axis=-1) > 0
    mask, order = topn_mask(scores, valid, n_select)
    return SelectionResult(mask, scores, order)


if "dirichlet_uniformity" not in STRATEGIES:
    register_strategy("dirichlet_uniformity", select_dirichlet_uniformity)
