"""The one traffic generator: a mix's parameters and ``--seed`` → label plans.

A traffic mix (``bench/traffic/<mix>.json``) names a scenario source and its
parameters; the configuration gives the population.  Every call runs
``seeds_per_call`` trials per strategy, each on its own plan, drawn on the
host from (seed, call index) when the call is made, as ``run()`` lowers its
scenario on every call.  A run's work is fixed by the seed: every seed gets
plans of one shape and the same sample counts in distribution, in another
draw.

The plan generators are the §III case plans, the Dirichlet label skew and
the ragged-size subsample, written out here so that the benchmark's inputs
do not depend on the program under test (same definitions as the program's
``repro.core.noniid``).
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

# Streams of the run seed: plan draws, per-call trial seeds, check sample.
PLAN_STREAM, TRIAL_STREAM, SAMPLE_STREAM = 1, 2, 3


def case_plan(case: str, rng: np.random.Generator, rounds: int,
              num_clients: int, num_classes: int, samples: int,
              majority: int) -> np.ndarray:
    """(T, N, n) int32: §III case 1-A/1-B (each client its own majority label
    per round; 1-B fills the minority uniformly over the other classes)."""
    if case not in ("case1a", "case1b"):
        raise ValueError(f"case {case!r} is not generated here; add it")
    major = rng.integers(0, num_classes, size=(rounds, num_clients)).astype(np.int32)
    plan = np.repeat(major[..., None], samples, axis=-1)
    if case == "case1b":
        draw = rng.integers(0, num_classes - 1,
                            size=major.shape + (samples - majority,))
        plan[..., majority:] = np.where(draw >= major[..., None], draw + 1, draw)
    return plan.astype(np.int32)


def dirichlet_plan(rng: np.random.Generator, num_clients: int, alpha: float,
                   num_classes: int, samples: int) -> np.ndarray:
    """(1, N, n) int32: each client's labels from its own Dirichlet(α) class
    mixture; static across rounds."""
    probs = rng.dirichlet(np.full(num_classes, alpha), size=num_clients)
    # Inverse-CDF draws for all clients at once (one rng call, not N).
    u = rng.random((num_clients, samples))
    cdf = np.cumsum(probs, axis=-1)
    cdf[:, -1] = 1.0
    labels = (u[..., None] > cdf[:, None, :]).sum(-1)
    return labels[None].astype(np.int32)


def ragged(plan: np.ndarray, rng: np.random.Generator, n_min: int) -> np.ndarray:
    """Each (round, client) keeps a uniform random subsample of
    U[n_min, n] of its labels; the tail is −1 padding."""
    t, n, s = plan.shape
    keys = rng.random(plan.shape)
    order = np.argsort(keys, axis=-1)
    shuffled = np.take_along_axis(plan, order, axis=-1)
    sizes = rng.integers(n_min, s + 1, size=(t, n))
    keep = np.arange(s)[None, None, :] < sizes[..., None]
    return np.where(keep, shuffled, np.int32(-1)).astype(np.int32)


def trial_plan(config: Dict[str, Any], traffic: Dict[str, Any],
               rng: np.random.Generator) -> np.ndarray:
    """One trial's (T, N, n) plan (T = rounds per call, or 1 when static)."""
    sc = traffic["scenario"]
    n_clients, n_classes = config["num_clients"], config["num_classes"]
    samples = config["samples_per_client"]
    if sc["source"] == "case":
        plan = case_plan(sc["case"], rng, traffic["rounds_per_call"],
                         n_clients, n_classes, samples,
                         config["majority_per_client"])
    elif sc["source"] == "dirichlet":
        plan = dirichlet_plan(rng, n_clients, sc["alpha"], n_classes, samples)
    else:
        raise ValueError(f"unknown scenario source {sc['source']!r}")
    if "samples_min" in config:
        plan = ragged(plan, rng, config["samples_min"])
    return plan


def call_plans(config: Dict[str, Any], traffic: Dict[str, Any], seed: int,
               call: int) -> np.ndarray:
    """The (R, T, N, n) int32 plans of call ``call``, one per trial seed."""
    rng = np.random.default_rng([seed, PLAN_STREAM, call])
    return np.stack([trial_plan(config, traffic, rng)
                     for _ in range(traffic["seeds_per_call"])])


def call_seeds(traffic: Dict[str, Any], seed: int, call: int) -> np.ndarray:
    """The (R,) int32 trial seeds of window call ``call``."""
    rng = np.random.default_rng([seed, TRIAL_STREAM, call])
    return rng.integers(0, 2 ** 31 - 1, size=traffic["seeds_per_call"],
                        dtype=np.int64).astype(np.int32)


def check_sample(n_calls: int, n_strategies: int, n_seeds: int, k: int,
                 seed: int) -> List[tuple]:
    """``k`` distinct (call, strategy, seed index) triples drawn from the
    seed among the window's calls — the strategies taken in turn, so every
    one is checked — or every triple where the window holds fewer."""
    rng = np.random.default_rng([seed, SAMPLE_STREAM])
    pools = [[(c, s, r) for c in range(n_calls) for r in range(n_seeds)]
             for s in range(n_strategies)]
    out = []
    for j in range(min(k, n_calls * n_strategies * n_seeds)):
        pool = pools[j % n_strategies]
        while not pool:
            j += 1
            pool = pools[j % n_strategies]
        out.append(pool.pop(int(rng.integers(len(pool)))))
    return out
