"""Whether the timed path's answers are right: a sample of the window's
trials, recomputed by the plain reference, by the numbers below, each held
to its limit from ``bench/limits/<cell>.json``.

Compared:

* ``num_selected_gap`` — largest |clients trained − reference| over the
  checked trials' rounds (selection and the budget): exact, limit 0;
* ``loss_mean_gap`` — mean over the checked trials and their rounds of
  |eval loss − reference eval loss|, in nats (training, aggregation, the
  server update, eval);
* ``loss_gap`` — the largest of those gaps: one trial, or one round, that
  is off.

Each trial is compared with the reference of its own strategy and seed, so
a trajectory that comes back under another trial's name reads as large as
the gap between the two trials.  Read and reported, not held to a limit:
``accuracy_gap`` (largest |accuracy − reference|).  Adam over 40 local
steps turns the rounding of the program's bf16-pass matmuls into zero-mean
noise of about 0.013 nats (sd) in a trial's round-0 loss, as large as what
bfloat16 weights and moments do to one trial; but bfloat16 shifts every
trial the same way and by more than the rounding's typical size, so the
mean gap over the checked trials tells them apart where the largest single
gap cannot.  The largest gap's limit is set from the faults planted by
``bench/faults.py`` (PERF.md gives the readings).

A non-finite program answer makes its numbers infinite.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

NUMBERS = ("num_selected_gap", "loss_mean_gap", "loss_gap")
READINGS = NUMBERS + ("accuracy_gap",)


def trial_gaps(program: Dict[str, np.ndarray],
               references: List[Dict[str, np.ndarray]]) -> Dict[str, float]:
    """One trial against the closest of the reference's trajectories (one
    per way of resolving a float32-ambiguous selection tie): the largest
    gaps and the mean loss gap over its rounds."""
    return min((_trial_gaps(program, r) for r in references),
               key=lambda g: (g["num_selected_gap"], g["loss_gap"]))


def _trial_gaps(program, reference) -> Dict[str, float]:
    p = {k: np.asarray(program[k], np.float64) for k in
         ("num_selected", "loss", "accuracy")}
    r = {k: np.asarray(reference[k], np.float64) for k in p}
    if not all(np.all(np.isfinite(v)) for v in p.values()):
        return dict.fromkeys(READINGS, float("inf"))
    d = np.abs(p["loss"] - r["loss"])
    return {"num_selected_gap": float(np.max(np.abs(p["num_selected"]
                                                    - r["num_selected"]))),
            "loss_mean_gap": float(np.mean(d)),
            "loss_gap": float(np.max(d)),
            "accuracy_gap": float(np.max(np.abs(p["accuracy"] - r["accuracy"])))}


def aggregate(trials: List[Dict[str, float]]) -> Dict[str, float]:
    """A run's numbers from its checked trials."""
    out = {k: max(t[k] for t in trials) for k in READINGS}
    out["loss_mean_gap"] = float(np.mean([t["loss_mean_gap"] for t in trials]))
    return out


def judge(numbers: Dict[str, float],
          limits: Dict[str, float]) -> Tuple[bool, Dict[str, dict], List[str]]:
    """(correct, {name: {value, limit}}, one line per compared number)."""
    table = {k: {"value": numbers[k], "limit": limits[k]} for k in NUMBERS}
    ok = all(numbers[k] <= limits[k] for k in NUMBERS)
    lines = [f"check {k}: {numbers[k]!r} (limit {limits[k]!r})"
             f"{'' if numbers[k] <= limits[k] else '  FAILED'}" for k in NUMBERS]
    return ok, table, lines
