"""The correctness check: the sim engine matches the plain reference at a
tiny size, and the check refuses the control and each fault the cells can
have, planted underneath an otherwise whole run."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from bench import correct, faults, run
from bench.tests import tiny

BIG_SEED = 2 ** 31 + 4242
CPU_PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def _run(cell):
    return run.run_cell(cell, BIG_SEED, 0.0, False, jax.devices(), CPU_PEAK)[0]


@pytest.mark.parametrize("name,extra", [
    ("paper_cnn.case1b", {}), ("paper_cnn.case1b_fedsgd", {}),
    ("paper_cnn.case1b", dict(traffic=tiny.DIRICHLET, **tiny.DIRICHLET_CONFIG))],
    ids=["case1b", "case1b_fedsgd", "dirichlet_ragged"])
def test_sim_engine_matches_the_reference(name, extra):
    result = _run(tiny.cell(name, **extra))
    assert result["correct"] is True, result["checks"]
    # On the CPU both sides are float32 arithmetic: far inside every limit.
    assert result["checks"]["num_selected_gap"]["value"] == 0
    assert result["checks"]["loss_mean_gap"]["value"] < 1e-4
    assert result["checks"]["loss_gap"]["value"] < 1e-4


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_fault_in_the_timed_path_is_refused(fault):
    cell = tiny.cell(strategies=("random", "labelwise"), seeds_per_call=2,
                     clients_per_round=4, num_clients=8)
    with faults.planted(fault):
        result = _run(cell)
    assert result["correct"] is False, result["checks"]
    assert result["failed"] > 0


def test_a_non_finite_answer_fails():
    want = {"num_selected": np.ones(2), "loss": np.ones(2),
            "accuracy": np.ones(2)}
    got = dict(want, loss=np.array([1.0, np.nan]))
    numbers = correct.aggregate([correct.trial_gaps(got, [want]),
                                 correct.trial_gaps(want, [want])])
    assert numbers["loss_mean_gap"] == numbers["loss_gap"] == float("inf")
    ok, table, lines = correct.judge(numbers, {k: 1.0 for k in correct.NUMBERS})
    assert not ok and lines[1].endswith("FAILED") and lines[2].endswith("FAILED")


def test_loss_gaps_take_the_closest_tie_and_see_a_permutation():
    ref_a = {"num_selected": np.full(2, 3.0), "loss": np.array([1.0, 0.5]),
             "accuracy": np.ones(2)}
    ref_b = dict(ref_a, loss=np.array([2.0, 0.5]))
    up = dict(ref_a, loss=np.array([1.1, 0.5]))
    down = dict(ref_a, loss=np.array([0.9, 0.5]))
    g_up = correct.trial_gaps(up, [ref_b, ref_a])
    assert g_up["loss_gap"] == pytest.approx(0.1)      # ref_a is the closer path
    assert g_up["loss_mean_gap"] == pytest.approx(0.05)
    n = correct.aggregate([g_up, correct.trial_gaps(down, [ref_a])])
    # Gaps of opposite sign do not cancel.
    assert n["loss_mean_gap"] == pytest.approx(0.05) and n["loss_gap"] == pytest.approx(0.1)
    # Two trials' answers swapped: each is compared with its own reference.
    swapped = correct.aggregate([correct.trial_gaps(ref_b, [ref_a]),
                                 correct.trial_gaps(ref_a, [ref_b])])
    assert swapped["loss_mean_gap"] == pytest.approx(0.5)
    assert swapped["loss_gap"] == pytest.approx(1.0)
