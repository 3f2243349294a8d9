"""The traffic generator: fixed work from the seed."""
from __future__ import annotations

import numpy as np
import pytest

from bench import cells, traffic_gen

BIG_SEED = 2 ** 31 + 987_654_321


DIRICHLET = ({"scenario": {"source": "dirichlet", "alpha": 0.5}},
             {"num_classes": 62, "samples_per_client": 316, "samples_min": 138})


@pytest.mark.parametrize("name,extra", [
    ("paper_cnn.case1b", ({}, {})), ("paper_cnn.case1b_fedsgd", ({}, {})),
    ("paper_cnn.case1b", DIRICHLET)], ids=["case1b", "case1b_fedsgd", "dirichlet"])
def test_same_seed_same_plans_and_every_seed_the_same_shapes(name, extra):
    cell = cells.load_cell(name)
    cfg = dict(cell.config, **extra[1])
    tr = dict(cell.traffic, **extra[0])
    a = [traffic_gen.call_plans(cfg, tr, BIG_SEED, i) for i in (0, 1)]
    b = [traffic_gen.call_plans(cfg, tr, BIG_SEED, i) for i in (0, 1)]
    c = [traffic_gen.call_plans(cfg, tr, 7, i) for i in (0, 1)]
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert not np.array_equal(a[0], a[1])
    shape = a[0].shape
    assert all(p.shape == shape and p.dtype == np.int32 for p in a + c)
    assert shape[:2] == (tr["seeds_per_call"],
                         tr["rounds_per_call"] if tr["scenario"]["source"] == "case" else 1)
    assert shape[2:] == (cfg["num_clients"], cfg["samples_per_client"])
    for p in a:
        labels = p[p >= 0]
        assert labels.max() < cfg["num_classes"]
        sizes = (p >= 0).sum(-1)
        assert sizes.min() >= cfg.get("samples_min", cfg["samples_per_client"])
        # Padding is a contiguous tail.
        assert np.array_equal(np.sort(p >= 0, axis=-1)[..., ::-1], p >= 0)


def test_case1b_has_the_papers_majority_and_minority():
    rng = np.random.default_rng(0)
    plan = traffic_gen.case_plan("case1b", rng, 3, 50, 10, 290, 200)
    major = plan[..., :1]
    assert (plan[..., :200] == major).all()
    assert (plan[..., 200:] != major).all()


def test_call_seeds_are_int32_and_differ_by_call():
    tr = {"seeds_per_call": 2}
    s0 = traffic_gen.call_seeds(tr, BIG_SEED, 0)
    assert s0.dtype == np.int32 and (s0 >= 0).all()
    assert not np.array_equal(s0, traffic_gen.call_seeds(tr, BIG_SEED, 1))
    assert np.array_equal(s0, traffic_gen.call_seeds(tr, BIG_SEED, 0))


def test_check_sample_takes_distinct_trials_every_strategy_in_turn():
    sample = traffic_gen.check_sample(5, 3, 2, 6, BIG_SEED)
    assert [s for _, s, _ in sample] == [0, 1, 2, 0, 1, 2]
    assert len(set(sample)) == 6
    assert all(0 <= c < 5 and 0 <= r < 2 for c, _, r in sample)
    assert sample == traffic_gen.check_sample(5, 3, 2, 6, BIG_SEED)
    # A window with fewer trials than asked for is checked whole.
    whole = traffic_gen.check_sample(1, 3, 2, 8, BIG_SEED)
    assert sorted(whole) == [(0, s, r) for s in range(3) for r in range(2)]
