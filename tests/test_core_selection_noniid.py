"""Tests for selection strategies, non-IID case generators, aggregation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (histogram, get_strategy, STRATEGIES, CASES,
                        case_label_plan, bias_mix_plan, dirichlet_plan,
                        plan_round, masked_mean, fedavg_aggregate,
                        interpolate, psum_aggregate, label_variance)

C = 10
KEY = jax.random.PRNGKey(0)


def hists_from_plan(plan_t):
    labels = jnp.asarray(plan_t)
    valid = labels >= 0
    return histogram(jnp.where(valid, labels, 0), C, valid)


class TestSelection:
    def setup_method(self):
        rng = np.random.default_rng(1)
        rows = []
        # 4 single-label clients, 3 two-label, 3 near-uniform
        for k in range(4):
            rows.append(np.full(100, k))
        for k in range(3):
            rows.append(np.concatenate([np.full(60, k), np.full(40, k + 5)]))
        for _ in range(3):
            rows.append(rng.integers(0, C, 100))
        self.hists = jnp.stack([histogram(jnp.asarray(r), C) for r in rows])

    def test_labelwise_filters_zero_variance(self):
        res = get_strategy("labelwise")(KEY, self.hists, 6)
        mask = np.asarray(res.mask)
        assert mask[:4].sum() == 0          # σ²=0 clients never selected
        assert mask.sum() == 6

    def test_labelwise_degrades_n_like_alg1(self):
        """Fewer valid clients than n → select all valid (count < n branch)."""
        res = get_strategy("labelwise")(KEY, self.hists, 9)
        assert int(res.num_selected) == 6   # only 6 have σ² ≠ 0

    def test_labelwise_prefers_uniform(self):
        res = get_strategy("labelwise")(KEY, self.hists, 3)
        mask = np.asarray(res.mask)
        assert mask[7:].sum() == 3          # the near-uniform clients win

    def test_random_selects_exactly_n(self):
        res = get_strategy("random")(KEY, self.hists, 5)
        assert int(res.num_selected) == 5

    def test_kl_prefers_uniform(self):
        res = get_strategy("kl")(KEY, self.hists, 3)
        assert np.asarray(res.mask)[7:].sum() == 3

    def test_all_strategies_jit(self):
        for name, fn in STRATEGIES.items():
            res = jax.jit(lambda k, h: fn(k, h, 5).mask)(KEY, self.hists)
            assert res.shape == (10,)
            assert set(np.unique(np.asarray(res))) <= {0.0, 1.0}, name

    def test_full(self):
        res = get_strategy("full")(KEY, self.hists, 3)
        assert int(res.num_selected) == 10


class TestNonIIDPlans:
    @pytest.mark.parametrize("case", CASES)
    def test_shapes_and_range(self, case):
        plan = case_label_plan(case, seed=0, num_rounds=5, num_clients=8)
        assert plan.shape == (5, 8, 290)
        assert plan.min() >= 0 and plan.max() < C

    def test_case1a_single_label_per_client(self):
        plan = case_label_plan("case1a", 0, 4, 16)
        for t in range(4):
            for i in range(16):
                assert len(set(plan[t, i])) == 1

    def test_case2a_shared_label_cycles_all_classes(self):
        plan = case_label_plan("case2a", 0, 20, 8)
        labels_per_round = [set(plan[t].ravel()) for t in range(20)]
        assert all(len(s) == 1 for s in labels_per_round)
        assert set().union(*labels_per_round) == set(range(C))  # ∪_T ⊃ ℒ

    def test_case3a_shared_label_random(self):
        plan = case_label_plan("case3a", 0, 30, 8)
        for t in range(30):
            assert len(set(plan[t].ravel())) == 1

    def test_b_cases_majority_minority_counts(self):
        plan = case_label_plan("case1b", 0, 2, 8)
        for i in range(8):
            major = plan[0, i, 0]
            counts = np.bincount(plan[0, i], minlength=C)
            assert counts[major] >= 200          # majority block
            assert counts.sum() - counts[major] <= 90
            # minority labels never equal the major label by construction
            assert (plan[0, i, 200:] != major).all()

    def test_b_case_has_positive_variance(self):
        plan = case_label_plan("case3b", 0, 1, 4)
        h = hists_from_plan(plan[0])
        assert (np.asarray(label_variance(h)) > 0).all()

    def test_bias_mix_raggedness(self):
        plan = bias_mix_plan(0, 50, p_bias=0.7)
        sizes = (plan[0] >= 0).sum(axis=1)
        assert sizes.min() >= 30 and sizes.max() <= 270
        biased = 0
        for i in range(50):
            lab = plan[0, i][plan[0, i] >= 0]
            biased += len(set(lab)) == 1
        assert 20 <= biased <= 50  # ≈70% of 50

    def test_dirichlet(self):
        plan = dirichlet_plan(0, 10, alpha=0.1)
        assert plan.shape == (1, 10, 290)

    def test_plan_round_static_broadcast(self):
        plan = bias_mix_plan(0, 4, 0.5)
        np.testing.assert_array_equal(plan_round(plan, 7), plan[0])


class TestAggregation:
    def test_masked_mean_uniform(self):
        stacked = {"w": jnp.arange(12.0).reshape(4, 3)}
        mask = jnp.array([1.0, 0.0, 1.0, 0.0])
        out = masked_mean(stacked, mask)
        np.testing.assert_allclose(np.asarray(out["w"]),
                                   (np.arange(3) + np.arange(6, 9)) / 2)

    def test_masked_mean_weighted(self):
        stacked = {"w": jnp.array([[0.0], [10.0]])}
        mask = jnp.ones(2)
        out = masked_mean(stacked, mask, weights=jnp.array([1.0, 3.0]))
        np.testing.assert_allclose(float(out["w"][0]), 7.5)

    def test_fedavg_preserves_dtype(self):
        stacked = {"w": jnp.ones((3, 4), jnp.bfloat16)}
        out = fedavg_aggregate(stacked, jnp.ones(3))
        assert out["w"].dtype == jnp.bfloat16

    def test_interpolate_server_lr(self):
        g = {"w": jnp.zeros(2)}
        a = {"w": jnp.ones(2)}
        np.testing.assert_allclose(np.asarray(interpolate(g, a, 0.5)["w"]), 0.5)

    def test_psum_aggregate_shard_map(self):
        """Masked psum over a 1-device 'pod' axis == identity on the one shard."""
        mesh = jax.make_mesh((1,), ("pod",))
        from jax.sharding import PartitionSpec as P

        def f(p, m):
            return psum_aggregate(p, m, "pod")

        out = jax.shard_map(f, mesh=mesh, in_specs=(P(), P()),
                            out_specs=P())(
            {"w": jnp.ones(4)}, jnp.ones(()))
        np.testing.assert_allclose(np.asarray(out["w"]), 1.0)


class TestEntropyStrategy:
    def test_entropy_prefers_uniform(self):
        import jax.numpy as jnp
        from repro.core import histogram, get_strategy
        rows = [np.full(100, 0), np.concatenate([np.full(50, 1), np.full(50, 2)]),
                np.arange(100) % 10]
        hists = jnp.stack([histogram(jnp.asarray(r), 10) for r in rows])
        res = get_strategy("entropy")(KEY, hists, 1)
        assert np.asarray(res.mask)[2] == 1.0         # uniform client wins
        assert float(res.scores[0]) < 1e-6            # single label → H ≈ 0 (ε-smoothing)

    def test_entropy_jits(self):
        from repro.core import histogram, get_strategy
        hists = histogram(jax.random.randint(KEY, (6, 50), 0, 10), 10)
        mask = jax.jit(lambda k, h: get_strategy("entropy")(k, h, 3).mask)(KEY, hists)
        assert float(mask.sum()) == 3.0
