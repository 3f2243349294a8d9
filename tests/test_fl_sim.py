"""Compiled-engine tests: host-loop parity, grid structure, scenario
transforms threading.

The parity tests pin the two drivers of repro/fl/sim.py's round step: the
engine's lax.scan round loop and the host engine's per-round calls must
return the same trajectories within float tolerance.
"""
import numpy as np
import pytest

from repro.configs.paper_cnn import FLConfig
from repro.core import (apply_availability, availability_plan,
                        case_label_plan, quantity_skew)
from repro.fl import (registered_strategies, run_fl, run_fl_host, run_grid,
                      simulate, stack_case_plans, strategy_id)

MICRO = FLConfig(num_clients=8, clients_per_round=3, global_epochs=3,
                 local_epochs=1, batch_size=16, lr=1e-3)


def micro_plan(case="iid", seed=3, rounds=3, clients=8, spc=16):
    return case_label_plan(case, seed=seed, num_rounds=rounds,
                           num_clients=clients, samples_per_client=spc,
                           majority=int(spc * 200 / 290))


class TestEngineParity:
    def test_scan_matches_host_loop(self):
        """3-round / 8-client run: sim trajectories == host trajectories."""
        plan = micro_plan()
        host = run_fl_host(plan, MICRO, strategy="labelwise",
                           eval_n_per_class=10)
        sim = simulate(plan, MICRO, strategy="labelwise", eval_n_per_class=10)
        assert len(host.accuracy) == sim.accuracy.shape[0] == 3
        np.testing.assert_allclose(sim.loss, host.loss, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(sim.accuracy, host.accuracy, atol=5e-3)
        np.testing.assert_array_equal(sim.num_selected, host.num_selected)

    def test_host_and_sim_run_one_round_step(self):
        """Both engines look the update up in ``repro.fl.sim``: with it
        patched to hand back the params it was given (``bench/faults.py``'s
        ``state_unchanged``), neither engine trains, and both return the
        untrained model's accuracy in every round."""
        import repro.fl.sim as sim_mod
        from unittest import mock
        from repro.fl import ExperimentSpec, ScenarioSpec, run
        scenario = ScenarioSpec.from_plan("micro", micro_plan())
        out = {}
        with mock.patch.object(sim_mod, "client_update_step",
                               lambda params, *a, **k: (params, {})):
            for engine in ("sim", "host"):
                out[engine] = run(ExperimentSpec(
                    scenarios=(scenario,), strategies=("labelwise",),
                    seeds=(0,), fl=MICRO, eval_n_per_class=10,
                    engine=engine)).accuracy[0, 0, 0]
        assert (out["sim"] == out["sim"][0]).all(), out["sim"]
        np.testing.assert_array_equal(out["host"], out["sim"])

    @pytest.mark.slow
    def test_run_fl_wrapper_delegates(self):
        """run_fl (default engine='sim') returns an FLHistory matching the
        engine's trajectories — the public API is preserved."""
        plan = micro_plan(seed=5)
        h = run_fl(plan, MICRO, strategy="random", eval_n_per_class=5)
        r = simulate(plan, MICRO, strategy="random", eval_n_per_class=5)
        assert h.final_accuracy == pytest.approx(float(r.accuracy[-1]))
        assert len(h.loss) == 3 and h.wall_s > 0

    def test_strategy_ids_stable(self):
        import repro.fl as fl
        from repro.core import BUILTIN_STRATEGIES, STRATEGIES
        # Pinned builtin ids 0..6: saved grids index by these — the registry
        # is append-only, so extensions may follow but never reorder.
        builtins = ("random", "labelwise", "labelwise_unnorm", "coverage",
                    "kl", "entropy", "full")
        assert BUILTIN_STRATEGIES == builtins
        assert registered_strategies()[:len(builtins)] == builtins
        # Registry drift guard: the id ledger and the dispatch dict agree.
        assert set(registered_strategies()) == set(STRATEGIES)
        for i, name in enumerate(registered_strategies()):
            assert strategy_id(name) == i
        # importing repro.fl registers the experiment module's extension
        assert "dirichlet_uniformity" in registered_strategies()
        # back-compat: the legacy tuple name is a live registry view
        assert fl.ENGINE_STRATEGIES == registered_strategies()
        with pytest.raises(KeyError):
            strategy_id("nope")


@pytest.mark.slow
class TestGrid:
    def test_grid_shapes_and_switch(self):
        """2 cases × 2 strategies × 2 seeds in one compiled call; the
        labelwise column respects the σ²≠0 gate (case1a selects nobody)."""
        cfg = FLConfig(num_clients=6, clients_per_round=2, global_epochs=2,
                       local_epochs=1, batch_size=8, lr=1e-3)
        plans = stack_case_plans(["iid", "case1a"], cfg, seed0=0,
                                 samples_per_client=8)
        res = run_grid(plans, cfg, strategies=("random", "labelwise"),
                       seeds=(0, 1), eval_n_per_class=2)
        assert res.accuracy.shape == (2, 2, 2, 2)
        # iid × any strategy selects the budget; case1a × labelwise selects 0
        assert (res.num_selected[0] == 2).all()
        assert (res.num_selected[1, 1] == 0).all()
        assert (res.num_selected[1, 0] == 2).all()
        assert res.success_rate().shape == (2, 2)

    def test_per_seed_plans(self):
        cfg = FLConfig(num_clients=6, clients_per_round=2, global_epochs=2,
                       local_epochs=1, batch_size=8, lr=1e-3)
        plans = np.stack([
            np.stack([micro_plan("iid", seed=s, rounds=2, clients=6, spc=8)
                      for s in (0, 1)])])          # (K=1, R=2, T, N, n)
        res = run_grid(plans, cfg, strategies=("random",), seeds=(0, 1),
                       eval_n_per_class=2)
        assert res.accuracy.shape == (1, 1, 2, 2)
        with pytest.raises(ValueError):
            run_grid(plans, cfg, strategies=("random",), seeds=(0, 1, 2),
                     eval_n_per_class=2)


@pytest.mark.slow
class TestAvailabilityThreading:
    def test_unavailable_never_selected(self):
        """A (T, N) availability mask threads into on-device selection: dark
        clients are excluded even under 'full' selection."""
        cfg = FLConfig(num_clients=6, clients_per_round=6, global_epochs=2,
                       local_epochs=1, batch_size=8, lr=1e-3)
        plan = micro_plan("iid", rounds=2, clients=6, spc=8)
        avail = np.ones((2, 6), np.float32)
        avail[0, :4] = 0.0       # round 1: only clients 4,5 up
        avail[1, 5] = 0.0        # round 2: client 5 down
        res = simulate(plan, cfg, strategy="full", avail=avail,
                       eval_n_per_class=2)
        np.testing.assert_array_equal(res.num_selected, [2.0, 5.0])

    def test_composed_plan_equivalent(self):
        """apply_availability (host transform) and the avail argument (device
        mask) express the same scenario: selection counts agree."""
        cfg = FLConfig(num_clients=6, clients_per_round=4, global_epochs=2,
                       local_epochs=1, batch_size=8, lr=1e-3)
        plan = micro_plan("iid", rounds=2, clients=6, spc=8)
        avail = availability_plan(0, 2, 6, p_drop=0.5)
        composed = apply_availability(plan, avail)
        r1 = simulate(composed, cfg, strategy="random", eval_n_per_class=2)
        r2 = simulate(plan, cfg, strategy="random",
                      avail=avail.astype(np.float32), eval_n_per_class=2)
        np.testing.assert_array_equal(r1.num_selected, r2.num_selected)


@pytest.mark.slow
class TestEngineParityFull:
    def test_fedsgd_and_bias_plan_parity(self):
        from repro.core import bias_mix_plan
        cfg = FLConfig(num_clients=8, clients_per_round=4, global_epochs=3,
                       local_epochs=1, batch_size=16, lr=1e-3)
        plan = bias_mix_plan(7, 8, p_bias=0.5, n_max=32, n_min=8)
        for agg in ("fedavg", "fedsgd"):
            host = run_fl_host(plan, cfg, strategy="random", aggregation=agg,
                               eval_n_per_class=10)
            sim = simulate(plan, cfg, strategy="random", aggregation=agg,
                           eval_n_per_class=10)
            np.testing.assert_allclose(sim.loss, host.loss, rtol=2e-4,
                                       atol=2e-5, err_msg=agg)
            np.testing.assert_array_equal(sim.num_selected, host.num_selected)

    def test_quantity_skew_composes_through_engine(self):
        cfg = FLConfig(num_clients=8, clients_per_round=3, global_epochs=2,
                       local_epochs=1, batch_size=8, lr=1e-3)
        plan = quantity_skew(micro_plan("case2b", rounds=2, spc=16), seed=1,
                             n_min=4, n_max=12)
        res = simulate(plan, cfg, strategy="labelwise", eval_n_per_class=5)
        assert res.accuracy.shape == (2,)
        assert np.isfinite(res.loss).all()
