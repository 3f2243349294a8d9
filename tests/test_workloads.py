"""Workload-registry tests: registration contract, LM host≡sim trajectory
parity, budget invariants, and the engines' workload-agnosticism.

The fast tier pins the acceptance contract for the registry subsystem: the
``lm`` workload (micro transformer over domain-skewed TokenDataset streams)
runs through the compiled engine AND the host engine with matching
trajectories, and ``repro.fl.sim`` imports no model/dataset code — every
workload reaches the engines through the registry alone.
"""
import inspect
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs.paper_cnn import FLConfig
from repro.core import case_label_plan
from repro.fl import (ExperimentSpec, ScenarioSpec,
                      get_workload, lm_workload, register_workload,
                      registered_workloads, run, run_fl_host, simulate)
from repro.fl.workloads import MICRO_LM_CONFIG

MICRO = FLConfig(num_clients=6, clients_per_round=2, global_epochs=2,
                 local_epochs=1, batch_size=4, lr=1e-3)


def micro_plan(case="iid", seed=3, rounds=2, clients=6, spc=8):
    return case_label_plan(case, seed=seed, num_rounds=rounds,
                           num_clients=clients, samples_per_client=spc,
                           majority=int(spc * 200 / 290))


class TestWorkloadRegistry:
    def test_builtins_registered(self):
        assert {"cnn", "lm"} <= set(registered_workloads())
        assert get_workload("cnn").batch_keys == ("images", "labels", "valid")
        assert get_workload("lm").batch_keys == ("tokens", "labels", "valid")
        # registration rewrites the bundle's name to the registry key
        for name in registered_workloads():
            assert get_workload(name).name == name

    def test_unknown_workload_raises(self):
        with pytest.raises(KeyError, match="unknown workload"):
            get_workload("nope")
        spec = ExperimentSpec(scenarios=(ScenarioSpec.from_case("iid"),),
                              workload="nope")
        with pytest.raises(KeyError, match="unknown workload"):
            spec.validate()

    def test_duplicate_and_bad_registrations(self):
        with pytest.raises(ValueError, match="already registered"):
            register_workload("cnn", get_workload("cnn"))
        with pytest.raises(ValueError, match="non-empty str"):
            register_workload("", get_workload("cnn"))
        with pytest.raises(TypeError, match="must be a Workload"):
            register_workload("_bad", lambda: None)

    def test_reregistration_keeps_behavior(self):
        """overwrite=True swaps the bundle in place: re-registering the same
        bundle leaves engine behavior identical (spec runs bit-identically)."""
        plan = micro_plan(spc=4, clients=4)
        cfg = FLConfig(num_clients=4, clients_per_round=2, global_epochs=1,
                       local_epochs=1, batch_size=4, lr=1e-3)
        before = simulate(plan, cfg, strategy="labelwise", eval_n_per_class=1)
        register_workload("cnn", get_workload("cnn"), overwrite=True)
        after = simulate(plan, cfg, strategy="labelwise", eval_n_per_class=1)
        np.testing.assert_array_equal(before.accuracy, after.accuracy)
        np.testing.assert_array_equal(before.loss, after.loss)

    def test_workload_instance_passthrough_and_metadata(self):
        wl = lm_workload(MICRO_LM_CONFIG, num_domains=4, seq_len=8)
        assert get_workload(wl) is wl
        ds = wl.make_dataset()
        assert wl.num_classes(ds) == 4
        shapes = wl.param_shapes(ds)       # static metadata, no weights
        leaves = jax.tree_util.tree_leaves(shapes)
        assert leaves and all(hasattr(l, "shape") for l in leaves)


class TestEnginesAreWorkloadAgnostic:
    def test_sim_has_no_model_or_dataset_imports(self):
        """Acceptance pin: the compiled engine contains no workload-specific
        imports — models/datasets reach it only through the registry."""
        import repro.fl.sim as sim
        src = inspect.getsource(sim)
        assert "repro.models" not in src
        assert "ImageDataset" not in src and "TokenDataset" not in src
        assert "materialize_round" not in src
        for name in ("cnn_init", "cnn_loss"):
            assert not hasattr(sim, name)


class TestLMEngineParity:
    def test_lm_host_sim_trajectory_parity(self):
        """Acceptance pin: workload='lm' through the compiled lax.scan engine
        reproduces the host engine's trajectories (same fold_in tree,
        same transformer round math)."""
        plan = micro_plan("iid")
        host = run_fl_host(plan, MICRO, strategy="labelwise", workload="lm",
                           eval_n_per_class=2)
        sim = simulate(plan, MICRO, strategy="labelwise", workload="lm",
                       eval_n_per_class=2)
        assert len(host.accuracy) == sim.accuracy.shape[0] == 2
        np.testing.assert_allclose(sim.loss, host.loss, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(sim.accuracy, host.accuracy, atol=5e-3)
        np.testing.assert_array_equal(sim.num_selected, host.num_selected)
        # clients actually trained and the model moved
        assert (np.asarray(host.num_selected) == 2).all()
        assert host.loss[1] != host.loss[0]

    def test_lm_budget_invariant_full_and_availability(self):
        """num_selected == mask.sum() (asserted inside the engines) and the
        'full' budget trains every AVAILABLE client — dark clients' zeroed
        domain histograms exclude them, same gate as the CNN workload."""
        plan = micro_plan("iid")
        avail = np.ones((2, 6), np.float32)
        avail[0, :3] = 0.0           # round 1: only clients 3..5 up
        r = simulate(plan, MICRO, strategy="full", workload="lm",
                     avail=avail, eval_n_per_class=1)
        np.testing.assert_array_equal(r.num_selected, [3.0, 6.0])


class TestSpecWorkloadSmoke:
    def test_spec_roundtrip_carries_workload(self):
        spec = ExperimentSpec(scenarios=(ScenarioSpec.from_case("iid"),),
                              workload="lm", fl=MICRO)
        back = ExperimentSpec.from_dict(spec.to_dict())
        assert back.workload == "lm"
        # default stays cnn for pre-workload specs
        d = spec.to_dict()
        del d["workload"]
        assert ExperimentSpec.from_dict(d).workload == "cnn"

    def test_lm_micro_smoke_through_run(self):
        """Tier-1 lm smoke: the declarative surface end-to-end on the
        compiled engine (scenario lowering → vmapped grid → labeled axes)."""
        cfg = FLConfig(num_clients=4, clients_per_round=2, global_epochs=1,
                       local_epochs=1, batch_size=4, lr=1e-3)
        res = run(ExperimentSpec(
            scenarios=(ScenarioSpec.from_case("iid", samples_per_client=4),),
            strategies=("labelwise",), seeds=(0,), engine="sim",
            workload="lm", fl=cfg, eval_n_per_class=1))
        assert res.accuracy.shape == (1, 1, 1, 1)
        assert np.isfinite(res.loss).all()
        traj = res.trajectory("iid", "labelwise", seed=0)
        assert traj["num_selected"].shape == (1,)


@pytest.mark.slow
class TestLMShardedEngine:
    def test_lm_runs_through_sharded_engine_matching_sim(self):
        """workload='lm' through the gather-based SPMD round (4 emulated
        devices, 8 clients in blocks of 2) pins trajectory parity against the
        compiled engine — the whole transformer pytree rides the workload's
        param_shapes-derived PartitionSpecs.  Subprocess: the device count
        must be forced before jax init."""
        script = textwrap.dedent("""
            import numpy as np
            from repro.configs.paper_cnn import FLConfig
            from repro.fl import ExperimentSpec, ScenarioSpec, run
            cfg = FLConfig(num_clients=8, clients_per_round=3,
                           global_epochs=2, local_epochs=1, batch_size=4,
                           lr=1e-3)
            base = dict(
                scenarios=(ScenarioSpec.from_case("iid",
                                                  samples_per_client=4),),
                strategies=("labelwise",), seeds=(0,), workload="lm",
                fl=cfg, eval_n_per_class=1)
            sh = run(ExperimentSpec(engine="sharded", **base))
            sim = run(ExperimentSpec(engine="sim", **base))
            np.testing.assert_array_equal(sh.num_selected, sim.num_selected)
            np.testing.assert_allclose(sh.loss, sim.loss, rtol=2e-4,
                                       atol=2e-5)
            np.testing.assert_allclose(sh.accuracy, sim.accuracy, atol=5e-3)
            st = sh.meta["sharded"]["strategies"]["labelwise"]
            assert st["budget"] == 3 and st["trained_per_round"] == 4
            print("LM_SHARDED_OK")
        """)
        env = dict(os.environ,
                   XLA_FLAGS="--xla_force_host_platform_device_count=4",
                   PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH", ""))
        proc = subprocess.run([sys.executable, "-c", script], env=env,
                              capture_output=True, text=True, timeout=540,
                              cwd=os.path.dirname(os.path.dirname(__file__)))
        assert proc.returncode == 0, proc.stderr[-3000:]
        assert "LM_SHARDED_OK" in proc.stdout
