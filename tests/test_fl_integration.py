"""Integration tests: full FL rounds on the paper CNN + sharded FL round.

Uses a scaled-down version of the paper's §VI setup (fewer clients/rounds)
so the suite stays fast; the full-size runs live in benchmarks/.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

pytestmark = pytest.mark.slow  # multi-minute tier; see tests/conftest.py

from repro.configs.paper_cnn import FLConfig
from repro.core import case_label_plan, bias_mix_plan
from repro.data import ImageDataset
from repro.core.selection import topn_mask
from repro.fl import run_fl, make_sharded_fl_round
from repro.ckpt import save_checkpoint, load_checkpoint, latest_checkpoint

SMALL = FLConfig(num_clients=16, clients_per_round=6, global_epochs=4,
                 local_epochs=2, batch_size=16, lr=1e-3)
DS = ImageDataset()


def plan_for(case, clients=16, rounds=4, spc=48):
    return case_label_plan(case, seed=3, num_rounds=rounds, num_clients=clients,
                           samples_per_client=spc, majority=int(spc * 200 / 290))


class TestFLLoop:
    def test_iid_fedavg_learns(self):
        hist = run_fl(plan_for("iid"), SMALL, strategy="random")
        assert hist.final_accuracy > 0.8

    def test_case1a_labelwise_vs_random(self):
        """Case 1-A: every client single-label → labelwise has nothing with
        σ²>0 round 1... all clients are σ²=0, so selection degrades to empty →
        global params unchanged; random trains on biased clients. Both should
        struggle; labelwise must not crash (Alg-1 count<n path)."""
        hist = run_fl(plan_for("case1a"), SMALL, strategy="labelwise")
        assert len(hist.accuracy) == 4
        assert hist.num_selected[0] == 0.0   # σ² = 0 everywhere → no client trains

    def test_bias_mix_labelwise_beats_random(self):
        """Paper Figs. 6–7 direction: p(bias)=0.7 → labelwise converges
        faster/stabler than random (mean accuracy across rounds)."""
        plan = bias_mix_plan(7, 16, p_bias=0.7, n_max=64, n_min=24)
        h_label = run_fl(plan, SMALL, strategy="labelwise", rounds=5)
        h_rand = run_fl(plan, SMALL, strategy="random", rounds=5, seed=11)
        assert (np.mean(h_label.accuracy)
                > np.mean(h_rand.accuracy) + 0.05), (h_label, h_rand)

    def test_fedsgd_runs(self):
        plan = bias_mix_plan(7, 16, p_bias=0.4, n_max=48, n_min=24)
        hist = run_fl(plan, SMALL, strategy="random", aggregation="fedsgd",
                      rounds=3)
        assert len(hist.accuracy) == 3
        assert np.isfinite(hist.loss[-1])

    def test_selected_counts_respect_budget(self):
        plan = bias_mix_plan(9, 16, p_bias=0.3, n_max=48, n_min=24)
        hist = run_fl(plan, SMALL, strategy="labelwise", rounds=2)
        assert all(0 <= s <= SMALL.clients_per_round for s in hist.num_selected)


class TestShardedRound:
    def test_topn_mask(self):
        scores = jnp.array([0.5, 0.0, 2.0, 1.0])
        mask, _ = topn_mask(scores, scores > 0, 2)
        np.testing.assert_array_equal(np.asarray(mask), [0, 0, 1, 1])

    def test_sharded_round_matches_masked_mean(self):
        """On a 1-axis mesh: selected clients' trained params are averaged
        and broadcast; unselected clients' updates are discarded — and the
        gather-based round trains only the budget (padded to the group
        count), not the whole fleet."""
        n_dev = jax.device_count()
        mesh = jax.make_mesh((n_dev,), ("clients",))
        num_classes = 4

        def local_step(params, batch):  # toy "training": add mean of data
            return {"w": params["w"] + batch["x"].mean()}

        round_fn = make_sharded_fl_round(
            mesh, "clients", local_step, n_select=1, num_classes=num_classes,
            params_pspec={"w": P()}, batch_pspec={"x": P()},
        )
        assert round_fn.budget == 1
        assert round_fn.trained_per_round == n_dev  # padded to group count
        params = {"w": jnp.zeros((3,), jnp.float32)}
        batch = {"x": jnp.arange(n_dev * 2, dtype=jnp.float32).reshape(n_dev, 2)}
        # one client has diverse labels (σ²>0), rest single-label
        labels = np.zeros((n_dev, 8), np.int32)
        labels[0, :4] = np.arange(4).repeat(1)
        valid = np.ones((n_dev, 8), bool)
        key = jax.random.PRNGKey(0)
        new_params, info = round_fn(params, batch,
                                    jnp.asarray(labels), jnp.asarray(valid),
                                    key)
        assert float(info["num_selected"]) == 1.0
        # client 0 was selected; its delta = mean of its x = 0.5
        np.testing.assert_allclose(np.asarray(new_params["w"]), 0.5, rtol=1e-6)

    def test_gather_mode_matches_masked_mode(self):
        """Multi-client-per-group: the gather-based round reproduces the
        masked-psum baseline exactly while training only B_pad of N clients."""
        n_dev = jax.device_count()
        mesh = jax.make_mesh((n_dev,), ("clients",))
        n_clients = 4 * n_dev
        rng = np.random.default_rng(0)
        labels = rng.integers(0, 4, (n_clients, 8)).astype(np.int32)
        valid = np.ones((n_clients, 8), bool)
        params = {"w": jnp.zeros((3,), jnp.float32)}
        batch = {"x": jnp.asarray(rng.normal(size=(n_clients, 2)), jnp.float32)}
        key = jax.random.PRNGKey(1)

        def local_step(params, batch):
            return {"w": params["w"] + batch["x"].mean()}

        outs = {}
        for mode in ("gather", "masked"):
            rf = make_sharded_fl_round(
                mesh, "clients", local_step, n_select=2,
                num_classes=4, params_pspec={"w": P()},
                batch_pspec={"x": P()}, num_clients=n_clients, mode=mode)
            outs[mode] = rf(params, batch, jnp.asarray(labels),
                            jnp.asarray(valid), key)
            if mode == "gather":
                assert rf.trained_per_round < n_clients
                assert rf.flop_sparsity > 0
        (p_g, i_g), (p_m, i_m) = outs["gather"], outs["masked"]
        np.testing.assert_allclose(np.asarray(p_g["w"]), np.asarray(p_m["w"]),
                                   rtol=1e-6)
        assert float(i_g["num_selected"]) == float(i_m["num_selected"]) == 2.0

    def test_sharded_round_availability_mask(self):
        """with_availability=True: a dark client is excluded from selection
        even when it is the only σ²>0 client — global params stay put."""
        n_dev = jax.device_count()
        mesh = jax.make_mesh((n_dev,), ("clients",))
        num_classes = 4

        def local_step(params, batch):
            return {"w": params["w"] + batch["x"].mean()}

        round_fn = make_sharded_fl_round(
            mesh, "clients", local_step, n_select=1, num_classes=num_classes,
            params_pspec={"w": P()}, batch_pspec={"x": P()},
            with_availability=True,
        )
        params = {"w": jnp.zeros((3,), jnp.float32)}
        batch = {"x": jnp.arange(n_dev * 2, dtype=jnp.float32).reshape(n_dev, 2)}
        labels = np.zeros((n_dev, 8), np.int32)
        labels[0, :4] = np.arange(4)          # only client 0 has σ² > 0
        valid = np.ones((n_dev, 8), bool)
        key = jax.random.PRNGKey(0)
        avail = np.zeros((n_dev,), np.float32)  # ...but every client is dark
        new_params, info = round_fn(params, batch, jnp.asarray(labels),
                                    jnp.asarray(valid), key,
                                    jnp.asarray(avail))
        assert float(info["num_selected"]) == 0.0
        np.testing.assert_allclose(np.asarray(new_params["w"]), 0.0, atol=1e-7)
        # and with client 0 available again, it is selected as before
        avail[0] = 1.0
        new_params, info = round_fn(params, batch, jnp.asarray(labels),
                                    jnp.asarray(valid), key,
                                    jnp.asarray(avail))
        assert float(info["num_selected"]) == 1.0
        np.testing.assert_allclose(np.asarray(new_params["w"]), 0.5, rtol=1e-6)


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        params = {"a": jnp.ones((3,), jnp.bfloat16),
                  "b": {"c": jnp.arange(4, dtype=jnp.float32)}}
        p = save_checkpoint(str(tmp_path), 7, params, extra={"note": "x"})
        assert latest_checkpoint(str(tmp_path)) == p
        loaded, meta = load_checkpoint(p, params)
        assert meta["step"] == 7 and meta["extra"]["note"] == "x"
        assert loaded["a"].dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(loaded["b"]["c"], np.float32),
                                      np.arange(4))
