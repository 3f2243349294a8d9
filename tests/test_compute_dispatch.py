"""Backend compute dispatch (repro.kernels.dispatch) + the rewritten
histogram reference + the O(B) selected-shard exchange.

Fast tier: the bincount-shaped histogram is BIT-IDENTICAL to the old one-hot
form; interpret-mode Pallas ≡ reference bit-identity for the label-hist
kernel and ulp-level identity for the weighted-agg kernel (XLA's dot uses
blocked-FMA accumulation, so the last bit differs from an elementwise
reduce — see the dispatch module docstring), exercised exactly as the
engines call them; backend resolution and env override; the exchange-bytes
calculator.  An end-to-end micro trial runs the compiled engine with the
Pallas path forced (interpret mode) against the reference path.

Slow tier: subprocess pin (emulated devices) that ``exchange="a2a"`` ≡
``exchange="allgather"`` trajectories bit-for-bit in the sharded round.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.label_stats import histogram
from repro.kernels import (client_histograms, compute_backend,
                           masked_weighted_mean, weighted_sum_tree)
from repro.kernels.dispatch import ENV_VAR, client_statistics

KEY = jax.random.PRNGKey(0)


def one_hot_histogram(labels, num_classes, valid=None):
    """The OLD reference — kept verbatim as the bit-identity oracle."""
    labels = labels.astype(jnp.int32)
    one_hot = jax.nn.one_hot(labels, num_classes, dtype=jnp.float32)
    if valid is not None:
        one_hot = one_hot * valid.astype(jnp.float32)[..., None]
    return one_hot.sum(axis=-2)


class TestHistogramReference:
    """core.label_stats.histogram: bincount-shaped ≡ old one-hot form."""

    @pytest.mark.parametrize("shape,c", [((8, 32), 10), ((100, 290), 10),
                                         ((3, 5, 7), 4), ((11,), 5),
                                         ((6, 1), 3), ((4, 64), 256)])
    def test_bit_identical_to_one_hot_form(self, shape, c):
        labels = jax.random.randint(KEY, shape, -1, c)    # −1 pad included
        for valid in (None, labels >= 0,
                      (jax.random.uniform(KEY, shape) > 0.3)):
            got = histogram(labels, c, valid)
            want = one_hot_histogram(labels, c, valid)
            assert got.dtype == want.dtype == jnp.float32
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_out_of_range_labels_dropped(self):
        labels = jnp.array([[0, 1, 5, -1, -7, 2, 1]])
        got = np.asarray(histogram(labels, 3))
        np.testing.assert_array_equal(got, [[1.0, 2.0, 1.0]])

    def test_float01_availability_weights_exact(self):
        # the engines multiply availability 0/1 floats into validity — counts
        # stay integer-valued, so bit-identity must survive float weights
        labels = jax.random.randint(KEY, (7, 40), 0, 6)
        avail = (jax.random.uniform(KEY, (7, 40)) > 0.5).astype(jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(histogram(labels, 6, avail)),
            np.asarray(one_hot_histogram(labels, 6, avail)))

    def test_under_vmap_and_jit(self):
        labels = jax.random.randint(KEY, (13, 9, 21), -1, 5)
        valid = labels >= 0
        got = jax.jit(jax.vmap(lambda l, v: histogram(l, 5, v)))(labels, valid)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(one_hot_histogram(labels, 5, valid)))


class TestBackendResolution:
    def test_cpu_auto_resolves_to_reference(self, monkeypatch):
        monkeypatch.delenv(ENV_VAR, raising=False)   # dev shells may set it
        assert compute_backend() == "reference"          # CPU container
        assert compute_backend("auto") == "reference"

    def test_explicit_backends_pass_through(self):
        assert compute_backend("reference") == "reference"
        assert compute_backend("pallas") == "pallas"
        assert compute_backend("pallas_interpret") == "pallas_interpret"

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv(ENV_VAR, "pallas_interpret")
        assert compute_backend() == "pallas_interpret"
        # explicit arg beats the env var
        assert compute_backend("reference") == "reference"

    def test_unknown_backend_raises(self):
        with pytest.raises(ValueError, match="compute backend"):
            compute_backend("cuda")

    @pytest.mark.parametrize("op", ["hist", "mean", "sum"])
    def test_forced_pallas_off_tpu_raises(self, op):
        # no silent switch to the interpreter: on the CPU a forced "pallas"
        # is an error, "pallas_interpret" the explicit way to the kernels
        labels = jnp.zeros((2, 4), jnp.int32)
        tree = {"w": jnp.ones((2, 3))}
        call = {"hist": lambda: client_histograms(labels, 3, backend="pallas"),
                "mean": lambda: masked_weighted_mean(tree, jnp.ones(2),
                                                     backend="pallas"),
                "sum": lambda: weighted_sum_tree(tree, jnp.ones(2),
                                                 backend="pallas")}[op]
        with pytest.raises(RuntimeError, match="pallas_interpret"):
            call()


class TestPallasInterpretParity:
    """Interpret-mode Pallas ≡ reference, at the shapes engines call with."""

    @pytest.mark.parametrize("n_clients,n,c", [(16, 24, 10), (8, 8, 10),
                                               (30, 48, 7)])
    def test_label_hist_bit_identical(self, n_clients, n, c):
        labels = jax.random.randint(KEY, (n_clients, n), -1, c)
        valid = labels >= 0
        safe = jnp.where(valid, labels, 0)
        ref = client_histograms(safe, c, valid, backend="reference")
        pal = client_histograms(safe, c, valid, backend="pallas_interpret")
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(pal))

    def test_label_hist_leading_dims_bit_identical(self):
        labels = jax.random.randint(KEY, (3, 6, 12), -1, 5)
        ref = client_histograms(labels, 5, backend="reference")
        pal = client_histograms(labels, 5, backend="pallas_interpret")
        assert pal.shape == (3, 6, 5)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(pal))

    def test_client_statistics_scores_bit_identical(self):
        labels = jax.random.randint(KEY, (12, 30), -1, 10)
        h_ref, s_ref = client_statistics(labels, 10, backend="reference")
        h_pal, s_pal = client_statistics(labels, 10,
                                         backend="pallas_interpret")
        np.testing.assert_array_equal(np.asarray(h_ref), np.asarray(h_pal))
        np.testing.assert_array_equal(np.asarray(s_ref), np.asarray(s_pal))

    def test_masked_weighted_mean_ulp_identical(self):
        # the engines aggregate a stacked param pytree with live×n_i weights;
        # dot-accumulation order differs from the elementwise reduce at the
        # last bit, so the pin is f32-ulp tolerance, not bit equality
        ks = jax.random.split(KEY, 4)
        tree = {"w": jax.random.normal(ks[0], (6, 5, 4)),
                "b": jax.random.normal(ks[1], (6, 3))}
        mask = jnp.array([1.0, 0, 1, 1, 0, 1])
        sizes = jax.random.uniform(ks[2], (6,), minval=1.0, maxval=9.0)
        ref = masked_weighted_mean(tree, mask, sizes, backend="reference")
        pal = masked_weighted_mean(tree, mask, sizes,
                                   backend="pallas_interpret")
        for k in tree:
            np.testing.assert_allclose(np.asarray(ref[k]), np.asarray(pal[k]),
                                       rtol=3e-7, atol=3e-7)

    def test_masked_weighted_mean_empty_selection_zero(self):
        tree = {"w": jnp.ones((4, 3))}
        zero = jnp.zeros(4)
        for backend in ("reference", "pallas_interpret"):
            out = masked_weighted_mean(tree, zero, backend=backend)
            np.testing.assert_array_equal(np.asarray(out["w"]),
                                          np.zeros((3,)))

    def test_weighted_sum_tree_ulp_identical(self):
        tree = {"d": jax.random.normal(KEY, (5, 8, 2))}
        w = jnp.array([0.0, 2.0, 1.0, 0.0, 3.0])
        ref = weighted_sum_tree(tree, w, backend="reference")
        pal = weighted_sum_tree(tree, w, backend="pallas_interpret")
        np.testing.assert_allclose(np.asarray(ref["d"]), np.asarray(pal["d"]),
                                   rtol=3e-7, atol=3e-7)

    def test_engine_trial_pallas_vs_reference(self, monkeypatch):
        """The compiled sim engine end-to-end on both backends: identical
        histograms → identical selection; aggregation within float ulp →
        trajectories agree tightly."""
        from repro.configs.paper_cnn import FLConfig
        from repro.core import case_label_plan
        from repro.fl import simulate

        cfg = FLConfig(num_clients=6, clients_per_round=2, global_epochs=2,
                       local_epochs=1, batch_size=4, lr=1e-3)
        plan = case_label_plan("case1b", seed=0, num_rounds=2, num_clients=6,
                               samples_per_client=4, majority=2)
        monkeypatch.delenv(ENV_VAR, raising=False)
        ref = simulate(plan, cfg, rounds=2, eval_n_per_class=2)
        monkeypatch.setenv(ENV_VAR, "pallas_interpret")
        pal = simulate(plan, cfg, rounds=2, eval_n_per_class=2)
        np.testing.assert_array_equal(ref.num_selected, pal.num_selected)
        np.testing.assert_allclose(ref.loss, pal.loss, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(ref.accuracy, pal.accuracy, atol=5e-3)


class TestExchangeBytes:
    def test_a2a_cuts_bytes_by_sparsity(self):
        from repro.fl import exchange_bytes_per_device
        # the benchmark config: 8 devices × 4 clients, budget 8 → B_pad 8,
        # sparsity 0.75 → a2a moves exactly ¼ of the all-gather bytes
        batch = {"images": jnp.zeros((32, 1, 8, 16, 16, 1)),
                 "labels": jnp.zeros((32, 1, 8), jnp.int32),
                 "valid": jnp.zeros((32, 1, 8), bool)}
        ag = exchange_bytes_per_device(batch, 32, 8, 8, "allgather")
        a2a = exchange_bytes_per_device(batch, 32, 8, 8, "a2a")
        assert a2a * 4 == ag
        with pytest.raises(ValueError, match="exchange"):
            exchange_bytes_per_device(batch, 32, 8, 8, "ring")


@pytest.mark.slow
class TestShardedExchangeParity:
    def test_a2a_matches_allgather_bit_for_bit(self):
        """Subprocess pin (8 emulated devices, 16 clients in blocks of 2):
        the O(B) selected-shard exchange and the O(N) all-gather produce
        BIT-IDENTICAL trajectories — every training slot has exactly one
        owning shard, so the psum_scatter sums one real contribution plus
        zeros.  Availability ON so dark-client routing is exercised."""
        script = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import availability_plan, case_label_plan
from repro.data import ImageDataset, client_batches, materialize_round
from repro.fl import exchange_bytes_per_device, make_sharded_fl_round
from repro.fl.client import local_train
from repro.models import cnn_init, cnn_loss
from repro.optim import get_optimizer

n_clients, devices, rounds = 16, 8, 3
mesh = jax.make_mesh((devices,), ("clients",))
ds = ImageDataset()
opt = get_optimizer("adam", 1e-3)
loss_fn = lambda p, b: cnn_loss(p, b["images"], b["labels"], b["valid"])
local_step = lambda p, b: local_train(p, opt, b, loss_fn, 1)[0]
key = jax.random.PRNGKey(0)
params0 = cnn_init(jax.random.fold_in(key, 1))
pspec = jax.tree_util.tree_map(lambda _: P(), params0)
plan = case_label_plan("case1b", seed=0, num_rounds=1,
                       num_clients=n_clients, samples_per_client=8,
                       majority=int(8 * 200 / 290))
avail = jnp.asarray(availability_plan(5, 1, n_clients, 0.3)[0], jnp.float32)
data = materialize_round(ds, plan[0], jax.random.fold_in(key, 2))
batches = client_batches(data, 4)
bp = {"images": P(), "labels": P(), "valid": P()}

trajs = {}
for exch in ("a2a", "allgather"):
    rf = make_sharded_fl_round(mesh, "clients", local_step, n_select=4,
                               num_classes=10, params_pspec=pspec,
                               batch_pspec=bp, num_clients=n_clients,
                               strategy="labelwise", with_availability=True,
                               exchange=exch)
    assert rf.exchange == exch
    p, traj = params0, []
    for t in range(rounds):
        p, info = rf(p, batches, data["labels"], data["valid"],
                     jax.random.fold_in(key, 10 + t), avail)
        traj.append(float(np.asarray(info["num_selected"])))
    trajs[exch] = (jax.tree_util.tree_map(np.asarray, p), traj)

pa, pb = trajs["a2a"][0], trajs["allgather"][0]
for la, lb in zip(jax.tree_util.tree_leaves(pa), jax.tree_util.tree_leaves(pb)):
    assert np.array_equal(la, lb), "exchange paths diverged bitwise"
assert trajs["a2a"][1] == trajs["allgather"][1]
a2a_b = exchange_bytes_per_device(batches, n_clients, 8, devices, "a2a")
ag_b = exchange_bytes_per_device(batches, n_clients, 8, devices, "allgather")
assert a2a_b * 2 == ag_b, (a2a_b, ag_b)   # B_pad = N/2 here
print("EXCHANGE_PARITY_OK")
"""
        env = dict(os.environ,
                   XLA_FLAGS="--xla_force_host_platform_device_count=8",
                   PYTHONPATH="src" + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        proc = subprocess.run([sys.executable, "-c", script], env=env,
                              capture_output=True, text=True, timeout=540,
                              cwd=os.path.dirname(os.path.dirname(__file__)))
        assert proc.returncode == 0, proc.stderr[-3000:]
        assert "EXCHANGE_PARITY_OK" in proc.stdout
