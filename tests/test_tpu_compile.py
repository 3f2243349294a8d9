"""Compiles of the main path for a described TPU v5e chip, with no chip.

The TPU compiler is installed with JAX, and it compiles for a topology that
is described and not attached.  That refuses what interpret mode cannot see:
unaligned tiles, kernels over their fast-memory budget, programs that do not
fit the device.  Nothing runs, so these tests say nothing about results or
time.  Each asserts the program holds its Pallas kernels as TPU custom calls.

The topology is described inside the ``topo`` fixture only — never at import,
in a ``skipif`` or a ``parametrize`` — because one process at a time may load
the TPU library; keep every such compile in this one file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_cnn import FLConfig
from repro.kernels import dispatch
from repro.kernels.label_hist.label_hist import label_hist_kernel
from repro.kernels.weighted_agg.weighted_agg import weighted_agg_kernel
from repro.obs import (convolutions_by_scope, memory_snapshots,
                       pallas_kernel_calls, record_memory_analysis)

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # a compile for a described chip is written to a persistent cache but
        # cannot be read back without one: keep the cache out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            from jax.experimental import topologies
            try:
                desc = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = fn.lower(*args).compile()
    return compiled, pallas_kernel_calls(compiled.as_text())


@pytest.mark.parametrize("clients,n,c", [(100, 290, 10), (3500, 512, 62)])
def test_label_hist_kernel_compiles(one_chip, clients, n, c):
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    fn = jax.jit(functools.partial(label_hist_kernel, num_classes=c))
    _, kernels = _compile(fn, sds((clients, n), jnp.int32),
                          sds((clients, n), jnp.bool_))
    assert kernels == {"label_hist_kernel": 1}


def test_weighted_agg_kernel_compiles_on_largest_cnn_leaf(one_chip):
    from repro.fl.workloads import get_workload

    wl = get_workload("cnn")
    leaf = max(jax.tree_util.tree_leaves(wl.param_shapes(wl.dataset())),
               key=lambda x: x.size)
    k = FLConfig().clients_per_round
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    _, kernels = _compile(jax.jit(weighted_agg_kernel),
                          sds((k, leaf.size), jnp.float32),
                          sds((k,), jnp.float32))
    assert leaf.size == 3136 * 128          # fc1: 7·7·64 → hidden 128
    assert kernels == {"weighted_agg_kernel": 1}


def test_sim_trial_compiles_at_paper_width(one_chip, monkeypatch):
    """One compiled-engine trial at FLConfig() width (100 clients, 30 per
    round, 4 local epochs, batch 32, 290 samples per client, the paper CNN),
    with the dispatch steered onto the Pallas branch as it resolves on a
    TPU: both kernels are in the program, and it fits one chip."""
    from repro.fl.sim import make_trial_fn

    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    monkeypatch.setattr(dispatch, "_PALLAS_PLATFORMS",
                        (jax.default_backend(),))
    cfg = FLConfig()
    rounds, n = 2, 290
    trial = make_trial_fn(cfg, rounds=rounds, eval_n_per_class=50,
                          strategies=("random", "labelwise"))
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled, kernels = _compile(
        jax.jit(trial), sds((rounds, cfg.num_clients, n), jnp.int32),
        sds((), jnp.int32), sds((), jnp.int32),
        sds((rounds, cfg.num_clients), jnp.float32))
    # one histogram per round body; one aggregation per CNN parameter leaf
    assert kernels == {"label_hist_kernel": 1, "weighted_agg_kernel": 8}
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes + ma.argument_size_in_bytes < V5E_HBM_BYTES
    # the engines' compile snapshot carries the same count
    record_memory_analysis("test:tpu_trial", compiled)
    snap = memory_snapshots()[-1]
    assert snap["label"] == "test:tpu_trial"
    assert snap["pallas_kernels"] == kernels
    # The TPU compiler writes matrix products as convolution instructions
    # too; none lowers a lax.conv under cnn.conv1, conv2 keeps its own.
    convs = snap["convolutions"]
    assert convs == convolutions_by_scope(compiled.as_text())
    assert convs.get("cnn.conv1", 0) == 0 and convs.get("cnn.conv2", 0) > 0
