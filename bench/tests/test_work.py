"""Operation and byte counts, and the table of peaks."""
from __future__ import annotations

import pytest

from bench import cells, work
from bench.references import cnn

PAPER_CELL = cells.load_cell("paper_cnn.case1b")
PAPER = PAPER_CELL.config


def test_cnn_flops_by_hand():
    f = cnn.forward_flops(PAPER)
    assert f == {"conv1": 2 * 28 * 28 * 32 * 9, "conv2": 2 * 14 * 14 * 64 * 9 * 32,
                 "fc1": 2 * 3136 * 128, "fc2": 2 * 128 * 10}
    fwd = sum(f.values())
    assert cnn.train_flops_per_sample(PAPER) == 3 * fwd - f["conv1"]
    assert cnn.train_flops_per_sample(PAPER) == 24_995_328


def test_param_count_matches_the_programs_cnn():
    import jax

    from bench.engines import sim
    from repro.fl.workloads import get_workload
    engine = sim.Engine(PAPER, PAPER_CELL.traffic, None)
    ds = engine.dataset()
    shapes = get_workload(engine.workload()).param_shapes(ds)
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == cnn.num_params(PAPER) == 421_642
    engine.check_model(ds)
    with pytest.raises(RuntimeError):
        sim.Engine(dict(PAPER, hidden=64), PAPER_CELL.traffic,
                   None).check_model(ds)


def test_roofline_share_and_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_share(0, 10, 2.0, peak) == (50.0, "memory")
    assert work.roofline_share(1000, 10, 20.0, peak) == (50.0, "compute")


def test_kernel_bytes():
    assert work.label_hist_bytes(100, 290, 10) == 100 * 290 * 5 + 100 * 10 * 4
    assert work.weighted_agg_bytes(30, 1000) == 30 * 1000 * 4 + 30 * 4 + 1000 * 4
    assert work.weighted_agg_flops(30, 1000) == 60_000


def test_peaks_are_keyed_by_device_kind_and_unknown_is_an_error():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["source"]
    with pytest.raises(KeyError):
        work.peaks("cpu")
