"""A configuration of another client model goes through the shared files
untouched: its work is counted by its own reference module, its stages by
the harness's count and its own model scopes, and the sim engine drives
another workload through a subclass that overrides only the model set-up."""
from __future__ import annotations

import contextlib
import sys
import types

import numpy as np
import pytest

from bench import cells, faults, run, scopes, traffic_gen, work
from bench.engines import sim
from bench.references import cnn
from bench.tests import tiny
from bench.tests.test_scopes import HLO

SEED = 2 ** 31 + 77
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CNN_KEYS = {"image_size", "channels", "conv1", "conv2", "hidden"}

# What the stub reference states: its parameters, and each strategy's
# training FLOPs for one trial.
STUB_PARAMS = 1_000_003
STUB_FLOPS = {"random": 7_000_000_001, "labelwise": 50_000_000_021,
              "kl": 300_000_000_007}
STUB_CONFIG = {"name": "stub_lm", "reference": "stub_lm", "workload": "lm",
               "num_clients": 6, "clients_per_round": 2, "num_classes": 4,
               "samples_per_client": 8, "majority_per_client": 5,
               "model_scopes": ["lm.attn", "opt.update"],
               "model_scopes_whole": ["opt.update"]}


@pytest.fixture
def stub(monkeypatch):
    """A cell of a model with none of the CNN's keys, whose reference is a
    stub module that states its counts: (cell, engine, calls, seeds)."""
    ref = types.ModuleType("bench.references.stub_lm")
    ref.num_params = lambda cfg: STUB_PARAMS
    ref.trial_train_flops = lambda cfg, tr, plan, strategy, seed: STUB_FLOPS[strategy]
    monkeypatch.setitem(sys.modules, "bench.references.stub_lm", ref)
    assert not CNN_KEYS & set(STUB_CONFIG)
    tr = dict(cells.load_cell("paper_cnn.case1b").traffic, seeds_per_call=2)
    cell = cells.Cell(name="stub_lm.case1b", chips=1, config=STUB_CONFIG,
                      traffic=tr, limits={}, end_to_end=[], per_layer=[])
    plans = {i: traffic_gen.call_plans(STUB_CONFIG, tr, SEED, i) for i in range(3)}
    engine = types.SimpleNamespace(plan=lambda i, r: plans[i][r])
    calls = [types.SimpleNamespace(index=i) for i in range(3)]
    seeds = {i: traffic_gen.call_seeds(tr, SEED, i) for i in range(3)}
    return cell, engine, calls, seeds


def test_window_work_counts_through_the_reference_alone(stub):
    cell, engine, calls, seeds = stub
    tr = cell.traffic
    w = run.window_work(cell, engine, calls, seeds)
    trials = len(calls) * tr["seeds_per_call"]
    aggs = trials * len(tr["strategies"]) * tr["rounds_per_call"]
    assert w["train_flops"] == trials * sum(STUB_FLOPS.values())
    assert w["weighted_agg_bytes"] == aggs * work.weighted_agg_bytes(2, STUB_PARAMS)
    assert w["weighted_agg_flops"] == aggs * 2 * 2 * STUB_PARAMS
    assert w["label_hist_bytes"] == trials * tr["rounds_per_call"] * (
        work.label_hist_bytes(6, 8, 4))


def test_round_mfu_is_the_references_flops_over_the_window(stub):
    w = run.window_work(*stub)
    ctx = {"work": w, "window_s": 10.25, "chips": 1, "peak": PEAK}
    mfu = cells.module("metrics", "round_mfu").read(ctx)
    assert mfu == 100.0 * w["train_flops"] / 10.25 / 197e12
    assert cells.module("metrics", "round_mfu").read(
        dict(ctx, work=dict(w, train_flops=0))) is None


def test_stage_times_divide_by_the_harness_count_and_read_the_models_scopes(
        stub, monkeypatch):
    monkeypatch.setattr(scopes, "_on_accelerator", lambda: True)
    cell, _, calls, _ = stub
    tr = cell.traffic
    rounds = (len(calls) * len(tr["strategies"]) * tr["seeds_per_call"]
              * tr["rounds_per_call"])
    ctx = {"config": cell.config, "trial_rounds": rounds,
           "hlo_text": HLO.replace("cnn.conv1", "lm.attn"),
           "trace": types.SimpleNamespace(op_s={"a": 1.0, "b": 2.0, "dot.3": 1.0,
                                                "mean.4": 0.5})}
    assert scopes.stage_ms(ctx, "train") == 4.0 / rounds * 1e3
    assert scopes.stage_ms(ctx, "aggregate") == 0.5 / rounds * 1e3
    r = scopes.read(ctx)
    assert r["trial_rounds"] == rounds
    assert r["model_s"] == {"lm.attn:fwd": 1.0, "lm.attn:bwd": 2.0}


@pytest.mark.parametrize("traffic", ["case1b", "case1b_fedsgd"])
def test_the_paper_cells_work_is_the_formula_it_had(traffic):
    """At the tiny size, to the bit: the CNN's FLOPs a trained sample ×
    the samples the selected clients trained (× local epochs under fedavg),
    and the weighted means' bytes over the CNN's 421,642 parameters."""
    cell = tiny.cell(f"paper_cnn.{traffic}", seeds_per_call=2)
    cfg, tr = cell.config, cell.traffic
    engine = sim.Engine(cfg, tr, lambda i: traffic_gen.call_plans(cfg, tr, SEED, i))
    calls = [types.SimpleNamespace(index=i) for i in range(2)]
    seeds = {i: traffic_gen.call_seeds(tr, SEED, i) for i in range(2)}
    w = run.window_work(cell, engine, calls, seeds)
    per_sample = 1 if tr["aggregation"] == "fedsgd" else cfg["local_epochs"]
    trained = sum(per_sample * cnn.selected_samples(
        cfg, tr, engine.plan(c.index, r), s, int(seeds[c.index][r]))
        for c in calls for s in tr["strategies"] for r in range(2))
    assert trained > 0
    assert w["train_flops"] == 24_995_328 * trained
    aggs = 2 * tr["rounds_per_call"] * len(tr["strategies"]) * 2
    k = cfg["clients_per_round"]
    assert w["weighted_agg_bytes"] == aggs * (k * 421_642 * 4 + k * 4 + 421_642 * 4)
    assert w["weighted_agg_flops"] == aggs * 2 * k * 421_642
    ctx = {"work": w, "window_s": 10.25, "chips": 1, "peak": PEAK}
    flops = 24_995_328 * trained
    assert cells.module("metrics", "round_mfu").read(ctx) == (
        100.0 * flops / 10.25 / (197e12 * 1))


class LMEngine(sim.Engine):
    """The sim engine on the registered micro language model: only the
    model set-up is overridden."""

    def workload(self):
        from repro.fl.workloads import get_workload
        return get_workload("lm")

    def dataset(self):
        return self.workload().make_dataset()

    def check_model(self, ds) -> None:
        shapes = self.workload().param_shapes(ds)
        got = tuple(shapes["embed"]["table"].shape)
        want = (self.cfg["vocab_size"], self.cfg["d_model"])
        if got != want or ds.num_domains != self.cfg["num_classes"]:
            raise RuntimeError(f"the program's LM is {got}, {ds.num_domains} "
                               f"domains; the configuration states {want}")


LM_CONFIG = {"name": "micro_lm", "engine": "micro_lm", "workload": "lm",
             "vocab_size": 256, "d_model": 64, "num_classes": 10,
             "num_clients": 6, "clients_per_round": 2, "samples_per_client": 8,
             "majority_per_client": 5, "local_epochs": 1, "batch_size": 4,
             "lr": 1e-3, "optimizer": "adam", "server_lr": 1.0,
             "eval_n_per_class": 1}


@pytest.fixture(scope="module")
def lm_engine():
    """The LM engine as a file of its own would give it, found by the
    configuration's ``engine``: ``run_engine(fault=None)`` builds it, runs
    call 0 and returns the engine and its output (the sound run once)."""
    mod = types.ModuleType("bench.engines.micro_lm")
    mod.Engine = LMEngine
    cfg = LM_CONFIG
    tr = dict(tiny.cell(strategies=("labelwise", "random")).traffic,
              seeds_per_call=2)
    runs = {}

    def run_engine(fault=None):
        if fault not in runs:
            with faults.planted(fault) if fault else contextlib.nullcontext():
                engine = cells.engine(cfg, tr)(
                    cfg, tr, lambda i: traffic_gen.call_plans(cfg, tr, SEED, i))
                engine.setup(run.WARM_CALL)
                out = engine.call(0, traffic_gen.call_seeds(tr, SEED, 0))
                engine.free()
            runs[fault] = engine, out
        return runs[fault]
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(sys.modules, "bench.engines.micro_lm", mod)
        yield run_engine


def test_the_configuration_names_the_engine_that_sets_its_model_up(lm_engine):
    """A model and a traffic mix stay independent files: the paper cells'
    mix names the sim engine, a configuration that names its own wins."""
    tr = cells.load_cell("paper_cnn.case1b").traffic
    assert cells.engine(LM_CONFIG, tr) is LMEngine
    assert cells.engine(cells.load_cell("paper_cnn.case1b").config, tr) is sim.Engine


def test_a_subclass_of_the_sim_engine_drives_a_language_model(lm_engine):
    cfg = LM_CONFIG
    engine, out = lm_engine()
    assert isinstance(engine, LMEngine)
    assert out["loss"].shape == (2, 2, engine.traffic["rounds_per_call"])
    for k in ("loss", "accuracy"):
        assert np.isfinite(out[k]).all()
    np.testing.assert_array_equal(out["num_selected"], out["mask_sum"])
    assert (out["num_selected"] == cfg["clients_per_round"]).all()
    assert engine.memory and engine.spans["compile_s"] > 0
    with pytest.raises(RuntimeError):
        LMEngine(dict(cfg, d_model=128), engine.traffic, None).check_model(
            engine.dataset())


@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_clients",
                                   "half_of_each_batch", "answer_altered"])
def test_the_faults_reach_another_models_program(lm_engine, fault):
    """Each fault in the program is planted in whatever workload the engine
    builds, not only in the CNN: the language model's answers move."""
    _, sound = lm_engine()
    _, bad = lm_engine(fault)
    np.testing.assert_array_equal(bad["num_selected"], sound["num_selected"])
    assert np.isfinite(bad["loss"]).all()
    assert not np.allclose(bad["loss"], sound["loss"], rtol=1e-4, atol=0)
    if fault == "answer_altered":
        np.testing.assert_allclose(bad["loss"], 1.5 * sound["loss"], rtol=1e-6)
