"""Device time by FL round stage: attribution on hand-written HLO, on a real
CPU trace of a small scoped program, the metric readers' edges, and a traced
tiny cell read as if its window had run on an accelerator."""
from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import pytest

from bench import cells, run, scopes, trace_reduce
from bench.tests import tiny
from bench.tests.test_harness import BIG_SEED, CPU_PEAK

STAGE_METRICS = [f"{s}_device_ms" for s in
                 ("materialize", "select", "train", "aggregate", "eval")]
NEW_METRICS = STAGE_METRICS + ["unscoped_device_share"]

# One computation per rule; names as XLA prints them.
HLO = """\
HloModule jit_grid, entry_computation_layout={()->f32[4]{0}}

%fused_train (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %a = f32[4]{0} tanh(%param_0), metadata={op_name="jit(grid)/vmap(fl.train)/vmap(jvp(cnn.conv1))/tanh"}
  %b = f32[4]{0} multiply(%a, %a), metadata={op_name="jit(grid)/fl.train/transpose(jvp(cnn.conv1))/mul"}
  ROOT %c = f32[4]{0} add(%a, %b), metadata={op_name="jit(grid)/fl.eval/add"}
}

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %gte.0 = s32[] get-tuple-element(%p), index=0
  %gte.1 = f32[4]{0} get-tuple-element(%p), index=1
  %copy.1 = f32[4]{0:T(128)} copy(%gte.1)
  %fusion.1 = f32[4]{0} fusion(%copy.1), kind=kLoop, calls=%fused_train
  %copy.2 = f32[4]{0} copy(%fusion.1)
  %dot.3 = f32[4]{0} multiply(%copy.2, %copy.2), metadata={op_name="jit(grid)/while/body/fl.train/dot_general"}
  %mean.4 = f32[4]{0} add(%dot.3, %dot.3), metadata={op_name="jit(grid)/fl.aggregate/add;jit(grid)/fl.aggregate/mul;jit(grid)/fl.select/mul"}
  %transpose.5 = f32[4]{0} transpose(%mean.4), dimensions={0}
  %sel.6 = f32[4]{0} negate(%gte.1), metadata={op_name="jit(grid)/fl.select/neg"}
  %copy.7 = f32[4]{0} copy(%sel.6)
  %add.8 = f32[4]{0} add(%copy.7, %transpose.5), metadata={op_name="jit(grid)/fl.aggregate/add"}
  %init.9 = f32[4]{0} negate(%add.8), metadata={op_name="jit(grid)/cnn_init/neg"}
  ROOT %tuple.10 = (s32[], f32[4]{0}) tuple(%gte.0, %init.9)
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %while.1 = (s32[], f32[4]{0}) while(%x), condition=%cond, body=%body
  ROOT %gte.9 = f32[4]{0} get-tuple-element(%while.1), index=1
}
"""


def test_the_four_rules_on_hand_written_hlo():
    instrs = scopes.parse(HLO)
    assert instrs["fusion.1"].calls == "fused_train"
    assert instrs["copy.1"].operands == ["gte.1"]
    key = scopes.attribute(instrs)
    # Rule 1: the op's own op_name, through vmap/jvp/transpose wrappers …
    assert key["a"] == "train" and key["b"] == "train"
    assert key["dot.3"] == "train" and key["c"] == "eval"
    # … and the most common of ;-joined op_names.
    assert key["mean.4"] == "aggregate"
    # Rule 2: a fusion takes the most common stage of what it calls.
    assert key["fusion.1"] == "train"
    # Rule 3: relayouts take the stage their neighbours share, looking past
    # unscoped tuple plumbing; where they disagree, none.
    assert key["copy.1"] == "train"              # gte.1 → copy → fusion
    assert key["copy.2"] == "train"              # fusion → copy → dot
    assert key["transpose.5"] == "aggregate"
    assert key["copy.7"] is None                  # select → copy → aggregate
    # Rule 4: an op outside every stage.
    assert key["init.9"] is None


def test_model_scope_and_direction():
    cfg = cells.load_cell("paper_cnn.case1b").config
    key = scopes.model_scope_key(cfg["model_scopes"], cfg["model_scopes_whole"])
    assert key("jit(g)/fl.train/vmap(jvp(cnn.conv1))/conv") == "cnn.conv1:fwd"
    assert key("jit(g)/vmap(transpose(jvp(cnn.pool2)))/select") == "cnn.pool2:bwd"
    assert key("jit(g)/fl.train/opt.update/mul") == "opt.update"
    assert key("jit(g)/fl.train/mul") is None
    assert key("jit(g)/fl.train/cnn.conv10/mul") is None
    # The scopes are the configuration's: another model names its own.
    other = scopes.model_scope_key(["lm.attn", "lm.moe"], ["lm.moe"])
    assert other("jit(g)/vmap(transpose(jvp(lm.attn)))/dot") == "lm.attn:bwd"
    assert other("jit(g)/lm.moe/dot") == "lm.moe"
    assert other("jit(g)/vmap(jvp(cnn.conv1))/conv") is None
    assert scopes.model_scope_key([])("jit(g)/cnn.conv1/conv") is None
    assert scopes.stage_of("jit(g)/fl.trainer/x") is None
    assert scopes.stage_of("jit(g)/fl.select/inner/fl.eval/x") == "eval"


def test_seconds_by_stage_and_unscoped_sum_to_the_non_container_total():
    key = scopes.attribute(scopes.parse(HLO))
    op_s = {"while.1": 9.0, "fusion.1": 2.0, "copy.2": 1.0, "mean.4": 0.5,
            "copy.7": 0.25, "init.9": 0.125, "not_in_text.3": 0.0625}
    by, unscoped, total = scopes.seconds_by(op_s, key)
    assert by == {"train": 3.0, "aggregate": 0.5}
    assert unscoped == 0.4375
    assert total == sum(v for n, v in op_s.items() if n != "while.1")
    assert sum(by.values()) + unscoped == pytest.approx(total)


def _scoped_program():
    def step(w, xs):
        with jax.named_scope("fl.train"):
            g = jax.vmap(lambda x: jax.grad(
                lambda w: jnp.tanh(x @ w).sum())(w))(xs)
        with jax.named_scope("fl.aggregate"):
            return w - 0.1 * g.mean(0)
    return jax.jit(step), jnp.ones((64, 64)), jnp.ones((8, 16, 64))


@pytest.fixture
def accelerator(monkeypatch):
    """Read a CPU trace as the readers read an accelerator's."""
    monkeypatch.setattr(scopes, "_on_accelerator", lambda: True)


def test_attribution_on_a_trace_recorded_on_the_cpu(tmp_path, monkeypatch):
    f, w, xs = _scoped_program()
    compiled = f.lower(w, xs).compile()
    compiled(w, xs).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench:window"):
            for _ in range(3):
                compiled(w, xs).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    summary = trace_reduce.load(str(tmp_path))
    instrs = scopes.parse(compiled.as_text())
    # The host events' hlo_op names are the program's instruction names.
    assert summary.op_s and set(summary.op_s) <= set(instrs)
    by, unscoped, total = scopes.seconds_by(summary.op_s,
                                            scopes.attribute(instrs))
    assert set(by) == {"train", "aggregate"} and unscoped == 0.0
    assert sum(by.values()) == pytest.approx(total)

    def ctx():
        return {"trace": summary, "hlo_text": compiled.as_text(),
                "trial_rounds": 3, "config": {}, "traffic": {}}
    # A CPU window's ops run on host threads: no device time to report.
    assert scopes.read(ctx()) is None
    assert cells.module("metrics", "train_device_ms").read(ctx()) is None
    monkeypatch.setattr(scopes, "_on_accelerator", lambda: True)
    ctx = ctx()
    r = scopes.read(ctx)
    assert r["stage_s"] == by
    train = cells.module("metrics", "train_device_ms").read(ctx)
    assert train == pytest.approx(by["train"] / 3 * 1e3)
    assert cells.module("metrics", "eval_device_ms").read(ctx) == 0.0
    assert cells.module("metrics", "unscoped_device_share").read(ctx) == 0.0


def _trace(op_s):
    return types.SimpleNamespace(op_s=op_s)


@pytest.mark.parametrize("name", STAGE_METRICS + ["unscoped_device_share"])
def test_readers_find_nothing_without_a_trace_or_program_text(
        name, accelerator):
    read = cells.module("metrics", name).read
    assert read({"trace": None, "hlo_text": HLO, "trial_rounds": 1}) is None
    assert read({"trace": _trace({"fusion.1": 1.0}), "trial_rounds": 1}) is None
    assert read({"trace": _trace({"fusion.1": 1.0}), "hlo_text": HLO}) is None


@pytest.mark.parametrize("name", STAGE_METRICS + ["unscoped_device_share"])
def test_readers_find_nothing_in_a_program_without_stages(name, accelerator):
    text = HLO.replace("fl.", "xx.")             # the parent's program
    ctx = {"trace": _trace({"fusion.1": 1.0, "dot.3": 1.0}),
           "hlo_text": text, "trial_rounds": 1}
    assert cells.module("metrics", name).read(ctx) is None


def test_no_program_text_is_no_reading_and_builds_no_engine(monkeypatch,
                                                            accelerator):
    """Without the harness's program text there is no reading, and no
    reader builds an engine to lower the program again."""
    def refuse(kind, name):
        raise AssertionError(f"a reader built {kind}.{name}")
    monkeypatch.setattr(cells, "module", refuse)
    ctx = {"trace": _trace({"fusion.1": 1.0}), "trial_rounds": 1,
           "config": cells.load_cell("paper_cnn.case1b").config,
           "traffic": cells.load_cell("paper_cnn.case1b").traffic}
    assert scopes.stage_ms(ctx, "train") is None


@pytest.fixture(scope="module")
def traced_tiny(tmp_path_factory):
    """One traced tiny run, read as on an accelerator, with the ``ctx`` the
    harness handed the readers."""
    seen = []
    read = scopes.read

    def spy(ctx):
        seen.append(ctx)
        return read(ctx)
    cell = tiny.cell(strategies=("labelwise",))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(scopes, "_on_accelerator", lambda: True)
        mp.setattr(scopes, "read", spy)
        result, _ = run.run_cell(
            cell, BIG_SEED, 0.5, True, jax.devices(), CPU_PEAK,
            trace_dir=str(tmp_path_factory.mktemp("trace")))
    return cell, result, seen[0]


def test_trial_rounds_come_from_the_aggregation_work(traced_tiny):
    """The trial-rounds the stage readers divide by are the harness's own
    count of the window's work, whatever the model: no model's sizes
    enter it."""
    cell, result, ctx = traced_tiny
    rounds = result["attempted"] * cell.traffic["rounds_per_call"]
    assert ctx["trial_rounds"] == rounds
    assert ctx["_scopes"]["trial_rounds"] == rounds
    # The program text is the executable's that ran the window.
    assert "fl.train" in ctx["hlo_text"]
    assert set(ctx["_scopes"]["model_s"]) >= {"cnn.conv1:fwd", "cnn.conv1:bwd",
                                              "opt.update"}


def test_the_log_gives_set_up_trace_seconds(monkeypatch, capsys, accelerator):
    import repro.obs
    spans = [{"name": "trace:trial", "ph": "X", "dur": 9e6},
             {"name": "trace:fl.train", "ph": "X", "dur": 1e6},
             {"name": "trace:trial", "ph": "X", "dur": 2.5e6},
             {"name": "compile", "ph": "X", "dur": 4e6}]
    monkeypatch.setattr(repro.obs, "events", lambda: list(spans))
    ctx = {"trace": _trace({"fusion.1": 1.0}), "trial_rounds": 1,
           "hlo_text": HLO}
    assert scopes.read(ctx) is not None
    log = capsys.readouterr().err
    assert "set-up trace seconds {'trace:trial': 2.5, 'trace:fl.train': 1.0}" in log


def test_the_new_metrics_are_declared_for_both_cells():
    spec = {m["name"]: m for m in cells.load_benchmark()["per_layer"]}
    for name in NEW_METRICS:
        assert spec[name]["workloads"] == ["paper_cnn.case1b",
                                           "paper_cnn.case1b_fedsgd"]
    for name in STAGE_METRICS:
        assert spec[name]["source"] == "device_trace"
        assert spec[name]["layer"].startswith("FL round: ")


def test_per_layer_entries_name_their_source_and_layer():
    sources = {"device_trace", "program_span", "program_counter", "host_clock"}
    for m in cells.load_benchmark()["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in sources and 1 <= len(m["layer"]) <= 200


def test_a_traced_tiny_cell_reports_the_stages(traced_tiny):
    _, result, _ = traced_tiny
    assert result["correct"] is True
    m = result["metrics"]
    assert set(NEW_METRICS) <= set(m)
    # The stages hold the window's device time, local training the most.
    assert max(STAGE_METRICS, key=lambda k: m[k]["value"]) == "train_device_ms"
    assert 0 <= m["unscoped_device_share"]["value"] < 50
