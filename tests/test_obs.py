"""Observability subsystem tests: the in-graph metrics registry, the
versioned telemetry envelope, trace spans, and the report renderer.

The acceptance pins:

- telemetry OFF is BIT-identical to the pre-telemetry engines — the
  metric-dependent scan-carry/ys leaves exist only when metrics resolve, so
  (acc, loss, nsel) match exactly, not just within tolerance;
- with the builtins enabled the envelope carries selection-entropy /
  cluster-occupancy / staleness / ‖Δθ‖ series and JSON round-trips exactly;
- the report renders a health flag on a seeded cluster-starvation run.
"""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from repro.configs.paper_cnn import FLConfig
from repro.core import case_label_plan
from repro.fl import ExperimentSpec, ScenarioSpec, run
from repro.obs import (BASE_AXES, PHASES, TELEMETRY_SCHEMA_VERSION,
                       build_envelope, convolutions_by_scope, get_metric,
                       health_flags, memory_snapshots, metric_id, phase,
                       record_memory_analysis, register_metric,
                       registered_metrics, render_report,
                       resolve_metrics, resolve_telemetry_request,
                       series_arrays, span, span_summary)
from repro.obs.registry import _METRIC_IDS, _METRICS
from repro.obs.trace import events as trace_events
from repro.obs.trace import write_trace

MICRO = FLConfig(num_clients=6, clients_per_round=2, global_epochs=2,
                 local_epochs=1, batch_size=8, lr=1e-3)

BUILTINS = ("selection_entropy", "selected_label_hist", "update_norm",
            "cluster_occupancy", "centroid_drift", "staleness_hist",
            "delta_outlier")


def micro_spec(**kw):
    # "iid" gives every client a mixed-label shard; single-label cases
    # (case1a at 6 clients) have sigma^2(L_i) = 0 for everyone, so labelwise
    # selects nobody and all series degenerate to zeros.
    plan = case_label_plan("iid", seed=3, num_rounds=2, num_clients=6,
                           samples_per_client=8, majority=5)
    base = dict(scenarios=(ScenarioSpec.from_plan("s0", plan),),
                strategies=("labelwise",), seeds=(0,), fl=MICRO)
    base.update(kw)
    return ExperimentSpec(**base)


_RUNS = {}


def cached_run(**kw):
    """One compile per distinct micro spec across the module's tests."""
    key = json.dumps(micro_spec(**kw).to_dict(), sort_keys=True)
    if key not in _RUNS:
        _RUNS[key] = run(micro_spec(**kw))
    return _RUNS[key]


# ---------------------------------------------------------------------------
# Registry contract (mirrors the strategy-registry tests)
# ---------------------------------------------------------------------------

class TestMetricRegistry:
    def test_builtin_ids_are_stable(self):
        assert registered_metrics()[:len(BUILTINS)] == BUILTINS
        for i, name in enumerate(BUILTINS):
            assert metric_id(name) == i

    def test_overwrite_keeps_id(self):
        m = get_metric("update_norm")
        mid = metric_id("update_norm")
        register_metric("update_norm", m.fn, requires=m.requires,
                        overwrite=True)
        assert metric_id("update_norm") == mid
        assert get_metric("update_norm").fn is m.fn

    def test_duplicate_without_overwrite_raises(self):
        with pytest.raises(ValueError, match="already registered"):
            register_metric("update_norm", lambda s: 0.0)

    def test_bad_registrations_raise(self):
        with pytest.raises(ValueError):
            register_metric("", lambda s: 0.0)
        with pytest.raises(TypeError):
            register_metric("_obs_notcallable", "nope")

    def test_unknown_metric_raises(self):
        with pytest.raises(KeyError, match="unknown metric"):
            get_metric("_obs_missing")
        with pytest.raises(KeyError, match="unknown metric"):
            metric_id("_obs_missing")

    def test_resolve_auto_expands_and_filters(self):
        sim_keys = ("hists", "mask", "num_classes", "params_old",
                    "params_new")
        names = [m.name for m in resolve_metrics(("auto",), sim_keys)]
        assert names == ["selection_entropy", "selected_label_hist",
                         "update_norm"]
        # async keys admit the staleness metric; clustered keys the k-means
        # pair — applicability is an engine fact, silently filtered
        assert [m.name for m in resolve_metrics(
            ("staleness_hist",), sim_keys)] == []
        with pytest.raises(KeyError, match="unknown metric"):
            resolve_metrics(("_obs_missing",), sim_keys)

    def test_env_request_resolution(self, monkeypatch):
        monkeypatch.delenv("REPRO_TELEMETRY", raising=False)
        assert resolve_telemetry_request(()) == ()
        assert resolve_telemetry_request(("update_norm",)) == ("update_norm",)
        monkeypatch.setenv("REPRO_TELEMETRY", "off")
        assert resolve_telemetry_request(()) == ()
        monkeypatch.setenv("REPRO_TELEMETRY", "1")
        assert resolve_telemetry_request(()) == ("auto",)
        monkeypatch.setenv("REPRO_TELEMETRY", "update_norm, selection_entropy")
        assert resolve_telemetry_request(()) == ("update_norm",
                                                 "selection_entropy")
        # the spec's own tuple wins over the env var
        assert resolve_telemetry_request(("auto",)) == ("auto",)

    def test_spec_validate_rejects_unknown_metric(self):
        with pytest.raises(KeyError, match="unknown metric"):
            micro_spec(telemetry=("_obs_missing",)).validate()

    def test_spec_dict_round_trip_carries_telemetry(self):
        spec = micro_spec(telemetry=("auto",))
        again = ExperimentSpec.from_dict(spec.to_dict())
        assert again.telemetry == ("auto",)


# ---------------------------------------------------------------------------
# Envelope
# ---------------------------------------------------------------------------

class TestEnvelope:
    def test_axes_and_version(self):
        env = build_envelope(
            "sim", series={"update_norm": np.ones((1, 1, 1, 3), np.float32),
                           "cluster_occupancy": np.ones((1, 1, 1, 3, 2),
                                                        np.float32)})
        assert env["version"] == TELEMETRY_SCHEMA_VERSION
        assert env["axes"] == list(BASE_AXES)
        assert env["series"]["update_norm"]["axes"] == list(BASE_AXES)
        assert env["series"]["cluster_occupancy"]["axes"] == \
            list(BASE_AXES) + ["cluster"]

    def test_exact_json_round_trip(self):
        rng = np.random.default_rng(0)
        arr = rng.standard_normal((2, 1, 1, 4)).astype(np.float32)
        env = build_envelope("sim", series={"update_norm": arr})
        again = json.loads(json.dumps(env))
        got = series_arrays(again)["update_norm"]
        # float32 → float64 is exact, and JSON float64 repr round-trips
        assert np.array_equal(got, arr.astype(np.float64))


# ---------------------------------------------------------------------------
# Engine threading (micro runs; one compile each, cached per module)
# ---------------------------------------------------------------------------

class TestEngineTelemetry:
    def test_off_is_bit_identical_sim(self):
        off = cached_run()
        on = cached_run(telemetry=("auto",))
        assert off.telemetry() is None
        assert np.array_equal(off.accuracy, on.accuracy)
        assert np.array_equal(off.loss, on.loss)
        assert np.array_equal(off.num_selected, on.num_selected)

    def test_sim_auto_series(self):
        tel = cached_run(telemetry=("auto",)).telemetry()
        assert tel["selection_entropy"].shape == (1, 1, 1, 2)
        assert tel["selected_label_hist"].shape == (1, 1, 1, 2, 10)
        assert tel["update_norm"].shape == (1, 1, 1, 2)
        assert (tel["update_norm"] > 0).all()
        # the selected pool is clients_per_round clients x 8 samples
        assert np.allclose(tel["selected_label_hist"].sum(-1), 16.0)

    def test_sim_clustered_series(self):
        res = cached_run(aggregation="clustered_fedavg", telemetry=("auto",))
        tel = res.telemetry()
        assert tel["cluster_occupancy"].shape == (1, 1, 1, 2, 2)
        assert tel["centroid_drift"].shape == (1, 1, 1, 2)
        # every valid client lands in exactly one cluster each round
        assert np.allclose(tel["cluster_occupancy"].sum(-1), 6.0)
        # round 0 drift measures from the zero state — strictly positive
        assert (tel["centroid_drift"][..., 0] > 0).all()
        # the old clustered alias is still present next to the envelope
        assert res.meta["clustered"] is not None
        assert res.meta["telemetry"]["engine_facts"]["clustered"] == \
            res.meta["clustered"]

    def test_host_matches_sim_series_and_accounts_compile(self):
        sim = cached_run(telemetry=("auto",))
        host = cached_run(engine="host", telemetry=("auto",))
        assert host.compile_s > 0
        assert np.array_equal(host.accuracy, sim.accuracy) or np.allclose(
            host.accuracy, sim.accuracy, atol=1e-6)
        for name in ("selection_entropy", "selected_label_hist"):
            # selection state is integer-exact on both engines
            assert np.allclose(host.telemetry()[name], sim.telemetry()[name],
                               atol=1e-5), name

    def test_async_staleness_series(self):
        res = cached_run(engine="async", telemetry=("auto",),
                         engine_options={"num_blocks": 2, "buffer_k": 2,
                                         "tau_max": 2})
        tel = res.telemetry()
        assert tel["staleness_hist"].shape == (1, 1, 1, 2, 3)
        # K buffered arrivals per server step, each at one staleness level
        assert np.allclose(tel["staleness_hist"].sum(-1), 2.0)

    def test_result_json_round_trip_exact(self):
        res = cached_run(telemetry=("auto",))
        again = type(res).from_json(res.to_json())
        t0, t1 = res.telemetry(), again.telemetry()
        assert sorted(t0) == sorted(t1)
        for name in t0:
            assert np.array_equal(t0[name], t1[name]), name
        assert again.meta["telemetry"]["version"] == TELEMETRY_SCHEMA_VERSION


# ---------------------------------------------------------------------------
# Trace spans
# ---------------------------------------------------------------------------

class TestTrace:
    def test_span_records_and_summarizes(self):
        before = len(trace_events())
        with span("unit_test_span", detail="x") as sp:
            pass
        assert sp.duration_s >= 0
        assert len(trace_events()) == before + 1
        summ = span_summary()
        assert summ["unit_test_span"]["count"] >= 1

    def test_run_emits_stage_spans(self):
        cached_run(telemetry=("auto",))
        summ = span_summary()
        for name in ("validate", "lower_scenarios", "engine_execute:sim"):
            assert name in summ, name

    def test_write_trace_emits_chrome_json(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_TRACE_DIR", str(tmp_path))
        with span("trace_file_span"):
            pass
        path = write_trace()
        assert path and os.path.exists(path)
        with open(path) as f:
            doc = json.load(f)
        assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
        ev = next(e for e in doc["traceEvents"]
                  if e["name"] == "trace_file_span")
        assert ev["ph"] == "X" and ev["dur"] >= 0

    def test_write_trace_noop_without_env(self, monkeypatch):
        monkeypatch.delenv("REPRO_TRACE_DIR", raising=False)
        assert write_trace() is None

    def test_engine_error_under_trace_dir_surfaces_as_itself(
            self, tmp_path, monkeypatch):
        # sim stacks scenarios into one grid, so plans of different n make
        # the engine itself raise — inside the profiler's trace
        monkeypatch.setenv("REPRO_TRACE_DIR", str(tmp_path))
        spec = ExperimentSpec(
            scenarios=(ScenarioSpec.from_case("iid", samples_per_client=8),
                       ScenarioSpec.from_case("case1b", samples_per_client=9)),
            strategies=("random",), seeds=(0,), fl=MICRO, engine="sim")
        with pytest.raises(ValueError, match="must share"):
            run(spec)
        # the profiler was stopped on the way out: a new trace can start
        with pytest.raises(ValueError, match="must share"):
            run(spec)

    def test_profiler_start_failure_raises(self, tmp_path, monkeypatch):
        import jax

        from repro.obs.trace import profiler

        def refuse(*a, **k):
            raise RuntimeError("profiler unavailable")

        monkeypatch.setenv("REPRO_TRACE_DIR", str(tmp_path))
        monkeypatch.setattr(jax.profiler, "start_trace", refuse)
        with pytest.raises(RuntimeError, match="could not start") as info:
            with profiler("sim"):
                pass
        assert "profiler unavailable" in str(info.value.__cause__)


    def test_span_is_a_profiler_event_on_the_same_clock(self, tmp_path):
        import glob
        import warnings

        import jax
        from jax.profiler import ProfileData

        jax.profiler.start_trace(str(tmp_path))
        try:
            with span("clock_probe"):
                pass
        finally:
            jax.profiler.stop_trace()
        path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                                recursive=True))[-1]
        start_ns, found = None, []
        for plane in ProfileData.from_file(path).planes:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", DeprecationWarning)
                stats = dict(plane.stats)
            start_ns = stats.get("profile_start_time", start_ns)
            found += [ev.start_ns for line in plane.lines
                      for ev in line.events if ev.name == "repro:clock_probe"]
        assert len(found) == 1 and start_ns is not None
        chrome = [e for e in trace_events() if e["name"] == "clock_probe"][-1]
        # The profile's event times are offsets from the session's start.
        assert abs(chrome["ts"] - (start_ns + found[0]) / 1e3) < 1000.0


# ---------------------------------------------------------------------------
# FL round stages as named scopes
# ---------------------------------------------------------------------------

def _micro_trial_hlo(aggregation=None):
    """(optimized HLO text, jaxpr text) of one micro sim trial."""
    import jax
    import jax.numpy as jnp

    from repro.fl.sim import make_trial_fn
    plan = case_label_plan("iid", seed=3, num_rounds=2, num_clients=6,
                           samples_per_client=8, majority=5)
    trial = make_trial_fn(MICRO, aggregation=aggregation, rounds=2,
                          eval_n_per_class=2, strategies=("labelwise",))
    args = (jnp.asarray(plan, jnp.int32), jnp.int32(0), jnp.int32(0),
            jnp.ones(plan.shape[:2], jnp.float32))
    text = jax.jit(trial).lower(*args).compile().as_text()
    return text, str(jax.make_jaxpr(trial)(*args))


_METADATA = re.compile(r',? ?metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')


def _without_metadata(hlo_text):
    """The HLO's computations with every ``metadata={…}`` removed (the
    stack-frame tables before the first computation go too)."""
    first = min(i for i in (hlo_text.find("\n%"), hlo_text.find("\nENTRY"))
                if i >= 0)
    return _METADATA.sub("", hlo_text[first:])


class TestPhases:
    def test_every_stage_is_named_in_the_compiled_program(self):
        text, _ = _micro_trial_hlo()
        names = set(re.findall(r'op_name="([^"]*)"', text))
        for stage in PHASES:
            if stage == "cluster":
                continue
            assert any(f"fl.{stage}" in n for n in names), stage
        # Local training's backward pass keeps its stage and model scope.
        assert any("fl.train" in n and "transpose(jvp(cnn.conv1))" in n
                   for n in names)
        assert not any("fl.cluster" in n for n in names)
        text, _ = _micro_trial_hlo("clustered_fedavg")
        assert "fl.cluster" in text

    def test_scopes_change_metadata_only(self, monkeypatch):
        import contextlib

        from repro.fl import round as fl_round
        from repro.fl import sim
        text, jaxpr = _micro_trial_hlo()
        for mod in (sim, fl_round):
            monkeypatch.setattr(mod, "phase",
                                lambda name: contextlib.nullcontext())
        bare_text, bare_jaxpr = _micro_trial_hlo()
        assert "fl.train" not in bare_text
        assert bare_jaxpr == jaxpr
        assert _without_metadata(bare_text) == _without_metadata(text)

    def test_trial_tracing_is_one_span_per_lowering(self):
        import jax
        import jax.numpy as jnp

        from repro.fl.sim import make_trial_fn

        def count(name):
            return sum(e["name"] == name for e in trace_events())

        plan = case_label_plan("iid", seed=3, num_rounds=2, num_clients=6,
                               samples_per_client=8, majority=5)
        args = (jnp.asarray(plan, jnp.int32), jnp.int32(0), jnp.int32(0),
                jnp.ones(plan.shape[:2], jnp.float32))
        trial = make_trial_fn(MICRO, rounds=2, eval_n_per_class=2,
                              strategies=("labelwise",))
        before = {n: count(n) for n in ("trace:trial", "trace:fl.train")}
        compiled = jax.jit(trial).lower(*args).compile()
        assert count("trace:trial") == before["trace:trial"] + 1
        assert count("trace:fl.train") == before["trace:fl.train"] + 1
        for _ in range(2):                   # running it traces nothing
            jax.block_until_ready(compiled(*args))
        assert count("trace:trial") == before["trace:trial"] + 1

    def test_unknown_phase_raises(self):
        with pytest.raises(ValueError, match="unknown FL round phase"):
            with phase("nope"):
                pass


# Hand-written optimized HLO in the TPU compiler's style: a convolution
# with its own op_name; a matrix product written as a convolution; one with
# no metadata in a fusion that carries the scope; one whose fusion carries
# none either, named only by its producer.
_CONV_HLO = """\
%fused_computation.1 (param_0: f32[4,8,8,1], param_1: f32[3,3,1,8]) -> f32[4,8,8,8] {
  %param_0 = f32[4,8,8,1]{3,2,1,0} parameter(0)
  %param_1 = f32[3,3,1,8]{3,2,1,0} parameter(1)
  ROOT %convolution.1 = f32[4,8,8,8]{3,2,1,0} convolution(%param_0, %param_1), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f
}

%fused_computation.2 (param_0.1: f32[4,8,8,1], param_1.1: f32[3,3,1,8]) -> f32[4,8,8,8] {
  %param_0.1 = f32[4,8,8,1]{3,2,1,0} parameter(0)
  %param_1.1 = f32[3,3,1,8]{3,2,1,0} parameter(1)
  ROOT %convolution.2 = f32[4,8,8,8]{3,2,1,0} convolution(%param_0.1, %param_1.1), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f, feature_group_count=1
}

ENTRY %main (p0: f32[4,8,8,1], p1: f32[3,3,1,8], p2: f32[4,8,8,8], p3: f32[3,3,8,8], p4: f32[4,64], p5: f32[64,8]) -> f32[4,8] {
  %p0 = f32[4,8,8,1]{3,2,1,0} parameter(0)
  %p1 = f32[3,3,1,8]{3,2,1,0} parameter(1)
  %p2 = f32[4,8,8,8]{3,2,1,0} parameter(2)
  %p3 = f32[3,3,8,8]{3,2,1,0} parameter(3)
  %p4 = f32[4,64]{1,0} parameter(4)
  %p5 = f32[64,8]{1,0} parameter(5)
  %convolution.3 = f32[4,8,8,8]{3,2,1,0} convolution(%p2, %p3), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f, metadata={op_name="jit(f)/fl.train/jvp(cnn.conv2)/conv_general_dilated"}
  %convolution.4 = f32[4,8]{1,0} convolution(%p4, %p5), dim_labels=bf_io->bf, metadata={op_name="jit(f)/fl.train/jvp(cnn.dense)/dot_general"}
  %fusion.1 = f32[4,8,8,8]{3,2,1,0} fusion(%p0, %p1), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(f)/fl.train/transpose(jvp(cnn.conv1))/conv_general_dilated"}
  %copy.1 = f32[3,3,1,8]{3,2,1,0} copy(%p1), metadata={op_name="jit(f)/fl.train/jvp(cnn.conv1)/reshape"}
  %copy.2 = f32[3,3,1,8]{2,3,1,0} copy(%copy.1)
  %fusion.2 = f32[4,8,8,8]{3,2,1,0} fusion(%p0, %copy.2), kind=kOutput, calls=%fused_computation.2
  %convolution.5 = f32[4,8,8,8]{3,2,1,0} convolution(%p2, %p3), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f
  ROOT %reduce.1 = f32[4,8]{1,0} add(%convolution.4, %convolution.4)
}
"""


class TestConvolutionCount:
    def test_rules_on_hand_written_hlo(self):
        assert convolutions_by_scope(_CONV_HLO) == {
            "cnn.conv1": 2, "cnn.conv2": 1, "unscoped": 1}

    def test_micro_trial_has_no_conv1_convolution(self):
        """conv1 is a sum over its taps; conv2 stays a convolution; the
        compile snapshot carries the same count."""
        import jax
        import jax.numpy as jnp

        from repro.fl.sim import make_trial_fn
        plan = case_label_plan("iid", seed=3, num_rounds=1, num_clients=6,
                               samples_per_client=8, majority=5)
        trial = make_trial_fn(MICRO, rounds=1, eval_n_per_class=1,
                              strategies=("random",))
        args = (jnp.asarray(plan, jnp.int32), jnp.int32(0), jnp.int32(0),
                jnp.ones(plan.shape[:2], jnp.float32))
        compiled = jax.jit(trial).lower(*args).compile()
        counts = convolutions_by_scope(compiled.as_text())
        assert counts.get("cnn.conv1", 0) == 0
        assert counts.get("cnn.conv2", 0) >= 1
        record_memory_analysis("test:convolutions", compiled)
        snap = [m for m in memory_snapshots()
                if m["label"] == "test:convolutions"][-1]
        assert snap["convolutions"] == counts


# ---------------------------------------------------------------------------
# Report + health flags
# ---------------------------------------------------------------------------

class TestReport:
    def test_report_renders_series_table(self):
        res = cached_run(telemetry=("auto",))
        out = render_report(json.loads(res.to_json()))
        assert "per-round means" in out
        assert "selection_entropy" in out
        assert "health:" in out

    def test_report_without_telemetry_still_renders(self):
        out = render_report(json.loads(cached_run().to_json()))
        assert "no telemetry series recorded" in out

    def test_cluster_starvation_flag(self):
        # Every client holds ONLY class 0, so the histogram k-means puts the
        # whole population in one cluster and the other starves — the
        # "cluster starved" failure the report layer must flag.
        plan = np.zeros((2, 6, 8), np.int32)
        spec = ExperimentSpec(
            scenarios=(ScenarioSpec.from_plan("starved", plan),),
            strategies=("labelwise",), seeds=(0,), fl=MICRO,
            aggregation="clustered_fedavg", telemetry=("auto",))
        res = run(spec)
        occ = res.telemetry()["cluster_occupancy"]
        assert (occ == 0).all(axis=(0, 1, 2, 3)).any()
        flags = health_flags(res.meta["telemetry"],
                             loss=np.asarray(res.loss))
        assert any("cluster starvation" in f for f in flags)
        out = render_report(json.loads(res.to_json()))
        assert "health: FLAGS" in out and "cluster starvation" in out

    def test_cli_exits_zero(self, tmp_path):
        p = tmp_path / "result.json"
        p.write_text(cached_run(telemetry=("auto",)).to_json())
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.abspath("src") + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        proc = subprocess.run(
            [sys.executable, "-m", "repro.obs", "report", str(p)],
            capture_output=True, text=True, env=env)
        assert proc.returncode == 0, proc.stderr
        assert "selection_entropy" in proc.stdout


# ---------------------------------------------------------------------------
# Registry hygiene for the temp metrics this module registers
# ---------------------------------------------------------------------------

@pytest.fixture(autouse=True, scope="module")
def _cleanup_temp_metrics():
    yield
    for name in [n for n in list(_METRICS) if n.startswith("_obs_")]:
        _METRICS.pop(name, None)
        if name in _METRIC_IDS:
            _METRIC_IDS.remove(name)
