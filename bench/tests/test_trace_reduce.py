"""The trace reduction, on a small trace recorded on the CPU and on
hand-made events."""
from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import pytest

from bench import trace_reduce as tr


def _ev(name, start, dur, stats=()):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats))


def _profile(planes):
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name=n, lines=[types.SimpleNamespace(name=ln, events=evs)
                                             for ln, evs in lines])
        for n, lines in planes])


def test_union_and_gaps():
    u = tr.union([(5, 8), (0, 2), (1, 3), (7, 9), (20, 30)], 0, 25)
    assert u == [(0, 3), (5, 9), (20, 25)]
    assert tr.gaps(u, 0, 25) == [(3, 5), (9, 20)]
    assert tr.gaps([], 0, 4) == [(0, 4)]


def test_op_name_of_a_tpu_event():
    text = ('%label_hist_kernel.1 = f32[104,10]{1,0} custom-call(s32[104,512] '
            '%copy-done), custom_call_target="tpu_custom_call"')
    assert tr.op_name(text) == "label_hist_kernel.1"
    assert tr.op_name("fusion.3") == "fusion.3"


def test_summary_of_a_two_device_trace():
    host = ("/host:CPU", [("python", [
        _ev("bench:window", 100, 1000), _ev("bench:call", 100, 500),
        _ev("bench:call", 600, 500), _ev("unrelated", 0, 2000)])])
    dev0 = ("/device:TPU:0", [("XLA Ops", [
        _ev("%while.1 = (f32[]) while(...)", 100, 400),
        _ev("%weighted_agg_kernel.3 = f32[4] custom-call(...)", 150, 100),
        _ev("%fusion.2 = f32[4] fusion(...)", 300, 100),
        _ev("%label_hist_kernel.1 = f32[4] custom-call(...)", 900, 300)]),
        ("XLA Modules", [_ev("jit_f", 100, 1000)])])
    dev1 = ("/device:TPU:1", [("XLA Ops", [_ev("%fusion.9 = f32[] fusion()", 600, 500)])])
    s = tr.summarize(_profile([host, dev0, dev1]))
    assert s.window_s == pytest.approx(1e-6)
    assert s.busy_s == {0: pytest.approx(0.6e-6), 1: pytest.approx(0.5e-6)}
    assert s.idle_share == pytest.approx(1 - 0.55)
    assert s.time_of("label_hist_kernel") == pytest.approx(0.2e-6)  # clipped at 1100
    assert s.time_of("weighted_agg_kernel") == pytest.approx(0.1e-6)
    b = s.breakdown()
    assert "while.1" not in [n for n, _ in b["device_ops"]]
    assert b["device_ops"][0] == ["fusion.9", pytest.approx(0.5e-6)]
    assert b["idle_gaps"][:2] == [["call", pytest.approx(0.5e-6)],
                                  ["call", pytest.approx(0.4e-6)]]
    assert len(b["idle_gaps"]) <= 10
    kinds = dict(s.by_kind())
    assert kinds.keys() == {"fusion", "label_hist_kernel", "weighted_agg_kernel"}
    assert kinds["fusion"] == pytest.approx(0.6e-6)


def test_a_trace_without_the_window_or_device_ops_is_refused():
    with pytest.raises(ValueError):
        tr.summarize(_profile([("/host:CPU", [("python", [])])]))
    with pytest.raises(ValueError):
        tr.summarize(_profile([("/host:CPU", [("python", [
            _ev("bench:window", 0, 10)])])]))


def test_reduction_of_a_trace_recorded_on_the_cpu(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x.T).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench:window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench:call"):
                    f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    s = tr.load(str(tmp_path))
    assert s.window_s > 0
    assert 0 < s.mean_busy_s <= s.window_s
    assert 0 <= s.idle_share < 1
    assert s.op_s and all(v > 0 for v in s.op_s.values())
    assert all(label in ("call", "window") for label, _ in s.gaps)
