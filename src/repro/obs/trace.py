"""Host-side trace spans + profiler hooks.

A *span* times one host-side pipeline stage (``lower_scenarios``, compile,
engine execute, eval).  Events accumulate in a process-global buffer in
Chrome ``trace_event`` format (complete ``"ph": "X"`` events, microsecond
timestamps) so :func:`write_trace` output loads directly into Perfetto /
``chrome://tracing``.  ``compile_s`` / ``wall_s`` engine timings fold into
the same stream as spans, so one file tells the whole wall-clock story.

``REPRO_TRACE_DIR=<dir>`` switches on the heavyweight hooks: engine
execution additionally runs under ``jax.profiler.trace`` (XLA-level
profile written to ``<dir>/jax/``) and each trace file is written to
``<dir>/trace_<pid>.json``.  ``compiled.memory_analysis()`` snapshots are
captured per AOT compile via :func:`record_memory_analysis` regardless —
they are cheap and ride the telemetry envelope.
"""
from __future__ import annotations

import contextlib
import json
import os
import re
import threading
import time
from typing import Any, Dict, List, Optional

ENV_TRACE_DIR = "REPRO_TRACE_DIR"

_LOCK = threading.Lock()
_EVENTS: List[Dict[str, Any]] = []
_MEMORY: List[Dict[str, Any]] = []
# trace_event timestamps are µs relative to an arbitrary epoch; pin one per
# process so spans from different modules line up on the same axis.
_T0 = time.perf_counter()


def trace_dir() -> Optional[str]:
    """The configured trace directory, or None when tracing is off."""
    d = os.environ.get(ENV_TRACE_DIR, "").strip()
    return d or None


def _now_us() -> float:
    return (time.perf_counter() - _T0) * 1e6


class Span:
    """Handle yielded by :func:`span`; ``duration_s`` is valid after exit."""

    def __init__(self, name: str, args: Dict[str, Any]):
        self.name = name
        self.args = args
        self.start_us = _now_us()
        self.duration_s = 0.0

    def close(self) -> None:
        end = _now_us()
        self.duration_s = (end - self.start_us) / 1e6
        ev = {"name": self.name, "ph": "X", "ts": self.start_us,
              "dur": end - self.start_us, "pid": os.getpid(),
              "tid": threading.get_ident()}
        if self.args:
            ev["args"] = dict(self.args)
        with _LOCK:
            _EVENTS.append(ev)


@contextlib.contextmanager
def span(name: str, **args: Any):
    """Time a host-side stage: ``with span("compile", engine="sim") as s: …``;
    records one complete trace event on exit (also on exception)."""
    s = Span(name, args)
    try:
        yield s
    finally:
        s.close()


def instant(name: str, **args: Any) -> None:
    """Record a zero-duration marker event."""
    ev = {"name": name, "ph": "i", "ts": _now_us(), "s": "p",
          "pid": os.getpid(), "tid": threading.get_ident()}
    if args:
        ev["args"] = dict(args)
    with _LOCK:
        _EVENTS.append(ev)


def record_duration(name: str, seconds: float, **args: Any) -> None:
    """Fold an externally-measured duration (an engine's ``compile_s`` /
    ``wall_s``) into the event stream as a complete event ending now."""
    dur_us = max(float(seconds), 0.0) * 1e6
    ev = {"name": name, "ph": "X", "ts": _now_us() - dur_us, "dur": dur_us,
          "pid": os.getpid(), "tid": threading.get_ident()}
    if args:
        ev["args"] = dict(args)
    with _LOCK:
        _EVENTS.append(ev)


def events() -> List[Dict[str, Any]]:
    """Snapshot of the accumulated trace events."""
    with _LOCK:
        return [dict(e) for e in _EVENTS]


def reset() -> None:
    """Clear buffered events and memory snapshots (tests)."""
    with _LOCK:
        _EVENTS.clear()
        _MEMORY.clear()


def span_summary() -> Dict[str, Dict[str, float]]:
    """name → {count, total_s} rollup of the complete events seen so far."""
    out: Dict[str, Dict[str, float]] = {}
    for ev in events():
        if ev.get("ph") != "X":
            continue
        agg = out.setdefault(ev["name"], {"count": 0, "total_s": 0.0})
        agg["count"] += 1
        agg["total_s"] += ev.get("dur", 0.0) / 1e6
    return out


def write_trace(path: Optional[str] = None) -> Optional[str]:
    """Write the buffered events as a Chrome trace file.  With no ``path``,
    uses ``$REPRO_TRACE_DIR/trace_<pid>.json`` (no-op returning None when
    the env var is unset)."""
    if path is None:
        d = trace_dir()
        if d is None:
            return None
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, f"trace_{os.getpid()}.json")
    else:
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
    with open(path, "w") as f:
        json.dump({"traceEvents": events(), "displayTimeUnit": "ms"}, f)
    return path


@contextlib.contextmanager
def profiler(label: str):
    """Wrap engine execution in ``jax.profiler.trace`` when REPRO_TRACE_DIR
    is set; a plain span otherwise.  An exception from the engine body
    propagates unchanged.  A profiler that cannot start (unsupported
    backend, a trace already running) raises: the caller asked for a trace,
    and a run without it would look traced when it was not."""
    d = trace_dir()
    with span(f"engine_execute:{label}"):
        if d is None:
            yield
            return
        import jax
        prof_dir = os.path.join(d, "jax")
        os.makedirs(prof_dir, exist_ok=True)
        try:
            jax.profiler.start_trace(prof_dir)
        except Exception as e:
            raise RuntimeError(f"{ENV_TRACE_DIR}={d}: jax.profiler could not "
                               f"start a trace in {prof_dir}") from e
        try:
            yield
        finally:
            jax.profiler.stop_trace()


_KERNEL_CALL = re.compile(
    r'custom_call_target="tpu_custom_call".*?jit\((\w+)\)/pallas_call')


def pallas_kernel_calls(hlo_text: str) -> Dict[str, int]:
    """Compiled Pallas TPU kernel calls in an optimized HLO module's text,
    counted by kernel: each ``tpu_custom_call`` instruction is keyed by the
    jitted kernel function its metadata names
    (``…/jit(label_hist_kernel)/pallas_call``).  Empty for a program that
    holds no kernel — a CPU compile, or a TPU compile of the references."""
    out: Dict[str, int] = {}
    for name in _KERNEL_CALL.findall(hlo_text):
        out[name] = out.get(name, 0) + 1
    return out


def record_memory_analysis(label: str, compiled: Any) -> None:
    """Best-effort ``compiled.memory_analysis()`` snapshot for one AOT
    compile, plus ``pallas_kernels`` — :func:`pallas_kernel_calls` of the
    compiled program — when it holds any.  Backends without the API (or
    donation-opaque executables) are skipped silently."""
    try:
        ma = compiled.memory_analysis()
        if ma is None:
            return
        snap = {"label": label}
        for field in ("temp_size_in_bytes", "output_size_in_bytes",
                      "argument_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
            v = getattr(ma, field, None)
            if v is not None:
                snap[field] = int(v)
        kernels = pallas_kernel_calls(compiled.as_text())
        if kernels:
            snap["pallas_kernels"] = kernels
        if len(snap) > 1:
            with _LOCK:
                _MEMORY.append(snap)
    except Exception:
        pass


def memory_snapshots() -> List[Dict[str, Any]]:
    with _LOCK:
        return [dict(m) for m in _MEMORY]
