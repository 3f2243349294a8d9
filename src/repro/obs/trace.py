"""Host-side trace spans, the FL round's stage scopes, and profiler hooks.

A *span* times one host-side pipeline stage (``lower_scenarios``, compile,
engine execute, eval).  Events accumulate in a process-global buffer in
Chrome ``trace_event`` format (complete ``"ph": "X"`` events, microsecond
timestamps) so :func:`write_trace` output loads directly into Perfetto /
``chrome://tracing``.  Each span also enters a
``jax.profiler.TraceAnnotation`` named ``repro:<name>``, and its timestamp
is read from the clock the profiler stamps its events with (``time.time_ns``,
the realtime clock), so under any profiler session the program's spans sit
in the same ``.xplane.pb`` as the device ops, on one time axis, and a span
starts at the same instant in the Chrome file and in the profile.

:func:`phase` names one stage of the FL round (:data:`PHASES`) where its
math is written: ``jax.named_scope("fl.<stage>")`` puts the stage into the
``op_name`` metadata of every HLO instruction the block lowers to (the
backward pass included), and a ``trace:fl.<stage>`` span times the Python
tracing of the block.  Both act only while JAX traces, never per round; the
compiled program is the same with or without them, up to metadata.

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

import jax

ENV_TRACE_DIR = "REPRO_TRACE_DIR"

_LOCK = threading.Lock()
_EVENTS: List[Dict[str, Any]] = []
_MEMORY: List[Dict[str, Any]] = []

# The FL round's stages, in round order; ``phase(name)`` takes one of these.
PHASES = ("materialize", "select", "train", "aggregate", "eval", "cluster")


def trace_dir() -> Optional[str]:
    """The configured trace directory, or None when tracing is off."""
    d = os.environ.get(ENV_TRACE_DIR, "").strip()
    return d or None


class Span:
    """Handle yielded by :func:`span`; ``duration_s`` is valid after exit.

    ``start_us`` is on the profiler's clock (µs since the Unix epoch); the
    duration comes from the monotonic ``perf_counter``."""

    def __init__(self, name: str, args: Dict[str, Any]):
        self.name = name
        self.args = args
        self.start_us = time.time_ns() / 1e3
        self._t0 = time.perf_counter()
        self.duration_s = 0.0

    def close(self) -> None:
        self.duration_s = time.perf_counter() - self._t0
        ev = {"name": self.name, "ph": "X", "ts": self.start_us,
              "dur": self.duration_s * 1e6, "pid": os.getpid(),
              "tid": threading.get_ident()}
        if self.args:
            ev["args"] = dict(self.args)
        with _LOCK:
            _EVENTS.append(ev)


@contextlib.contextmanager
def span(name: str, **args: Any):
    """Time a host-side stage: ``with span("compile", engine="sim") as s: …``;
    records one complete trace event on exit (also on exception), and is a
    ``repro:<name>`` host event in any running ``jax.profiler`` trace."""
    with jax.profiler.TraceAnnotation("repro:" + name):
        s = Span(name, args)
        try:
            yield s
        finally:
            s.close()


@contextlib.contextmanager
def phase(name: str):
    """Name one stage of the FL round where its math is written:
    ``with phase("train"): …`` scopes the block's ops as ``fl.train`` in the
    HLO ``op_name`` metadata and times its Python tracing as the span
    ``trace:fl.train``.  ``name`` is one of :data:`PHASES`."""
    if name not in PHASES:
        raise ValueError(f"unknown FL round phase {name!r}; have {PHASES}")
    with jax.named_scope(f"fl.{name}"), span(f"trace:fl.{name}"):
        yield


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
    if d is None:
        with span(f"engine_execute:{label}"):
            yield
        return
    prof_dir = os.path.join(d, "jax")
    os.makedirs(prof_dir, exist_ok=True)
    try:
        jax.profiler.start_trace(prof_dir)
    except Exception as e:
        raise RuntimeError(f"{ENV_TRACE_DIR}={d}: jax.profiler could not "
                           f"start a trace in {prof_dir}") from e
    # The span opens inside the profiler session, so the profile holds it.
    try:
        with span(f"engine_execute:{label}"):
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


_HLO_LINE = re.compile(r'^\s*(?:ROOT )?%([\w.\-]+) = ')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r'calls=%([\w.\-]+)')
_OPERAND = re.compile(r'%([\w.\-]+)')
_CNN_SCOPE = re.compile(r'cnn\.\w+')


def convolutions_by_scope(hlo_text: str) -> Dict[str, int]:
    """``convolution`` instructions of an optimized HLO module that lower
    ``lax.conv_general_dilated``, counted by the ``cnn.*`` scope their
    ``op_name`` carries (``"unscoped"`` where none is found).

    An instruction without metadata takes the ``op_name`` of the fusion
    that calls its computation; where that has none either, the scope most
    of its nearest producers with metadata carry.  The TPU compiler writes
    matrix products as ``convolution`` too; those keep the ``dot_general``
    their ``op_name`` ends in and are not counted."""
    op_name: Dict[str, str] = {}
    operands: Dict[str, List[str]] = {}
    caller: Dict[str, str] = {}         # called computation → calling instr
    home: Dict[str, str] = {}           # instruction → its computation
    convs: List[str] = []
    comp = ""
    for line in hlo_text.splitlines():
        if line.startswith(("%", "ENTRY")):
            comp = line.split()[1 if line.startswith("ENTRY") else 0][1:]
            continue
        m = _HLO_LINE.match(line)
        if not m:
            continue
        name, rest = m.group(1), line[m.end():]
        home[name] = comp
        on = _OP_NAME.search(rest)
        if on:
            op_name[name] = on.group(1)
        operands[name] = _OPERAND.findall(rest.split("), ", 1)[0])
        for callee in _CALLS.findall(rest):
            caller.setdefault(callee, name)
        if re.match(r'\S+ convolution\(', rest):
            convs.append(name)

    out: Dict[str, int] = {}
    for name in convs:
        while name not in op_name and home.get(name) in caller:
            name = caller[home[name]]
        if op_name.get(name, "").endswith("dot_general"):
            continue
        frontier, named, seen = {name}, [], {name}
        while frontier and not named:
            named = [op_name[n] for n in sorted(frontier) if n in op_name]
            frontier = {o for n in frontier
                        for o in operands.get(n, ())} - seen
            seen |= frontier
        scopes = [s for n in named for s in _CNN_SCOPE.findall(n)[-1:]]
        scope = max(scopes, key=scopes.count) if scopes else "unscoped"
        out[scope] = out.get(scope, 0) + 1
    return out


def record_memory_analysis(label: str, compiled: Any) -> None:
    """Best-effort ``compiled.memory_analysis()`` snapshot for one AOT
    compile, plus ``pallas_kernels`` — :func:`pallas_kernel_calls` of the
    compiled program — when it holds any, and ``convolutions`` —
    :func:`convolutions_by_scope` of it.  Backends without the API (or
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
        text = compiled.as_text()
        kernels = pallas_kernel_calls(text)
        if kernels:
            snap["pallas_kernels"] = kernels
        snap["convolutions"] = convolutions_by_scope(text)
        if len(snap) > 1:
            with _LOCK:
                _MEMORY.append(snap)
    except Exception:
        pass


def memory_snapshots() -> List[Dict[str, Any]]:
    with _LOCK:
        return [dict(m) for m in _MEMORY]
