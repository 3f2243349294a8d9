"""Shared benchmark scaffolding.

CPU-budget note: the paper's full protocol (100 clients × 30 rounds × 30
trials) is hours on this 1-core container; ``fast=True`` (the default for
``python -m benchmarks.run``) scales the protocol down (16 clients, 5–8
rounds, 1–3 trials) while keeping every structural element — the *orderings*
the paper claims are what the numbers demonstrate.  ``--full`` restores the
paper's sizes.
"""
from __future__ import annotations

import os
import time
from typing import Callable


from repro.configs.paper_cnn import FLConfig

FAST_FL = FLConfig(num_clients=16, clients_per_round=6, global_epochs=5,
                   local_epochs=2, batch_size=16, lr=1e-3)
FULL_FL = FLConfig()  # the paper's §VI constants

FAST_SPC = 48    # samples per client (paper: 290)
FAST_TRIALS = 1
FULL_TRIALS = 30


def fl_cfg(fast: bool) -> FLConfig:
    return FAST_FL if fast else FULL_FL


def spc(fast: bool) -> int:
    return FAST_SPC if fast else 290


def trials(fast: bool) -> int:
    return FAST_TRIALS if fast else FULL_TRIALS


def timeit_us(fn: Callable, n: int = 10, warmup: int = 2) -> float:
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6


def emit(name: str, us_per_call: float, derived: str) -> None:
    """Contract output: ``name,us_per_call,derived`` CSV line."""
    print(f"{name},{us_per_call:.1f},{derived}")


# Version of the BENCH_*.json report shape (top-level keys below + the
# repro.obs telemetry block); bump on breaking layout changes.
BENCH_SCHEMA_VERSION = 2


def _git_sha() -> str:
    """The repo HEAD sha the report was produced from ("unknown" outside a
    checkout — e.g. an unpacked artifact re-run)."""
    import subprocess
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10, cwd=os.path.dirname(os.path.abspath(__file__)),
            check=True).stdout.strip()
    except Exception:
        return "unknown"


def write_report(out_path: str, report: dict, *,
                 compile_s: float | None = None,
                 telemetry: dict | None = None) -> str:
    """The one ``BENCH_*.json`` writer (all suites route through it).

    Injects the uniform top-level environment keys every report carries —
    ``compile_s`` (pass it explicitly, or leave the report's own value),
    ``backend`` and ``device_count``, plus the provenance stamps
    ``schema_version`` and ``git_sha`` — so cached vs cold runs and
    cross-backend numbers are comparable at a glance, then writes ``report``
    to ``out_path`` (indent=2).  Returns ``out_path``.

    ``telemetry`` optionally embeds a run's ``meta["telemetry"]`` envelope
    (repro.obs) under ``report["telemetry"]``; the accumulated trace-span
    summary is always recorded under ``report["spans"]`` when any spans
    fired, so BENCH artifacts carry the compile/execute breakdown."""
    import json

    import jax

    from repro.obs import span_summary

    report = dict(report)
    if compile_s is not None:
        report["compile_s"] = float(compile_s)
    elif "compile_s" not in report:
        raise ValueError("BENCH report needs a top-level compile_s — pass "
                         "compile_s= or put it in the report")
    report["schema_version"] = BENCH_SCHEMA_VERSION
    report["git_sha"] = _git_sha()
    report["backend"] = jax.default_backend()
    report["device_count"] = int(jax.device_count())
    if telemetry is not None:
        report["telemetry"] = telemetry
    spans = span_summary()
    if spans and "spans" not in report:
        report["spans"] = spans
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    return out_path
