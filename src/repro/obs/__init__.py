"""repro.obs — round-level observability: in-graph metrics, trace spans,
profiler hooks, and the telemetry reporting surface.

Three layers (see the module docstrings for the contracts):

- :mod:`repro.obs.registry` — ``register_metric`` open registry of traced
  round metrics the engines compile into their round bodies.
- :mod:`repro.obs.trace` — host-side span API emitting Chrome trace_event
  JSON, plus ``jax.profiler`` / ``memory_analysis`` hooks gated on
  ``REPRO_TRACE_DIR``.
- :mod:`repro.obs.envelope` / :mod:`repro.obs.report` — the versioned
  ``meta["telemetry"]`` envelope and the ``python -m repro.obs report``
  rendering with convergence-health flags.
"""
from repro.obs.envelope import (
    TELEMETRY_SCHEMA_VERSION,
    build_envelope,
    series_arrays,
)
from repro.obs.registry import (
    BASE_AXES,
    ENV_TELEMETRY,
    Metric,
    collect_metrics,
    get_metric,
    make_collector,
    metric_id,
    metrics_registry,
    register_metric,
    registered_metrics,
    resolve_metrics,
    resolve_telemetry_request,
)
from repro.obs.report import health_flags, render_report, report_file
from repro.obs.trace import (
    ENV_TRACE_DIR,
    events,
    instant,
    memory_snapshots,
    pallas_kernel_calls,
    profiler,
    record_duration,
    record_memory_analysis,
    span,
    span_summary,
    trace_dir,
    write_trace,
)

__all__ = [
    "TELEMETRY_SCHEMA_VERSION",
    "build_envelope",
    "series_arrays",
    "BASE_AXES",
    "ENV_TELEMETRY",
    "Metric",
    "collect_metrics",
    "get_metric",
    "make_collector",
    "metric_id",
    "metrics_registry",
    "register_metric",
    "registered_metrics",
    "resolve_metrics",
    "resolve_telemetry_request",
    "health_flags",
    "render_report",
    "report_file",
    "ENV_TRACE_DIR",
    "events",
    "instant",
    "memory_snapshots",
    "pallas_kernel_calls",
    "profiler",
    "record_duration",
    "record_memory_analysis",
    "span",
    "span_summary",
    "trace_dir",
    "write_trace",
]
