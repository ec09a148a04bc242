"""Unified observability: tracing spans, named metrics, bench reports.

Zero-dependency (stdlib only; jax touched lazily and optionally).  The
three pieces every subsystem reports through:

- :mod:`.trace` — nestable ``span("name", **attrs)`` context managers
  over the SumProd / boosting / serving hot paths, a process
  :class:`Tracer` with JSONL + Chrome-trace (Perfetto) export,
  ``jax.profiler`` annotation passthrough, and :func:`fence` for
  explicit ``block_until_ready`` attribution.  Default-off: disabled
  spans are a shared no-op context manager.  ``scope("name")`` names
  device-path work: a ``jax.named_scope`` under jit, a profiler
  annotation (and, while tracing, a span) when run eagerly.
- :mod:`.metrics` — a thread-safe :class:`MetricsRegistry` of counters,
  gauges, and log-bucketed histograms with snapshot/diff/merge
  semantics; ``QueryCounter``, ``MessageCache``, the serving LRU cache
  and ``ServiceStats`` all mirror into it as named series.
- :mod:`.report` — :class:`BenchReport` writes schema-versioned
  ``BENCH_<name>.json`` artifacts (machine fingerprint, metric
  snapshots, span rollups) so the perf trajectory is tracked
  PR-over-PR; ``benchmarks/report.py --check`` gates CI on them.

Live telemetry (this layer observing a RUNNING system, not just a
finished one):

- :mod:`.flight` — :class:`FlightRecorder`: always-on ring-buffer
  tracing (O(1) memory) with latency/error-triggered Perfetto dumps;
- :mod:`.exposition` — Prometheus/JSON rendering of any registry
  snapshot, the :class:`TelemetryServer` HTTP endpoints
  (``/metricsz`` ``/healthz`` ``/statusz`` ``/tracez``), and the
  :class:`PeriodicSampler` JSONL time series;
- :mod:`.slo` — declarative :class:`SLOObjective`s evaluated by an
  :class:`SLOMonitor` with multi-window burn rates into a
  healthy/degraded/unhealthy state the service consumes as an
  overload signal.
"""
from .exposition import (
    PeriodicSampler, TelemetryServer, render_json, render_prometheus,
)
from .flight import FlightRecorder
from .metrics import (
    Counter, Gauge, Histogram, MetricsRegistry, diff_snapshots,
    format_summary_table, get_registry, merge_snapshots, reset_registry,
)
from .report import BenchReport, bench_path, fingerprint, validate_bench
from .slo import SLOMonitor, SLOObjective, parse_slo_spec
from .trace import (
    Tracer, disable_tracing, enable_tracing, fence, get_tracer, scope, span,
    tracing_enabled,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "diff_snapshots", "merge_snapshots", "format_summary_table",
    "get_registry", "reset_registry",
    "BenchReport", "bench_path", "fingerprint", "validate_bench",
    "Tracer", "span", "scope", "fence", "enable_tracing", "disable_tracing",
    "tracing_enabled", "get_tracer",
    "FlightRecorder",
    "TelemetryServer", "PeriodicSampler", "render_prometheus", "render_json",
    "SLOMonitor", "SLOObjective", "parse_slo_spec",
]
