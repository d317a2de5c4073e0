"""Unified telemetry layer: metrics, virtual-time tracing, health probes.

One spine for the stack's observability (see each submodule's docstring):

- :mod:`repro.obs.registry` — labeled counters/gauges/histograms with a
  no-op default (telemetry off costs one attribute lookup + empty call).
- :mod:`repro.obs.tracing` — virtual/wall-clock spans exported as Chrome
  trace-event JSON (Perfetto-viewable), and the program spans written into
  the JAX profiler's trace (``span``, ``SPAN_NAMES``).
- :mod:`repro.obs.sentinel` — jit retrace counters per compiled plane.
- :mod:`repro.obs.records` — typed history/ledger records with dict views.
- :mod:`repro.obs.probes` — host-side emission of in-graph health probes.
- :mod:`repro.obs.reqtrace` — head-sampled per-request serving span trees.
- :mod:`repro.obs.slo` — declarative SLOs with multi-window burn-rate alerts.
- :mod:`repro.obs.drift` — RF-MMD domain-drift detection over live moments.
"""
from repro.obs import sentinel
from repro.obs.drift import DriftMonitor, DriftRecord
from repro.obs.probes import emit_probes, quarantine_totals
from repro.obs.records import (
    CommRecord,
    CrashRecord,
    EvalRecord,
    FlushRecord,
    Record,
    RoundRecord,
    as_rows,
)
from repro.obs.registry import (
    NULL,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NullRegistry,
    get_registry,
    set_registry,
    use_registry,
)
from repro.obs.reqtrace import RequestTracer
from repro.obs.slo import Slo, SloEngine, SloViolation, quarantine_slo
from repro.obs.tracing import (
    PID_VIRTUAL,
    PID_WALL,
    SPAN_NAMES,
    Tracer,
    count_request_trees,
    get_tracer,
    set_tracer,
    span,
    use_tracer,
    validate_trace,
    validate_trace_file,
)

# `metrics()` reads better than `get_registry()` at instrumentation sites:
#   metrics().counter("comm.bytes").inc(n, kind=kind)
metrics = get_registry

__all__ = [
    "NULL",
    "PID_VIRTUAL",
    "PID_WALL",
    "SPAN_NAMES",
    "CommRecord",
    "Counter",
    "CrashRecord",
    "DriftMonitor",
    "DriftRecord",
    "EvalRecord",
    "FlushRecord",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NullRegistry",
    "Record",
    "RequestTracer",
    "RoundRecord",
    "Slo",
    "SloEngine",
    "SloViolation",
    "Tracer",
    "as_rows",
    "count_request_trees",
    "emit_probes",
    "get_registry",
    "get_tracer",
    "metrics",
    "quarantine_slo",
    "quarantine_totals",
    "sentinel",
    "set_registry",
    "set_tracer",
    "span",
    "use_registry",
    "use_tracer",
    "validate_trace",
    "validate_trace_file",
]
