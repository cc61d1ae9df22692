"""Observability spine: metrics and request tracing.

  metrics.py  label-aware Counter/Gauge/Histogram registry (injectable
              clock, per-instance labels on a process-global default,
              NULL_REGISTRY bare mode, StatsView back-compat mapping)
  trace.py    spans (context-manager + explicit begin/end), parent/child
              links, batcher-ticket correlation; each span is also a
              ``repro.<name>`` annotation in a running profiler trace
  export.py   JSONL + Prometheus text exposition; Chrome-trace JSON
  train.py    fit-callback metrics for the training spine (epoch wall
              time, loss trajectory, SweepSchedule block visits)

Threaded through ``serve/`` (batcher, mesh, publish, ann),
``launch/serve.py`` (``--metrics-out``/``--trace-out``), the benches
(instrumented-vs-bare overhead hard-gated < 3%), and
``examples/observability.py`` (end-to-end train → serve-under-faults →
Perfetto trace). See ``serve/README.md`` § "Metrics & tracing" for the
metric catalogue and label conventions.
"""
from repro.obs.export import (
    chrome_trace,
    metrics_jsonl,
    prometheus_text,
    write_metrics,
    write_trace,
)
from repro.obs.metrics import (
    DEFAULT_BUCKETS,
    NULL_REGISTRY,
    MetricsRegistry,
    StatsView,
    default_registry,
    next_instance_id,
    resolve_registry,
    set_default_registry,
)
from repro.obs.trace import Span, Tracer, trace_for_ticket
from repro.obs.train import compose_callbacks, fit_metrics_callback

__all__ = [
    "DEFAULT_BUCKETS",
    "MetricsRegistry",
    "NULL_REGISTRY",
    "Span",
    "StatsView",
    "Tracer",
    "chrome_trace",
    "compose_callbacks",
    "default_registry",
    "fit_metrics_callback",
    "metrics_jsonl",
    "next_instance_id",
    "prometheus_text",
    "resolve_registry",
    "set_default_registry",
    "trace_for_ticket",
    "write_metrics",
    "write_trace",
]
