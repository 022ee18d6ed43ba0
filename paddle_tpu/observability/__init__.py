"""Unified observability substrate: spans, metrics, sanitizer, logging.

One telemetry layer for the whole framework (SURVEY §5.1, §5.2, §5.5 —
the reference's RecordEvent/DeviceTracer/timeline.py/FLAGS_check_nan_inf/
FetchHandler stack, rebuilt TPU-native):

* ``tracer``    — thread-aware span tracer (``trace_scope``) with a
  Chrome-trace JSON exporter; open any run in chrome://tracing/Perfetto.
* ``metrics``   — typed counters/gauges/bucketed histograms in one
  registry with Prometheus-style text exposition (``scrape_text``).
* ``sanitizer`` — the FLAGS_check_nan_inf interpreter mode: every op
  output checked, violations named with the op and its user callstack.
* ``logger``    — namespaced loggers and rate-limited logging.
* ``fetcher``   — background periodic fetchers for long training loops
  (FetchHandlerMonitor) and registry scrapes (PeriodicMetricsDump).
* ``lockdep``   — runtime lock-order witness: named lock classes, one
  global may-acquire-while-holding graph, cycle + declared-hierarchy
  violations raised at acquire time (env-gated, PADDLE_TPU_LOCKDEP=1).

The legacy surfaces (``paddle_tpu.profiler``, ``serving.metrics``,
``resilience.supervisor`` events) are thin shims over this layer, so
serving stats, gang-restart events, and compile-cache hit rates all land
in ONE timeline and ONE scrape.
"""

from paddle_tpu.observability.tracer import (
    Tracer,
    disable_tracing,
    enable_tracing,
    export_chrome_trace,
    get_tracer,
    instant,
    span,
    trace_scope,
    tracing,
    tracing_enabled,
)
from paddle_tpu.observability.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    registry,
    scrape_text,
)
from paddle_tpu.observability.logger import (
    RateLimitedLogger,
    get_logger,
)
from paddle_tpu.observability.sanitizer import (
    NanInfError,
    check_output,
    sanitize_nan_inf,
)
from paddle_tpu.observability.fetcher import (
    FetchHandlerMonitor,
    PeriodicMetricsDump,
)
from paddle_tpu.observability import lockdep
from paddle_tpu.observability.lockdep import (
    LockOrderError,
    declare_order,
    named_condition,
    named_lock,
)

__all__ = [
    "Tracer",
    "trace_scope",
    "span",
    "instant",
    "tracing",
    "tracing_enabled",
    "enable_tracing",
    "disable_tracing",
    "export_chrome_trace",
    "get_tracer",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "registry",
    "scrape_text",
    "RateLimitedLogger",
    "get_logger",
    "NanInfError",
    "check_output",
    "sanitize_nan_inf",
    "FetchHandlerMonitor",
    "PeriodicMetricsDump",
    "lockdep",
    "LockOrderError",
    "declare_order",
    "named_condition",
    "named_lock",
]
