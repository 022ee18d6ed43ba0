"""Profiler: host-side event timing + device traces.

Reference: paddle/fluid/platform/profiler.h:199-209 (RAII RecordEvent around
each op-dispatch phase), device_tracer.h:41 (CUPTI kernel timeline ->
chrome-trace), python/paddle/fluid/profiler.py:129-253 (context managers,
sorted report). TPU translation:

* device side: `jax.profiler` traces (TensorBoard/XPlane, viewable in
  chrome://tracing via tensorboard) replace CUPTI — start_profiler /
  stop_profiler wrap jax.profiler.start_trace/stop_trace.
* host side: `RecordEvent` spans + a per-op timing mode in the interpretive
  executor path (profile_ops below); the whole-block compiled path is ONE
  XLA computation, so per-op host timing only exists in interpreted mode —
  the same trade the reference makes between graph and dygraph profiling.

This module is now a thin shim over `paddle_tpu.observability`: every
RecordEvent lands as a span on the tracer (when tracing is on — any run
exports to chrome://tracing) and as a `profiler_event_seconds` histogram
in the metrics registry; every `incr_counter` mirrors into
`profiler_counter_total{name=...}`. The sorted-report API and the
enable/disable gate keep their historical semantics.
"""

import contextlib
import os
import time
from collections import defaultdict

from paddle_tpu.observability import metrics as _obs_metrics
from paddle_tpu.observability import tracer as _obs_tracer

__all__ = [
    "RecordEvent",
    "start_profiler",
    "stop_profiler",
    "reset_profiler",
    "profiler",
    "profile_ops",
    "incr_counter",
    "get_counters",
    "get_profile_report",
    "print_profiler_report",
]

_events = defaultdict(lambda: [0, 0.0, 0.0, float("inf")])  # count,total,max,min
_counters = defaultdict(int)
_enabled = False
_trace_dir = None

# registry mirrors created through this module (reset_profiler resets them)
_counter_series = {}
_hist_series = {}


def _event_histogram(name):
    h = _hist_series.get(name)
    if h is None:
        h = _hist_series[name] = _obs_metrics.registry().histogram(
            "profiler_event_seconds", "RecordEvent span durations",
            labels={"event": name},
        )
    return h


class RecordEvent:
    """RAII host span (reference: profiler.h:205). Usable as context manager
    or decorator; nests freely. Emits to the observability tracer whenever
    tracing is enabled (independent of the profiler gate) and aggregates
    into the sorted report when the profiler is enabled. With both gates
    off, entering and leaving is two attribute checks: no clock is read.

    ``span`` is the live tracer span while tracing is on, else None —
    the handle for arguments known only inside the event
    (``ev.span.set(bytes=n)``)."""

    __slots__ = ("name", "_t0", "span")

    def __init__(self, name):
        self.name = name
        self._t0 = None
        self.span = None

    def __enter__(self):
        if _obs_tracer._TRACER.enabled:
            self.span = _obs_tracer.trace_scope(self.name, cat="event")
            self.span.__enter__()
        if _enabled:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self.span is not None:
            self.span.__exit__(*exc)
            self.span = None
        t0, self._t0 = self._t0, None
        if t0 is None or not _enabled:
            return False
        dt = time.perf_counter() - t0
        rec = _events[self.name]
        rec[0] += 1
        rec[1] += dt
        rec[2] = max(rec[2], dt)
        rec[3] = min(rec[3], dt)
        _event_histogram(self.name).observe(dt)
        return False

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapped(*a, **kw):
            with RecordEvent(self.name):
                return fn(*a, **kw)

        return wrapped


def record_event(name):
    return RecordEvent(name)


def incr_counter(name, n=1):
    """Monotonic named counter (occurrence metric with no duration —
    e.g. serving admissions/rejections/batch rows). Gated on the same
    enable switch as RecordEvent; counters land in the report's counter
    section, get_counters(), and the metrics registry
    (`profiler_counter_total{name=...}`)."""
    if _enabled:
        _counters[name] += n
        c = _counter_series.get(name)
        if c is None:
            c = _counter_series[name] = _obs_metrics.registry().counter(
                "profiler_counter_total", "profiler occurrence counters",
                labels={"name": name},
            )
        c.inc(n)


def get_counters():
    return dict(_counters)


def start_profiler(state="All", tracer_option="Default", trace_dir=None):
    """state/tracer_option accepted for parity (reference: profiler.py:196);
    device tracing starts when trace_dir is given (jax.profiler)."""
    global _enabled, _trace_dir
    _enabled = True
    if trace_dir:
        import jax

        _trace_dir = trace_dir
        os.makedirs(trace_dir, exist_ok=True)
        jax.profiler.start_trace(trace_dir)


def stop_profiler(sorted_key="total", profile_path=None):
    global _enabled, _trace_dir
    _enabled = False
    if _trace_dir:
        import jax

        jax.profiler.stop_trace()
        _trace_dir = None
    report = get_profile_report(sorted_key)
    if profile_path:
        with open(profile_path, "w") as f:
            f.write(_format_report(report))
    return report


def reset_profiler():
    _events.clear()
    _counters.clear()
    for series in _counter_series.values():
        series.reset()
    for series in _hist_series.values():
        series.reset()


@contextlib.contextmanager
def profiler(state="All", sorted_key="total", profile_path=None,
             trace_dir=None):
    """with profiler.profiler(): ... (reference: profiler.py:253)."""
    start_profiler(state, trace_dir=trace_dir)
    try:
        yield
    finally:
        report = stop_profiler(sorted_key, profile_path)
        print_profiler_report(report)


@contextlib.contextmanager
def profile_ops():
    """Per-op interpretive profiling: forces the interpreted executor path
    with a RecordEvent around every op lowering — the analog of the
    reference's in-dispatch event records (operator.cc:959-988)."""
    global _enabled
    from paddle_tpu.utils.flags import flags

    old_bench, old_enabled = flags.benchmark, _enabled
    flags.benchmark = True
    _enabled = True
    try:
        yield
    finally:
        flags.benchmark = old_bench
        _enabled = old_enabled


def get_profile_report(sorted_key="total"):
    keyfn = {
        "total": lambda r: r[1][1],
        "calls": lambda r: r[1][0],
        "max": lambda r: r[1][2],
        "min": lambda r: r[1][3],
        "ave": lambda r: r[1][1] / max(r[1][0], 1),
    }.get(sorted_key, lambda r: r[1][1])
    rows = sorted(_events.items(), key=keyfn, reverse=True)
    return [
        {
            "name": name,
            "calls": c,
            "total_s": tot,
            "max_s": mx,
            "min_s": mn if c else 0.0,
            "ave_s": tot / max(c, 1),
        }
        for name, (c, tot, mx, mn) in rows
    ]


def _format_report(report):
    lines = [
        f"{'Event':<48}{'Calls':>8}{'Total(s)':>12}{'Avg(s)':>12}{'Max(s)':>12}"
    ]
    for r in report:
        lines.append(
            f"{r['name']:<48}{r['calls']:>8}{r['total_s']:>12.6f}"
            f"{r['ave_s']:>12.6f}{r['max_s']:>12.6f}"
        )
    if _counters:
        lines.append(f"{'Counter':<48}{'Value':>8}")
        for name in sorted(_counters):
            lines.append(f"{name:<48}{_counters[name]:>8}")
    return "\n".join(lines)


def print_profiler_report(report=None):
    print(_format_report(report if report is not None else get_profile_report()))
