"""Thread-aware span tracer with a Chrome-trace JSON exporter.

Host-side analog of the reference's RecordEvent + DeviceTracer +
tools/timeline.py pipeline (reference: paddle/fluid/platform/profiler.h:199,
device_tracer.h:41, tools/timeline.py): spans are recorded per thread on a
monotonic clock and exported as Chrome trace-event JSON, so any run opens
directly in chrome://tracing or Perfetto. Device-side traces remain
jax.profiler's job (profiler.start_profiler(trace_dir=...)); this tracer
covers the host dispatch path the whole-block XLA design leaves outside
the device timeline.

Zero-overhead-when-disabled contract: ``trace_scope.__enter__`` performs a
single module-global attribute check and returns; no clock is read, no
allocation happens. The hot execute path stays within the <=2% budget
(tools/trace_view.py --smoke measures it). ``span(name)`` goes one further
for hot loops: disabled, it hands back ONE shared no-op (nothing is built,
no argument is evaluated) whose ``__enter__`` gives None; enabled, the live
span takes its arguments through ``set()``.

One clock with the device trace: while a ``jax.profiler`` trace is running,
an enabled span also opens a ``jax.profiler.TraceAnnotation`` of the same
name and arguments, so xprof / Perfetto show the host's phases beside the
device operations they launched.

Lanes: a producer that knows when something OFF the host ran (the decode
engine's ready watcher, ``serving/decode/lane.py``: when the device had
finished each launch) records it with ``record_lane`` on a named track of
its own (``device:0``). A lane event is on the tracer's clock and in the
Chrome export, beside the host spans, but is NOT a span: ``spans()`` does
not return it (a consumer that cuts time along whatever span started last
would cut along the device's events too), ``lanes()`` does, and it has no
``TraceAnnotation`` twin (xprof has the device itself). Lanes are asked for,
``tracing(path, lanes=True)``: their producer costs a thread that wakes with
every launch, which a capture that wants the host's spans alone (the
benchmark's traced run) does not pay.

    with tracing("/tmp/run.trace.json"):
        with trace_scope("step"):
            with trace_scope("fwd"):
                ...

    @trace_scope("load_batch")
    def load_batch(...): ...
"""

import functools
import json
import os
import sys
import threading
import time

__all__ = [
    "Tracer",
    "trace_scope",
    "span",
    "instant",
    "tracing",
    "tracing_enabled",
    "lanes_enabled",
    "enable_tracing",
    "disable_tracing",
    "export_chrome_trace",
    "get_tracer",
]

# span tuple layout (kept flat — dicts are built once, at export):
# (name, cat, start_ns, dur_ns, tid, thread_name, depth, args)
# lane event layout: (track, name, start_ns, dur_ns, args)


class Tracer:
    """Span collector. One instance is the process-global default; tests
    may build private ones. ``enabled`` is read unlocked on the hot path
    (a stale read merely drops or keeps one span at the toggle edge)."""

    def __init__(self, max_events=1_000_000):
        self.enabled = False
        self.lanes_on = False       # while enabled: lanes were asked for
        self._default_max_events = int(max_events)
        self.max_events = int(max_events)
        self._lock = threading.Lock()
        self._spans = []
        self._instants = []
        self._lanes = []
        self._dropped = 0
        self._epoch_ns = time.perf_counter_ns()
        self._tls = threading.local()

    # -- lifecycle ---------------------------------------------------------
    def start(self, max_events=None, lanes=False):
        with self._lock:
            # a cap set for one capture does not leak into the next
            self.max_events = (int(max_events) if max_events is not None
                               else self._default_max_events)
            self._spans = []
            self._instants = []
            self._lanes = []
            self._dropped = 0
            self._epoch_ns = time.perf_counter_ns()
            self.lanes_on = bool(lanes)
            self.enabled = True

    def stop(self):
        self.enabled = False
        self.lanes_on = False

    def clear(self):
        with self._lock:
            self._spans = []
            self._instants = []
            self._lanes = []
            self._dropped = 0

    # -- per-thread nesting ------------------------------------------------
    def _stack(self):
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def current_depth(self):
        return len(self._stack())

    # -- recording ---------------------------------------------------------
    def record_span(self, name, cat, start_ns, end_ns, depth, args=None):
        ev = (
            name, cat, start_ns, end_ns - start_ns,
            threading.get_ident(), threading.current_thread().name,
            depth, args,
        )
        with self._lock:
            if len(self._spans) >= self.max_events:
                self._dropped += 1
                return
            self._spans.append(ev)

    def instant(self, name, cat="event", **args):
        """One-shot structured event (chrome-trace 'i' phase) — the span
        analog of a log line; supervisor restarts, breaker trips, etc."""
        if not self.enabled:
            return
        ev = (
            name, cat, time.perf_counter_ns(), 0,
            threading.get_ident(), threading.current_thread().name,
            len(self._stack()), args or None,
        )
        with self._lock:
            if len(self._instants) >= self.max_events:
                self._dropped += 1
                return
            self._instants.append(ev)

    def record_lane(self, track, name, start_ns, end_ns, args=None):
        """One event of a lane (module docstring): what ran on ``track``
        from ``start_ns`` to ``end_ns`` of ``perf_counter_ns``. Recorded
        whether or not the tracer is still enabled: the producer learns of
        an event after it ended, and the last ones of a capture end after
        its ``stop()``. An event that began before the capture did belongs
        to the one before and is dropped."""
        with self._lock:
            if start_ns < self._epoch_ns:
                return
            if len(self._lanes) >= self.max_events:
                self._dropped += 1
                return
            self._lanes.append((track, name, start_ns, end_ns - start_ns,
                                args))

    # -- introspection (tests, summaries) ----------------------------------
    def spans(self):
        """Snapshot of finished spans as dicts (ns-resolution, epoch-
        relative start). For programmatic consumers; the chrome JSON is
        the interchange format."""
        with self._lock:
            spans = list(self._spans)
        return [
            {
                "name": name, "cat": cat,
                "start_ns": start_ns - self._epoch_ns, "dur_ns": dur_ns,
                "tid": tid, "thread": tname, "depth": depth,
                "args": args or {},
            }
            for name, cat, start_ns, dur_ns, tid, tname, depth, args in spans
        ]

    def instants(self):
        with self._lock:
            evs = list(self._instants)
        return [
            {
                "name": name, "cat": cat,
                "ts_ns": ts - self._epoch_ns,
                "tid": tid, "thread": tname, "args": args or {},
            }
            for name, cat, ts, _dur, tid, tname, _d, args in evs
        ]

    def lanes(self):
        """Snapshot of the lane events as dicts, in the order recorded
        (ns-resolution, epoch-relative start)."""
        with self._lock:
            lanes = list(self._lanes)
        return [
            {"track": track, "name": name,
             "start_ns": start_ns - self._epoch_ns, "dur_ns": dur_ns,
             "args": args or {}}
            for track, name, start_ns, dur_ns, args in lanes
        ]

    @property
    def dropped(self):
        return self._dropped

    @property
    def epoch_ns(self):
        """When the current capture began (``start()``), on
        ``perf_counter_ns``: an event stamped before it is another
        capture's."""
        return self._epoch_ns

    # -- export ------------------------------------------------------------
    def chrome_trace(self):
        """The trace as a chrome://tracing-loadable dict: complete ('X')
        events with ts/dur in microseconds, instant ('i') events, and
        process/thread metadata ('M') so tracks carry real names."""
        pid = os.getpid()
        with self._lock:
            spans = list(self._spans)
            instants = list(self._instants)
            lanes = list(self._lanes)
            epoch = self._epoch_ns
            dropped = self._dropped
        events = [
            {
                "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                "args": {"name": "paddle_tpu"},
            }
        ]
        seen_tids = {}
        for name, cat, start_ns, dur_ns, tid, tname, depth, args in spans:
            seen_tids.setdefault(tid, tname)
            ev = {
                "name": name,
                "cat": cat,
                "ph": "X",
                "ts": (start_ns - epoch) / 1e3,
                "dur": dur_ns / 1e3,
                "pid": pid,
                "tid": tid,
            }
            if args or depth:
                ev["args"] = dict(args or {})
                ev["args"]["depth"] = depth
            events.append(ev)
        for name, cat, ts_ns, _dur, tid, tname, _depth, args in instants:
            seen_tids.setdefault(tid, tname)
            ev = {
                "name": name,
                "cat": cat,
                "ph": "i",
                "s": "t",
                "ts": (ts_ns - epoch) / 1e3,
                "pid": pid,
                "tid": tid,
            }
            if args:
                ev["args"] = dict(args)
            events.append(ev)
        # a lane is a track of its own: small tids, which no thread's
        # ident (an address) takes; 0 carries the process's name
        lane_tids = {}
        for track, name, start_ns, dur_ns, args in lanes:
            ev = {
                "name": name,
                "cat": "lane",
                "ph": "X",
                "ts": (start_ns - epoch) / 1e3,
                "dur": dur_ns / 1e3,
                "pid": pid,
                "tid": lane_tids.setdefault(track, len(lane_tids) + 1),
            }
            if args:
                ev["args"] = dict(args)
            events.append(ev)
        seen_tids.update((tid, track) for track, tid in lane_tids.items())
        for tid, tname in seen_tids.items():
            events.append({
                "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                "args": {"name": tname},
            })
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {"producer": "paddle_tpu.observability",
                          "dropped_events": dropped},
        }

    def export(self, path):
        """Write the Chrome-trace JSON; returns the number of trace events
        written (metadata included)."""
        doc = self.chrome_trace()
        d = os.path.dirname(os.path.abspath(path))
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f)
        return len(doc["traceEvents"])


_TRACER = Tracer()


def get_tracer():
    return _TRACER


def tracing_enabled():
    return _TRACER.enabled


def lanes_enabled():
    return _TRACER.lanes_on


def enable_tracing(max_events=None, lanes=False):
    _TRACER.start(max_events=max_events, lanes=lanes)
    return _TRACER


def disable_tracing():
    _TRACER.stop()
    return _TRACER


def export_chrome_trace(path):
    return _TRACER.export(path)


class tracing:
    """Context manager: enable the default tracer, optionally exporting a
    Chrome-trace JSON on exit; ``lanes=True`` asks the lanes' producers
    to record too (module docstring).

        with tracing("/tmp/step.trace.json") as tr: ...
    """

    def __init__(self, path=None, max_events=None, lanes=False):
        self.path = path
        self.max_events = max_events
        self.lanes = lanes

    def __enter__(self):
        return enable_tracing(max_events=self.max_events, lanes=self.lanes)

    def __exit__(self, *exc):
        disable_tracing()
        if self.path:
            export_chrome_trace(self.path)
        return False


def _profiler_annotation(name, args):
    """An entered ``jax.profiler.TraceAnnotation`` while a jax.profiler
    trace is running, else None. jax is never imported from here: a
    running trace means it already is."""
    jax = sys.modules.get("jax")
    if jax is None or not jax.profiler.TraceAnnotation.is_enabled():
        return None
    ann = jax.profiler.TraceAnnotation(name, **(args or {}))
    ann.__enter__()
    return ann


class trace_scope:
    """RAII span: context manager or decorator; nests freely across
    threads (each thread is its own track). Disabled cost is one global
    attribute check."""

    __slots__ = ("name", "cat", "args", "_t0", "_ann")

    def __init__(self, name, cat="host", **args):
        self.name = name
        self.cat = cat
        self.args = args or None
        self._t0 = None

    def __enter__(self):
        tr = _TRACER
        if not tr.enabled:
            self._t0 = None
            return self
        tr._stack().append(self.name)
        # the annotation first, as the benchmark's anchor does: the two
        # clocks are then read in the same order at every span
        self._ann = _profiler_annotation(self.name, self.args)
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        if self._t0 is None:
            return False
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        tr = _TRACER
        stack = tr._stack()
        if stack:
            stack.pop()
        tr.record_span(self.name, self.cat, self._t0, t1, len(stack),
                       self.args)
        self._t0 = None
        return False

    def set(self, **args):
        """Arguments known only once the span is open (a byte count, a
        request id). For a live span only (``_ann`` exists from its
        ``__enter__`` on): ``span()``'s ``as`` target is None when tracing
        is off, so the values are never computed then."""
        self.args = {**self.args, **args} if self.args else args
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def elapsed_ns(self):
        """Nanoseconds since the live span opened: one clock read, for a
        phase boundary inside a span that must not hold a child span."""
        return time.perf_counter_ns() - self._t0

    def opened_ns(self):
        """When the live span opened, on ``perf_counter_ns``: what turns an
        ``elapsed_ns`` into a time on the tracer's clock. No clock read."""
        return self._t0

    def __call__(self, fn):
        name, cat, args = self.name, self.cat, self.args or {}

        @functools.wraps(fn)
        def wrapped(*a, **kw):
            with trace_scope(name, cat, **args):
                return fn(*a, **kw)

        return wrapped


class _NullSpan:
    """What ``span()`` hands out while tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


def span(name, cat="host"):
    """``trace_scope(name, cat)`` while tracing is on, else a shared no-op:

        with span("decode::feeds") as sp:
            ...
            if sp is not None:
                sp.set(active=len(active))
    """
    if not _TRACER.enabled:
        return _NULL_SPAN
    return trace_scope(name, cat)


def instant(name, cat="event", **args):
    """Record an instant event on the default tracer (no-op when
    disabled)."""
    _TRACER.instant(name, cat=cat, **args)
