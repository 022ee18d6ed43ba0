"""Starts and stops the two tracers of a traced run together: jax's
profiler (device operations) and the repo's observability tracer (the
program's host spans), tied by one anchor so that ``trace.py`` can put both
on one clock."""

import contextlib
import glob
import os
import shutil

from benchmark import trace as tr


class Traced:
    """One traced stretch. Inside the window only the tracers run; the
    trace is read and reduced by ``read()``, after the window, so that
    parsing it does not take the host from the system under test."""

    def __init__(self, directory, on_chip):
        self.directory = directory
        self.on_chip = on_chip
        self.tracer_spans = None

    def read(self):
        """{"spans", "trace", "trace_window"}: the program's spans as
        [name, start_s, end_s] and, on the chip, ``trace.py``'s dict of
        device events on the same clock. Off the chip (the CPU rehearsal)
        there is no device plane: ``trace`` is None and the window is the
        tracer's own."""
        if not self.on_chip:
            spans = [[s["name"], s["start_ns"] * 1e-9,
                      (s["start_ns"] + s["dur_ns"]) * 1e-9]
                     for s in self.tracer_spans if s["name"] != tr.ANCHOR]
            (window,) = [s for s in spans if s[0] == tr.WINDOW]
            return {"spans": spans, "trace": None,
                    "trace_window": (window[1], window[2])}
        (path,) = glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb"))
        trace = tr.load_xplane(path)
        shutil.rmtree(self.directory, ignore_errors=True)
        return {"spans": tr.spans_on_trace_clock(self.tracer_spans, trace),
                "trace": trace,
                "trace_window": tr.host_event(trace, tr.WINDOW)}


@contextlib.contextmanager
def traced_window(traced):
    """Everything inside is the traced window of ``traced``."""
    import jax
    from paddle_tpu import observability as obs

    shutil.rmtree(traced.directory, ignore_errors=True)
    obs.enable_tracing()
    if traced.on_chip:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(traced.directory, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(tr.ANCHOR), \
                obs.trace_scope(tr.ANCHOR):
            pass
        with jax.profiler.TraceAnnotation(tr.WINDOW), \
                obs.trace_scope(tr.WINDOW):
            yield
    finally:
        if traced.on_chip:
            jax.profiler.stop_trace()
        obs.disable_tracing()
    traced.tracer_spans = obs.get_tracer().spans()
