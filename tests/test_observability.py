"""Observability subsystem tests: span tracer + Chrome-trace export,
metrics registry (histogram quantiles vs reference computation,
Prometheus exposition), NaN/Inf sanitizer attribution, rate-limited
logging, background fetchers, and the one-registry migration of
profiler/serving/supervisor telemetry."""

import json
import logging
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability as obs
from paddle_tpu import profiler
from paddle_tpu.core.ir import Program, program_guard
from paddle_tpu.observability.metrics import (
    Histogram,
    MetricsRegistry,
)
from paddle_tpu.observability.logger import RateLimitedLogger
from paddle_tpu.observability.sanitizer import NanInfError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tracer():
    t = obs.enable_tracing()
    yield t
    obs.disable_tracing()
    t.clear()


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_spans_nest_correctly(tracer):
    with obs.trace_scope("outer"):
        with obs.trace_scope("mid"):
            with obs.trace_scope("inner"):
                time.sleep(0.001)
    spans = {s["name"]: s for s in tracer.spans()}
    assert spans["outer"]["depth"] == 0
    assert spans["mid"]["depth"] == 1
    assert spans["inner"]["depth"] == 2
    # time containment: each child starts no earlier and ends no later
    for parent, child in (("outer", "mid"), ("mid", "inner")):
        p, c = spans[parent], spans[child]
        assert c["start_ns"] >= p["start_ns"]
        assert (c["start_ns"] + c["dur_ns"]) <= (p["start_ns"] + p["dur_ns"])


def test_trace_scope_decorator_and_args(tracer):
    @obs.trace_scope("work", kind="unit")
    def work(n):
        return n * 2

    assert work(21) == 42
    (span,) = tracer.spans()
    assert span["name"] == "work"
    assert span["args"]["kind"] == "unit"


def test_per_thread_tracks(tracer):
    def worker():
        with obs.trace_scope("in_thread"):
            pass

    t = threading.Thread(target=worker, name="obs-worker")
    t.start()
    t.join()
    with obs.trace_scope("in_main"):
        pass
    spans = {s["name"]: s for s in tracer.spans()}
    assert spans["in_thread"]["tid"] != spans["in_main"]["tid"]
    assert spans["in_thread"]["thread"] == "obs-worker"
    # thread nesting is independent: both are roots of their own track
    assert spans["in_thread"]["depth"] == 0


def test_chrome_trace_export_is_valid(tracer, tmp_path):
    with obs.trace_scope("alpha"):
        with obs.trace_scope("beta"):
            pass
    obs.instant("marker", detail="x")
    path = str(tmp_path / "trace.json")
    n = obs.export_chrome_trace(path)
    assert n >= 4  # 2 spans + instant + metadata
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"alpha", "beta"}
    for e in events:
        assert "ph" in e and "pid" in e and "tid" in e
        if e["ph"] == "X":
            assert "ts" in e and "dur" in e and e["dur"] >= 0
    instants = [e for e in events if e["ph"] == "i"]
    assert instants and instants[0]["name"] == "marker"
    names = [e for e in events if e["ph"] == "M"]
    assert any(e["name"] == "process_name" for e in names)
    assert any(e["name"] == "thread_name" for e in names)


def test_tracer_disabled_records_nothing():
    t = obs.get_tracer()
    assert not t.enabled
    before = len(t.spans())
    with obs.trace_scope("ghost"):
        pass
    obs.instant("ghost-instant")
    assert len(t.spans()) == before


def test_tracer_max_events_drops_not_grows():
    t = obs.enable_tracing(max_events=3)
    try:
        for i in range(10):
            with obs.trace_scope(f"s{i}"):
                pass
    finally:
        obs.disable_tracing()
    assert len(t.spans()) == 3
    assert t.dropped == 7
    t.clear()


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_histogram_quantiles_match_reference():
    h = Histogram("h_seconds", buckets=[1.0, 2.0, 4.0, 8.0])
    samples = [0.5] * 4 + [3.0] * 4 + [7.0] * 2
    for v in samples:
        h.observe(v)
    # reference computation: rank r = q*N walks cumulative bucket counts,
    # then linear interpolation between the bucket's bounds
    # p50: rank 5 -> bucket (2,4] (cum before = 4, c = 4): 2 + 2*(1/4)
    assert h.quantile(0.50) == pytest.approx(2.5)
    # p90: rank 9 -> bucket (4,8] (cum before = 8, c = 2): 4 + 4*(1/2)
    assert h.quantile(0.90) == pytest.approx(6.0)
    # p10: rank 1 -> bucket [0,1] : 0 + 1*(1/4)
    assert h.quantile(0.10) == pytest.approx(0.25)
    # bucket-width error bound vs the exact sample percentile
    for q in (0.25, 0.5, 0.75, 0.9):
        exact = float(np.percentile(samples, q * 100))
        got = h.quantile(q)
        lo_bound = max(b for b in (0.0, 1.0, 2.0, 4.0, 8.0) if b <= exact + 1e-9)
        hi_bound = min(b for b in (1.0, 2.0, 4.0, 8.0) if b >= exact - 1e-9)
        assert lo_bound - 1e-9 <= got <= hi_bound + 1e-9, (q, got, exact)
    assert h.count == 10
    assert h.sum == pytest.approx(sum(samples))
    assert h.avg == pytest.approx(np.mean(samples))


def test_histogram_monotone_and_inf_bucket():
    h = Histogram("h2", buckets=[1.0, 10.0])
    for v in (0.5, 5.0, 100.0, 200.0):  # two land in +Inf
        h.observe(v)
    qs = [h.quantile(q) for q in (0.1, 0.5, 0.9, 0.99)]
    assert qs == sorted(qs)
    assert h.quantile(0.99) == 10.0  # +Inf bucket reports last finite bound


def test_registry_counter_gauge_and_type_conflict():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert reg.counter("reqs_total") is c  # get-or-create
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("depth")
    g.set(7)
    g.dec(2)
    assert g.value == 5
    with pytest.raises(ValueError):
        reg.gauge("reqs_total")  # family type conflict


def test_registry_labels_isolate_series():
    reg = MetricsRegistry()
    a = reg.counter("served_total", labels={"engine": "a"})
    b = reg.counter("served_total", labels={"engine": "b"})
    a.inc(3)
    b.inc(10)
    assert a.value == 3 and b.value == 10
    text = reg.to_text()
    assert 'served_total{engine="a"} 3' in text
    assert 'served_total{engine="b"} 10' in text


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("hits_total", "cache hits").inc(2)
    h = reg.histogram("lat_seconds", "latency", buckets=[0.1, 1.0])
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.to_text()
    assert "# TYPE hits_total counter" in text
    assert "# HELP hits_total cache hits" in text
    assert "# TYPE lat_seconds histogram" in text
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1.0"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert "lat_seconds_count 3" in text
    # dotted names sanitize to legal prometheus names
    reg.counter("serving.admitted").inc()
    assert "serving_admitted 1" in reg.to_text()


# ---------------------------------------------------------------------------
# sanitizer
# ---------------------------------------------------------------------------

def test_sanitizer_pinpoints_injected_nan_op(rng):
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", shape=[-1, 4])
        bad = fluid.layers.log(fluid.layers.scale(x, scale=-1.0))
        loss = fluid.layers.mean(bad)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    with pytest.raises(NanInfError) as ei:
        with obs.sanitize_nan_inf():
            exe.run(main, feed={"x": rng.rand(2, 4).astype("float32")},
                    fetch_list=[loss])
    err = ei.value
    assert err.op_type == "log"
    assert err.var_name and "tmp" in err.var_name
    assert err.op_callstack, "user callstack must be attached"
    # the callstack points at USER code (this test file), not the executor
    assert any("test_observability" in line for line in err.op_callstack)
    assert "NaN" in str(err)
    # violation counted in the registry, labeled by op
    v = obs.registry().get("sanitizer_violations_total", labels={"op": "log"})
    assert v is not None and v.value >= 1


def test_sanitizer_scoped_flag_restores(rng):
    from paddle_tpu.utils.flags import flags

    assert not flags.check_nan_inf
    with obs.sanitize_nan_inf():
        assert flags.check_nan_inf
    assert not flags.check_nan_inf


# ---------------------------------------------------------------------------
# rate-limited logging
# ---------------------------------------------------------------------------

def test_rate_limited_logger_caps_then_summarizes(caplog):
    lg = logging.getLogger("paddle_tpu.test.ratelimit")
    limited = RateLimitedLogger(lg, max_records=3)
    with caplog.at_level(logging.WARNING, logger=lg.name):
        for i in range(10):
            limited.warning("bad record %d", i)
        n = limited.summarize(what="bad records")
    msgs = [r.getMessage() for r in caplog.records]
    passed_through = [m for m in msgs if m.startswith("bad record")]
    assert len(passed_through) == 3  # capped
    assert any("rate limit reached" in m for m in msgs)
    assert any("10 bad records total (3 logged, 7 suppressed" in m
               for m in msgs)
    assert n == 10
    assert limited.total == 10


def test_robust_reader_logs_are_rate_limited(caplog):
    class Flaky:
        def __init__(self, n, bad_every=2):
            self.i = 0
            self.n = n
            self.bad_every = bad_every

        def __iter__(self):
            return self

        def __next__(self):
            if self.i >= self.n:
                raise StopIteration
            self.i += 1
            if self.i % self.bad_every == 0:
                raise ValueError(f"bad record {self.i}")
            return self.i

    reader = fluid.io.robust(lambda: Flaky(40), max_skips=30)
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.reader.robust"):
        got = list(reader())
    assert len(got) == 20  # every odd record served
    msgs = [r.getMessage() for r in caplog.records]
    skips_logged = [m for m in msgs if m.startswith("skipping bad record")]
    assert len(skips_logged) == 8  # capped at log_first_n
    assert any("20 skipped records total (8 logged, 12 suppressed" in m
               for m in msgs)


# ---------------------------------------------------------------------------
# background fetchers
# ---------------------------------------------------------------------------

def test_fetch_handler_monitor_delivers_latest():
    seen = []

    class H(fluid.FetchHandler):
        def handler(self, fetch_vars):
            seen.append(dict(fetch_vars))

    mon = obs.FetchHandlerMonitor(H(period_secs=0.05)).start()
    for i in range(3):
        mon.update({"loss": i})
        time.sleep(0.07)
    mon.stop()
    assert seen, "monitor never delivered"
    assert seen[-1]["loss"] == 2
    # delivers the LATEST value, not a backlog of every update
    assert len(seen) <= 5


def test_fetch_handler_background_in_train_from_dataset(tmp_path, rng):
    lines = []
    for i in range(8):
        x = rng.rand(4)
        lines.append("4 " + " ".join(f"{v:.4f}" for v in x)
                     + f" 1 {x.sum():.4f}")
    p = tmp_path / "d.txt"
    p.write_text("\n".join(lines) + "\n")

    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", shape=[-1, 4])
        y = fluid.data("y", shape=[-1, 1])
        pred = fluid.layers.fc(x, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    ds = fluid.DatasetFactory().create_dataset("InMemoryDataset")
    ds.set_batch_size(4)
    ds.set_use_var([x, y])
    ds.set_filelist([str(p)])

    seen = []

    class H(fluid.FetchHandler):
        def handler(self, fetch_vars):
            seen.append(dict(fetch_vars))

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    exe.train_from_dataset(
        main, ds, fetch_list=[loss],
        fetch_handler=H(period_secs=0.02, background=True),
    )
    # the final stop() tick guarantees at least one delivery
    assert seen and loss.name in seen[-1]


def test_periodic_metrics_dump_writes_scrape(tmp_path):
    path = str(tmp_path / "metrics.prom")
    obs.registry().counter("dump_probe_total").inc(3)
    dump = obs.PeriodicMetricsDump(path, period_secs=30)
    dump.start()
    dump.stop()  # final tick writes
    with open(path) as f:
        text = f.read()
    assert "dump_probe_total 3" in text


# ---------------------------------------------------------------------------
# one-registry migration: profiler / serving / supervisor / executor
# ---------------------------------------------------------------------------

def test_profiler_counters_land_in_registry():
    profiler.reset_profiler()
    profiler.start_profiler()
    try:
        profiler.incr_counter("probe.count", 5)
    finally:
        profiler.stop_profiler()
    assert profiler.get_counters()["probe.count"] == 5
    series = obs.registry().get("profiler_counter_total",
                                labels={"name": "probe.count"})
    assert series is not None and series.value == 5
    profiler.reset_profiler()
    assert series.value == 0  # reset flows through to the registry mirror


def test_record_event_feeds_tracer_and_histogram(tracer):
    profiler.reset_profiler()
    profiler.start_profiler()
    try:
        with profiler.RecordEvent("bridged"):
            pass
    finally:
        profiler.stop_profiler()
    assert any(s["name"] == "bridged" for s in tracer.spans())
    h = obs.registry().get("profiler_event_seconds",
                           labels={"event": "bridged"})
    assert h is not None and h.count >= 1


def test_serving_metrics_per_engine_isolation():
    from paddle_tpu.serving.metrics import ServingMetrics

    a = ServingMetrics(engine_label="iso-a")
    b = ServingMetrics(engine_label="iso-b")
    a.incr("admitted", 3)
    b.incr("admitted", 10)
    assert a.snapshot()["admitted"] == 3
    assert b.snapshot()["admitted"] == 10
    text = obs.scrape_text()
    assert 'serving_admitted_total{engine="iso-a"} 3' in text
    assert 'serving_admitted_total{engine="iso-b"} 10' in text


def test_serving_latency_percentiles_from_histogram():
    from paddle_tpu.serving.metrics import ServingMetrics

    m = ServingMetrics(engine_label="hist-test")

    class R:
        pass

    for wait in [0.001] * 8 + [0.02] * 2:
        r = R()
        r.submit_time = 100.0
        r.dispatch_time = 100.0 + wait

        class Resp:
            finish_time = None

        r.response = Resp()
        m.observe_request(r)
    snap = m.snapshot()
    assert snap["queue_wait_count"] == 10
    assert snap["queue_wait_p99_s"] >= snap["queue_wait_p50_s"] > 0
    # p50 sits in the bucket containing 1ms, p99 in the one containing 20ms
    assert snap["queue_wait_p50_s"] <= 0.0025
    assert snap["queue_wait_p99_s"] >= 0.01


def test_supervisor_events_land_in_registry_and_tracer(tracer):
    from paddle_tpu.resilience.supervisor import GangSupervisor

    before = obs.registry().get("resilience_events_total",
                                labels={"kind": "probe_event"})
    base = before.value if before is not None else 0
    sup = GangSupervisor(["true"], nproc=1)
    sup._emit("probe_event", rank=0, detail="x")
    series = obs.registry().get("resilience_events_total",
                                labels={"kind": "probe_event"})
    assert series is not None and series.value == base + 1
    assert any(i["name"] == "resilience.probe_event"
               for i in tracer.instants())
    assert sup.events[-1]["kind"] == "probe_event"


def test_executor_cache_counters(rng):
    from paddle_tpu.core.executor import _CACHE_HITS, _CACHE_MISSES

    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", shape=[-1, 4])
        h = fluid.layers.fc(x, size=2)
        loss = fluid.layers.mean(h)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    h0, m0 = _CACHE_HITS.value, _CACHE_MISSES.value
    feed = {"x": rng.rand(2, 4).astype("float32")}
    exe.run(main, feed=feed, fetch_list=[loss])
    exe.run(main, feed=feed, fetch_list=[loss])
    exe.run(main, feed=feed, fetch_list=[loss])
    assert _CACHE_MISSES.value == m0 + 1  # one trace+compile
    assert _CACHE_HITS.value == h0 + 2    # then steady-state hits


def test_executor_spans_cover_compile_and_execute(tracer, rng):
    # the compile cache is content-addressed and process-wide: an
    # identical program lowered by an earlier test would be served from
    # the memory tier (no trace span) — start cold
    from paddle_tpu.core import compile_cache

    compile_cache.clear_memory_cache()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", shape=[-1, 4])
        loss = fluid.layers.mean(fluid.layers.fc(x, size=2))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = {"x": rng.rand(2, 4).astype("float32")}
    exe.run(main, feed=feed, fetch_list=[loss])
    exe.run(main, feed=feed, fetch_list=[loss])
    names = [s["name"] for s in obs.get_tracer().spans()]
    assert "executor::plan" in names
    assert "executor::trace_compile_execute" in names
    assert "executor::execute" in names
    assert "executor::feed" in names
    assert "executor::fetch" in names


# ---------------------------------------------------------------------------
# CLI smoke (fast-tier wiring, like chaos_train)
# ---------------------------------------------------------------------------

def test_trace_view_smoke_cli(tmp_path):
    """tools/trace_view.py --smoke: capture a train step + serving burst,
    export valid Chrome-trace JSON with nested compile/execute/batch-form
    spans, verify the single registry and the <=2% disabled overhead."""
    out = str(tmp_path / "smoke.trace.json")
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_view.py"),
         "--smoke", "--out", out],
        capture_output=True, text=True, timeout=560,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    assert "TRACE_SMOKE_OK" in res.stdout, res.stdout
    with open(out) as f:
        doc = json.load(f)
    assert doc["traceEvents"]


def test_trace_view_summarize_mode(tmp_path, tracer):
    with obs.trace_scope("sum-probe"):
        pass
    obs.disable_tracing()
    path = str(tmp_path / "t.json")
    obs.export_chrome_trace(path)
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_view.py"),
         "--mode", "summarize", "--trace", path],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert res.returncode == 0, res.stderr
    assert "sum-probe" in res.stdout
