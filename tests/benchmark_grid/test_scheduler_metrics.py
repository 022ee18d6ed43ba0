"""The per-layer metrics that read the decode scheduler from inside (ISSUE
25): ten data files under ``benchmark/metrics/`` over readers that exist,
held here to the manifest, to a CPU rehearsal of the serving cell and of a
training cell, to a hand-built trace of two scheduler iterations, and (the
one clock) to the profiler's own annotations.

Like its neighbour, this module loads no TPU library while it is imported.
"""

import glob
import json
import os
import statistics
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, readers  # noqa: E402
from benchmark import trace as tr  # noqa: E402

BENCH = manifest.load_manifest()
SERVING = "decoder_1024x24.chat_steady"
TRAINING = "resnet50.train_b128"

# metric -> (reader, what it reads in the program)
NEW = {
    "serve_queue_wait_ms": ("histogram_mean", "serving_queue_wait_seconds"),
    "serve_first_token_ms": ("histogram_mean",
                             "serving_decode_first_token_seconds"),
    "serve_inter_token_ms": ("histogram_mean",
                             "serving_decode_inter_token_seconds"),
    "prefill_kv_fetch_ms": ("span_stat", "decode::prefill_fetch"),
    "decode_logits_fetch_ms": ("span_stat", "decode::step_fetch"),
    "decode_feeds_ms": ("span_stat", "decode::feeds"),
    "decode_sample_ms": ("span_stat", "decode::sample"),
    "serve_fed_mb_per_step": ("counter_ratio", "serving_fed_bytes_total"),
    "serve_fetched_mb_per_step": ("counter_ratio",
                                  "serving_fetched_bytes_total"),
    "serve_shed": ("counter_delta", "serving_brownout_shed_total"),
}


def _read(run, name):
    """What the metric's file reads out of ``run``, through its reader."""
    spec = manifest.load_metric(name)
    return readers.READERS[spec["reader"]](spec["args"], run)


# -- the files ----------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(NEW))
def test_metric_file_passes_the_manifest_and_names_what_it_reads(name):
    spec = manifest.load_metric(name)
    reader, source = NEW[name]
    assert spec["reader"] == reader and reader in readers.READERS
    assert source in json.dumps(spec["args"])
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    for key in ("layer", "unit", "better", "source", "moves"):
        assert spec[key] == entry[key], key
    # the entry alone lists the cells; a later cell appends itself
    assert "workloads" not in spec and SERVING in entry["workloads"]
    assert entry["moves"] == "serve_token_latency_p50"
    assert entry["layer"] in ("decode scheduler", "model step, serving")
    assert entry["source"] == {"span_stat": "program_span"}.get(
        reader, "program_counter")
    assert spec["what"] and "\n" not in spec["what"]


def test_the_new_entries_come_last_and_nothing_before_them_moved():
    """PR 25's ten, found by name: they follow what was there before them
    in the order they were added, and what was there kept its order. Later
    PRs append after them (PR 29 two, PR 35 two)."""
    names = [m["name"] for m in BENCH["per_layer"]]
    first = names.index(next(iter(NEW)))
    assert names[first:first + len(NEW)] == list(NEW)
    assert names[:first] == [
        "cache_load_s", "window_compiles.train", "window_compiles.serve",
        "host_step_ms.train", "train_mfu", "flash_device_share",
        "flash_attention_roofline", "collective_exposed_ms",
        "decode_step_ms", "decode_occupancy", "prefill_share",
        "serve_output_rate", "serve_requests_finished",
        "generator_lateness_ms", "decode_step_device_ms",
        "device_idle.train", "device_idle.serve", "hbm_compiled_gb"]
    assert len(set(names)) == len(names)


# -- a rehearsal of the serving cell and of a training cell -------------------

@pytest.fixture(scope="module")
def rehearsed():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    procs = {
        cell: subprocess.Popen(
            [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
             "--workload", cell, "--seed", str(3000000011 + i),
             "--seconds", "1", "--trace", "1", "--rehearse-cpu"],
            cwd=ROOT, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True)
        for i, cell in enumerate((SERVING, TRAINING))}
    lines = {}
    for cell, p in procs.items():
        stdout, stderr = p.communicate(timeout=300)
        assert p.returncode == 0, stderr[-2000:]
        lines[cell] = json.loads(stdout.strip().splitlines()[-1])
    return lines


def test_the_serving_rehearsal_reads_every_new_metric(rehearsed):
    line = rehearsed[SERVING]
    assert line["correct"] is True and line["failed"] == 0
    units = {m["name"]: m["unit"] for m in BENCH["per_layer"]}
    for name in NEW:
        # present: its reader found its histogram, counter or span; null,
        # as every value of a CPU run is
        assert line["metrics"][name] == {"value": None, "unit": units[name]}


def test_the_tail_stands_beside_the_median_as_a_per_layer_reading(rehearsed):
    """PR 35: a window finishes a hundred-odd requests and the 90th
    percentile is a dozen of them, so it carries no bound; the driver still
    measures it over every request that finished, as it does the median."""
    assert "serve_token_latency_p90" not in [
        m["name"] for m in BENCH["end_to_end"]]
    (entry,) = [m for m in BENCH["per_layer"]
                if m["name"] == "serve_token_latency_p90"]
    assert (entry["unit"], entry["moves"], entry["source"]) == (
        "ms/token", "serve_token_latency_p50", "host_clock")
    assert rehearsed[SERVING]["metrics"]["serve_token_latency_p90"] == {
        "value": None, "unit": "ms/token"}
    assert "serve_token_latency_p90" not in rehearsed[TRAINING]["metrics"]
    assert _read({"facts": {"token_latency_p90_ms": 6.9}},
                 "serve_token_latency_p90") == 6.9
    assert _read({"facts": {}}, "serve_token_latency_p90") is None


def test_a_training_cell_leaves_them_out(rehearsed):
    line = rehearsed[TRAINING]
    assert line["correct"] is True and line["metrics"]
    assert not set(NEW) & set(line["metrics"])


def test_their_readers_find_nothing_in_a_run_that_served_nothing():
    """What the parent commit, or a training cell, gives these readers: a
    registry without the families and spans without the names. Each
    returns None and does not raise."""
    families = {"executor_cache_misses_total": {"{}": 1},
                "serving_decode_steps_total": {'{engine="e"}': 40}}
    run = {"registry": ({}, families), "facts": {"window_s": 1.0},
           "sizes": {"model": {}},
           "spans": [["executor::execute", 0.1, 0.2],
                     ["decode::step", 0.3, 0.4]],
           "trace_window": (0.0, 1.0)}
    for name in NEW:
        assert _read(run, name) is None, name


def test_the_registry_readers_read_the_window():
    label = '{engine="e"}'
    before = {"serving_queue_wait_seconds": {label: {"count": 2, "sum": 1.0}},
              "serving_decode_first_token_seconds": {
                  label: {"count": 2, "sum": 1.0}},
              "serving_decode_inter_token_seconds": {
                  label: {"count": 2, "sum": 0.2}},
              "serving_fed_bytes_total": {label: 1_000_000},
              "serving_fetched_bytes_total": {label: 2_000_000},
              "serving_step_launches_total": {label: 10},
              "serving_brownout_shed_total": {label: 0}}
    after = {"serving_queue_wait_seconds": {label: {"count": 12, "sum": 1.8}},
             "serving_decode_first_token_seconds": {
                 label: {"count": 12, "sum": 4.86}},
             "serving_decode_inter_token_seconds": {
                 label: {"count": 12, "sum": 1.53}},
             "serving_fed_bytes_total": {label: 3_001_000_000},
             "serving_fetched_bytes_total": {label: 5_602_000_000},
             "serving_step_launches_total": {label: 110},
             "serving_brownout_shed_total": {label: 3}}
    run = {"registry": (before, after), "facts": {"window_s": 51.0},
           "sizes": {"model": {"slots": 48}}}

    assert _read(run, "serve_queue_wait_ms") == pytest.approx(80.0)
    assert _read(run, "serve_first_token_ms") == pytest.approx(386.0)
    assert _read(run, "serve_inter_token_ms") == pytest.approx(133.0)
    assert _read(run, "serve_fed_mb_per_step") == pytest.approx(30.0)
    assert _read(run, "serve_fetched_mb_per_step") == pytest.approx(56.0)
    assert _read(run, "serve_shed") == 3


# -- two scheduler iterations, by hand ------------------------------------------

@pytest.fixture(scope="module")
def hand():
    with open(os.path.join(os.path.dirname(__file__),
                           "hand_trace_scheduler.json")) as f:
        doc = json.load(f)
    trace = doc["trace"]
    spans = tr.spans_on_trace_clock(doc["tracer_spans"], trace)
    return trace, spans, tr.host_event(trace, tr.WINDOW)


def test_the_steps_module_still_goes_to_decode_step(hand):
    trace, spans, window = hand
    assert window == (1.0, 3.0)
    by_span = tr.module_seconds_by_span(trace["devices"]["0"], spans, window)
    # each module starts 10 ms after its launch span opened, before the
    # fetch span that follows opens: iterate, admit and feeds started
    # earlier, no span starts inside a launch span
    assert by_span == {"decode::prefill": pytest.approx(0.20),
                       "decode::inject": pytest.approx(0.12),
                       "decode::step": pytest.approx(0.84)}
    run = {"trace": trace, "spans": spans, "trace_window": window}
    spec = manifest.load_metric("decode_step_device_ms")
    assert readers.device_seconds_per_span(spec["args"], run) == \
        pytest.approx(420.0)


def test_the_gap_after_a_prefill_goes_to_the_fetch_that_fills_it(hand):
    trace, spans, window = hand
    busy, gaps = tr.busy_and_gaps(trace["devices"]["0"], window)
    assert busy == pytest.approx(1.16)
    idle = tr.attribute_gaps(gaps, tr.span_segments(spans))
    # [1.00,1.13] window 0.05, iterate 0.05, admit 0.02, prefill 0.01
    # [1.33,1.63] prefill_fetch until 1.60, admit 0.02, inject 0.01
    # [1.75,1.87] inject 0.03, admit 0.02, iterate 0.02, feeds 0.03,
    #             iterate 0.01, step 0.01
    # [2.29,2.52] step 0.01, step_fetch 0.05, iterate 0.01, sample 0.06,
    #             iterate 0.03, between the iterations 0.01, iterate 0.01,
    #             feeds 0.03, iterate 0.01, step 0.01
    # [2.94,3.00] step 0.01, step_fetch 0.02, sample 0.02, iterate 0.01
    assert idle == {
        "bench::window": pytest.approx(0.05),
        "decode::iterate": pytest.approx(0.15),
        "decode::admit": pytest.approx(0.06),
        "decode::prefill": pytest.approx(0.01),
        "decode::prefill_fetch": pytest.approx(0.27),
        "decode::inject": pytest.approx(0.04),
        "decode::feeds": pytest.approx(0.06),
        "decode::step": pytest.approx(0.04),
        "decode::step_fetch": pytest.approx(0.07),
        "decode::sample": pytest.approx(0.08),
        "after:decode::iterate": pytest.approx(0.01),
    }
    assert sum(idle.values()) == pytest.approx(2.0 - 1.16)
    ranked = sorted(idle, key=idle.get, reverse=True)
    assert ranked[0] == "decode::prefill_fetch"
    assert not [name for name in ranked[:5] if name.startswith("after:")]
    under = sum(v for k, v in idle.items() if k.startswith("decode::"))
    assert under / sum(idle.values()) >= 0.90


def test_the_span_readers_take_the_phases_means(hand):
    trace, spans, window = hand
    run = {"trace": trace, "spans": spans, "trace_window": window}

    assert _read(run, "prefill_kv_fetch_ms") == pytest.approx(400.0)
    assert _read(run, "decode_logits_fetch_ms") == pytest.approx(35.0)   # 50, 20
    assert _read(run, "decode_feeds_ms") == pytest.approx(30.0)
    assert _read(run, "decode_sample_ms") == pytest.approx(40.0)         # 60, 20
    # the pattern is anchored: decode::step_fetch is not decode::step
    assert readers.span_stat({"pattern": "^decode::step$", "stat": "count"},
                             run) == 2


# -- one clock ----------------------------------------------------------------------

def test_a_spans_annotation_and_its_anchor_shifted_tracer_span_agree(
        tmp_path):
    """While a jax.profiler trace runs, every enabled span also opens a
    TraceAnnotation of its name; shifted by the benchmark's anchor, the
    tracer's start of a span lies within 100 us of its annotation's (the
    median everywhere; every span on the chip, whose host is not shared
    with five other test workers)."""
    import jax
    from jax.profiler import ProfileData

    from paddle_tpu import observability as obs

    obs.enable_tracing()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(tr.ANCHOR), \
                obs.trace_scope(tr.ANCHOR):
            pass
        for i in range(40):
            with obs.span("decode::iterate") as it:
                it.set(iteration=i)
                with obs.trace_scope("decode::step", request=i):
                    time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
        obs.disable_tracing()
    spans = obs.get_tracer().spans()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    annotations = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == tr.ANCHOR or e.name.startswith("decode::"):
                    annotations.setdefault(e.name, []).append(
                        (e.start_ns * 1e-9, dict(e.stats)))
    # two of them: the benchmark's own, opened first, and the one the
    # tracer's anchor span opened inside it; trace.py takes the first
    anchor_a = min(annotations[tr.ANCHOR])
    assert len(annotations[tr.ANCHOR]) == 2
    (anchor_t,) = [s for s in spans if s["name"] == tr.ANCHOR]
    shift = anchor_a[0] - anchor_t["start_ns"] * 1e-9
    apart = []
    for name in ("decode::iterate", "decode::step"):
        ours = sorted(s["start_ns"] * 1e-9 + shift
                      for s in spans if s["name"] == name)
        theirs = sorted(annotations[name])
        assert len(ours) == len(theirs) == 40
        apart += [abs(a - b[0]) for a, b in zip(ours, theirs)]
        # the arguments ride along, those set late too
        key = "iteration" if name == "decode::iterate" else "request"
        assert sorted(stats[key] for _t, stats in theirs) == list(range(40))
    assert statistics.median(apart) < 100e-6
    if jax.devices()[0].platform == "tpu":
        assert max(apart) < 100e-6
