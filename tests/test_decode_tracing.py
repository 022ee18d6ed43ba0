"""The decode scheduler measured from inside (ISSUE 25).

Hand-stepped ``_iterate`` over a toy decoder: tracing off records nothing,
reads the tracer's clock never and serves the same bytes; tracing on, every
phase of an iteration is a span inside one ``decode::iterate``, a request's
spans carry its id, every returned token has a time stamp, the bytes that
cross the device boundary are counted, and the overload decisions (brownout
transitions, refusals at the door) are events with a time. ``RecordEvent``
and ``span()`` cost a check and nothing else while their gates are off.

Since ISSUE 34 a greedy step's fetch comes after the NEXT step's launch.
What is pinned here about the order of an iteration is pinned in both
orders, as cases: ``ahead`` is the built model, ``serial`` the same
programs without ``token_fetch``, whose every step fetches its logits and
lands before anything else is launched.

Since ISSUE 38 the loop's sleeps are ``decode::wait`` spans observed in
``serving_decode_wait_seconds``, the two halves of a step's launch are
observed in ``serving_decode_step_put_seconds`` / ``..._call_seconds``
whether tracing is on or off, and a step's launch and its landing carry
one ``launch`` number.

Since ISSUE 42 a chunked admission runs in the launch-ahead order too:
``decode::chunk`` says whether it was launched with a step in flight
(``ahead=``) and whether it is its prompt's last (``last=``), the
``decode::chunk_fetch`` of that last chunk's one row whether a launch was
made over it first (``deferred=``), and two counters stand beside the
spans, tracing on or off: ``serving_decode_drains_total{why=}`` (one per
``decode::step_fetch`` that carries ``drain=``) and
``serving_chunk_launches_ahead_total`` (one per ``decode::chunk`` with
``ahead=True``).
"""

import time

import jax
import numpy as np
import pytest
from decode_testing import without_token_fetch

from paddle_tpu import observability as obs
from paddle_tpu import profiler
from paddle_tpu.observability import tracer as tracer_mod
from paddle_tpu.serving.brownout import BrownoutController
from paddle_tpu.serving.decode import GenerationEngine, build_decoder_model
from paddle_tpu.serving.decode import engine as engine_mod
from paddle_tpu.serving.decode import metrics as metrics_mod
from paddle_tpu.serving.decode.metrics import TOKEN_BUCKETS
from paddle_tpu.serving.decode.model import DecodeModel
from paddle_tpu.serving.request import Priority, RejectedError, Response

PROMPT_LENS = (3, 9, 2, 12)     # two one-shot prefills, two chunked (C=4)
MAX_NEW = 5

# the spans of one iteration, by what they may lie in
PHASES = {"decode::admit", "decode::prefill", "decode::prefill_fetch",
          "decode::inject", "decode::chunk", "decode::chunk_fetch",
          "decode::feeds", "decode::step", "decode::step_fetch",
          "decode::sample"}
# the loop's sleep: in the ``ahead`` order the drain that delivers the last
# tokens is followed, in the same iteration, by the idle poll
WAIT = "decode::wait"
LAUNCHES = ("decode::step", "decode::prefill", "decode::chunk",
            "decode::inject")
WITH_REQUEST = {"decode::admit", "decode::prefill", "decode::prefill_fetch",
                "decode::inject", "decode::chunk", "decode::chunk_fetch"}


def _model(name):
    # 24 blocks of 4 rows for 4 slots: occupancy stays under the brownout
    # ladder's first rung, so no transition reads the clock
    return build_decoder_model(
        vocab_size=32, hidden=8, num_layers=2, slots=4, max_len=24,
        block_size=4, chunk_tokens=4, name=name, version="1")


def _prompts():
    rng = np.random.RandomState(3)
    return [[int(t) for t in rng.randint(0, 32, size=n)]
            for n in PROMPT_LENS]


ORDERS = ("ahead", "serial")


def _serve(name, traced, order="ahead", **submit):
    """Four requests hand-stepped to the end; returns (entry, requests'
    responses). The engine thread is never started."""
    engine = GenerationEngine(queue_depth=32, breaker_threshold=0)
    model = _model(name)
    entry = engine.register_model(
        model if order == "ahead" else without_token_fetch(model))
    if traced:
        obs.enable_tracing()
    try:
        resps = [engine.submit(p, max_new_tokens=MAX_NEW, **submit)
                 for p in _prompts()]
        for _ in range(400):
            if all(r.done() for r in resps):
                break
            entry._iterate()
    finally:
        if traced:
            obs.disable_tracing()
    assert all(r.done() for r in resps)
    return entry, resps


@pytest.fixture
def clean_tracer():
    obs.get_tracer().clear()
    yield obs.get_tracer()
    obs.disable_tracing()
    obs.get_tracer().clear()


@pytest.fixture(scope="module", params=ORDERS)
def traced_run(request):
    obs.get_tracer().clear()
    entry, resps = _serve("trc_on", traced=True, order=request.param)
    spans = obs.get_tracer().spans()
    obs.get_tracer().clear()
    entry.order = request.param
    return entry, resps, spans


class _CountingClock:
    """Stands in for the ``time`` module of one module and counts the
    reads of its clocks."""

    def __init__(self):
        self.reads = 0

    def perf_counter(self):
        self.reads += 1
        return time.perf_counter()

    def perf_counter_ns(self):
        self.reads += 1
        return time.perf_counter_ns()

    def __getattr__(self, name):
        return getattr(time, name)


# -- tracing off ---------------------------------------------------------------

def test_tracing_off_records_nothing_and_serves_the_same_bytes(
        clean_tracer, traced_run):
    entry, resps = _serve("trc_off", traced=False)
    assert clean_tracer.spans() == [] and clean_tracer.instants() == []
    assert entry._iteration == 0
    _entry, traced_resps, _spans = traced_run
    off = [r.result()["tokens"].tobytes() for r in resps]
    on = [r.result()["tokens"].tobytes() for r in traced_resps]
    assert off == on
    for p, r in zip(_prompts(), resps):
        assert [int(t) for t in r.result()["tokens"]] == \
            entry.offline_decode(p, MAX_NEW)


@pytest.mark.parametrize("order", ORDERS)
def test_tracing_off_an_iteration_reads_the_clock_as_the_parent_did_plus_one_per_request(
        clean_tracer, monkeypatch, order):
    """What the engine reads off ``time`` with tracing off: per decode step
    three (start, the tokens' ``now``, end), per one-shot prefill and per
    chunk two (start, end), per admitted request its dispatch time and —
    the one read ISSUE 25's instrumentation added — its first token's
    stamp. A step that is left in flight and then DRAINED is timed in two
    pieces, the body that launched it (start, end) and the drain (start,
    ``now``, end): two reads more for every launch that was not ahead of
    a fetch. Since ISSUE 38 the two always-on measurements add theirs:
    three per launch of the step program (its start, the end of the
    feeds' puts, the call's return: with tracing ON the span's own pair
    serves and the engine reads none) and two per sleep of the loop.
    Two idle iterations at the end make sure there are sleeps to count.
    The tracer's own clock is never read."""
    engine_clock, tracer_clock = _CountingClock(), _CountingClock()
    monkeypatch.setattr(engine_mod, "time", engine_clock)
    monkeypatch.setattr(tracer_mod, "time", tracer_clock)
    entry, resps = _serve("trc_clock", traced=False, order=order)
    for _ in range(2):
        entry._iterate()
    m = entry.metrics
    assert m.count("brownout_transitions") == 0
    admitted = len(resps)
    steps, ahead = m.count("decode_steps"), m.count("decode_steps_ahead")
    assert (ahead > 0) == (order == "ahead")
    drained = steps - ahead if order == "ahead" else 0
    st = entry.stats()
    launches, waits = m.count("step_launches"), st["decode_wait_count"]
    assert launches == steps == st["step_put_count"] == st["step_call_count"]
    assert waits == (3 if order == "ahead" else 2)
    # one more per request: GenerationRequest's submit_time
    assert engine_clock.reads == (
        3 * steps + 2 * drained + 2 * m.count("prefills")
        + 2 * m.count("chunk_runs") + 2 * admitted + admitted
        + 3 * launches + 2 * waits)
    assert tracer_clock.reads == 0


def test_span_off_is_one_shared_noop_and_builds_nothing(clean_tracer):
    a, b = tracer_mod.span("decode::feeds"), tracer_mod.span("decode::step")
    assert a is b is tracer_mod._NULL_SPAN
    with a as sp:
        assert sp is None
    assert clean_tracer.spans() == []


def test_record_event_reads_no_clock_when_both_gates_are_off(
        clean_tracer, monkeypatch):
    clock = _CountingClock()
    monkeypatch.setattr(profiler, "time", clock)
    assert not profiler._enabled and not obs.tracing_enabled()
    with profiler.RecordEvent("decode::step") as ev:
        assert ev.span is None
    assert clock.reads == 0
    # the report, a documented use, still times the event when asked to
    profiler.reset_profiler()
    profiler.start_profiler()
    try:
        with profiler.RecordEvent("decode::step"):
            pass
    finally:
        report = profiler.stop_profiler()
    assert clock.reads == 2
    assert [r["calls"] for r in report if r["name"] == "decode::step"] == [1]
    profiler.reset_profiler()


def test_record_event_hands_out_the_live_span_for_late_arguments(
        clean_tracer):
    obs.enable_tracing()
    with profiler.RecordEvent("decode::inject") as ev:
        assert ev.span is not None
        ev.span.set(request=7)
        ev.span.set(bytes=64)
    obs.disable_tracing()
    assert ev.span is None
    (s,) = clean_tracer.spans()
    assert s["name"] == "decode::inject" and s["cat"] == "event"
    assert s["args"] == {"request": 7, "bytes": 64}


# -- tracing on: the spans of an iteration ---------------------------------------

def _inside(inner, outer):
    return (outer["start_ns"] <= inner["start_ns"] and
            inner["start_ns"] + inner["dur_ns"]
            <= outer["start_ns"] + outer["dur_ns"])


def test_every_phase_lies_inside_one_iteration(traced_run):
    entry, _resps, spans = traced_run
    iterations = [s for s in spans if s["name"] == "decode::iterate"]
    numbers = [s["args"]["iteration"] for s in iterations]
    assert numbers == list(range(1, len(iterations) + 1))
    assert iterations[0]["args"]["queued"] == len(PROMPT_LENS)
    assert iterations[0]["args"]["active"] == 0
    assert iterations[1]["args"]["active"] == len(PROMPT_LENS)
    phases = [s for s in spans if s["name"] != "decode::iterate"]
    assert {s["name"] for s in phases} == PHASES | (
        {WAIT} if entry.order == "ahead" else set())
    for s in phases:
        holders = [it for it in iterations if _inside(s, it)]
        assert len(holders) == 1, s
    # the order of a step's phases inside their iteration
    for it in iterations:
        names = [s["name"][8:] for s in sorted(
            (s for s in phases if _inside(s, it)),
            key=lambda s: s["start_ns"])
            if s["name"] in ("decode::feeds", "decode::step",
                             "decode::step_fetch", "decode::sample")]
        if entry.order == "serial":
            # feeds, launch, the step's own fetch, its host half
            assert names in ([], ["feeds"],
                             ["feeds", "step", "step_fetch", "sample"]), names
        else:
            # a fetch and its host half go together, after the launch of
            # the next step (ahead) or before everything else (a drain,
            # at the iteration's top or when nothing steps again); a
            # launch may leave its step in flight
            assert names in (
                [], ["feeds"], ["feeds", "step"],
                ["feeds", "step", "step_fetch", "sample"],
                ["step_fetch", "sample"],
                ["step_fetch", "sample", "feeds"],
                ["step_fetch", "sample", "feeds", "step"],
                ["feeds", "step_fetch", "sample"]), names
    steps = [s for s in spans if s["name"] == "decode::step"]
    fetches = [s for s in spans if s["name"] == "decode::step_fetch"]
    ahead = [s["args"]["ahead"] for s in steps]
    drains = [s["args"].get("drain") for s in fetches]
    assert sum(ahead) == entry.metrics.count("decode_steps_ahead")
    if entry.order == "serial":
        assert not any(ahead) and set(drains) == {None}
    else:
        # every launch is ahead of a fetch or follows a drain, one for one
        assert any(ahead) and ahead[0] is False
        assert len(ahead) - sum(ahead) == len(drains) - drains.count(None)
        assert set(drains) - {None} <= {"admission", "prefill", "idle"}


def test_the_spans_of_a_request_carry_its_id(traced_run):
    _entry, resps, spans = traced_run
    by_request = {}
    for s in spans:
        if s["name"] in WITH_REQUEST:
            by_request.setdefault(s["args"]["request"], []).append(s["name"])
    assert len(by_request) == len(resps)
    ids = sorted(by_request)            # ids follow the order of submission
    for rid, n in zip(ids, PROMPT_LENS):
        names = by_request[rid]
        assert names.count("decode::admit") == 1
        if n <= 4:
            assert names.count("decode::prefill") == 1
            assert names.count("decode::prefill_fetch") == 1
            assert names.count("decode::inject") == 1
            assert "decode::chunk" not in names
        else:
            assert names.count("decode::chunk") == -(-n // 4)
            assert names.count("decode::chunk_fetch") == 1   # the last only
            assert "decode::prefill" not in names
    # a launch span's children of the admission lie inside decode::admit
    admits = [s for s in spans if s["name"] == "decode::admit"]
    for s in spans:
        if s["name"] in ("decode::prefill", "decode::prefill_fetch",
                         "decode::inject"):
            (holder,) = [a for a in admits if _inside(s, a)]
            assert holder["args"]["request"] == s["args"]["request"]


def test_a_launch_span_says_where_the_host_spent_its_time(traced_run):
    _entry, _resps, spans = traced_run
    launches = [s for s in spans if s["name"] in LAUNCHES]
    assert launches
    for s in launches:
        a = s["args"]
        assert a["bytes"] > 0
        assert 0 < a["put_ns"] and 0 < a["call_ns"]
        assert a["put_ns"] + a["call_ns"] <= s["dur_ns"]
    # no span opens inside a launch span: the device module of a launch
    # goes to the program span that started last before it
    for s in launches:
        assert not [c for c in spans if c is not s and _inside(c, s)
                    and c["depth"] > s["depth"]]
    # and none is named like the executor's, which host_step_ms.train sums
    assert not [s for s in spans
                if s["name"].startswith(("executor::", "compiled_program::"))]


def test_step_spans_carry_their_sizes(traced_run):
    entry, _resps, spans = traced_run
    m = entry.model
    steps = [s for s in spans if s["name"] == "decode::step"]
    fetches = [s for s in spans if s["name"] == "decode::step_fetch"]
    samples = [s for s in spans if s["name"] == "decode::sample"]
    feeds = [s for s in spans if s["name"] == "decode::feeds"]
    assert len(steps) == len(fetches) == len(samples) \
        == entry.metrics.count("decode_steps")
    assert len(feeds) >= len(steps)
    # four greedy requests: every step brings its [S, 1] tokens, chosen
    # by the step program, and leaves the logits on the device; the model
    # without them brings the logits, every step
    if entry.order == "ahead":
        assert {s["args"]["rows"] for s in fetches} == {"tokens"}
        assert {s["args"]["bytes"] for s in fetches} == {
            m.slots * jax.dtypes.canonicalize_dtype(np.int64).itemsize}
        assert entry.metrics.count("decode_logits_fetch_steps") == 0
    else:
        assert {s["args"]["rows"] for s in fetches} == {"logits"}
        assert {s["args"]["bytes"] for s in fetches} == {
            4 * m.slots * m.vocab_size}
        assert entry.metrics.count("decode_logits_fetch_steps") \
            == len(steps)
    assert sum(s["args"]["tokens"] for s in samples) == \
        entry.metrics.count("generated_tokens")
    assert all(1 <= s["args"]["active"] <= m.slots for s in feeds
               if s["args"])


def test_the_launch_histograms_hold_what_the_step_spans_say(traced_run):
    """Every launch of the step program is observed once in each of the
    two histograms, and with tracing on by the span's own pair of clock
    reads: the sums agree to within a microsecond a launch."""
    entry, _resps, spans = traced_run
    steps = [s for s in spans if s["name"] == "decode::step"]
    st = entry.stats()
    assert st["step_put_count"] == st["step_call_count"] == len(steps) \
        == entry.metrics.count("step_launches")
    for half in ("put", "call"):
        in_spans = sum(s["args"][half + "_ns"] for s in steps) * 1e-9
        observed = st[f"step_{half}_count"] * st[f"step_{half}_avg_s"]
        assert abs(observed - in_spans) <= 1e-6 * len(steps), half
    # the other programs' halves stay arguments of their spans
    assert all("put_ns" in s["args"] for s in spans
               if s["name"] in LAUNCHES)


def test_a_step_fetch_carries_the_launch_of_one_earlier_step(traced_run):
    """``launch`` is the running number of step launches on the
    ``decode::step`` span and on the ``decode::step_fetch`` that lands
    that step: one fetch a launch, after it, whether the fetch comes under
    the next launch (``ahead``) or in the launching body or a drain."""
    entry, _resps, spans = traced_run
    steps = [s for s in spans if s["name"] == "decode::step"]
    fetches = [s for s in spans if s["name"] == "decode::step_fetch"]
    numbers = [s["args"]["launch"] for s in steps]
    assert numbers == list(range(1, len(steps) + 1))
    assert numbers[-1] == entry.metrics.count("step_launches")
    assert sorted(f["args"]["launch"] for f in fetches) == numbers
    by_number = {s["args"]["launch"]: s for s in steps}
    later = 0
    for f in fetches:
        step = by_number[f["args"]["launch"]]
        assert step["start_ns"] + step["dur_ns"] <= f["start_ns"]
        # launches that started between this step's and its landing
        between = [s for s in steps
                   if step["start_ns"] < s["start_ns"] < f["start_ns"]]
        assert len(between) <= 1            # depth one
        later += len(between)
    assert later == entry.metrics.count("decode_steps_ahead")
    assert (later > 0) == (entry.order == "ahead")


# -- a chunked admission in the launch-ahead order (ISSUE 42) --------------------------

def test_a_chunk_says_ahead_and_last_and_its_rows_fetch_says_deferred(
        traced_run):
    entry, _resps, spans = traced_run
    spans = sorted(spans, key=lambda s: s["start_ns"])
    chunks = [s for s in spans if s["name"] == "decode::chunk"]
    rows = [s for s in spans if s["name"] == "decode::chunk_fetch"]
    steps = [s for s in spans if s["name"] == "decode::step"]
    assert len(chunks) == sum(-(-n // 4) for n in PROMPT_LENS if n > 4)
    by_request = {}
    for s in chunks:
        assert type(s["args"]["ahead"]) is type(s["args"]["last"]) is bool
        by_request.setdefault(s["args"]["request"], []).append(s)
    assert len(by_request) == len(rows) == 2
    for row in rows:
        mine = by_request[row["args"]["request"]]
        # the prompt's last chunk, and that one alone, says so
        assert [c["args"]["last"] for c in mine] \
            == [False] * (len(mine) - 1) + [True]
        assert mine[-1]["start_ns"] < row["start_ns"]
        # deferred: a step was launched between the chunk and its row's
        # fetch (every stepping slot lands in its own body when serial,
        # and the row still waits for that launch)
        over = [st for st in steps
                if mine[-1]["start_ns"] < st["start_ns"] < row["start_ns"]]
        assert row["args"]["deferred"] is bool(over)
        assert row["args"]["bytes"] == 4 * entry.model.vocab_size
    if entry.order == "serial":
        assert not any(c["args"]["ahead"] for c in chunks)
    else:
        # one prompt's last chunk found nothing stepping (its row was
        # fetched at once), the other's ran under a step in flight
        assert any(c["args"]["ahead"] for c in chunks)
        assert sorted(r["args"]["deferred"] for r in rows) == [False, True]


def test_the_drain_counter_equals_the_fetches_that_say_drain(traced_run):
    entry, _resps, spans = traced_run
    said = [s["args"]["drain"] for s in spans
            if s["name"] == "decode::step_fetch" and "drain" in s["args"]]
    drains = entry.metrics.drains()
    assert sorted(drains) == sorted(metrics_mod.DRAIN_REASONS)
    assert {why: said.count(why) for why in drains} == drains
    assert sum(drains.values()) == len(said)
    assert entry.stats()["decode_drains"] == drains
    if entry.order == "serial":
        assert said == []
    else:
        # all four were admitted with nothing in flight; no prompt's
        # last chunk drained; a step that nothing followed did
        assert drains["idle"] >= 1
        assert drains["admission"] == drains["prefill"] == 0
        assert drains["slots"] == 0
    # every reason is a series of the family from the start
    family = obs.registry().snapshot()["serving_decode_drains_total"]
    mine = {k: v for k, v in family.items()
            if f'engine="{entry.metrics.engine_label}"' in k}
    assert len(mine) == len(metrics_mod.DRAIN_REASONS)
    assert sum(mine.values()) == len(said)


def test_chunk_launches_ahead_equals_the_chunk_spans_that_say_ahead(
        traced_run):
    entry, _resps, spans = traced_run
    chunks = [s for s in spans if s["name"] == "decode::chunk"]
    m = entry.metrics
    assert m.count("chunk_runs") == len(chunks)
    assert m.count("chunk_launches_ahead") \
        == sum(s["args"]["ahead"] for s in chunks)
    assert entry.stats()["chunk_launches_ahead"] \
        == m.count("chunk_launches_ahead")


def test_the_two_counters_count_with_tracing_off(clean_tracer, traced_run):
    traced_entry, _resps, _spans = traced_run
    entry, _ = _serve("trc_off_drains", traced=False,
                      order=traced_entry.order)
    assert clean_tracer.spans() == []
    assert entry.metrics.drains() == traced_entry.metrics.drains()
    assert entry.metrics.count("chunk_launches_ahead") \
        == traced_entry.metrics.count("chunk_launches_ahead")
    assert "serving_decode_drains_total" in obs.scrape_text()
    assert "serving_chunk_launches_ahead_total" in obs.scrape_text()


# -- the loop's sleeps ------------------------------------------------------------------

@pytest.fixture(scope="module")
def idle_run():
    """Three iterations with nothing to do (the idle poll), two under an
    open breaker with two requests queued (the breaker's wait), then the
    requests served once the breaker is closed again."""
    obs.get_tracer().clear()
    engine = GenerationEngine(queue_depth=32, breaker_threshold=1,
                              breaker_cooldown_s=0.15)
    entry = engine.register_model(_model("trc_idle"))
    obs.enable_tracing()
    try:
        for _ in range(3):
            entry._iterate()
        resps = [engine.submit(p, max_new_tokens=MAX_NEW)
                 for p in _prompts()[:2]]
        entry._breaker.record_failure()
        assert entry._breaker.state == "open"
        for _ in range(2):
            entry._iterate()
        for _ in range(400):
            if all(r.done() for r in resps):
                break
            entry._iterate()
    finally:
        obs.disable_tracing()
    spans = obs.get_tracer().spans()
    obs.get_tracer().clear()
    assert all(r.done() and r.error() is None for r in resps)
    return entry, spans


def test_every_wait_lies_inside_one_iteration_and_says_why(idle_run):
    _entry, spans = idle_run
    iterations = [s for s in spans if s["name"] == "decode::iterate"]
    waits = [s for s in spans if s["name"] == WAIT]
    assert len(waits) >= 5
    for w in waits:
        assert len([it for it in iterations if _inside(w, it)]) == 1
        # nothing opens inside a wait: the loop is asleep
        assert not [c for c in spans if c is not w and _inside(c, w)
                    and c["depth"] > w["depth"]]
        assert set(w["args"]) == {"why", "queued", "parked", "pending"}
    assert [w["args"]["why"] for w in waits[:5]] == \
        ["idle"] * 3 + ["breaker"] * 2
    # a wait taken with requests queued says so
    assert [w["args"]["queued"] for w in waits[:5]] == [0, 0, 0, 2, 2]
    assert {(w["args"]["parked"], w["args"]["pending"])
            for w in waits} == {(0, 0)}
    # the idle poll is 20 ms; the breaker's waits are 0.1 s at most each
    # and together the 0.15 s of its cooldown
    assert all(15e6 < w["dur_ns"] < 500e6 for w in waits[:3])
    assert 0.1e9 < sum(w["dur_ns"] for w in waits[3:5]) < 1e9


def test_the_wait_histogram_sums_the_wait_spans(idle_run):
    """``serving_decode_wait_seconds`` observes each sleep from inside its
    span: the same sleeps, the sum a few clock reads and one ``set`` a
    wait under the spans' total."""
    entry, spans = idle_run
    waits = [s for s in spans if s["name"] == WAIT]
    st = entry.stats()
    assert st["decode_wait_count"] == len(waits)
    observed = st["decode_wait_count"] * st["decode_wait_avg_s"]
    in_spans = sum(w["dur_ns"] for w in waits) * 1e-9
    assert observed <= in_spans
    assert in_spans - observed < 2e-3 * len(waits)
    assert observed > 0.9 * in_spans


def test_the_new_histograms_are_cut_where_their_values_fall():
    from paddle_tpu.serving.decode.metrics import (LAUNCH_BUCKETS,
                                                   WAIT_BUCKETS)

    assert (WAIT_BUCKETS[0], WAIT_BUCKETS[-1]) == (1e-4, 0.1)
    assert (LAUNCH_BUCKETS[0], LAUNCH_BUCKETS[-1]) == (5e-5, 5e-2)
    for buckets in (WAIT_BUCKETS, LAUNCH_BUCKETS):
        assert list(buckets) == sorted(set(buckets))
    # a poll that runs out (20 ms and a little) has a bucket of its own
    assert 0.02 in WAIT_BUCKETS and 0.025 in WAIT_BUCKETS
    # both halves of a launch are 0.9-1.2 ms on the chip: 0.1 ms apart there
    inner = [b for b in LAUNCH_BUCKETS if 5e-4 <= b <= 1.5e-3]
    assert max(b - a for a, b in zip(inner, inner[1:])) < 1.01e-4


# -- a time stamp for every token ---------------------------------------------------

@pytest.mark.parametrize("mode", ["greedy", "beam", "speculative"])
def test_one_monotone_time_per_returned_token(mode):
    engine = GenerationEngine(queue_depth=32, breaker_threshold=0)
    entry = engine.register_model(lambda: _model("tt_" + mode))
    submit = {}
    if mode == "beam":
        submit = {"beam_width": 2}
    elif mode == "speculative":
        engine.register_model(lambda: build_decoder_model(
            vocab_size=32, hidden=8, num_layers=1, slots=4, max_len=24,
            block_size=4, chunk_tokens=4, name="tt_draft", version="1"))
        submit = {"draft_model": "tt_draft", "spec_k": 3, "draft_kv": False}
    before = time.perf_counter()
    resps = [engine.submit(p, model="tt_" + mode, max_new_tokens=MAX_NEW,
                           **submit) for p in _prompts()[:3]]
    with entry._cond:
        reqs = list(entry._queue.iter_requests())
    for _ in range(400):
        if all(r.done() for r in resps):
            break
        entry._iterate()
    for req, r in zip(reqs, resps):
        assert req.response is r
        tokens = r.result()["tokens"]
        assert len(r.token_times) == len(tokens) >= 1
        assert r.token_times == sorted(r.token_times)
        assert r.first_token_time == r.token_times[0]
        assert before <= req.submit_time <= req.dispatch_time \
            <= r.first_token_time
        assert r.token_times[-1] <= r.finish_time
    # observed at retirement, always on
    st = entry.stats()
    assert st["first_token_count"] == len(resps)
    assert st["inter_token_count"] == len(resps)
    assert st["first_token_avg_s"] > 0


def test_a_response_that_generated_nothing_has_no_token_times():
    r = Response()
    assert r.token_times == [] and r.first_token_time is None
    r._complete(outputs={})
    assert r.first_token_time is None


def test_token_histograms_resolve_ten_milliseconds_where_tokens_fall():
    inner = [b for b in TOKEN_BUCKETS if 0.05 <= b <= 0.5]
    assert len(inner) == 46
    assert max(b - a for a, b in zip(inner, inner[1:])) < 0.0101
    assert list(TOKEN_BUCKETS) == sorted(set(TOKEN_BUCKETS))
    from paddle_tpu.observability.metrics import Histogram

    h = Histogram("t", buckets=TOKEN_BUCKETS)
    for v in (0.131, 0.132, 0.133, 0.134, 0.139):
        h.observe(v)
    assert 0.13 <= h.quantile(0.5) <= 0.14      # a median, not a mean


# -- bytes at the device boundary ---------------------------------------------------

@pytest.mark.parametrize("token_feed", ["host", "device"])
def test_byte_counters_equal_the_nbytes_of_a_hand_built_step(token_feed):
    engine = GenerationEngine(queue_depth=8, breaker_threshold=0)
    entry = engine.register_model(lambda: _model("bytes_" + token_feed))
    m = entry.model
    metrics = entry.metrics
    S, L = m.slots, m.max_len
    # the step's one host array: a row of 4 + ceil(L / block) int32 a slot
    feeds = {
        DecodeModel.DEC_TOKEN: np.zeros((S, 1), "int64"),
        DecodeModel.DEC_STEP: m.step_feed(),
    }
    fed = sum(a.nbytes for a in feeds.values())
    assert fed == 8 * S + 4 * S * (4 + -(-L // m.block_size))
    if token_feed == "device":
        # a launched-ahead step's tokens are the previous step's output:
        # on the device already, so nothing of them is fed
        feeds[DecodeModel.DEC_TOKEN] = jax.device_put(
            np.zeros((S, 1), jax.dtypes.canonicalize_dtype(np.int64)),
            engine.device)
        fed -= 8 * S
    fetches = entry._run("step", feeds)
    assert metrics.count("fed_bytes") == fed
    assert metrics.count("step_launches") == 1
    assert metrics.count("fetched_bytes") == 0      # nothing fetched yet
    logits = entry._fetch(fetches[0])
    assert logits.shape == (S, 1, m.vocab_size)
    assert metrics.count("fetched_bytes") == logits.nbytes \
        == 4 * S * m.vocab_size
    # a prefill puts its own feeds and is not a step; its causal bias is
    # a constant put at registration, so nothing of it is fed
    pre = entry._prefill_feeds([1, 2, 3])
    assert isinstance(pre[DecodeModel.PRE_BIAS], jax.Array)
    entry._run("prefill", pre)
    assert metrics.count("fed_bytes") == fed + sum(
        a.nbytes for a in pre.values() if not isinstance(a, jax.Array)) \
        == fed + 2 * 8 * L
    assert metrics.count("step_launches") == 1


def test_byte_counters_add_up_over_a_served_run(traced_run):
    entry, _resps, spans = traced_run
    m = entry.metrics
    launches = [s for s in spans if s["name"] in LAUNCHES]
    assert m.count("fed_bytes") == sum(s["args"]["bytes"] for s in launches)
    fetched = [s for s in spans if s["name"] in (
        "decode::step_fetch", "decode::prefill_fetch", "decode::chunk_fetch")]
    assert m.count("fetched_bytes") == sum(
        s["args"]["bytes"] for s in fetched)
    assert m.count("step_launches") == m.count("decode_steps")


def test_a_one_shot_admission_moves_one_row_and_the_live_rows(traced_run):
    """Since ISSUE 37: ``decode::prefill_fetch``, once per one-shot miss,
    brings the ``[V]`` logits row and the ``[2 * layers, P, H]`` live K/V
    rows (P = ``chunk_tokens`` here) and no more; the prefill launch puts
    tokens and positions, the inject launch its row map alone, its K/V
    feeds being the prefill program's outputs; a last chunk's fetch is
    the one row too."""
    entry, _resps, spans = traced_run
    m = entry.model
    V, H, L, P = m.vocab_size, m.hidden, m.max_len, m.chunk_tokens
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s["args"])
    one_shot = sum(n <= P for n in PROMPT_LENS)
    assert len(by_name["decode::prefill_fetch"]) == one_shot \
        == entry.metrics.count("prefills") \
        == entry.metrics.count("prefill_device_injects")
    assert {a["bytes"] for a in by_name["decode::prefill_fetch"]} \
        == {4 * V + 2 * len(m.state_names) * P * H * 4}
    assert [a["bytes"] for a in by_name["decode::prefill"]] \
        == [2 * 8 * L] * one_shot
    assert [a["bytes"] for a in by_name["decode::inject"]] \
        == [8 * L] * one_shot
    assert [a["bytes"] for a in by_name["decode::chunk_fetch"]] \
        == [4 * V] * (len(PROMPT_LENS) - one_shot)


# -- overload decisions on the timeline ----------------------------------------------

def test_the_controller_reads_no_clock_and_takes_the_callers():
    ctl = BrownoutController()
    ctl.step(occupancy=0.97)
    (t,) = ctl.transitions
    assert "time" not in t and (t["from"], t["to"]) == (0, 4)
    assert ctl.stamp(0, 12.5) == [t] and t["time"] == 12.5
    assert ctl.stamp(1, 99.0) == [] and t["time"] == 12.5
    assert ctl.snapshot()["transitions"][0]["time"] == 12.5


def test_a_forced_l4_is_a_timed_transition_and_a_shed_instant(clean_tracer):
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    entry = engine.register_model(lambda: _model("shed"))
    obs.enable_tracing()
    before = time.perf_counter()
    # a deferred admission saturates the occupancy signal: straight to L4
    entry._pending.append(object())
    try:
        entry._brownout_tick()
        assert entry._brownout.level == 4
        with pytest.raises(RejectedError, match="shedding non-HIGH"):
            engine.submit([1, 2], max_new_tokens=2, tenant="t1")
        high = engine.submit([1, 2], max_new_tokens=2,
                             priority=Priority.HIGH)
    finally:
        entry._pending.pop()
    obs.disable_tracing()
    after = time.perf_counter()
    (t,) = entry.stats()["brownout"]["transitions"]
    assert (t["from"], t["to"], t["trigger"]) == (0, 4, "occupancy")
    assert before <= t["time"] <= after
    assert entry.metrics.count("brownout_transitions") == 1
    assert entry.metrics.count("brownout_shed") == 1
    events = {e["name"]: e for e in clean_tracer.instants()}
    assert events["brownout::transition"]["args"] == {
        "from": 0, "to": 4, "trigger": "occupancy", "value": 1.0}
    assert events["brownout::shed"]["args"] == {
        "level": 4, "priority": Priority.NORMAL, "tenant": "t1",
        "why": "l4_non_high"}
    assert events["brownout::transition"]["ts_ns"] \
        <= events["brownout::shed"]["ts_ns"]
    assert not high.done()
    engine.shutdown()


def test_the_ladder_itself_did_not_change():
    """Thresholds, hysteresis and who is shed are the parent's."""
    ctl = BrownoutController()
    assert ctl.enter == (0.60, 0.75, 0.85, 0.95)
    assert ctl.exit == (0.45, 0.60, 0.70, 0.80) and ctl.hold == 3
    levels = [ctl.step(queue_seconds=v)
              for v in (0.2, 0.96, 0.5, 0.5, 0.5, 0.5, 0.5, 0.5)]
    assert levels == [0, 4, 4, 4, 3, 3, 3, 2]
    assert [set(t) for t in ctl.transitions] == [
        {"step", "from", "to", "trigger", "value"}] * 3


# -- the tracer ---------------------------------------------------------------------

def test_span_arguments_set_late_and_elapsed_time(clean_tracer):
    obs.enable_tracing()
    with tracer_mod.span("decode::feeds") as sp:
        assert isinstance(sp, obs.trace_scope)
        first = sp.elapsed_ns()
        sp.set(active=3)
        sp.set(beam_groups=0)
        assert sp.elapsed_ns() >= first >= 0
    obs.disable_tracing()
    (s,) = clean_tracer.spans()
    assert s["args"] == {"active": 3, "beam_groups": 0}
    assert s["dur_ns"] >= first


def test_no_annotation_is_opened_without_a_running_profiler(clean_tracer):
    obs.enable_tracing()
    with obs.trace_scope("decode::step", request=1) as sp:
        assert sp._ann is None
    obs.disable_tracing()
    assert [s["name"] for s in clean_tracer.spans()] == ["decode::step"]
