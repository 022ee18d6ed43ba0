"""The engine's own device lane (ISSUE 53): when the device had FINISHED each
launch of ``_ModelEntry._run``; an operator's instrument, asked for with the
tracer (``tracing(path, lanes=True)``).

The account (``serving/decode/lane.py``) is a pure function of the launches'
stamps and is held here on hand-made timelines: every launch queued, none
queued, a sleep between two launches, an unstamped inject between a prefill
and a step, a stamp taken late, a wait that failed; the four parts always
add up to the wall time between the first and the last stamp. An engine
hand-stepped with lanes on moves the four ``serving_device_*`` counters and
records one ``device::<kind>`` event a stamped launch, in launch order,
``with=`` naming what rode along unstamped, on a track of the Chrome export
and NOT among ``Tracer.spans()``; with tracing off, or on without lanes, it
starts no thread and moves none of them; ``shutdown`` joins the watcher. The
lowered executables carry their entry's label as their name, outside the
exported module.
"""

import json
import random
import threading

import pytest
from decode_testing import sharpen

from paddle_tpu import observability as obs
from paddle_tpu.core import lowering
from paddle_tpu.serving.decode import GenerationEngine, build_decoder_model
from paddle_tpu.serving.decode import lane as lane_mod
from paddle_tpu.serving.decode.lane import Account, Launch, Parts
from paddle_tpu.serving.decode.metrics import DecodeMetrics

MS = 1_000_000          # the timelines below are written in milliseconds

DEVICE = [c for c in DecodeMetrics.COUNTERS if c.startswith("device_")]
PARTS = ("queued", "unqueued", "idle_empty", "idle_host")


def test_the_lane_moves_four_counters():
    assert sorted(DEVICE) == sorted(f"device_{p}_seconds" for p in PARTS)


def _launch(kind, call0, call1, ready, slept=0, busy=False, seen=None,
            programs=0, rode=()):
    """``slept``: what the loop slept since its launch before; ``busy``:
    what the launching thread saw of the launch before as its call
    returned; ``seen``: when a later hand-over saw THIS launch done."""
    return Launch("a", kind, 0, int(call0 * MS), int(call1 * MS),
                  int(slept * MS), busy=busy, rode=rode, programs=programs,
                  ready_ns=None if ready is None else int(ready * MS),
                  seen_ns=None if seen is None else int(seen * MS))


def _account(records):
    acc = Account()
    return [acc.add(rec) for rec in records]


def _ms(parts):
    return None if parts is None else (
        parts.queued, parts.device_ns / MS, parts.idle_empty_ns / MS,
        parts.idle_host_ns / MS, parts.unqueued_ns / MS)


def _observe(label, parts):
    m = DecodeMetrics(engine_label=label)
    for p in parts:
        if p is not None:
            m.observe_device(p)
    return {part: m.count(f"device_{part}_seconds") for part in PARTS}


# -- the account, on hand-made timelines -----------------------------------------

def test_every_launch_queued_gives_each_the_time_between_two_stamps():
    """A device-bound loop: each step is handed over (0.3 ms) while the one
    before still runs 14 ms, so each interval is that step's device time."""
    records = [_launch("step", 14 * k, 14 * k + 0.3, 14 * (k + 1) + 5,
                       busy=k > 0) for k in range(5)]
    parts = _account(records)
    assert parts[0] is None         # it opens the account: no stamp before
    assert [_ms(p) for p in parts[1:]] == [(True, 14.0, 0, 0, 0)] * 4
    assert [p.start_ns for p in parts[1:]] == [
        r.ready_ns for r in records[:-1]]
    assert _observe("lane_queued", parts) == pytest.approx(
        {"queued": 0.056, "unqueued": 0, "idle_empty": 0, "idle_host": 0})


def test_none_queued_parts_each_interval_into_idle_and_unqueued():
    """A host-bound loop: the device (2 ms a step) is done long before the
    next call, 3 ms after the last one. No sleep: the idle time is the
    host's."""
    records = [_launch("step", 3 * k, 3 * k + 0.4, 3 * k + 2)
               for k in range(4)]
    parts = _account(records)
    assert parts[0] is None
    # ready_{k-1} = 3k - 1, call0_k = 3k: 1 ms certain idle, then 2 ms from
    # the call to the stamp that nobody can part
    assert [_ms(p) for p in parts[1:]] == [(False, 0, 0, 1.0, 2.0)] * 3
    assert all(p.start_ns == r.t_call0 for p, r in zip(parts[1:],
                                                       records[1:]))
    assert _observe("lane_unqueued", parts) == pytest.approx(
        {"queued": 0, "unqueued": 0.006, "idle_empty": 0, "idle_host": 0.003})


def test_a_launch_before_that_ends_under_the_call_is_unqueued_with_no_idle():
    """The launch before was done when the call had returned, but not
    when it began: no certain idle, and the time from the stamp before is
    dispatch and device time together."""
    parts = _account([_launch("step", 0, 1, 10),
                      _launch("step", 9.5, 10.5, 20)])
    assert _ms(parts[1]) == (False, 0, 0, 0, 10.0)
    assert parts[1].start_ns == 10 * MS


def test_a_sleep_between_two_launches_is_an_empty_queue_up_to_the_idle_time():
    """20 ms idle before the second launch, of which the loop slept 15 (the
    rest the host's); 5 ms idle before the third with a sleep of 30 that
    began while the device was still busy: no more than the idle time is
    put down to it."""
    parts = _account([
        _launch("step", 0, 1, 3),
        _launch("step", 23, 24, 26, slept=15),
        _launch("step", 31, 32, 34, slept=30),
    ])
    assert _ms(parts[1]) == (False, 0, 15.0, 5.0, 3.0)
    assert _ms(parts[2]) == (False, 0, 5.0, 0.0, 3.0)


def test_a_stamp_taken_late_counts_as_no_later_than_the_output_was_seen_ready():
    """The watcher woke 100 ms late (the interpreter's lock was held) and
    stamped the first launch AFTER the second had been handed over; the
    launching thread saw the first's output ready at 30.1 ms. The idle
    device is not called busy: the second launch is unqueued, and the first
    ends where it was seen done."""
    records = [
        _launch("step", 0, 1, 2),
        _launch("step", 3, 4, 130, seen=30.1),      # done by 30.1, says k+1
        _launch("step", 29, 30, 131),
    ]
    parts = _account(records)
    assert _ms(parts[1]) == (False, 0, 0, 1.0, 27.1)
    # 30.1 - 29 under the call: no certain idle; 100.9 ms to its own stamp
    assert _ms(parts[2]) == (False, 0, 0, 0, pytest.approx(100.9))
    assert parts[2].start_ns == int(30.1 * MS)
    # a bound earlier than the stamp before it never runs the clock back
    late = _account([_launch("step", 0, 1, 50),
                     _launch("step", 2, 3, 51, busy=True, seen=40)])
    assert _ms(late[1]) == (True, 0.0, 0, 0, 0)


def test_an_unstamped_inject_rides_in_the_next_stamped_launchs_interval():
    """prefill, inject (no output to wait for: not stamped), step, all
    handed over while the prefill runs: the step's interval holds the
    inject's time too, and is the queued part all the same. Handed over to
    a device that was seen done, an interval that an unstamped PROGRAM ran
    in has no certain idle: all of it is unqueued."""
    records = [
        _launch("prefill", 0, 1, 30),
        _launch("step", 2, 3, 45, busy=True, programs=1, rode=("inject",)),
        _launch("step", 4, 5, 59, busy=True),
        _launch("step", 80, 81, 95, programs=1, rode=("inject",)),
        _launch("step", 110, 111, 125, rode=("pick_row",)),
    ]
    parts = _account(records)
    assert _ms(parts[1]) == (True, 15.0, 0, 0, 0)
    assert _ms(parts[2]) == (True, 14.0, 0, 0, 0)
    assert _ms(parts[3]) == (False, 0, 0, 0, 36.0)
    assert parts[3].start_ns == 59 * MS
    assert _ms(parts[4]) == (False, 0, 0, 15.0, 15.0)   # a helper: let ride


def test_a_failed_wait_closes_the_account_and_the_next_launch_opens_it():
    records = [
        _launch("step", 0, 1, 14),
        _launch("step", 2, 3, 28, busy=True),
        _launch("step", 16, 17, None, busy=True),   # the arena was lost
        _launch("step", 60, 61, 75),                # opens the account again
        _launch("step", 62, 63, 89, busy=True),
    ]
    parts = _account(records)
    assert [p is None for p in parts] == [True, False, True, True, False]
    assert _ms(parts[4]) == (True, 14.0, 0, 0, 0)
    # the 47 ms across the failure are in nobody's account
    assert sum(_observe("lane_failed", parts).values()) == pytest.approx(
        0.028)


@pytest.mark.parametrize("seed", range(8))
def test_the_four_parts_add_up_to_the_stamped_wall_time(seed):
    """Any timeline the device's order allows: calls in launch order, each
    stamp after its call's end and after the stamp before, a launch busy
    where the one before ended after its hand-over, and one stamp in ten
    taken late but seen done by the next hand-over."""
    rng = random.Random(seed)
    t = ready = 0
    records = []
    for _ in range(200):
        t += rng.choice((0, 1, 5, 40)) * MS // 10
        sleep = rng.choice((0, 0, 20)) * MS
        t += sleep
        call0 = t
        t += rng.randint(1, 12) * MS // 10
        busy = ready > t
        if records and not busy:
            records[-1].seen_ns = t
            if rng.random() < 0.1:
                records[-1].ready_ns += 100 * MS
        ready = max(ready, t) + rng.randint(1, 30) * MS // 10
        records.append(Launch("a", rng.choice(("step", "chunk", "prefill")),
                              0, call0, t, sleep, busy=busy,
                              programs=rng.random() < 0.05, ready_ns=ready))
    parts = _account(records)
    assert parts[0] is None and None not in parts[1:]
    assert {p.queued for p in parts[1:]} == {True, False}
    for p in parts[1:]:
        assert min(p[1:5]) >= 0
        assert (p.device_ns == 0) if not p.queued else (
            p.idle_empty_ns == p.idle_host_ns == p.unqueued_ns == 0)
    total = sum(p.device_ns + p.idle_empty_ns + p.idle_host_ns
                + p.unqueued_ns for p in parts[1:])
    first = min(records[0].ready_ns, records[0].seen_ns or records[0].ready_ns)
    assert total == records[-1].ready_ns - first
    assert sum(_observe(f"lane_sum_{seed}", parts).values()) == pytest.approx(
        total * 1e-9)
    assert isinstance(parts[1], Parts)


# -- an engine, hand-stepped ------------------------------------------------------

PROMPT_LENS = (3, 9, 2, 12)     # two one-shot prefills, two chunked (C = 4)


def _serve(name, traced, lanes=False):
    """Four requests hand-stepped to the end (the engine's threads are
    never started), every launch of ``_run`` written down. Returns (engine,
    entry, [(kind, had a span, had an output)])."""
    engine = GenerationEngine(queue_depth=32, breaker_threshold=0)
    entry = sharpen(engine.register_model(build_decoder_model(
        vocab_size=32, hidden=8, num_layers=2, slots=4, max_len=24,
        block_size=4, chunk_tokens=4, name=name, version="1")))
    run, seen = entry._run, []

    def running(kind, feeds, span=None):
        fetches = run(kind, feeds, span)
        seen.append((kind, span is not None, bool(fetches)))
        return fetches

    entry._run = running
    if traced:
        obs.get_tracer().clear()
        obs.enable_tracing(lanes=lanes)
    try:
        resps = [engine.submit(list(range(1, n + 1)), max_new_tokens=5)
                 for n in PROMPT_LENS]
        for _ in range(400):
            if all(r.done() for r in resps):
                break
            entry._iterate()
    finally:
        if traced:
            obs.disable_tracing()
    assert all(r.done() for r in resps)
    return engine, entry, seen


@pytest.fixture(scope="module")
def traced_serving():
    engine, entry, seen = _serve("lane_on", traced=True, lanes=True)
    watched = engine.lane._thread is not None
    joined = engine.lane.close(timeout=30)      # everything handed over is
    tracer = obs.get_tracer()                   # stamped before it returns
    out = {"engine": engine, "entry": entry, "seen": seen,
           "watched": watched, "joined": joined, "lanes": tracer.lanes(),
           "spans": tracer.spans(), "chrome": tracer.chrome_trace(),
           "stats": entry.stats()}
    tracer.clear()
    return out


def test_a_traced_serving_gives_one_lane_event_a_stamped_launch_in_launch_order(
        traced_serving):
    seen, lanes = traced_serving["seen"], traced_serving["lanes"]
    assert traced_serving["watched"] and traced_serving["joined"]
    assert traced_serving["engine"].lane._thread is None
    stamped = [kind for kind, span, out in seen if span and out]
    assert {"step", "chunk", "prefill"} <= set(stamped)
    assert [ev["name"] for ev in lanes] == [f"device::{k}" for k in stamped]
    assert {ev["track"] for ev in lanes} == {
        f"device:{traced_serving['engine'].device.id}"}
    # on the tracer's clock, one after the other: the device's order
    ends = [ev["start_ns"] + ev["dur_ns"] for ev in lanes]
    assert ends == sorted(ends)
    assert all(ev["dur_ns"] >= 0 and ev["start_ns"] > 0 for ev in lanes)
    assert all(a <= b["start_ns"] for a, b in zip(ends, lanes[1:]))
    # each program's launches carry its always-on counter's numbers
    m = traced_serving["entry"].metrics
    for kind, counter in (("step", "step_launches"), ("chunk", "chunk_runs"),
                          ("prefill", "prefills")):
        numbers = [ev["args"]["launch"] for ev in lanes
                   if ev["name"] == f"device::{kind}"]
        assert numbers == list(range(1, int(m.count(counter)) + 1))
    # all but the launch that opens the account say whether they were queued
    assert "queued" not in lanes[0]["args"]
    assert all(isinstance(ev["args"]["queued"], bool) for ev in lanes[1:])


def test_with_names_what_rode_along_unstamped(traced_serving):
    """Walk the launches as `_run` saw them: an inject (no output) rides
    with the next stamped launch, and so do the row picker and the
    stack-and-trim of a one-shot admission and the picker behind a prompt's
    last chunk; every name is in exactly one event's ``with=``."""
    seen, lanes = traced_serving["seen"], traced_serving["lanes"]
    programs = []       # unstamped programs, by the stamped launch after
    riding = []
    for kind, span, out in seen:
        if span and out:
            programs.append(riding)
            riding = []
        else:
            riding.append(kind)
    assert riding == []
    rode = [ev["args"]["with"] for ev in lanes]
    assert [[n for n in names if n == "inject"] for names in rode] == programs
    flat = [n for names in rode for n in names]
    one_shot = sum(1 for kind, _s, _o in seen if kind == "prefill")
    chunked = len(PROMPT_LENS) - one_shot
    assert flat.count("inject") == one_shot >= 1
    assert flat.count("stack_live") == one_shot
    assert flat.count("pick_row") == one_shot + chunked
    assert set(flat) == {"inject", "pick_row", "stack_live"}
    # an inject is launched between its prefill and the helpers' launches
    for names in rode:
        if "inject" in names:
            assert names[:3] == ["inject", "pick_row", "stack_live"]


def test_a_traced_serving_moves_the_counters_and_they_add_up(traced_serving):
    st, lanes = traced_serving["stats"], traced_serving["lanes"]
    # the four parts are the wall time from the first stamp to the last
    first = lanes[0]["start_ns"] + lanes[0]["dur_ns"]
    last = lanes[-1]["start_ns"] + lanes[-1]["dur_ns"]
    assert last > first
    assert sum(st[f"device_{p}_seconds"] for p in PARTS) == pytest.approx(
        (last - first) * 1e-9)
    # the queued part and the unqueued part are their events' durations;
    # the idle parts are the gaps an unqueued event leaves before it
    for part, flag in (("queued", True), ("unqueued", False)):
        events = [ev for ev in lanes if ev["args"].get("queued") is flag]
        assert st[f"device_{part}_seconds"] == pytest.approx(
            sum(ev["dur_ns"] for ev in events) * 1e-9)
    # the scrape carries them under the registry's names
    text = obs.scrape_text()
    for name in DEVICE:
        assert f"serving_{name}_total" in text


def test_the_lane_is_a_track_of_the_chrome_export_and_no_span(traced_serving):
    spans, lanes = traced_serving["spans"], traced_serving["lanes"]
    assert spans and not [s for s in spans if s["name"].startswith("device::")]
    assert {s["name"] for s in spans} >= {"decode::step", "decode::iterate"}
    doc = traced_serving["chrome"]
    json.dumps(doc)     # the export is plain JSON
    events = doc["traceEvents"]
    device = [e for e in events if e.get("cat") == "lane"]
    assert [e["name"] for e in device] == [ev["name"] for ev in lanes]
    (tid,) = {e["tid"] for e in device}
    (track,) = [e for e in events if e["ph"] == "M"
                and e["name"] == "thread_name" and e["tid"] == tid]
    assert track["args"]["name"] == \
        f"device:{traced_serving['engine'].device.id}"
    # a track of its own: no host span shares the tid
    assert not [e for e in events if e["ph"] == "X" and e["tid"] == tid
                and e.get("cat") != "lane"]
    assert all(e["ph"] == "X" and e["args"]["launch"] >= 1 for e in device)


@pytest.mark.parametrize("traced", [False, True])
def test_without_lanes_no_thread_starts_and_no_counter_moves(traced):
    """Tracing off, and tracing on as the benchmark's traced run has it
    (nobody asked for lanes): `_run` hands nothing over, the spans are
    there as ever, and the serving is the parent's."""
    obs.get_tracer().clear()
    before = {t.name for t in threading.enumerate()}
    engine, entry, seen = _serve(f"lane_off_{int(traced)}", traced=traced)
    assert {"step", "chunk", "prefill", "inject"} <= {k for k, _s, _o in seen}
    assert any(span for _k, span, _o in seen) == traced
    lane = engine.lane
    assert lane._thread is None and lane._last is None
    assert lane._rode == [] and not lane._records
    assert {t.name for t in threading.enumerate()} == before
    st = entry.stats()
    assert [st[name] for name in DEVICE] == [0] * len(DEVICE)
    tracer = obs.get_tracer()
    assert tracer.lanes() == []
    assert bool(tracer.spans()) == traced
    tracer.clear()


def test_shutdown_joins_the_watcher_and_a_second_capture_opens_a_new_account():
    """A started engine, two captures with unstamped serving between them:
    the second capture's first launch opens the account anew (the stamp
    before it is the first capture's, and the time between is nobody's),
    and `shutdown` leaves no watcher behind."""
    engine = GenerationEngine(queue_depth=32, breaker_threshold=0)
    entry = sharpen(engine.register_model(build_decoder_model(
        vocab_size=32, hidden=8, num_layers=2, slots=4, max_len=24,
        block_size=4, chunk_tokens=4, name="lane_twice", version="1")))
    tracer = obs.get_tracer()

    def settle():   # the watcher stamps behind the scheduler's delivery
        for _ in range(3000):
            if not engine.lane._records:
                return
            lane_mod.time.sleep(0.01)
        raise AssertionError("the watcher did not catch up")

    def accounted():
        st = entry.stats()
        return sum(st[f"device_{p}_seconds"] for p in PARTS)

    with engine:
        with obs.tracing(lanes=True):
            engine.submit([1, 2, 3], max_new_tokens=4).result(timeout=60)
        thread = engine.lane._thread
        assert thread is not None and thread.is_alive()
        settle()
        first = len(tracer.lanes())
        assert first >= 2
        engine.submit([4, 5, 6, 7], max_new_tokens=4).result(timeout=60)
        assert len(tracer.lanes()) == first        # unstamped: tracing is off
        before = accounted()
        with obs.tracing(lanes=True):
            engine.submit([2, 7, 1], max_new_tokens=4).result(timeout=60)
        settle()
        second = tracer.lanes()     # the first capture's went with `start`
        assert second and "queued" not in second[0]["args"]
        # less than the second capture's own length was added to the account
        grew = accounted() - before
        assert 0 <= grew * 1e9 <= max(
            ev["start_ns"] + ev["dur_ns"] for ev in second)
        assert engine.lane._thread is thread
    assert not thread.is_alive() and engine.lane._thread is None
    assert not [t for t in threading.enumerate()
                if t.name.startswith("decode-lane")]
    tracer.clear()


class _Output:
    """What the watcher waits for, standing in for a device array."""

    def __init__(self, gate=None, lost=False):
        self.gate, self.lost = gate, lost

    def is_ready(self):
        return self.gate is None or self.gate.is_set()

    def block_until_ready(self):
        if self.gate is not None:
            assert self.gate.wait(30)
        if self.lost:
            raise RuntimeError("the arena was lost")


class _Device:
    id = 7


def test_the_watcher_stamps_in_launch_order_and_a_wait_that_raises_closes_the_account():
    """The lane alone, fed by hand, four launches handed over while the
    first still runs: the watcher stamps them in the order handed over, a
    launch is queued where the output of the one before was not ready as it
    was handed over, a wait that raises leaves no event and the launch
    after it opens the account anew, and what rode unstamped is named by
    the next launch handed over."""
    obs.get_tracer().clear()
    obs.enable_tracing(lanes=True)
    try:
        lane = lane_mod.DeviceLane(_Device())
        m = DecodeMetrics(engine_label="lane_alone")
        now = lane_mod.time.perf_counter_ns
        gate = threading.Event()
        t = now()
        lane.launched(m, "step", 1, t, t + 1000, 0, _Output(gate))
        lane.rode("inject", program=True)
        lane.rode("pick_row")
        t = now()
        lane.launched(m, "step", 2, t, t + 1000, 0, _Output(gate))
        t = now()
        lane.launched(m, "chunk", 1, t, t + 1000, 0,
                      _Output(gate, lost=True))
        t = now()
        lane.launched(m, "step", 3, t, t + 1000, 0, _Output(gate))
        assert lane._thread is not None and obs.get_tracer().lanes() == []
        assert [r.busy for r in lane._records] == [False, True, True, True]
        assert [r.seen_ns for r in lane._records] == [None] * 4
        gate.set()
        assert lane.close(timeout=30) and lane._thread is None
    finally:
        obs.disable_tracing()
    events = obs.get_tracer().lanes()
    obs.get_tracer().clear()
    assert [(e["track"], e["name"], e["args"]["launch"]) for e in events] == [
        ("device:7", "device::step", n) for n in (1, 2, 3)]
    assert [e["args"]["with"] for e in events] == [
        [], ["inject", "pick_row"], []]
    assert [e["args"].get("queued") for e in events] == [None, True, None]
    # the second step was queued behind the first: the one interval there is
    assert m.count("device_queued_seconds") == pytest.approx(
        events[1]["dur_ns"] * 1e-9)
    assert sum(m.count(f"device_{p}_seconds") for p in PARTS) == m.count(
        "device_queued_seconds") > 0


def test_a_late_watcher_does_not_call_an_idle_device_busy():
    """The watcher is held (as behind the interpreter's lock) while two
    launches are handed over 30 ms apart, both outputs ready at once: the
    second was NOT queued, and the first counts as done no later than the
    second's hand-over, whatever the clock read when the watcher woke (the
    time up to that hand-over is unqueued: nobody saw when it ended)."""
    obs.get_tracer().clear()
    obs.enable_tracing(lanes=True)
    try:
        lane = lane_mod.DeviceLane(_Device())
        m = DecodeMetrics(engine_label="lane_late")
        now = lane_mod.time.perf_counter_ns
        held = threading.Event()
        stamp = lane._stamp

        def stamping(rec, acc):
            assert held.wait(30)
            stamp(rec, acc)

        lane._stamp = stamping
        t = now()
        lane.launched(m, "step", 1, t, t + 1000, 0, _Output())
        t = now()
        lane.launched(m, "step", 2, t, t + 1000, 0, _Output())
        lane_mod.time.sleep(0.03)
        t = now()
        lane.launched(m, "step", 3, t, t + 1000, 0, _Output())
        seen = [r.seen_ns for r in lane._records]
        assert seen[0] is not None and seen[0] < t and seen[1] > t
        assert seen[2] is None
        lane_mod.time.sleep(0.03)
        held.set()
        assert lane.close(timeout=30)
    finally:
        obs.disable_tracing()
    events = obs.get_tracer().lanes()
    obs.get_tracer().clear()
    assert [e["args"].get("queued") for e in events] == [None, False, False]
    # all three stamps were taken within a millisecond of one another, 30 ms
    # after the third hand-over; the first two count as no later than the
    # hand-over that saw them done, and nothing is put down to a busy device
    assert m.count("device_queued_seconds") == 0
    ends = [e["start_ns"] + e["dur_ns"] for e in events]
    assert 25 * MS < ends[1] - ends[0] < 60 * MS
    assert ends[2] - ends[1] > 25 * MS
    assert m.count("device_unqueued_seconds") == pytest.approx(
        (ends[2] - ends[0]) * 1e-9)


# -- the executables' names -------------------------------------------------------

def test_an_executable_is_named_for_its_entry_outside_the_exported_module(
        tmp_path, monkeypatch):
    """`decode:<model>:<kind>` names the function ``jax.jit`` is handed, so
    a profiler's module line says which program a module was. The name is
    outside what is exported: the persisted module's bytes and the
    fingerprint are the same under another label, and the persistent tier
    serves one label's module to the other."""
    from paddle_tpu.core import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    compile_cache.clear_memory_cache()
    engine = GenerationEngine(queue_depth=8, breaker_threshold=0)
    model = build_decoder_model(
        vocab_size=32, hidden=8, num_layers=1, slots=2, max_len=16,
        block_size=4, chunk_tokens=4, name="na-me", version="1")
    entry = engine.register_model(model)
    assert entry.compile_sources["trace"] == 4
    prints = {}
    for kind, (low, executable) in entry._entries.items():
        assert low.fn.__name__ == f"decode_na_me_1_{kind}"
        assert f"jit_decode_na_me_1_{kind}" in executable.as_text()
        prints[kind] = low.fingerprint
    payloads = {p.name: p.read_bytes()
                for p in (tmp_path / "ptcc").rglob("*") if p.is_file()}
    assert payloads
    assert not [n for n, b in payloads.items() if b"decode_na_me" in b]

    # the same programs under another label: nothing is traced, the module
    # on the disk is served, and the name follows the label
    compile_cache.clear_memory_cache()
    m = model
    low, source = lowering.lower_step(
        m.decode_program, entry._scope, m.decode_feed_sig(),
        [m.logits_fetch, m.token_fetch], donate=True,
        label="decode:other@2:step")
    assert source == "disk" and low.fingerprint == prints["step"]
    assert low.fn.__name__ == "decode_other_2_step"
    assert payloads == {p.name: p.read_bytes()
                        for p in (tmp_path / "ptcc").rglob("*")
                        if p.is_file()}
    # the plain-jit step (nothing persisted) is named too
    compile_cache.clear_memory_cache()
    low, source = lowering.lower_step(
        m.decode_program, entry._scope, m.decode_feed_sig(),
        [m.logits_fetch, m.token_fetch], donate=True, persist=False,
        use_cache=False, label="decode:plain:step")
    assert source == "trace" and low.fn.__name__ == "decode_plain_step"
    assert low.fingerprint == prints["step"]
    compile_cache.clear_memory_cache()


@pytest.mark.parametrize("label, name", [
    ("decode:decoder_1024x24@1:step", "decode_decoder_1024x24_1_step"),
    ("executor", "executor"), ("9lives", "_9lives"), ("", "_"),
])
def test_a_label_becomes_a_valid_identifier(label, name):
    fn = lowering._named(lambda a, b, c, d: (a, b, c, d), label)
    assert fn.__name__ == name and name.isidentifier()
    assert fn(1, 2, 3, 4) == (1, 2, 3, 4)
