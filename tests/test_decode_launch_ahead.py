"""Launching decode step N+1 ahead of step N's fetch (ISSUE 34).

A step whose slots need only their tokens stays on the device after its
launch; the next iteration launches the following step, fed those tokens
as the device array they are, and fetches and delivers the first one only
then. Anything that would wait on the device or change who steps drains
the step in flight first. Hand-stepped through ``entry._iterate()`` over
the toy decoder, sharpened so that a wrong K/V row or a wrong token feed
moves the served tokens. The serial engine of every comparison is the same
model built without ``token_fetch``: each of its steps fetches the logits
and lands before the next is launched, as every step once did.
"""

import numpy as np
import pytest
from decode_testing import record_step_logits, sharpen, without_token_fetch

from paddle_tpu import observability as obs
from paddle_tpu.resilience import faults
from paddle_tpu.serving.decode import (
    CompiledGrammar,
    GenerationEngine,
    SamplingParams,
    build_decoder_model,
)
from paddle_tpu.serving.request import DeadlineExceededError, RequestError

VOCAB = ["<eos>"] + list("abcdefghijklmnopqrstuvwxyz") + list("01234")
SAMPLED = SamplingParams(temperature=0.9, top_k=8, seed=11)


def _build(name, **opts):
    geom = dict(vocab_size=32, hidden=8, num_layers=2, slots=4, max_len=32,
                block_size=4, name=name, version="1")
    geom.update(opts)
    return build_decoder_model(**geom)


def _engine(name, serial=False, **opts):
    model = _build(name, **opts)
    if serial:
        model = without_token_fetch(model)
    engine = GenerationEngine(queue_depth=32, breaker_threshold=0)
    return engine, sharpen(engine.register_model(model))


def _play(entry, engine, script, iterations=80):
    """Hand-step ``entry``: ``script[i]`` is the list of submits made just
    before iteration ``i`` (each a dict of ``engine.submit`` arguments with
    the prompt under ``"prompt"``). Stops once every scripted request is
    done. Returns the responses in the order of submission."""
    resps = []
    last = max(script)
    for i in range(iterations):
        for kw in script.get(i, ()):
            kw = dict(kw)
            resps.append(engine.submit(kw.pop("prompt"), **kw))
        if i >= last and all(r.done() for r in resps):
            break
        entry._iterate()
    assert all(r.done() for r in resps)
    assert entry._launched is None
    return resps


def _tokens(resp):
    return [int(t) for t in resp.result()["tokens"]]


def _assert_stamps(resp):
    times = resp.token_times
    assert len(times) == len(resp.result()["tokens"]) >= 1
    assert times == sorted(times)
    assert times[-1] <= resp.finish_time


@pytest.fixture
def tracer():
    obs.get_tracer().clear()
    obs.enable_tracing()
    yield obs.get_tracer()
    obs.disable_tracing()
    obs.get_tracer().clear()


# -- the schedule, step by step ------------------------------------------------------

def test_counter_and_spans_follow_the_hand_stepped_schedule(tracer):
    """One request alone, a second admitted in mid-flight, each ending at
    its ``max_new``: which launch is ahead of a fetch and which fetch is a
    drain, and why, written down by hand."""
    engine, entry = _engine("la_sched")
    ref_a = entry.offline_decode([3, 1, 4], 6)
    ref_b = entry.offline_decode([9, 2, 6, 5, 3], 3)
    a = engine.submit([3, 1, 4], max_new_tokens=6)
    # 1: admit a (its first token is the prefill's), launch step 1, which
    #    stays in flight: nothing delivered yet
    entry._iterate()
    (sa,) = [st for st in entry._slots if st is not None]
    assert (len(sa.generated), sa.ahead, sa.cursor) == (1, 1, 4)
    assert entry._launched is not None
    assert entry.metrics.count("decode_steps") == 0
    # 2: launch step 2 ahead, then step 1 lands
    entry._iterate()
    assert (len(sa.generated), sa.ahead, sa.cursor) == (2, 1, 5)
    # 3: b arrives and a slot is free: step 2 is drained, b is admitted,
    #    step 3 is launched from the host's tokens and stays in flight
    b = engine.submit([9, 2, 6, 5, 3], max_new_tokens=3)
    entry._iterate()
    sb = [st for st in entry._slots if st is not None and st is not sa][0]
    assert (len(sa.generated), sa.ahead) == (3, 1)
    assert (len(sb.generated), sb.ahead) == (1, 1)
    # 4: step 4 ahead (both), step 3 lands. 5: b has its three tokens once
    #    step 4 lands, so step 5 steps a alone, ahead; b retires
    entry._iterate()
    entry._iterate()
    assert b.done() and not a.done()
    assert (len(sa.generated), sa.ahead) == (5, 1)
    # 6: nothing of step 5 steps again: drained, a retires
    entry._iterate()
    assert a.done() and entry._launched is None
    assert _tokens(a) == ref_a and _tokens(b) == ref_b
    for r in (a, b):
        _assert_stamps(r)

    spans = tracer.spans()
    steps = [s for s in spans if s["name"] == "decode::step"]
    fetches = [s for s in spans if s["name"] == "decode::step_fetch"]
    samples = [s for s in spans if s["name"] == "decode::sample"]
    assert [s["args"]["ahead"] for s in steps] == [
        False, True, False, True, True]
    assert [s["args"].get("drain") for s in fetches] == [
        None, "admission", None, None, "idle"]
    assert {s["args"]["rows"] for s in fetches} == {"tokens"}
    assert [s["args"]["tokens"] for s in samples] == [1, 1, 2, 2, 1]
    # a fetch made under a launched step follows that launch; a drained
    # one comes before the next launch
    order = sorted((s for s in spans if s["name"] in (
        "decode::step", "decode::step_fetch")), key=lambda s: s["start_ns"])
    assert [(s["name"][8:], s["args"].get("ahead", s["args"].get("drain")))
            for s in order] == [
        ("step", False), ("step", True), ("step_fetch", None),
        ("step_fetch", "admission"), ("step", False), ("step", True),
        ("step_fetch", None), ("step", True), ("step_fetch", None),
        ("step_fetch", "idle")]
    m = entry.metrics
    assert m.count("decode_steps_ahead") == 3
    assert m.count("step_launches") == m.count("decode_steps") == 5
    assert m.count("generated_tokens") == 7
    assert m.count("decode_logits_fetch_steps") == 0
    assert entry.stats()["decode_steps_ahead"] == 3
    text = obs.scrape_text()
    assert "serving_decode_steps_ahead_total" in text
    # the token feed of a launch ahead never left the device, and a step
    # launched after its fetch carries its tokens in the same ONE host
    # array (``dec_step``, PR 39): either way that array is all that is put
    assert {(s["args"]["puts"], s["args"]["bytes"]) for s in steps} == {
        (1, entry.model.step_feed().nbytes)}


# -- the same tokens as the serial engine -------------------------------------------------

def _boundary(entry):
    # cursors 3, 7 and 4: every request opens blocks while a step is in
    # flight, one of them with its very first step
    return {0: [dict(prompt=[3, 1, 4], max_new_tokens=12),
                dict(prompt=[2, 7, 1, 8, 2, 8, 1], max_new_tokens=11),
                dict(prompt=[5, 9, 2, 6], max_new_tokens=9)]}


def _admissions(entry):
    # arrivals while steps are in flight, the last one into a slot that a
    # retirement frees; a chunked prompt among them (chunks of 5)
    rng = np.random.RandomState(5)
    long_prompt = [int(t) for t in rng.randint(0, 32, size=13)]
    return {0: [dict(prompt=[3, 1, 4, 1, 5], max_new_tokens=14)],
            3: [dict(prompt=[9, 2, 6], max_new_tokens=4)],
            4: [dict(prompt=long_prompt, max_new_tokens=6),
                dict(prompt=[7, 7, 1], max_new_tokens=9)],
            9: [dict(prompt=[1, 8, 2, 8], max_new_tokens=5)]}


def _retirements(entry):
    # four requests that end at max_new one after another, the first with
    # the first step (two tokens: the prefill's and one more), and one
    # whose only token is the prefill's
    return {0: [dict(prompt=[3, 1, 4], max_new_tokens=2),
                dict(prompt=[9, 2, 6, 5], max_new_tokens=3),
                dict(prompt=[2, 7], max_new_tokens=7),
                dict(prompt=[5, 5, 5, 1], max_new_tokens=10)],
            2: [dict(prompt=[8, 3], max_new_tokens=1)]}


def _arena_end(entry):
    # the arena's last position: the cursor ends the request, not max_new
    return {0: [dict(prompt=list(range(1, 27)), max_new_tokens=6),
                dict(prompt=[4, 4, 2], max_new_tokens=8)]}


SCENARIOS = {"block_boundary": (_boundary, {}),
             "admission_in_mid_flight": (_admissions, {"chunk_tokens": 5}),
             "retirement_at_max_new": (_retirements, {}),
             "end_of_the_arena": (_arena_end, {})}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_greedy_tokens_equal_the_serial_engines(scenario):
    script_of, opts = SCENARIOS[scenario]
    served = {}
    for serial in (False, True):
        engine, entry = _engine(f"la_{scenario}", serial=serial, **opts)
        script = script_of(entry)
        resps = _play(entry, engine, script)
        served[serial] = [_tokens(r) for r in resps]
        for r in resps:
            _assert_stamps(r)
        m = entry.metrics
        assert m.count("failed") == 0
        assert m.count("step_launches") == m.count("decode_steps")
        assert m.count("generated_tokens") + m.count("prefill_tokens") \
            == sum(len(t) for t in served[serial])
        if serial:
            assert m.count("decode_steps_ahead") == 0
            assert m.count("decode_logits_fetch_steps") \
                == m.count("decode_steps")
        else:
            assert m.count("decode_steps_ahead") > 0
            assert m.count("decode_logits_fetch_steps") == 0
            # fewer iterations' worth of launches were NOT ahead than were
            if scenario != "admission_in_mid_flight":
                assert 2 * m.count("decode_steps_ahead") \
                    > m.count("decode_steps")
        entry.block_pool.check_conservation()
        assert entry.block_pool.stats()["blocks_live"] == 0
        kws = [kw for i in sorted(script) for kw in script[i]]
        for kw, got in zip(kws, served[serial]):
            assert got == entry.offline_decode(kw["prompt"],
                                               kw["max_new_tokens"])
    assert served[False] == served[True]
    assert any(len(set(t)) > 2 for t in served[False]), served


def test_a_chunk_that_is_not_its_prompts_last_runs_under_a_step_in_flight(
        tracer):
    """A prefilling slot's chunk is a launch and no fetch, so it does not
    drain the step in flight; the prompt's LAST chunk fetches its logits
    and turns the slot into a stepping one, so it does."""
    engine, entry = _engine("la_chunks", chunk_tokens=4)
    rng = np.random.RandomState(7)
    long_prompt = [int(t) for t in rng.randint(0, 32, size=14)]
    refs = [entry.offline_decode([3, 1, 4], 12),
            entry.offline_decode(long_prompt, 4)]
    resps = _play(entry, engine, {
        0: [dict(prompt=[3, 1, 4], max_new_tokens=12)],
        2: [dict(prompt=long_prompt, max_new_tokens=4)]})
    assert [_tokens(r) for r in resps] == refs
    spans = sorted(tracer.spans(), key=lambda s: s["start_ns"])
    its = [s for s in spans if s["name"] == "decode::iterate"]

    def within(it, name):
        return [s for s in spans if s["name"] == name
                and it["start_ns"] <= s["start_ns"]
                < it["start_ns"] + it["dur_ns"]]

    chunk_its = [it for it in its if within(it, "decode::chunk")]
    assert len(chunk_its) == 4                      # 14 tokens in fours
    ahead = [[s["args"]["ahead"] for s in within(it, "decode::step")]
             for it in chunk_its]
    drains = [[s["args"].get("drain")
               for s in within(it, "decode::step_fetch")]
              for it in chunk_its]
    # the admission's own iteration drained for it, the middle chunks ran
    # under a step in flight, the last chunk drained as "prefill"
    assert ahead == [[False], [True], [True], [False]]
    assert drains == [["admission"], [None], [None], ["prefill"]]
    assert len(within(chunk_its[-1], "decode::chunk_fetch")) == 1


# -- a deadline that expires with a step in flight ----------------------------------------

def test_a_deadline_that_expires_with_a_step_in_flight_drops_that_token():
    engine, entry = _engine("la_deadline")
    ref_live = entry.offline_decode([9, 2, 6], 10)
    ref_next = entry.offline_decode([7, 7, 1, 8], 5)
    doomed = engine.submit([3, 1, 4, 1], max_new_tokens=12,
                           deadline_ms=600000)
    live = engine.submit([9, 2, 6], max_new_tokens=10)
    entry._iterate()
    entry._iterate()
    sd = entry._slots[0]
    assert sd.request.response is doomed and sd.ahead == 1
    sd.request.deadline = 0.0
    # the next step is launched ahead with the doomed slot in it; the step
    # that lands under it finds the deadline gone
    entry._iterate()
    assert doomed.done() and entry._slots[0] is None
    assert sd in entry._launched.states
    with pytest.raises(DeadlineExceededError,
                       match="mid-generation after 3 tokens"):
        doomed.result()
    assert len(doomed.token_times) == 3
    # the freed slot takes the next request while that step's row for the
    # old one is dropped
    nxt = engine.submit([7, 7, 1, 8], max_new_tokens=5)
    for _ in range(40):
        if live.done() and nxt.done():
            break
        entry._iterate()
    assert _tokens(live) == ref_live and _tokens(nxt) == ref_next
    for r in (live, nxt):
        _assert_stamps(r)
    m = entry.metrics
    assert m.count("deadline_missed") == 1
    assert m.count("generated_tokens") + m.count("prefill_tokens") \
        == 3 + 10 + 5
    entry.block_pool.check_conservation()
    assert entry.block_pool.stats()["blocks_live"] == 0


# -- an eos_id model: one wasted row, never read ------------------------------------------

def test_an_eos_models_wasted_row_is_never_read_by_the_blocks_next_owner():
    """A slot that turns out to have ended at step N was stepped at N+1
    for nothing: that row lands in a block the slot still owned at the
    launch, the token is dropped, and whoever gets the block next writes
    every row before a bias lets it be read. The next owner's logits rows
    are, byte for byte, those of an engine that never saw the first
    request."""
    prompt, follower = [3, 1, 4, 1, 5], [9, 2, 6]
    _engine_probe, probe = _engine("la_eos")
    free_run = probe.offline_decode(prompt, 12)
    # a token whose first appearance is in mid-stream ends the request
    # there (offline_decode and the engine share the rule)
    at = next(i for i in range(3, 10) if free_run[i] not in free_run[:i])
    eos = free_run[at]

    def serve(with_first):
        engine, entry = _engine("la_eos", eos_id=eos)
        rows = {}
        record_step_logits(entry, rows)
        out = {}
        if with_first:
            first = engine.submit(prompt, max_new_tokens=12)
            wasted = False
            for _ in range(40):
                st = entry._slots[0]
                entry._iterate()
                if first.done():
                    # the step over the one that brought the eos holds
                    # the ended slot
                    wasted = (entry._launched is not None
                              and st in entry._launched.states)
                    break
            assert wasted
            out["first"] = _tokens(first)
            out["first_blocks"] = {b.id for b in st.blocks} or None
            out["first_rows"] = rows[id(first)]
        second = engine.submit(follower, max_new_tokens=10)
        entry._iterate()
        out["second_blocks"] = {b.id for b in entry._slots[0].blocks}
        for _ in range(40):
            if second.done():
                break
            entry._iterate()
        out["second"] = _tokens(second)
        out["second_rows"] = rows[id(second)]
        out["ref"] = entry.offline_decode(follower, 10)
        out["metrics"] = entry.metrics
        entry.block_pool.check_conservation()
        return out

    used, clean = serve(True), serve(False)
    assert used["first"] == free_run[:at + 1] and used["first"][-1] == eos
    # one row per delivered token after the prefill's: none for the row
    # that was stepped for nothing
    assert len(used["first_rows"]) == at
    m = used["metrics"]
    assert m.count("generated_tokens") + m.count("prefill_tokens") \
        == len(used["first"]) + len(used["second"])
    assert used["second"] == clean["second"] == used["ref"]
    assert len(used["second_rows"]) == len(clean["second_rows"]) > 0
    for x, y in zip(used["second_rows"], clean["second_rows"]):
        assert x.tobytes() == y.tobytes()
    assert m.count("decode_steps_ahead") > 0


def test_the_next_owner_gets_the_block_the_wasted_row_landed_in():
    """What the test above rests on: released blocks are handed out again
    last-released first, so the follower's first block IS the ended
    slot's tail block."""
    engine, entry = _engine("la_eos_blocks")
    first = engine.submit([3, 1, 4, 1, 5], max_new_tokens=4)
    entry._iterate()
    tail = entry._slots[0].blocks[-1].id
    for _ in range(10):
        entry._iterate()
    assert first.done()
    engine.submit([9, 2, 6], max_new_tokens=2)
    entry._iterate()
    assert entry._slots[0].blocks[0].id == tail


# -- policies that need more than the token never launch ahead ----------------------------

def _grammar():
    return CompiledGrammar.from_regex("[a-f]+[0-4]", VOCAB, eos_id=0)


POLICIES = {
    "sampled": ({}, lambda: {"sampling": SAMPLED}),
    "beam": ({}, lambda: {"beam_width": 2}),
    "grammar_on_the_device": ({"logits_mask": True, "eos_id": 0},
                              lambda: {"grammar": _grammar()}),
}


@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_a_batch_with_one_such_slot_never_launches_ahead(policy, tracer):
    """While the sampled, beam or grammar request lives every step lands
    in the body that launched it, greedy batchmates included, and the
    tokens are the serial engine's; once it is gone the greedy rest
    launches ahead."""
    opts, special = POLICIES[policy]
    greedy = [dict(prompt=[9, 2, 6], max_new_tokens=16),
              dict(prompt=[27, 18, 28, 18], max_new_tokens=14)]
    served = {}
    for serial in (False, True):
        engine, entry = _engine(f"la_{policy}", serial=serial, **opts)
        tracer.clear()
        first = dict(prompt=[3, 1, 4, 1, 5], max_new_tokens=6, **special())
        resps = [engine.submit(kw.pop("prompt"), **kw)
                 for kw in [dict(first)] + [dict(kw) for kw in greedy]]
        ahead = {True: [], False: []}   # by whether the first was alive
        seen = 0
        for _ in range(80):
            if all(r.done() for r in resps):
                break
            alive = not resps[0].done()
            entry._iterate()
            steps = [s["args"]["ahead"] for s in tracer.spans()
                     if s["name"] == "decode::step"]
            ahead[alive] += steps[seen:]
            seen = len(steps)
        assert all(r.done() for r in resps) and entry._launched is None
        outs = [r.result() for r in resps]
        served[serial] = [
            (o["tokens"].tolist(),
             [(b["tokens"].tolist(), b["score"])
              for b in o.get("beams", ())]) for o in outs]
        if serial:
            assert entry.metrics.count("decode_steps_ahead") == 0
            continue
        assert ahead[True] and not any(ahead[True])
        assert any(ahead[False])
        assert entry.metrics.count("decode_steps_ahead") \
            == sum(ahead[False])
        for kw, (toks, _beams) in zip(greedy, served[serial][1:]):
            assert toks == entry.offline_decode(kw["prompt"],
                                                kw["max_new_tokens"])
    assert served[False] == served[True]


# -- a fault in a step launched ahead -----------------------------------------------------

def test_a_fault_in_a_launched_ahead_step_fails_both_steps_slots_and_recovers():
    """The launch of step 3 raises with step 2 in flight. The short
    request had its last token in step 2 and is not in step 3: it fails
    too, loudly, since step 2 is not delivered from a dead arena."""
    engine, entry = _engine("la_fault")
    ref = entry.offline_decode([3, 1, 4, 1], 8)
    faults.configure([{"site": "decode.step", "action": "raise",
                       "at_call": 3}])
    try:
        long = engine.submit([3, 1, 4, 1], max_new_tokens=8)
        short = engine.submit([9, 2, 6], max_new_tokens=3)
        entry._iterate()                # step 1 in flight
        entry._iterate()                # step 2 ahead, step 1 lands
        states = list(entry._launched.states)
        assert len(states) == 2 and not long.done() and not short.done()
        entry._iterate()                # step 3's launch raises
        assert entry._launched is None
        assert [st for st in entry._slots if st is not None] == []
        for r in (long, short):
            with pytest.raises(RequestError, match="decode-step failure"):
                r.result(timeout=1)
        m = entry.metrics
        assert m.count("step_failures") == 1
        assert m.count("decode_steps") == 1        # step 2 never landed
        assert m.count("generated_tokens") == 2
        assert len(short.token_times) == 2         # no third stamp
        again = engine.submit([3, 1, 4, 1], max_new_tokens=8)
        for _ in range(20):
            if again.done():
                break
            entry._iterate()
        assert _tokens(again) == ref
        entry.block_pool.check_conservation()
        assert entry.block_pool.stats()["blocks_live"] == 0
    finally:
        faults.reset()


# -- a step handed its tokens by hand -----------------------------------------------------

def test_step_called_by_hand_drains_for_a_slot_the_step_in_flight_lacks(
        tracer):
    """`_step` itself holds the rule its token feed rests on: every
    stepping slot has its token in the step in flight. A caller that
    admits by hand between two steps gets a drain, not a stale feed."""
    engine, entry = _engine("la_byhand")
    refs = [entry.offline_decode([3, 1, 4], 5),
            entry.offline_decode([9, 2, 6, 5], 4)]
    a = engine.submit([3, 1, 4], max_new_tokens=5)
    assert entry._admit_free_slots() == 1
    entry._step()
    entry._step()
    b = engine.submit([9, 2, 6, 5], max_new_tokens=4)
    assert entry._admit_free_slots() == 1
    for _ in range(10):
        entry._step()
    assert _tokens(a) == refs[0] and _tokens(b) == refs[1]
    drains = [s["args"].get("drain") for s in tracer.spans()
              if s["name"] == "decode::step_fetch"]
    assert drains[:2] == [None, "slots"] and drains[-1] == "idle"
