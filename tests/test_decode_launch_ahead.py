"""Launching decode step N+1 ahead of step N's fetch (ISSUE 34), and a
chunked admission in the same order (ISSUE 42, the file's second half).

A step whose slots need only their tokens stays on the device after its
launch; the next iteration launches the following step, fed those tokens
as the device array they are, and fetches and delivers the first one only
then. Anything that has to see the device or changes who steps drains
the step in flight first; since ISSUE 42 an arrival whose admission is a
block acquisition and a prefilling slot, a prompt's last chunk and the new
slot's first step do not. Hand-stepped through ``entry._iterate()`` over
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
    drain the step in flight; since ISSUE 42 neither does the arrival
    (its admission is a block acquisition and a slot in mode "prefill")
    nor the prompt's LAST chunk (its row is fetched behind the next
    step's launch)."""
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
    # every chunk ran under a step in flight, the admission's own and the
    # prompt's last too: the last chunk does not drain
    assert ahead == [[True], [True], [True], [True]]
    assert drains == [[None], [None], [None], [None]]
    chunks = [s for it in chunk_its for s in within(it, "decode::chunk")]
    assert [(s["args"]["ahead"], s["args"]["last"]) for s in chunks] == [
        (True, False), (True, False), (True, False), (True, True)]
    (row,) = within(chunk_its[-1], "decode::chunk_fetch")
    assert row["args"]["deferred"] is True
    (step,) = within(chunk_its[-1], "decode::step")
    assert step["start_ns"] < row["start_ns"]
    assert entry.metrics.drains()["prefill"] == 0
    assert entry.metrics.drains()["admission"] == 0
    assert entry.metrics.count("chunk_launches_ahead") \
        == entry.metrics.count("chunk_runs") == 4


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
            out["first_blocks"] = {b.id for b in st.kv.blocks} or None
            out["first_rows"] = rows[id(first)]
        second = engine.submit(follower, max_new_tokens=10)
        entry._iterate()
        out["second_blocks"] = {b.id for b in entry._slots[0].kv.blocks}
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
    tail = entry._slots[0].kv.blocks[-1].id
    for _ in range(10):
        entry._iterate()
    assert first.done()
    engine.submit([9, 2, 6], max_new_tokens=2)
    entry._iterate()
    assert entry._slots[0].kv.blocks[0].id == tail


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

def test_step_called_by_hand_steps_a_slot_the_step_in_flight_lacks(
        tracer):
    """`_step` itself holds the rule its token feed rests on: a stepping
    slot has its token in the step in flight, or on the host. A caller
    that admits by hand between two steps gets the new slot stepped from
    its host token beside the others' -1 (a drain until ISSUE 42), not a
    stale feed."""
    engine, entry = _engine("la_byhand")
    refs = [entry.offline_decode([3, 1, 4], 5),
            entry.offline_decode([9, 2, 6, 5], 4)]
    a = engine.submit([3, 1, 4], max_new_tokens=5)
    assert entry._admit_free_slots() == 1
    entry._step()
    entry._step()
    b = engine.submit([9, 2, 6, 5], max_new_tokens=4)
    assert entry._admit_free_slots() == 1
    fed = []
    _watch_step_tokens(entry, fed)
    sb = entry._slots[1]
    first = sb.last_token
    for _ in range(10):
        entry._step()
    assert fed[0][:2] == [-1, first] and fed[1][:2] == [-1, -1]
    assert _tokens(a) == refs[0] and _tokens(b) == refs[1]
    drains = [s["args"].get("drain") for s in tracer.spans()
              if s["name"] == "decode::step_fetch"]
    assert drains[:2] == [None, None] and drains[-1] == "idle"
    assert entry.metrics.drains()["slots"] == 0


# == ISSUE 42: a chunked admission joins the launch-ahead order ===========================
#
# An arrival whose admission is a block acquisition and a slot in mode
# "prefill", every chunk of its prompt (the last one too) and the new slot's
# first step run under the step in flight. Toy decoder with chunks of 4 (a
# prompt of up to 4 tokens is one-shot and still drains) and a small recurrent
# hybrid (every prompt chunked), each against its serial engine.

HYBRID = dict(
    vocab_size=96, hidden_size=64, hybrid_override_pattern="MEM*E",
    mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
    conv_kernel=4, chunk_size=8, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, n_routed_experts=4,
    router_experts=8, num_experts_per_tok=2, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=48, routed_scaling_factor=2.5,
    norm_topk_prob=True, layer_norm_epsilon=1e-5, dtype="float32",
    expert_rank=1, slots=4, max_len=48, block_size=4, chunk_tokens=4)


def _hybrid(name, serial=False, **opts):
    """A small recurrent hybrid, as tests/test_nemotron_h_serving.py
    ``_model`` builds its own: no prefix cache, no host tier."""
    from paddle_tpu.serving.decode import build_nemotron_h_model

    model = build_nemotron_h_model(name=name, **dict(HYBRID, **opts))
    model.startup_program.random_seed = 7
    if serial:
        model = without_token_fetch(model)
    engine = GenerationEngine(prefix_cache_size=0, host_tier_mb=0,
                              breaker_threshold=0)
    return engine, engine.register_model(model)


def _make(kind, name, serial=False, **opts):
    if kind == "hybrid":
        return _hybrid(name, serial=serial, **opts)
    return _engine(name, serial=serial, chunk_tokens=4, **opts)


def _prompt(n, seed, vocab=32):
    rng = np.random.RandomState(seed)
    return [int(t) for t in rng.randint(1, vocab, size=n)]


def _watch_step_tokens(entry, into):
    """Keep the token column of every step's ``dec_step`` feed."""
    run = entry._run

    def running(kind, feeds, span=None):
        if kind == "step":
            into.append(feeds["dec_step"][:, 0].tolist())
        return run(kind, feeds, span)

    entry._run = running


def _spans(tracer, name):
    return sorted((s for s in tracer.spans() if s["name"] == name),
                  key=lambda s: s["start_ns"])


def test_an_arrival_its_chunks_and_its_first_step_run_under_steps_in_flight(
        tracer):
    """The schedule by hand: ``a`` steps alone; ``b`` (10 tokens: chunks of
    4, 4 and 2) arrives with a step in flight. No fetch of a step is a
    drain until the end; every chunk is a launch ahead; the last chunk's
    row lands behind the next step's launch; ``b`` joins the step after
    with its token from the host beside ``a``'s -1."""
    engine, entry = _engine("la42_sched", chunk_tokens=4)
    pb = _prompt(10, 3)
    ref_a = entry.offline_decode([3, 1, 4], 12)
    ref_b = entry.offline_decode(pb, 4)
    fed = []
    _watch_step_tokens(entry, fed)
    a = engine.submit([3, 1, 4], max_new_tokens=12)
    entry._iterate()            # 1: a admitted (one-shot), step 1 in flight
    entry._iterate()            # 2: step 2 ahead, step 1 lands
    sa = entry._slots[0]
    b = engine.submit(pb, max_new_tokens=4)
    # 3: b admitted under step 2, its first chunk launched under it, step 3
    #    ahead for a alone
    entry._iterate()
    sb = entry._slots[1]
    assert (sb.mode, sb.done) == ("prefill", 4) and sa.ahead == 1
    assert entry.metrics.drains()["admission"] == 0
    entry._iterate()            # 4: chunk 2 ahead, step 4 ahead
    assert (sb.mode, sb.done) == ("prefill", 8)
    # 5: the last chunk is a launch too; step 5 (a alone) goes out BEFORE
    #    its row is fetched; the row lands and b has its first token on the
    #    host, in no step yet
    entry._iterate()
    assert (sb.mode, sb.done, sb.cursor, sb.ahead) == ("decode", 10, 10, 0)
    assert sb.generated == ref_b[:1] and len(b.token_times) == 1
    assert sb not in entry._launched.states
    assert fed[-1][:2] == [-1, -1]              # b's row: not stepping
    # 6: step 6 ahead of step 5's fetch: a rides on the device, b is fed
    #    its token from the host
    entry._iterate()
    assert fed[-1][:2] == [-1, ref_b[0]]
    assert sb in entry._launched.states and sb.ahead == 1
    entry._iterate()            # 7: both ride on the device
    assert fed[-1][:2] == [-1, -1]
    for _ in range(20):
        if a.done() and b.done():
            break
        entry._iterate()
    assert _tokens(a) == ref_a and _tokens(b) == ref_b
    for r in (a, b):
        _assert_stamps(r)

    chunks = _spans(tracer, "decode::chunk")
    assert [(s["args"]["tokens"], s["args"]["ahead"], s["args"]["last"])
            for s in chunks] == [(4, True, False), (4, True, False),
                                 (2, True, True)]
    (row,) = _spans(tracer, "decode::chunk_fetch")
    assert row["args"]["deferred"] is True
    steps = _spans(tracer, "decode::step")
    # the row's fetch follows the launch of the step after its chunk
    assert [s["start_ns"] < row["start_ns"] for s in steps[:6]] == [
        True, True, True, True, True, False]
    assert chunks[-1]["start_ns"] < steps[4]["start_ns"]
    fetches = _spans(tracer, "decode::step_fetch")
    assert [s["args"].get("drain") for s in fetches] \
        == [None] * (len(fetches) - 1) + ["idle"]
    assert [s["args"]["ahead"] for s in steps] \
        == [False] + [True] * (len(steps) - 1)
    m = entry.metrics
    assert m.drains() == dict.fromkeys(m.drains(), 0) | {"idle": 1}
    assert m.count("chunk_runs") == m.count("chunk_launches_ahead") == 3
    assert m.count("decode_steps_ahead") == m.count("step_launches") - 1
    assert "serving_decode_drains_total" in obs.scrape_text()
    assert "serving_chunk_launches_ahead_total" in obs.scrape_text()
    entry.block_pool.check_conservation()
    assert entry.block_pool.stats()["blocks_live"] == 0


@pytest.mark.parametrize("plen", [1, 4, 5])
@pytest.mark.parametrize("kind", ["decoder", "hybrid"])
def test_prompts_of_1_c_and_c_plus_1_tokens_equal_the_serial_engines(
        kind, plen):
    """A prompt of 1, C and C + 1 tokens arriving with a step in flight:
    one chunk that is the last, a full last chunk, a chunk and a last one
    of one token (one-shot for the first two on the decoder). The tokens
    are the serial engine's and, where there is one, the offline
    reference's."""
    vocab = 96 if kind == "hybrid" else 32
    script = {0: [dict(prompt=_prompt(6, 1, vocab), max_new_tokens=14)],
              3: [dict(prompt=_prompt(plen, 2, vocab), max_new_tokens=6)],
              5: [dict(prompt=_prompt(9, 4, vocab), max_new_tokens=5)]}
    served = {}
    for serial in (False, True):
        engine, entry = _make(kind, f"la42_{kind}_{plen}", serial=serial)
        resps = _play(entry, engine, script)
        served[serial] = [_tokens(r) for r in resps]
        for r in resps:
            _assert_stamps(r)
        m = entry.metrics
        assert m.count("failed") == 0
        assert m.count("step_launches") == m.count("decode_steps")
        assert m.count("generated_tokens") + m.count("prefill_tokens") \
            == sum(len(t) for t in served[serial])
        entry.block_pool.check_conservation()
        assert entry.block_pool.stats()["blocks_live"] == 0
        if serial:
            assert m.count("decode_steps_ahead") == 0
            assert m.count("chunk_launches_ahead") == 0
            assert sum(m.drains().values()) == 0
            continue
        chunked = kind == "hybrid" or plen > 4
        assert m.drains()["admission"] == (0 if chunked else 1)
        assert m.drains()["prefill"] == m.drains()["slots"] == 0
        # the first request's two chunks found no step to run under,
        # every later chunk did
        assert m.count("chunk_launches_ahead") == m.count("chunk_runs") - 2
        if kind == "decoder":
            kws = [kw for i in sorted(script) for kw in script[i]]
            for kw, got in zip(kws, served[serial]):
                assert got == entry.offline_decode(kw["prompt"],
                                                   kw["max_new_tokens"])
    assert served[False] == served[True]
    assert any(len(set(t)) > 2 for t in served[False]), served


# -- a first token that ends the request --------------------------------------------------

@pytest.mark.parametrize("ending", ["max_new_of_1", "eos_id"])
def test_a_first_token_that_ends_the_request_retires_at_the_landing(ending):
    """The step launched over the last chunk did not include the new slot
    (its row had not landed), so a request that its first token ends is
    retired at the landing and no step wastes a row on it."""
    pb = _prompt(7, 9)
    _probe_engine, probe = _engine("la42_end", chunk_tokens=4)
    first = probe.offline_decode(pb, 1)[0]
    long_run = probe.offline_decode([3, 1, 4], 12)
    opts, max_new = {}, 1
    if ending == "eos_id":
        assert first not in long_run
        opts, max_new = {"eos_id": first}, 6
    engine, entry = _engine("la42_end", chunk_tokens=4, **opts)
    fed = []
    _watch_step_tokens(entry, fed)
    a = engine.submit([3, 1, 4], max_new_tokens=12)
    entry._iterate()
    entry._iterate()
    b = engine.submit(pb, max_new_tokens=max_new)
    entry._iterate()                        # admitted, first chunk
    sb = entry._slots[1]
    assert sb.mode == "prefill" and not b.done()
    steps = entry.metrics.count("step_launches")
    entry._iterate()                        # last chunk, its row landed
    assert b.done() and _tokens(b) == [first]
    assert entry._slots[1] is None and sb not in entry._launched.states
    assert entry.metrics.count("step_launches") == steps + 1
    for _ in range(20):
        if a.done():
            break
        entry._iterate()
    assert _tokens(a) == long_run
    # b's slot never carried a token or stepped: its column stayed -1
    assert {row[1] for row in fed} == {-1}
    m = entry.metrics
    assert m.count("active_slot_steps") == m.count("generated_tokens") == 11
    assert m.count("prefill_tokens") == 2 and m.count("retired") == 2
    assert m.drains()["admission"] == m.drains()["prefill"] == 0
    entry.block_pool.check_conservation()
    assert entry.block_pool.stats()["blocks_live"] == 0


# -- a wasted row against a next owner admitted under that step ---------------------------

@pytest.mark.parametrize("kind", ["decoder", "hybrid"])
def test_a_next_owner_admitted_under_the_step_that_wastes_a_row(kind, tracer):
    """``first`` ends at an ``eos_id`` that the landing of step N shows,
    after step N+1 was launched with it. The follower arrives next, is
    ADMITTED UNDER step N+1 (no drain) into the slot and the blocks that
    step still writes, and its chunks, launched later, overwrite the wasted
    row; a recurrent slot's chunk at position 0 resets the state that step
    dirtied. Its logits rows are, byte for byte, those of an engine that
    never saw ``first``."""
    vocab = 96 if kind == "hybrid" else 32
    name = f"la42_waste_{kind}"
    prompt, keeper_prompt = _prompt(6, 21, vocab), _prompt(5, 22, vocab)
    follower = _prompt(9, 23, vocab)
    # the serial engine's free run of the first request names the token
    # that will end it in mid-stream
    engine0, probe = _make(kind, name, serial=True)
    (free,) = _play(probe, engine0,
                    {0: [dict(prompt=prompt, max_new_tokens=12)]})
    free_run = _tokens(free)
    at = next(i for i in range(3, 10) if free_run[i] not in free_run[:i])
    eos = free_run[at]

    def serve(with_first):
        engine, entry = _make(kind, name, eos_id=eos)
        rows, out = {}, {}
        record_step_logits(entry, rows)
        keeper = engine.submit(keeper_prompt, max_new_tokens=26)
        if with_first:
            first = engine.submit(prompt, max_new_tokens=12)
            for _ in range(60):
                st = entry._slots[1]
                entry._iterate()
                if first.done():
                    break
            # the step over the one that brought the eos holds the slot
            assert st in entry._launched.states
            assert entry._slots[1] is None
            out["first"] = _tokens(first)
            # where that step writes the row nobody will read
            out["tail"] = st.kv.blocks[(st.cursor - 1) // 4].id
            tracer.clear()
        else:
            for _ in range(6):
                entry._iterate()
        wasting = entry._launched
        second = engine.submit(follower, max_new_tokens=10)
        entry._iterate()
        sf = entry._slots[1]
        assert sf.request.response is second and sf.mode == "prefill"
        out["blocks"] = [b.id for b in sf.kv.blocks]
        # admitted and given its first chunk with that step untouched
        assert entry.metrics.drains()["admission"] == 0
        chunk = _spans(tracer, "decode::chunk")[0] if with_first else None
        out["ahead"] = chunk and chunk["args"]["ahead"]
        out["resets"] = [i["args"]["slot"] for i in tracer.instants()
                         if i["name"] == "decode::state_reset"]
        out["wasting"] = wasting
        for _ in range(80):
            if second.done() and keeper.done():
                break
            entry._iterate()
        out["second"] = _tokens(second)
        out["second_rows"] = rows[id(second)]
        out["keeper"] = _tokens(keeper)
        assert eos not in out["keeper"][:-1]
        out["drains"] = entry.metrics.drains()
        entry.block_pool.check_conservation()
        assert entry.block_pool.stats()["blocks_live"] == 0
        return out

    used, clean = serve(True), serve(False)
    assert used["first"] == free_run[:at + 1]
    # the follower holds the block the wasted row was written to
    assert used["tail"] in used["blocks"] and used["ahead"] is True
    if kind == "hybrid":
        assert used["resets"] == [1]
    assert used["second"] == clean["second"]
    assert used["keeper"] == clean["keeper"]
    assert len(used["second_rows"]) == len(clean["second_rows"]) > 0
    for x, y in zip(used["second_rows"], clean["second_rows"]):
        assert x.tobytes() == y.tobytes()
    assert used["drains"]["admission"] == used["drains"]["prefill"] == 0


# -- a deadline that expires with the last chunk unlanded ---------------------------------

def test_a_deadline_that_expires_with_the_last_chunk_unlanded():
    """The row is on the device, the deadline runs out, the next step is
    launched without the slot, and the landing delivers the one token the
    device paid for and then fails the request: no step ever holds it."""
    engine, entry = _engine("la42_deadline", chunk_tokens=4)
    ref = entry.offline_decode([3, 1, 4], 10)
    pb = _prompt(7, 5)
    first = entry.offline_decode(pb, 1)
    a = engine.submit([3, 1, 4], max_new_tokens=10)
    entry._iterate()
    entry._iterate()
    doomed = engine.submit(pb, max_new_tokens=8, deadline_ms=600000)
    entry._iterate()                                # admitted, chunk 1
    sd = entry._slots[1]
    assert entry._launched is not None
    assert entry._advance_prefills() == 1           # the last chunk
    assert len(entry._chunk_rows) == 1 and sd.mode == "prefill"
    assert entry._advance_prefills() == 0           # nothing left to chunk
    sd.request.deadline = 0.0
    entry._step()
    assert entry._chunk_rows == [] and entry._slots[1] is None
    assert sd not in entry._launched.states
    with pytest.raises(DeadlineExceededError,
                       match="mid-generation after 1 tokens"):
        doomed.result()
    assert len(doomed.token_times) == 1 and sd.generated == first
    for _ in range(20):
        if a.done():
            break
        entry._iterate()
    assert _tokens(a) == ref
    m = entry.metrics
    assert m.count("deadline_missed") == 1 and m.count("failed") == 0
    assert m.count("generated_tokens") + m.count("prefill_tokens") == 10 + 1
    entry.block_pool.check_conservation()
    assert entry.block_pool.stats()["blocks_live"] == 0


# -- a fault in a chunk launched under a step ---------------------------------------------

def test_a_fault_at_the_chunk_with_a_step_in_flight_fails_both_and_recovers():
    """The launch of the prompt's second chunk raises with a step in
    flight: the arena is undefined, so the prefilling slot and the slots of
    that step fail loudly, the step is not delivered, and the engine
    serves the next request."""
    engine, entry = _engine("la42_fault", chunk_tokens=4)
    pb = _prompt(10, 6)
    ref = entry.offline_decode(pb, 4)
    faults.configure([{"site": "decode.chunk", "action": "raise",
                       "at_call": 2}])
    try:
        a = engine.submit([3, 1, 4], max_new_tokens=12)
        entry._iterate()
        entry._iterate()
        b = engine.submit(pb, max_new_tokens=4)
        entry._iterate()                    # chunk 1 under a step in flight
        assert entry._launched is not None and not a.done()
        delivered = entry.metrics.count("decode_steps")
        entry._iterate()                    # chunk 2 raises
        assert entry._launched is None and entry._chunk_rows == []
        assert [st for st in entry._slots if st is not None] == []
        for r in (a, b):
            with pytest.raises(RequestError, match="chunk-prefill failure"):
                r.result(timeout=1)
        m = entry.metrics
        assert m.count("step_failures") == 1
        assert m.count("decode_steps") == delivered     # never landed
        again = engine.submit(pb, max_new_tokens=4)
        for _ in range(20):
            if again.done():
                break
            entry._iterate()
        assert _tokens(again) == ref
        entry.block_pool.check_conservation()
        assert entry.block_pool.stats()["blocks_live"] == 0
    finally:
        faults.reset()


# -- what still drains, and why -----------------------------------------------------------

def _still_drains_one_shot():
    # a prompt the chunk budget covers: its prefill_fetch waits
    return {}, {0: [dict(prompt=[3, 1, 4], max_new_tokens=12)],
                3: [dict(prompt=[9, 2, 6], max_new_tokens=5)]}, \
        {"admission": 1}


def _still_drains_beam():
    # a beam request drains at its admission, and again for its last
    # chunk: the first selection forks slots and copies arena rows
    return {}, {0: [dict(prompt=[3, 1, 4], max_new_tokens=12)],
                3: [dict(prompt=_prompt(7, 8), max_new_tokens=5,
                         beam_width=2)]}, {"admission": 1, "prefill": 1}


def _still_drains_sampled():
    # admitted and chunked under steps in flight; the new slot's first
    # step brings the logits over, so the step in flight is drained for it
    return {}, {0: [dict(prompt=[3, 1, 4], max_new_tokens=12)],
                3: [dict(prompt=_prompt(7, 8), max_new_tokens=5,
                         sampling=SAMPLED)]}, \
        {"admission": 0, "prefill": 0, "slots": 1}


def _still_drains_parked():
    # three sessions outgrow eight blocks: sessions are parked and, while
    # one waits to be resumed, every iteration drains ("parked"); the
    # ladder's moves drain too ("brownout")
    return {"num_blocks": 8, "slots": 3}, {
        0: [dict(prompt=[3, 1, 4], max_new_tokens=14),
            dict(prompt=[9, 2, 6], max_new_tokens=14),
            dict(prompt=[7, 7, 1], max_new_tokens=14)]}, \
        {"parked": 1, "brownout": 1}


STILL_DRAINS = {"one_shot": _still_drains_one_shot,
                "beam": _still_drains_beam,
                "sampled": _still_drains_sampled,
                "parked": _still_drains_parked}


@pytest.mark.parametrize("case", sorted(STILL_DRAINS))
def test_what_has_to_see_the_device_still_drains_and_says_why(case, tracer):
    opts, script, reasons = STILL_DRAINS[case]()
    served = {}
    for serial in (False, True):
        engine, entry = _engine(f"la42_{case}", serial=serial,
                                chunk_tokens=4, **opts)
        tracer.clear()
        resps = _play(entry, engine, script, iterations=200)
        outs = [r.result() for r in resps]
        served[serial] = [
            (o["tokens"].tolist(),
             [(b["tokens"].tolist(), b["score"])
              for b in o.get("beams", ())]) for o in outs]
        entry.block_pool.check_conservation()
        assert entry.block_pool.stats()["blocks_live"] == 0
        if serial:
            continue
        drains = entry.metrics.drains()
        for why, n in reasons.items():
            assert (drains[why] >= n) if n else (drains[why] == 0), drains
        spans = [s["args"]["drain"] for s in tracer.spans()
                 if s["name"] == "decode::step_fetch"
                 and "drain" in s["args"]]
        assert {why: spans.count(why) for why in drains} == drains
    assert served[False] == served[True]
