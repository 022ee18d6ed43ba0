"""Generation-modes subsystem (paddle_tpu/serving/decode/generate).

The acceptance contract (ISSUE 17): every decode POLICY — committed
threefry sampling, COW beam search, draft-KV speculative slots,
grammar-constrained masks — is bit-identical to its offline
whole-sequence reference REGARDLESS of admission order, slot assignment,
or batchmates; none of them widens the compiled program set (grammar
masks ride the DEC_MASK data feed: zero retraces after warmup); beam
fork/prune conserves the block pool exactly.
"""

import json
import re
import time

import numpy as np
import pytest
from decode_testing import SPEC_PROMPTS, jits, spec_leg

from paddle_tpu.serving.decode import (
    BeamParams,
    CompiledGrammar,
    GenerationEngine,
    GrammarConstraint,
    SamplingParams,
    build_decoder_model,
)
from paddle_tpu.serving.decode.generate import sample_token
from paddle_tpu.serving.decode.generate.beam import (
    finished_ranking,
    offline_beam_decode,
    select,
)
from paddle_tpu.serving.request import RejectedError

VOCAB = ["<eos>"] + list("abcdefghijklmnopqrstuvwxyz") + list("01234")


def _gen_model(name, version="1", slots=4, max_len=32, hidden=8,
               num_layers=2, **kw):
    return build_decoder_model(
        vocab_size=32, hidden=hidden, num_layers=num_layers, slots=slots,
        max_len=max_len, block_size=4, name=name, version=version, **kw)


# ---------------------------------------------------------------------------
# sampling primitives
# ---------------------------------------------------------------------------


def test_sample_token_committed_stream_is_pure():
    """Same (row, params, step) => same token, every time: the stream is
    a pure function of the request's seed and the absolute emitted-token
    index — nothing about WHEN or WHERE the step ran enters."""
    rng = np.random.RandomState(0)
    row = rng.randn(32).astype("float32")
    sp = SamplingParams(temperature=0.8, top_k=6, top_p=0.9, seed=7)
    draws = {sample_token(row, sp, step) for _ in range(4) for step in (0,)}
    assert len(draws) == 1
    # distinct steps consult distinct counters of the same stream
    toks = [sample_token(row, sp, s) for s in range(32)]
    assert len(set(toks)) > 1
    # a different seed is a different stream
    sp2 = SamplingParams(temperature=0.8, top_k=6, top_p=0.9, seed=8)
    assert [sample_token(row, sp2, s) for s in range(32)] != toks


def test_sample_token_respects_topk_topp_and_greedy():
    rng = np.random.RandomState(1)
    row = rng.randn(32).astype("float32")
    top3 = set(np.argsort(-row)[:3].tolist())
    sp = SamplingParams(temperature=1.2, top_k=3, seed=0)
    assert all(sample_token(row, sp, s) in top3 for s in range(64))
    greedy = SamplingParams(temperature=0.0, seed=123)
    assert sample_token(row, greedy, 0) == int(np.argmax(row))
    with pytest.raises(ValueError):
        SamplingParams(temperature=-1.0)
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)


# ---------------------------------------------------------------------------
# grammar compilation
# ---------------------------------------------------------------------------


def test_grammar_regex_dfa_masks_and_fork():
    g = CompiledGrammar.from_regex("ab*c", VOCAB, eos_id=0)
    c = GrammarConstraint(g)
    a, b, cc = VOCAB.index("a"), VOCAB.index("b"), VOCAB.index("c")
    m0 = c.mask()
    assert m0[a] == 0.0 and m0[b] < 0 and m0[0] < 0   # only 'a'; no EOS
    c.advance(a)
    m1 = c.mask()
    assert m1[b] == 0.0 and m1[cc] == 0.0 and m1[0] < 0
    c2 = c.fork()                      # COW the constraint with the beam
    c.advance(b)
    c2.advance(cc)                     # fork diverges independently
    assert not c.accepting() and c2.accepting()
    assert c2.mask()[0] == 0.0         # EOS exactly in accepting states
    c.advance(cc)
    assert c.accepting()


def test_grammar_json_schema_boolean_accepts_only_booleans():
    g = CompiledGrammar.from_json_schema({"type": "boolean"}, VOCAB,
                                         eos_id=0)
    for text in ("true", "false"):
        c = GrammarConstraint(g)
        for ch in text:
            t = VOCAB.index(ch)
            assert c.mask()[t] == 0.0, (text, ch)
            c.advance(t)
        assert c.accepting()
    c = GrammarConstraint(g)
    assert c.mask()[VOCAB.index("x")] < 0


# ---------------------------------------------------------------------------
# beam selection primitives
# ---------------------------------------------------------------------------


def test_beam_select_deterministic_tie_break():
    """Exact score ties rank by (parent, token): the committed total
    order that makes engine-vs-offline comparison byte-meaningful."""
    rows = [np.zeros(8, dtype="float32"), np.zeros(8, dtype="float32")]
    live, fin = select([0.0, 0.0], rows, 3, eos_id=None)
    # every candidate scores -log(8): (parent, token) breaks all ties
    assert [(p, t) for p, t, _s in live] == [(0, 0), (0, 1), (0, 2)]
    assert fin == []
    ranked = finished_ranking([([2, 1], -1.0), ([1, 9], -1.0), ([3], 0.0)])
    assert [t for t, _s in ranked] == [[3], [1, 9], [2, 1]]


def test_offline_beam_reference_beats_or_equals_greedy():
    """Width-3 beam's best total log-prob >= the greedy path's — on a
    deterministic synthetic oracle with a designed greedy trap."""
    V = 8

    def logits_fn(tokens):
        # log-softmax is shift-invariant, so a trap must SPLIT mass, not
        # just lower a logit: after greedy's pick the distribution is
        # bimodal (~ -log 2 per step); after the runner-up it is peaked
        row = np.full(V, -10.0, dtype="float32")
        if len(tokens) == 1:
            row[1], row[2] = 2.0, 1.9        # greedy grabs 1...
        elif tokens[-1] == 1:
            row[3] = row[6] = 0.0            # ...then faces a coin flip
        elif tokens[-1] == 2:
            row[4] = 3.0                     # runner-up opens a highway
        else:
            row[5] = 1.0
        return row

    def score(toks):
        total, seq = 0.0, [0]
        for t in toks:
            row = logits_fn(seq).astype("float64")
            total += float(row[t] - np.log(np.sum(np.exp(row))))
            seq.append(t)
        return total

    ranked = offline_beam_decode(logits_fn, [0], 3, BeamParams(3),
                                 eos_id=None, max_len=16)
    greedy = []
    seq = [0]
    for _ in range(3):
        t = int(np.argmax(logits_fn(seq)))
        greedy.append(t)
        seq.append(t)
    assert ranked[0][1] >= score(greedy) - 1e-12
    assert ranked[0][0][0] == 2              # the trap was escaped


# ---------------------------------------------------------------------------
# engine integration: the bit-identity contract per mode
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def gen_served():
    """One warm masked-logits engine + a byte-identical draft entry."""
    engine = GenerationEngine(queue_depth=64, breaker_threshold=0)
    entry = engine.register_model(lambda: _gen_model(
        "gens", eos_id=0, logits_mask=True))
    engine.register_model(lambda: _gen_model("gens_d", eos_id=0))
    engine.start()
    yield engine, entry
    engine.shutdown()


def test_sampled_decode_bit_identical_any_admission_order(gen_served):
    """Same seed + shuffled admission + different slot assignment =>
    byte-identical streams. The committed threefry stream is keyed per
    (request seed, emitted-token index); batchmates, slots, and timing
    never enter it."""
    engine, entry = gen_served
    rng = np.random.RandomState(3)
    prompts = [list(int(t) for t in rng.randint(1, 32, size=n))
               for n in (5, 3, 7, 2, 6)]
    sp = SamplingParams(temperature=0.9, top_k=8, top_p=0.95, seed=42)
    refs = [entry.offline_decode(p, 6, sampling=sp) for p in prompts]
    for order_seed in (0, 1, 2):
        order = np.random.RandomState(order_seed).permutation(len(prompts))
        resps = {}
        for i in order:
            # mixed batchmates: a greedy rider shares the batch
            if int(i) == int(order[0]):
                engine.submit(prompts[i], model="gens", max_new_tokens=3)
            resps[int(i)] = engine.submit(
                prompts[i], model="gens", max_new_tokens=6,
                sampling={"temperature": 0.9, "top_k": 8, "top_p": 0.95,
                          "seed": 42})
        for i, r in resps.items():
            got = [int(t) for t in r.result(timeout=120)["tokens"]]
            assert got == refs[i], (order_seed, i, got, refs[i])


def test_sampled_spec_distinct_draft_realizes_target_stream(gen_served):
    """Rejection-rule speculation with a draft whose weights DIFFER from
    the target (different depth): proposals are frequently wrong, yet
    the realized stream equals the target-only sampled stream
    bit-for-bit — the committed-coupling rule derives every emitted
    token from the target's own stream and merely checks the proposal
    against it."""
    engine, entry = gen_served
    engine.register_model(lambda: _gen_model(
        "gens_far", eos_id=0, num_layers=1))
    sp = SamplingParams(temperature=1.1, top_k=0, top_p=1.0, seed=9)
    prompts = [[4, 9, 2, 7], [13, 5, 1, 1, 8]]
    refs = [entry.offline_decode(p, 7, sampling=sp) for p in prompts]
    before = entry.stats()
    for p, ref in zip(prompts, refs):
        got = engine.submit(p, model="gens", max_new_tokens=7, sampling=sp,
                            draft_model="gens_far",
                            spec_k=3).result(timeout=120)
        assert [int(t) for t in got["tokens"]] == ref
    st = entry.stats()
    d = st["spec_accepted_tokens"] - before["spec_accepted_tokens"]
    p = st["spec_proposed_tokens"] - before["spec_proposed_tokens"]
    assert p > 0 and d < p              # distinct draft: real rejections


def test_beam_matches_offline_reference_and_conserves_blocks(gen_served):
    engine, entry = gen_served
    prompts = [[7, 2, 9, 4], [3, 3, 8, 1, 5]]
    before = entry.stats()
    for p in prompts:
        ref = entry.offline_beam(p, 6, BeamParams(3))
        got = engine.submit(p, model="gens", max_new_tokens=6,
                            beam_width=3).result(timeout=120)
        assert [int(t) for t in got["tokens"]] == list(ref[0][0])
        assert ([[int(t) for t in h["tokens"]] for h in got["beams"]]
                == [list(rt) for rt, _rs in ref])
        for h, (_rt, rs) in zip(got["beams"], ref):
            # decode-path vs whole-sequence-prefill logits: equal to
            # accumulated float32 ulp, same budget as the greedy contract
            assert abs(h["score"] - rs) <= 1e-5 * max(1.0, abs(rs))
    st = entry.stats()
    assert st["beam_requests"] - before["beam_requests"] == 2
    assert st["beam_forks"] > before["beam_forks"]
    assert st["beam_finished"] - before["beam_finished"] == 6
    entry.block_pool.check_conservation()
    assert entry.block_pool.stats()["blocks_live"] == 0
    assert st["active_slots"] == 0      # width-reserved slots all returned


def test_a_beam_forks_tail_copy_is_counted_as_an_arena_read(gen_served):
    """A fork copies its parent's partial tail block arena to arena
    through the host, by the store's one read (`KVStore.read_block`):
    every arena comes over whole and is counted, as a spill's and a
    write-back's are (it was read uncounted before PR 46)."""
    engine, entry = gen_served
    before = entry.stats()
    engine.submit([7, 2, 9, 4, 1], model="gens", max_new_tokens=6,
                  beam_width=3).result(timeout=120)
    st = entry.stats()
    forks = st["beam_forks"] - before["beam_forks"]
    read = st["arena_read_bytes"] - before["arena_read_bytes"]
    arena = entry.model.arena_bytes()
    assert st["sessions_parked"] == before["sessions_parked"]
    assert forks > 0 and read > 0 and read % arena == 0
    assert read // arena <= forks       # an aligned fork copies nothing
    assert st["fetched_bytes"] - before["fetched_bytes"] >= read


def test_beam_with_grammar_matches_offline(gen_served):
    engine, entry = gen_served
    g = CompiledGrammar.from_regex("a(b|c)*d", VOCAB, eos_id=0)
    ref = entry.offline_beam([6, 2, 11], 8, BeamParams(3), grammar=g)
    got = engine.submit([6, 2, 11], model="gens", max_new_tokens=8, beam_width=3,
                        grammar=g).result(timeout=120)
    assert [int(t) for t in got["tokens"]] == list(ref[0][0])
    for toks, _s in ref:
        text = "".join(VOCAB[t] for t in toks if t != 0)
        assert re.fullmatch("a(b|c)*d", text) or len(toks) == 8, toks


def test_grammar_decode_conforms_zero_retraces(gen_served):
    """Grammar masks are DATA through the DEC_MASK feed: constrained
    decode compiles nothing after warmup, conforms to its own DFA, and
    equals the offline masked reference."""
    engine, entry = gen_served
    g = CompiledGrammar.from_json_schema({"type": "boolean"}, VOCAB,
                                         eos_id=0)
    ref = entry.offline_decode([9, 1, 4], 10, grammar=g)
    j0 = jits()
    got = engine.submit([9, 1, 4], model="gens", max_new_tokens=10,
                        grammar=g).result(timeout=120)
    assert jits() == j0
    toks = [int(t) for t in got["tokens"]]
    assert toks == ref
    text = "".join(VOCAB[t] for t in toks if t != 0)
    assert isinstance(json.loads(text), bool)


def test_zero_mask_feed_is_a_bitwise_noop():
    """A logits_mask model fed all-zero masks (no grammar) emits byte-
    identical streams to the SAME weights built without the mask feed:
    +0.0f addition never changes a logit."""
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    plain = engine.register_model(lambda: _gen_model("nm_plain"))
    masked = engine.register_model(lambda: _gen_model(
        "nm_masked", logits_mask=True))
    engine.start()
    try:
        rng = np.random.RandomState(5)
        for n in (4, 9):
            p = [int(t) for t in rng.randint(1, 32, size=n)]
            a = engine.submit(p, model="nm_plain",
                              max_new_tokens=5).result(timeout=120)
            b = engine.submit(p, model="nm_masked",
                              max_new_tokens=5).result(timeout=120)
            assert [int(t) for t in a["tokens"]] == \
                [int(t) for t in b["tokens"]]
            assert plain.offline_decode(p, 5) == \
                masked.offline_decode(p, 5)
    finally:
        engine.shutdown()


def test_grammar_submit_validation(gen_served):
    engine, entry = gen_served
    bad_eos = CompiledGrammar.from_regex("ab", VOCAB, eos_id=3)
    with pytest.raises(RejectedError, match="eos_id"):
        engine.submit([1, 2], model="gens", grammar=bad_eos)
    with pytest.raises(RejectedError, match="logits_mask"):
        # nm-style plain model rejects grammar without the mask feed
        e2 = GenerationEngine(queue_depth=4, breaker_threshold=0)
        e2.register_model(lambda: _gen_model("nogm", eos_id=0))
        g = CompiledGrammar.from_regex("ab", VOCAB, eos_id=0)
        try:
            e2.submit([1, 2], grammar=g)
        finally:
            e2.shutdown()
    with pytest.raises(RejectedError, match="beam"):
        engine.submit([1, 2], model="gens", beam_width=2,
                      sampling=SamplingParams(temperature=1.0))
    with pytest.raises(RejectedError, match="beam width"):
        engine.submit([1, 2], model="gens", beam_width=99)


def test_draft_kv_pins_entry_and_falls_back_when_busy():
    """Draft-KV is an ADMISSION-TIME bargain: an idle draft entry gets
    pinned (then refuses primary traffic, loudly); a busy one silently
    downgrades the request to r13 replay proposals — output identical
    either way."""
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    tgt = engine.register_model(lambda: _gen_model("pin_t"))
    drf = engine.register_model(lambda: _gen_model("pin_d"))
    engine.start()
    try:
        prompt = [3, 9, 2, 6, 1]
        ref = tgt.offline_decode(prompt, 6)
        # busy draft: primary traffic active on it => replay fallback
        hold = engine.submit([5, 5, 4], model="pin_d", max_new_tokens=24)
        got = engine.submit(prompt, model="pin_t", max_new_tokens=6,
                            draft_model="pin_d",
                            spec_k=3).result(timeout=120)
        hold.result(timeout=120)
        assert [int(t) for t in got["tokens"]] == ref
        st0 = tgt.stats()
        assert st0["spec_draft_kv_prefills"] == 0   # replay path used
        # idle draft: pinned, O(1) proposals, primary now rejected
        deadline = time.time() + 30
        while drf.stats()["active_slots"] > 0:      # let the hold retire
            assert time.time() < deadline
            time.sleep(0.01)
        got = engine.submit(prompt, model="pin_t", max_new_tokens=6,
                            draft_model="pin_d",
                            spec_k=3).result(timeout=120)
        assert [int(t) for t in got["tokens"]] == ref
        st = tgt.stats()
        assert st["spec_draft_kv_prefills"] == 1
        assert st["spec_draft_kv_steps"] > 0
        assert st["spec_draft_kv_fallbacks"] == 0
        assert st["draft_pinned"] is False          # target isn't the draft
        with pytest.raises(RejectedError, match="pinned"):
            engine.submit([1, 2, 3], model="pin_d", max_new_tokens=2)
    finally:
        engine.shutdown()


def test_draft_kv_target_steps_equal_replay_baseline():
    """Draft-KV slots change WHO computes the proposals, not what they
    are: the target's verify steps per emitted token do not exceed the
    replay-proposal leg's (run here, same scenario), the draft does
    O(1) slot steps per token after one prefill per request, nothing
    falls back, nothing compiles, tokens equal target-only decode."""
    replay, _, replay_same = spec_leg("kvbase_r", draft_kv=False)
    st, retraces, same = spec_leg("kvbase")
    assert replay_same and same
    assert replay["spec_draft_kv_steps"] == 0, replay
    assert st["spec_emitted_tokens"] == replay["spec_emitted_tokens"]
    assert st["spec_target_steps"] <= replay["spec_target_steps"], st
    assert st["spec_steps_per_token"] <= 0.7, st
    assert st["spec_draft_kv_prefills"] == len(SPEC_PROMPTS), st
    assert st["spec_draft_kv_steps"] > 0, st
    assert st["spec_draft_kv_fallbacks"] == 0, st
    assert retraces == 0


def test_every_generation_mode_on_one_warm_engine_compiles_nothing():
    """Sampled (two admission orders), beam, grammar (regex and JSON
    schema) and sampled speculation run back to back on ONE warmed
    engine under ONE jit counter: each equals its offline reference and
    the whole sequence compiles nothing."""
    prompts = ([5, 9, 2, 4, 7], [11, 3, 8], [6, 1, 12, 2, 9, 4, 3], [14, 2])
    engine = GenerationEngine(queue_depth=32, breaker_threshold=0)
    tgt = engine.register_model(lambda: _gen_model(
        "modes", eos_id=0, logits_mask=True))
    engine.register_model(lambda: _gen_model("modes_d", eos_id=0))
    sp = SamplingParams(temperature=0.9, top_k=8, top_p=0.95, seed=42)
    sampled_refs = [tgt.offline_decode(p, 6, sampling=sp) for p in prompts]
    beam_refs = [tgt.offline_beam(p, 6, BeamParams(3)) for p in prompts[:2]]
    grammars = (CompiledGrammar.from_regex("ab*c", VOCAB, eos_id=0),
                CompiledGrammar.from_json_schema({"type": "boolean"}, VOCAB,
                                                 eos_id=0))
    grammar_refs = [tgt.offline_decode(prompts[0], 10, grammar=g)
                    for g in grammars]

    def tokens(resp):
        return [int(t) for t in resp.result(timeout=120)["tokens"]]

    engine.start()
    j0 = jits()
    try:
        for order_seed in (0, 1):
            order = np.random.RandomState(order_seed).permutation(len(prompts))
            resps = {int(i): engine.submit(prompts[i], model="modes",
                                           max_new_tokens=6, sampling=sp)
                     for i in order}
            assert [tokens(resps[i]) for i in range(len(prompts))] \
                == sampled_refs, order_seed
        assert tgt.stats()["sampled_tokens"] > 0

        for p, ref in zip(prompts[:2], beam_refs):
            got = engine.submit(p, model="modes", beam_width=3,
                                max_new_tokens=6).result(timeout=120)
            assert ([[int(t) for t in h["tokens"]] for h in got["beams"]]
                    == [list(rt) for rt, _rs in ref])
            for h, (_rt, rs) in zip(got["beams"], ref):
                assert abs(h["score"] - rs) <= 1e-5 * max(1.0, abs(rs))
        assert tgt.stats()["beam_forks"] > 0
        tgt.block_pool.check_conservation()

        got_re, got_js = [tokens(engine.submit(
            prompts[0], model="modes", max_new_tokens=10, grammar=g))
            for g in grammars]
        assert [got_re, got_js] == grammar_refs
        assert re.fullmatch(
            "ab*c", "".join(VOCAB[t] for t in got_re if t != 0))
        assert isinstance(json.loads(
            "".join(VOCAB[t] for t in got_js if t != 0)), bool)
        assert tgt.stats()["grammar_steps"] > 0

        got = tokens(engine.submit(
            prompts[2], model="modes", max_new_tokens=6, sampling=sp,
            draft_model="modes_d", spec_k=3))
        assert got == sampled_refs[2]
        assert tgt.stats()["spec_draft_kv_fallbacks"] == 0
    finally:
        engine.shutdown()
    assert jits() == j0, "a generation mode compiled after warm-up"
