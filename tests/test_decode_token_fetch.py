"""Greedy token choice inside the decode step (ISSUE 31).

The step program ends in ``arg_max`` over the logits it already computes
and the engine decides per step, from the policies of the slots it steps,
what to bring to the host: the ``[S, 1]`` tokens when every slot is
greedy (and a grammar's mask, if any, was added on the device), the whole
``[S, 1, V]`` logits when a slot samples, searches beams or masks on the
host. Each case is served twice over the same weights: by the built model,
and by a hand-built ``DecodeModel`` without ``token_fetch``, which forces
the logits path of the parent. The tokens must be the same.
"""

import jax
import numpy as np
import pytest
from decode_testing import sharpen, without_token_fetch

from paddle_tpu import observability as obs
from paddle_tpu.serving.decode import (
    CompiledGrammar,
    GenerationEngine,
    SamplingParams,
    build_decoder_model,
)
from paddle_tpu.serving.decode.model import DecodeModel

VOCAB = ["<eos>"] + list("abcdefghijklmnopqrstuvwxyz") + list("01234")
PROMPTS = ([3, 1, 4, 1, 5], [9, 2, 6], [27, 18, 28, 18])
GREEDY = SamplingParams(temperature=0.0, seed=5)
SAMPLED = SamplingParams(temperature=0.9, top_k=8, seed=11)

# what the step program's arg_max comes back as: int64 only under x64
TOKEN_BYTES = jax.dtypes.canonicalize_dtype(np.int64).itemsize


def _grammar():
    return CompiledGrammar.from_regex("[a-f]+[0-4]", VOCAB, eos_id=0)


# case -> (builder options, per-request submit options, grammar applied on
# the host, whether EVERY step has to fetch the logits)
CASES = {
    "greedy": ({}, [{}, {"sampling": GREEDY}, {}], False, False),
    "greedy_device_grammar": (
        {"logits_mask": True, "eos_id": 0},
        [{"grammar": _grammar}, {}, {"grammar": _grammar}], False, False),
    "greedy_host_grammar": (
        {"eos_id": 0}, [{"grammar": _grammar}, {}, {"grammar": _grammar}],
        True, True),
    "sampled": ({}, [{"sampling": SAMPLED}] * 3, False, True),
    "beam": ({}, [{"beam_width": 3}], False, True),
    # the sampled request is the longest, so it is in every step
    "mixed": ({}, [{"sampling": SAMPLED, "max_new_tokens": 12},
                   {"max_new_tokens": 9}, {"max_new_tokens": 7}],
              False, True),
}


def _build(name, **opts):
    return build_decoder_model(
        vocab_size=32, hidden=8, num_layers=2, slots=4, max_len=32,
        block_size=4, name=name, version="1", **opts)


def _serve(model, submits, host_grammar):
    """Every request queued before the engine starts, so all are admitted
    in one round and step together. Returns (entry, answers, the
    ``decode::step_fetch`` spans)."""
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    entry = sharpen(engine.register_model(model))
    resps = []
    for prompt, kw in zip(PROMPTS, submits):
        kw = dict({"max_new_tokens": 10}, **kw)
        grammar = kw.pop("grammar", None)
        if grammar is not None and not host_grammar:
            kw["grammar"] = grammar()
        resps.append(engine.submit(prompt, **kw))
        if grammar is not None and host_grammar:
            # submit() refuses a grammar the step cannot mask on the
            # device; the scheduler itself does not, and masks the row
            # on the host. Hand the queued request its grammar.
            entry._queue.iter_requests()[-1].grammar = grammar()
    obs.get_tracer().clear()
    obs.enable_tracing()
    try:
        engine.start()
        outs = [r.result(timeout=120) for r in resps]
    finally:
        engine.shutdown()
        obs.disable_tracing()
    spans = [s for s in obs.get_tracer().spans()
             if s["name"] == "decode::step_fetch"]
    obs.get_tracer().clear()
    answers = [{"tokens": out["tokens"].tolist(),
                "beams": [(b["tokens"].tolist(), b["score"])
                          for b in out.get("beams", ())]} for out in outs]
    return entry, answers, spans


@pytest.mark.parametrize("case", sorted(CASES))
def test_served_tokens_equal_the_logits_path_and_the_fetch_follows_the_slots(
        case):
    opts, submits, host_grammar, every_step = CASES[case]
    built = _build(f"tf_{case}", **opts)
    assert built.token_fetch is not None
    entry, got, spans = _serve(built, submits, host_grammar)
    ref_entry, want, ref_spans = _serve(
        without_token_fetch(_build(f"tf_{case}", **opts)), submits,
        host_grammar)
    assert got == want
    assert any(len(set(a["tokens"])) > 2 for a in got), got

    m = entry.model
    steps = entry.metrics.count("decode_steps")
    assert steps > 0 and len(spans) == steps
    logits_steps = entry.metrics.count("decode_logits_fetch_steps")
    assert entry.stats()["decode_logits_fetch_steps"] == logits_steps
    logits_bytes = m.slots * m.vocab_size * 4
    if every_step:
        assert logits_steps == steps
        assert {(s["args"]["rows"], s["args"]["bytes"]) for s in spans} \
            == {("logits", logits_bytes)}
    else:
        assert logits_steps == 0
        assert {(s["args"]["rows"], s["args"]["bytes"]) for s in spans} \
            == {("tokens", m.slots * TOKEN_BYTES)}
    # a step launches ahead of the previous one's fetch only where that
    # one needed its tokens alone and stepped no grammar (ISSUE 34)
    ahead = entry.metrics.count("decode_steps_ahead")
    if every_step:
        assert ahead == 0
    elif case == "greedy":
        assert 0 < ahead < steps
    # the hand-built model has no tokens to fetch: every step, the logits,
    # and every step lands before the next is launched
    ref_m = ref_entry.metrics
    assert ref_m.count("decode_steps_ahead") == 0
    assert ref_m.count("decode_logits_fetch_steps") \
        == ref_m.count("decode_steps") == len(ref_spans)
    assert {s["args"]["rows"] for s in ref_spans} == {"logits"}
    if "grammar" in submits[0]:
        assert entry.metrics.count("grammar_steps") \
            == ref_m.count("grammar_steps") > 0
    if case != "beam":
        # and both equal the whole-sequence reference under the policy
        for prompt, kw, a in zip(PROMPTS, submits, got):
            g = kw["grammar"]() if "grammar" in kw else None
            assert a["tokens"] == entry.offline_decode(
                prompt, kw.get("max_new_tokens", 10),
                sampling=kw.get("sampling"), grammar=g)


def test_a_greedy_batch_takes_the_short_path_once_its_sampled_mate_retires():
    """The choice is per STEP: while the sampled request lives every step
    fetches the logits, and the greedy requests that outlive it step on
    tokens alone."""
    submits = [{"sampling": SAMPLED, "max_new_tokens": 4},
               {"max_new_tokens": 10}, {"max_new_tokens": 10}]
    entry, got, spans = _serve(_build("tf_retire"), submits, False)
    rows = [s["args"]["rows"] for s in spans]
    # the first token of each request is the prefill's: 3 steps sampled
    assert rows == ["logits"] * 3 + ["tokens"] * 6
    assert entry.metrics.count("decode_logits_fetch_steps") == 3
    # and only the greedy rest launches ahead of a fetch: its first step
    # follows a step that landed in its own body, the other five a step
    # in flight; the last fetch has nothing launched over it
    assert entry.metrics.count("decode_steps_ahead") == 5
    assert [s["args"].get("drain") for s in spans] == [None] * 8 + ["idle"]
    for prompt, kw, a in zip(PROMPTS, submits, got):
        assert a["tokens"] == entry.offline_decode(
            prompt, kw["max_new_tokens"], sampling=kw.get("sampling"))


def test_two_equal_maxima_give_the_lower_index_on_both_paths():
    """``jnp.argmax`` in the program and ``np.argmax`` on the host both
    return the FIRST index of a row's maximum. With the head's weights
    zeroed every logit of a row is its bias; tokens 7 and 19 share the
    largest."""
    tokens = {}
    for path, shape in (("tokens", lambda m: m),
                        ("logits", without_token_fetch)):
        engine = GenerationEngine(queue_depth=8, breaker_threshold=0)
        entry = engine.register_model(shape(_build("tf_tie")))
        scope = entry._scope
        (w,) = [n for n in scope.var_names() if n.endswith(".head.w")]
        (b,) = [n for n in scope.var_names() if n.endswith(".head.b")]
        scope.set(w, scope.find_var(w) * 0.0)
        bias = np.zeros(32, "float32")
        bias[[19, 7]] = 2.5
        bias[30] = 2.0
        scope.set(b, scope.find_var(b) * 0.0 + bias)
        resp = engine.submit([5, 6], max_new_tokens=4)
        assert entry._admit_free_slots() == 1
        while not resp.done():
            entry._step()
        tokens[path] = resp.result()["tokens"].tolist()
        want = 0 if path == "tokens" else entry.metrics.count("decode_steps")
        assert entry.metrics.count("decode_logits_fetch_steps") == want
    assert tokens["tokens"] == tokens["logits"] == [7, 7, 7, 7]


# -- the programs ---------------------------------------------------------------

def _ops(program):
    return [op.type for op in program.global_block().ops]


@pytest.mark.parametrize("logits_mask", [False, True])
def test_the_decode_program_ends_in_one_arg_max_and_the_others_are_the_parents(
        logits_mask, monkeypatch):
    import paddle_tpu as fluid

    geom = dict(vocab_size=32, hidden=8, num_layers=2, slots=4, max_len=32,
                block_size=4, chunk_tokens=4, name="tf_prog", version="1",
                logits_mask=logits_mask, eos_id=0)
    m = build_decoder_model(**geom)
    block = m.decode_program.global_block()
    ops = _ops(m.decode_program)
    assert ops.count("arg_max") == 1 and ops[-1] == "arg_max"
    last = block.ops[-1]
    # over the vocabulary axis of the very logits the step fetches: after
    # the head's bias, and after the mask where the model has one
    assert last.inputs["X"] == [m.logits_fetch]
    assert last.outputs["Out"] == [m.token_fetch]
    assert last.attrs["axis"] == -1
    producer = [op for op in block.ops
                if m.logits_fetch in op.outputs.get("Out", ())]
    assert [op.type for op in producer] == ["elementwise_add"]
    if logits_mask:
        assert DecodeModel.DEC_MASK in producer[0].inputs["Y"]
    for other in (m.prefill_program, m.inject_program, m.chunk_program):
        assert "arg_max" not in _ops(other)

    # the relaunch contract: a rebuild is the same bytes, all five programs
    def five(model):
        return [p.to_bytes() for p in (
            model.decode_program, model.prefill_program,
            model.inject_program, model.chunk_program,
            model.startup_program)]

    again = build_decoder_model(**geom)
    assert five(again) == five(m)
    assert five(m.builder()) == five(m)
    assert again.token_fetch == m.token_fetch

    # with the one new layer call taken out the builder is the parent's:
    # prefill, inject, chunk and startup keep the parent's bytes (no name
    # of theirs moved), and the decode program differs by that one op
    monkeypatch.setattr(fluid.layers, "argmax",
                        lambda x, axis=-1, name=None: x)
    parent = build_decoder_model(**geom)
    assert five(parent)[1:] == five(m)[1:]
    assert _ops(parent.decode_program) == ops[:-1]
    assert parent.logits_fetch == m.logits_fetch


def test_the_step_executable_fetches_logits_then_tokens():
    engine = GenerationEngine(queue_depth=8, breaker_threshold=0)
    entry = engine.register_model(_build("tf_exec"))
    m = entry.model
    feeds = {n: np.zeros(shape, dtype)
             for n, shape, dtype in m.decode_feed_sig()}
    feeds[DecodeModel.DEC_STEP] = m.step_feed()      # nobody steps
    assert list(entry._entries["step"][0].fetch_names) \
        == [m.logits_fetch, m.token_fetch]
    fetches = entry._run("step", feeds)
    assert len(fetches) == 2
    logits, tokens = (np.asarray(f) for f in fetches)
    assert logits.shape == (m.slots, 1, m.vocab_size)
    assert logits.dtype == np.float32
    assert tokens.shape == (m.slots, 1)
    assert tokens.dtype.itemsize == TOKEN_BYTES
    assert (tokens == np.argmax(logits, axis=-1)).all()
    # nothing was counted as fetched: `_fetch` is the one counting door
    assert entry.metrics.count("fetched_bytes") == 0
    hand = GenerationEngine(queue_depth=8, breaker_threshold=0) \
        .register_model(without_token_fetch(_build("tf_exec")))
    assert len(hand._run("step", feeds)) == 1
