"""Shared by the decode test modules: weights under which the served
tokens depend on the prompt and on the K/V rows, a model whose every
step is the serial one, a recorder of each delivered logits row, the jit
counter, and the three-request speculative scenario."""

import collections

import numpy as np

from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.serving.decode import GenerationEngine, build_decoder_model
from paddle_tpu.serving.decode.model import DecodeModel

SPEC_PROMPTS = ([3, 1, 4, 1, 5], [9, 2, 6], [3, 1, 4, 1, 5, 9])
SPEC_MAX_NEW = (12, 10, 12)


def sharpen(entry, factor=8.0):
    """Scale the entry's token embedding in place. Under the startup
    program's Xavier draws the position embedding drowns the token
    embedding: every answer is nearly the same whatever the prompt, and
    a wrong K/V row cannot change a served token (PERF.md, PR 23), so
    "tokens equal the reference's" could not fail. ``offline_decode``
    reads the same scope, so the reference follows. Returns the entry."""
    scope = entry._scope
    for name in scope.var_names():
        if name.endswith(".tok_emb"):
            scope.set(name, scope.find_var(name) * factor)
    return entry


def without_token_fetch(m):
    """The same programs under the same names, built by hand with no
    ``token_fetch``: every step fetches its logits alone and lands
    before the next is launched, as every step of the engine once
    did."""
    return DecodeModel(
        decode_program=m.decode_program, prefill_program=m.prefill_program,
        inject_program=m.inject_program, startup_program=m.startup_program,
        chunk_program=m.chunk_program, chunk_tokens=m.chunk_tokens,
        chunk_logits_fetch=m.chunk_logits_fetch,
        slots=m.slots, max_len=m.max_len, vocab_size=m.vocab_size,
        hidden=m.hidden, state_names=m.state_names,
        logits_fetch=m.logits_fetch,
        prefill_logits_fetch=m.prefill_logits_fetch,
        prefill_kv_fetches=m.prefill_kv_fetches,
        inject_kv_feeds=m.inject_kv_feeds, block_size=m.block_size,
        num_blocks=m.num_blocks, eos_id=m.eos_id, name=m.name,
        version=m.version, logits_mask=m.logits_mask,
        kv_width=m.kv_width, kv_dtype=m.kv_dtype,
        slot_states=m.slot_states)


def record_step_logits(entry, into):
    """Keep every delivered slot's logits row in ``into``, by response:
    the decode step's first output, read here whichever of its outputs
    the engine brings to the host (a greedy step fetches its tokens
    alone). Steps are delivered in the order of their launches, one of
    them perhaps after the next one's launch: the rows wait in that
    order."""
    run, sample = entry._run, entry._sample
    launched = collections.deque()

    def running(kind, feeds, span=None):
        fetches = run(kind, feeds, span)
        if kind == "step":
            launched.append(np.asarray(fetches[0]))
        return fetches

    def recording(fetched, active, groups, now, tokens_only):
        logits = launched.popleft()
        slots = list(active) + [s for g in groups for s in g.order]
        for s in slots:
            into.setdefault(id(entry._slots[s].request.response), []).append(
                np.array(logits[s, 0]))
        return sample(fetched, active, groups, now, tokens_only)

    entry._run = running
    entry._sample = recording


def jits():
    """jax.jit computations created through the lowering chokepoint."""
    m = obs_metrics.registry().get("lowering_jit_total")
    return int(m.value) if m is not None else 0


def spec_leg(name, **submit_kw):
    """Three speculative requests (spec_k=3) against a draft of the
    target's geometry — deterministic init makes its weights
    byte-identical: the acceptance upper bound — on a fresh engine.
    Returns the target's stats, the jits past registration and whether
    every stream equals target-only decode."""
    geom = dict(vocab_size=32, hidden=8, num_layers=2, slots=4, max_len=32,
                block_size=4, version="1")
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    tgt = engine.register_model(
        lambda: build_decoder_model(name=f"{name}_t", **geom))
    engine.register_model(
        lambda: build_decoder_model(name=f"{name}_d", **geom))
    refs = [tgt.offline_decode(p, n)
            for p, n in zip(SPEC_PROMPTS, SPEC_MAX_NEW)]
    j0 = jits()
    engine.start()
    try:
        resps = [engine.submit(p, model=f"{name}_t", max_new_tokens=n,
                               draft_model=f"{name}_d", spec_k=3,
                               **submit_kw)
                 for p, n in zip(SPEC_PROMPTS, SPEC_MAX_NEW)]
        outs = [[int(t) for t in r.result(timeout=120)["tokens"]]
                for r in resps]
    finally:
        engine.shutdown()
    return tgt.stats(), jits() - j0, outs == refs
