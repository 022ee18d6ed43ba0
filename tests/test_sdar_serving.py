"""The ``sdar_moe`` block-diffusion decoder (grouped-query attention with
QK-norm and rotary positions under a block mask, softmax-routed gated
experts of which a share is held, an answer filled a block of positions at
a time over several passes) served through ``GenerationEngine``, at a tiny
size on the CPU, against its plain reference
(``benchmark/references/plain_sdar.py``: float32, whole sequence, no cache).

Logits are compared, not tokens: every launch of the block-pass program is
recorded at the entry's ``_run`` (its one host feed, the block state it was
handed on the device, its ``[S, B, V]`` logits and the state it left) and
every slot's pass is held to the reference's rows for that prefix and that
block state; every prompt chunk's logits to the reference's over the clean
prompt under the block mask.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.references import plain_sdar as reference  # noqa: E402
from paddle_tpu import kernels  # noqa: E402
from paddle_tpu.core.registry import OpRegistry  # noqa: E402
from paddle_tpu.kernels import attention, moe  # noqa: E402
from paddle_tpu.serving import (  # noqa: E402
    GenerationEngine, ServingError, build_sdar_model)
from paddle_tpu.serving.decode import SamplingParams  # noqa: E402
from paddle_tpu.serving.decode.model import DecodeModel  # noqa: E402

#: the published keys at a tiny size
CONFIG = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    num_experts=4, num_experts_per_tok=2, moe_intermediate_size=24,
    norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=1000000)
ROUTER, RANK, B, MASK = 8, 1, 4, 95     # experts 4..7 of 8 are held
GEOMETRY = dict(slots=4, max_len=48, block_size=4, chunk_tokens=8)
#: every ``p mod 4``, under, at and over a chunk, shorter than a block; more
#: requests than slots, so that some are admitted while others are mid-block
PROMPT_LENS = (5, 13, 8, 20, 3, 10, 7)
#: whole blocks and answers that end inside one
ANSWERS = (6, 9, 4, 10, 12, 5, 8)
REFERENCE = dict(CONFIG, block_len=B, mask_token_id=MASK)
#: float32 build against the float32 reference: summation order alone
EXACT_BAND = 1e-4
#: bfloat16 build: parameters and each sub-layer's input rounded to 8 bits
#: of mantissa through 6 sub-layers; measured 0.013 of a row's standard
#: deviation in the median and 0.017 at the 90th percentile of 156 rows
#: (three rows where a top-2-of-8 router's choice flipped read 0.14-0.53)
BF16_BAND = 5e-2


def _model(dtype="float32", name="sdar", **over):
    m = build_sdar_model(
        **CONFIG, router_experts=ROUTER, expert_rank=RANK, block_len=B,
        denoising_steps=B, mask_token_id=MASK, dtype=dtype, name=name,
        initializer_range=0.12, **dict(GEOMETRY, **over))
    m.startup_program.random_seed = 7
    return m


def _engine(model, started=True):
    engine = GenerationEngine(prefix_cache_size=0, host_tier_mb=0)
    entry = engine.register_model(model)
    if started:
        engine.start()
    return engine, entry


def _prompts(seed=0, lens=PROMPT_LENS):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, CONFIG["vocab_size"], n)]
            for n in lens]


def _weights(entry):
    scope, prefix = entry._scope, f"{entry.model.name}_v1."
    return {n[len(prefix):]: scope.find_var(n) for n in scope.var_names()
            if n.startswith(prefix)}


def _record(entry):
    """Every launch of the entry's step and chunk programs: ``(kind, the
    request of each slot, feeds as numpy, fetches as numpy, whether a step
    was in flight)``."""
    launches, run = [], entry._run

    def recording(kind, feeds, span=None):
        out = run(kind, feeds, span)
        if kind in ("step", "chunk"):
            launches.append((
                kind,
                [st.request.id if st is not None else None
                 for st in entry._slots],
                {k: np.array(v) for k, v in feeds.items()},
                [np.array(o) for o in out],
                entry._launched is not None))
        return out

    entry._run = recording
    return launches


def _serve(engine, prompts, answers):
    responses = [engine.submit(p, max_new_tokens=n)
                 for p, n in zip(prompts, answers)]
    return [r.result(timeout=300) for r in responses]


def _pass_of(feeds, s):
    """Slot ``s`` of a recorded block pass: ``(first position, block
    tokens, decided)``, from the host's columns or the device's state."""
    step = feeds[DecodeModel.DEC_STEP]
    if step[s, 0] < 0:
        state = feeds[DecodeModel.DEC_TOKEN]
        return int(step[s, 1]), state[s, :B], state[s, B:] > 0
    given = step[s, DecodeModel.STEP_TABLE:DecodeModel.STEP_TABLE + B]
    return int(step[s, 1]), given, given >= 0


def _passes(entry, launches, by_id):
    """(passes compared; every row's max |difference| from the
    reference's, in standard deviations of the reference's row; passes
    whose decision is not the reference's) over every slot of every
    recorded block pass."""
    weights = _weights(entry)
    offset = RANK * CONFIG["num_experts"]
    n, other, rows = 0, [], []
    for kind, ids, feeds, out, _ahead in launches:
        if kind != "step":
            continue
        for s in range(GEOMETRY["slots"]):
            if not feeds[DecodeModel.DEC_STEP][s, DecodeModel.STEP_LENGTH]:
                continue
            prompt, served = by_id[ids[s]]
            start, block, decided = _pass_of(feeds, s)
            prefix = (prompt + served)[:start]
            assert len(prefix) == start
            want = reference.block_pass(
                weights, REFERENCE, prefix, block, decided,
                pad_to=GEOMETRY["max_len"], expert_offset=offset)
            rows.extend(np.abs(out[0][s] - want).max(1) / want.std(1))
            n += 1
            after = out[1][s]
            if decided.all():
                assert not after[B:].any()      # the next block opens
                continue
            at, token, _margin = reference.decide(want, decided, MASK)
            if not (after[B + at] == 1 and after[at] == token):
                other.append((ids[s], start, at, token, after))
    return n, np.asarray(rows), other


@pytest.fixture(scope="module")
def exact():
    """The float32 build, served once with every launch recorded."""
    engine, entry = _engine(_model())
    launches = _record(entry)
    prompts = _prompts()
    try:
        outs = _serve(engine, prompts, ANSWERS)
    finally:
        engine.shutdown()
    by_id = {i + 1: (p, [int(t) for t in o["tokens"]])
             for i, (p, o) in enumerate(zip(prompts, outs))}
    return engine, entry, launches, prompts, outs, by_id


# -- (a) every pass of every block, and every chunk ---------------------------

def test_every_pass_of_every_block_gives_the_references_logits(exact):
    _engine_, entry, launches, _prompts_, outs, by_id = exact
    for out, n in zip(outs, ANSWERS):
        assert len(out["tokens"]) == len(out["decided_at"]) == n
        assert MASK not in out["tokens"]
    n, rows, other = _passes(entry, launches, by_id)
    # 55 tokens decided in as many fill passes, and the commit passes
    assert n > sum(ANSWERS)
    assert rows.max() < EXACT_BAND, rows.max()
    assert not other, other


def test_slots_ran_at_different_passes_and_joined_mid_block(exact):
    _engine_, entry, launches, _prompts_, _outs, _by_id = exact
    steps = [(feeds, ahead) for kind, _ids, feeds, _out, ahead in launches
             if kind == "step"]
    mixed = joined = 0
    seen = {}
    for feeds, _ahead in steps:
        step = feeds[DecodeModel.DEC_STEP]
        live = [s for s in range(GEOMETRY["slots"])
                if step[s, DecodeModel.STEP_LENGTH]]
        undecided = {s: int((~_pass_of(feeds, s)[2]).sum()) for s in live}
        mixed += len(set(undecided.values())) > 1
        for s in live:
            new = step[s, 0] >= 0       # its block came from the host
            if new and any(seen.get(o) and 0 < undecided[o] < B
                           for o in live if o != s):
                joined += 1
        seen = {s: True for s in live}
    assert mixed, "no pass held slots at different passes of their blocks"
    assert joined, "no slot was admitted while another was mid-block"
    # launch-ahead: a pass launched before the one before it was fetched is
    # handed that pass's block state on the device, and one pass drained
    ahead = [a for _f, a in steps]
    assert any(ahead) and not all(ahead)
    stats = entry.stats()
    assert stats["decode_steps_ahead"] == sum(ahead)
    assert sum(stats["decode_drains"].values()) >= 1


def test_every_chunk_gives_the_references_logits_under_the_block_mask(exact):
    _engine_, entry, launches, prompts, _outs, _by_id = exact
    weights = _weights(entry)
    by_tokens = {}
    for kind, _ids, feeds, out, _ahead in launches:
        if kind != "chunk":
            continue
        real = int((feeds[DecodeModel.CHU_WRITE_ROWS]
                    < entry.model.rows).sum())
        start = int(feeds[DecodeModel.CHU_POSITIONS][0, 0])
        toks = tuple(feeds[DecodeModel.CHU_TOKENS][0, :real])
        by_tokens[start, toks] = out[0][0, :real]
        # block-causal: a position sees its whole block and no later one
        # (the mask the device makes of the chunk's two integers)
        assert [start, real] == list(feeds[DecodeModel.CHU_SPAN])
        bias = np.asarray(attention.chunk_mask_bias(
            feeds[DecodeModel.CHU_SPAN], GEOMETRY["chunk_tokens"],
            GEOMETRY["max_len"], B))[0]
        assert np.array_equal(bias, entry.model.chunk_bias(start, real)[0])
        for i in range(real):
            sees = np.flatnonzero(bias[i] == 0.0)
            assert sees[-1] == (start + i) // B * B + B - 1
    assert by_tokens
    compared = 0
    for prompt in prompts:
        whole = len(prompt) - len(prompt) % B
        for start in range(0, whole, GEOMETRY["chunk_tokens"]):
            stop = min(start + GEOMETRY["chunk_tokens"], whole)
            got = by_tokens[start, tuple(prompt[start:stop])]
            want = reference.logits(
                weights, REFERENCE, prompt[:whole], range(start, stop),
                pad_to=GEOMETRY["max_len"],
                expert_offset=RANK * CONFIG["num_experts"])
            assert (np.abs(got - want).max(1) / want.std(1)).max() \
                < EXACT_BAND
            compared += 1
    # a prompt shorter than a block runs no chunk at all
    assert compared == sum(-(-(n - n % B) // GEOMETRY["chunk_tokens"])
                           for n in PROMPT_LENS)


def test_the_order_of_filling_is_the_references(exact):
    """At this size the reference's own margins between the position it
    decides and the runner-up are far above the float32 build's error: the
    served order, pass by pass, is the reference's."""
    _engine_, entry, _launches, prompts, outs, _by_id = exact
    weights = _weights(entry)
    margins = []
    for prompt, out in zip(prompts, outs):
        served = [int(t) for t in out["tokens"]]
        p = len(prompt)
        whole = prompt + served
        # the blocks that lie wholly inside the answer
        for start in range(p - p % B, (p + len(served)) // B * B, B):
            block = [whole[j] if j < p else -1
                     for j in range(start, start + B)]
            order = reference.fill_order(
                weights, REFERENCE, whole[:start], block,
                [t >= 0 for t in block], pad_to=GEOMETRY["max_len"],
                expert_offset=RANK * CONFIG["num_experts"])
            for k, (at, token, margin) in enumerate(order):
                i = start + at - p
                assert (served[i], int(out["decided_at"][i])) == (token, k)
                margins.append(margin)
    assert len(margins) > 30 and min(margins) > 1e-5, min(margins)


def test_a_drained_engine_and_a_full_one_serve_the_same(exact):
    """One request alone (every pass after the first launched ahead of the
    fetch before it, then a drain) gives the tokens and the order it got
    among six others."""
    _engine_, _entry, _launches, prompts, outs, _by_id = exact
    engine, _entry2 = _engine(_model(name="sdar_alone"))
    try:
        for i in (1, 3):
            alone = _serve(engine, [prompts[i]], [ANSWERS[i]])[0]
            assert list(alone["tokens"]) == list(outs[i]["tokens"])
            assert list(alone["decided_at"]) == list(outs[i]["decided_at"])
    finally:
        engine.shutdown()


def test_bfloat16_build_is_inside_its_band_and_outside_the_exact_one():
    engine, entry = _engine(_model("bfloat16", name="sdar_bf16"))
    launches = _record(entry)
    prompts = _prompts(1)[:4]
    try:
        outs = _serve(engine, prompts, ANSWERS[:4])
    finally:
        engine.shutdown()
    by_id = {i + 1: (p, [int(t) for t in o["tokens"]])
             for i, (p, o) in enumerate(zip(prompts, outs))}
    _n, rows, _other = _passes(entry, launches, by_id)
    p90 = float(np.percentile(rows, 90))
    assert EXACT_BAND < p90 < BF16_BAND, p90


def test_the_kernels_serve_the_engine_like_the_composites(exact):
    """Both kernels interpreted (``paged_attention`` with a block's 4
    positions x 2 query heads as 8 query rows of a K/V head, ``moe_experts``
    over 4 x 4 tokens): the same logits within the float32 band."""
    _engine_, _entry, _launches, prompts, outs, _by_id = exact
    before = kernels.fallback_counter().value
    with kernels.scoped_mode("interpret"):
        engine, entry = _engine(_model(name="sdar_kernels"))
        launches = _record(entry)
        try:
            again = _serve(engine, prompts[:4], ANSWERS[:4])
        finally:
            engine.shutdown()
    assert kernels.fallback_counter().value == before
    by_id = {i + 1: (p, [int(t) for t in o["tokens"]])
             for i, (p, o) in enumerate(zip(prompts, again))}
    _n, rows, other = _passes(entry, launches, by_id)
    assert rows.max() < EXACT_BAND and not other
    for a, b in zip(again, outs):
        assert list(a["tokens"]) == list(b["tokens"])


# -- (b) counters, stamps, refusals --------------------------------------------

def test_the_passes_are_counted_and_the_tokens_stamped(exact):
    _engine_, entry, launches, prompts, outs, _by_id = exact
    stats = entry.stats()
    tokens = sum(ANSWERS)
    fills, commits = (stats["block_passes"]["fill"],
                      stats["block_passes"]["commit"])
    # a fill pass decides one token; some decided past an answer's end
    assert stats["block_tokens_decided"] == fills >= tokens
    assert stats["generated_tokens"] == fills
    assert stats["active_slot_steps"] == fills + commits
    assert stats["blocks_committed"] == commits
    # every block but a request's last is committed
    want = sum((len(p) + n - 1) // B - (len(p) - len(p) % B) // B
               for p, n in zip(prompts, ANSWERS))
    assert commits == want
    assert stats["decode_steps"] == sum(
        1 for kind, *_ in launches if kind == "step")
    # routing counts ride in the pass's one fetch
    assert stats["moe_assignments"] > stats["moe_held_assignments"] > 0
    assert stats["completed"] == len(prompts) and stats["failed"] == 0


def test_refusals_say_why():
    model = _model(name="sdar_refused")
    for how in (dict(prefix_cache_size=4, host_tier_mb=0),
                dict(prefix_cache_size=0, host_tier_mb=1)):
        with pytest.raises(ServingError, match="committed"):
            GenerationEngine(**how).register_model(model)
    engine, _entry = _engine(model, started=False)
    prompt = _prompts()[0]
    for how in (dict(sampling=SamplingParams(temperature=1.0, seed=1)),
                dict(beam_width=2),
                dict(draft_model="sdar_refused")):
        with pytest.raises(ServingError, match="not served"):
            engine.submit(prompt, max_new_tokens=4, **how)
    # a greedy policy stated outright is what is served anyway
    engine.submit(prompt, max_new_tokens=4,
                  sampling=SamplingParams(temperature=0.0))
    with pytest.raises(ValueError, match="one position a pass"):
        build_sdar_model(**CONFIG, router_experts=ROUTER, block_len=4,
                         denoising_steps=2, **GEOMETRY)
    with pytest.raises(ValueError, match="has to divide"):
        _model(block_size=6, max_len=48)


# -- (c) the router, and the shares add up --------------------------------------

def test_route_softmax_by_hand_and_sigmoid_bit_for_bit():
    x = jnp.asarray([[1.0, 0.0], [0.0, 2.0]], jnp.float32)
    gate = jnp.asarray([[1.0, 0.0], [0.0, 1.0], [-1.0, 0.0], [1.0, 1.0]],
                       jnp.float32)
    zeros = jnp.zeros((4,), jnp.float32)
    idx, w = moe.route(x, gate, zeros, 2, 1.0, True, score="softmax")
    # token 0's products: 1, 0, -1, 1; token 1's: 0, 2, 0, 2
    e = np.exp([[1.0, 0.0, -1.0, 1.0], [0.0, 2.0, 0.0, 2.0]])
    p = e / e.sum(1, keepdims=True)
    assert [sorted(r) for r in np.asarray(idx)] == [[0, 3], [1, 3]]
    np.testing.assert_allclose(w, [[0.5, 0.5], [0.5, 0.5]], rtol=1e-6)
    _idx, raw = moe.route(x, gate, zeros, 2, 1.0, False, score="softmax")
    np.testing.assert_allclose(np.sort(raw, 1), np.sort(
        np.take_along_axis(p, np.asarray(idx), 1), 1), rtol=1e-6)
    # the default is the sigmoid it was: same bits with or without the name
    rng = np.random.RandomState(3)
    xs, gs = (jnp.asarray(rng.randn(9, 16).astype("float32")),
              jnp.asarray(rng.randn(8, 16).astype("float32")))
    bias = jnp.asarray(0.1 * rng.randn(8).astype("float32"))
    named = moe.route(xs, gs, bias, 3, 2.5, True, 1e-6, score="sigmoid")
    plain = moe.route(xs, gs, bias, 3, 2.5, True, 1e-6)
    s = jax.nn.sigmoid(jnp.matmul(xs, gs.T,
                                  precision=jax.lax.Precision.HIGHEST))
    _, by_hand = jax.lax.top_k(s + bias, 3)
    for a, b in zip(named, plain):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert np.array_equal(plain[0], by_hand)
    with pytest.raises(KeyError):
        moe.route(xs, gs, bias, 3, 1.0, True, score="tanh")


def test_the_eight_ranks_parts_are_the_uncut_layer():
    rng = np.random.RandomState(11)
    t, hidden, ffn, ranks, held, k = 12, 32, 12, 8, 2, 3
    everyone = ranks * held
    draw = lambda *s: jnp.asarray(rng.randn(*s).astype("float32"))  # noqa
    h, norm_w = draw(t, hidden), jnp.ones((hidden,))
    gate = draw(everyone, hidden)
    w1, w3, w2 = (0.3 * draw(everyone, ffn, hidden) for _ in range(3))
    sizes = dict(REFERENCE, num_experts_per_tok=k)
    experts = reference._functions(
        tuple((key, sizes[key]) for key in reference._KEYS)
        + (("rope_theta", 1e6),))[4]
    with jax.default_matmul_precision("highest"):
        whole = experts(h, norm_w, gate, w1, w3, w2, offset=0) - h
    normed = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + 1e-6)
    op = OpRegistry.get("moe_routed_experts").lower
    parts, counts = [], []
    for rank in range(ranks):
        mine = slice(rank * held, (rank + 1) * held)
        # a pass's [S, B] positions: the op flattens them
        out = op({"X": [normed.reshape(3, 4, hidden)], "GateW": [gate],
                  "SelectBias": [jnp.zeros((everyone,))],
                  "WGate": [w1[mine]], "WUp": [w3[mine]],
                  "WDown": [w2[mine]],
                  "WriteRows": [jnp.zeros((t,), jnp.int32)]},
                 {"k": k, "normalize": True, "expert_offset": rank * held,
                  "num_rows": 1, "score": "softmax"})
        parts.append(out["Out"][0].reshape(t, hidden))
        counts.append(np.asarray(out["Counts"][0]))
    # nothing is computed alike on every rank (no shared expert): the
    # eight parts, each counted once, are the whole layer
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-4, atol=1e-5)
    assert sum(c[1] for c in counts) == t * k == counts[0][0]
    assert not np.allclose(parts[0], 0) and not np.allclose(
        sum(parts[:4]), whole, atol=1e-3)


def test_the_block_ops_by_hand():
    feeds = OpRegistry.get("paged_block_feeds").lower
    packed = np.zeros((2, 4 + B + 3), "int32")
    # slot 0: its block from the host (one token decided); slot 1: from
    # the device's state
    packed[0] = [0, 8, 12, 20, 7, -1, -1, -1, 5, 2, 6]
    packed[1] = [-1, 4, 8, 44, -1, -1, -1, -1, 1, 3, 0]
    state = np.array([[0] * 8, [11, 12, 13, 14, 0, 1, 1, 0]], "int32")
    out = feeds({"Packed": [jnp.asarray(packed)],
                 "State": [jnp.asarray(state)]},
                {"length": 12, "block_size": 4, "block_len": B,
                 "mask_token": MASK})
    assert np.array_equal(out["TokenOut"][0],
                          [[7, MASK, MASK, MASK], [MASK, 12, 13, MASK]])
    assert np.array_equal(out["Position"][0], [[8, 9, 10, 11], [4, 5, 6, 7]])
    assert np.array_equal(out["WriteRows"][0],
                          [20, 21, 22, 23, 44, 45, 46, 47])
    assert np.array_equal(out["Decided"][0], [[1, 0, 0, 0], [0, 1, 1, 0]])
    bias = np.asarray(out["Bias"][0])[:, 0]
    assert (bias[0] == 0).all() and (bias[1, :8] == 0).all() \
        and (bias[1, 8:] == -1e9).all()
    assert np.array_equal(np.asarray(out["Rows"][0]).reshape(2, 12)[0],
                          [20, 21, 22, 23, 8, 9, 10, 11, 24, 25, 26, 27])
    decide = OpRegistry.get("block_fill_decide").lower
    logits = np.zeros((3, B, 96), "float32")
    logits[0, 1, 40] = 3.0          # position 1 is surer than position 2
    logits[0, 2, 41] = 2.0
    logits[0, 0, 42] = 9.0          # decided already: out of the running
    logits[1, :, MASK] = 50.0       # the mask token is never a candidate
    logits[1, 3, 17] = 1.0          # a tie on confidence elsewhere: lowest
    logits[1, 0, 18] = 1.0
    held = np.array([[7, 0, 0, 0], [0, 0, 0, 0], [1, 2, 3, 4]], "int32")
    decided = np.array([[1, 0, 0, 0], [0, 0, 0, 0], [1, 1, 1, 1]], "int32")
    got = decide({"Logits": [jnp.asarray(logits)], "Held": [held],
                  "Decided": [decided]}, {"mask_token": MASK})
    state, host = np.asarray(got["State"][0]), np.asarray(got["Host"][0])
    assert list(host) == [1, 0, -1, 40, 18, host[5]]
    assert list(state[0]) == [7, 40, 0, 0, 1, 1, 0, 0]
    assert list(state[1][:1]) == [18] and list(state[1][B:]) == [1, 0, 0, 0]
    assert not state[2][B:].any()       # a commit pass opens the next block
