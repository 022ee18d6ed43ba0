"""The ``nemotron_h`` hybrid decoder (Mamba-2 state beside paged grouped-query
K/V rows, routed experts of which a share is held) served through
``GenerationEngine``, at a tiny size on the CPU, against its plain reference
(``benchmark/references/plain_nemotron_h.py``: float32, whole sequence,
sequential recurrence, no cache).

Logits are compared, not tokens (with random weights the largest logit
changes on rounding): a sampled request makes the engine fetch every step's
row, and ``_choose_token`` is where each delivered row passes.
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

from benchmark.references import plain_nemotron_h as reference  # noqa: E402
from paddle_tpu import kernels  # noqa: E402
from paddle_tpu.core.registry import OpRegistry  # noqa: E402
from paddle_tpu.kernels import mamba  # noqa: E402
from paddle_tpu.serving import (  # noqa: E402
    GenerationEngine, RejectedError, build_nemotron_h_model)
from paddle_tpu.serving.decode import SamplingParams  # noqa: E402
from paddle_tpu.serving.decode.model import DecodeModel  # noqa: E402
from paddle_tpu.utils.enforce import EnforceError  # noqa: E402

CONFIG = dict(
    vocab_size=96, hidden_size=64, hybrid_override_pattern="MEM*E",
    mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
    conv_kernel=4, chunk_size=8, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, n_routed_experts=4,
    router_experts=8, num_experts_per_tok=2, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=48, routed_scaling_factor=2.5,
    norm_topk_prob=True, layer_norm_epsilon=1e-5)
GEOMETRY = dict(slots=4, max_len=48, block_size=4, chunk_tokens=8)
RANK = 1                    # experts 4..7 of 8 are held
PROMPT_LENS = (5, 13, 8, 20, 3, 9)      # under, over and at the chunk
ANSWERS = (6, 9, 4, 10, 12, 5)
#: float32 build against the float32 reference: summation order alone
EXACT_BAND = 1e-4
#: bfloat16 build: parameters and each mixer's input rounded to 8 bits of
#: mantissa (2^-9 relative), through 5 blocks; measured 1.7e-2 of a row's
#: standard deviation, and a float32 state keeps it from growing with the
#: sequence
BF16_BAND = 6e-2


def _model(dtype="float32", name="hybrid", **over):
    m = build_nemotron_h_model(
        **CONFIG, **dict(GEOMETRY, **over), dtype=dtype, expert_rank=RANK,
        name=name)
    m.startup_program.random_seed = 7
    return m


def _engine(model, started=True):
    engine = GenerationEngine(prefix_cache_size=0, host_tier_mb=0)
    entry = engine.register_model(model)
    if started:
        engine.start()
    return engine, entry


def _prompts(seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, CONFIG["vocab_size"], n)]
            for n in PROMPT_LENS]


def _weights(entry):
    scope, prefix = entry._scope, f"{entry.model.name}_v1."
    return {n[len(prefix):]: scope.find_var(n) for n in scope.var_names()
            if n.startswith(prefix)}


def _record_rows(entry):
    """Every logits row the engine delivers, by request id."""
    rows, choose = {}, entry._choose_token

    def recording(st, row, device_masked):
        rows.setdefault(st.request.id, []).append(np.array(row, np.float32))
        return choose(st, row, device_masked)

    entry._choose_token = recording
    return rows


def _worst_row(entry, prompts, answers, rows, first_id=1):
    """The worst delivered row's max |difference| from the reference's
    full forward over the served tokens, in standard deviations of the
    reference's row."""
    weights, worst = _weights(entry), 0.0
    for i, (prompt, out) in enumerate(zip(prompts, answers)):
        tokens = prompt + [int(t) for t in out[:-1]]
        want = reference.logits(
            weights, CONFIG, tokens,
            range(len(prompt) - 1, len(prompt) - 1 + len(out)),
            pad_to=GEOMETRY["max_len"],
            expert_offset=RANK * CONFIG["n_routed_experts"])
        got = np.stack(rows[first_id + i])
        worst = max(worst, float(
            (np.abs(got - want).max(1) / want.std(1)).max()))
    return worst


def _serve_sampled(entry, engine, prompts):
    responses = [
        engine.submit(p, max_new_tokens=n,
                      sampling=SamplingParams(temperature=1.0, seed=i))
        for i, (p, n) in enumerate(zip(prompts, ANSWERS))]
    return [r.result(timeout=300)["tokens"] for r in responses]


def _serve_greedy(engine, prompts, answers=ANSWERS):
    responses = [engine.submit(p, max_new_tokens=n)
                 for p, n in zip(prompts, answers)]
    return [[int(t) for t in r.result(timeout=300)["tokens"]]
            for r in responses]


@pytest.fixture(scope="module")
def exact():
    """The float32 build, served once: sampled requests (their rows
    recorded), then the same prompts greedy."""
    engine, entry = _engine(_model())
    rows = _record_rows(entry)
    prompts = _prompts()
    sampled = _serve_sampled(entry, engine, prompts)
    greedy = _serve_greedy(engine, prompts)
    yield {"engine": engine, "entry": entry, "rows": rows,
           "prompts": prompts, "sampled": sampled, "greedy": greedy}
    engine.shutdown()


# -- (a) prefill then decode through the engine, against the reference ------

def test_float32_build_gives_the_references_logits(exact):
    worst = _worst_row(exact["entry"], exact["prompts"], exact["sampled"],
                       exact["rows"])
    assert worst < EXACT_BAND, worst
    # six requests over four slots: slots were reused, prompts under, over
    # and at the chunk size all went through the chunk program
    stats = exact["entry"].stats()
    assert stats["chunk_runs"] >= len(PROMPT_LENS) + 2
    assert stats["prefills"] == 0


def test_bfloat16_build_is_inside_its_band_and_outside_the_exact_one():
    engine, entry = _engine(_model("bfloat16", name="hybrid_bf16"))
    try:
        rows = _record_rows(entry)
        prompts = _prompts()
        sampled = _serve_sampled(entry, engine, prompts)
    finally:
        engine.shutdown()
    worst = _worst_row(entry, prompts, sampled, rows)
    assert EXACT_BAND * 10 < worst < BF16_BAND, worst


def test_a_state_that_is_not_reset_fails_the_exact_band():
    """The control: a prompt's first chunk fed as if it opened at position
    1 leaves the slot what its last request left there."""
    engine, entry = _engine(_model(name="hybrid_stale", slots=1))
    run = entry._run

    def stale(kind, feeds, span=None):
        if kind == "chunk":
            feeds = dict(feeds)
            feeds[DecodeModel.CHU_POSITIONS] = (
                feeds[DecodeModel.CHU_POSITIONS] + 1)
        return run(kind, feeds, span)

    try:
        rows = _record_rows(entry)
        prompts = _prompts()[:2]
        first = _serve_sampled(entry, engine, prompts[:1])
        entry._run = stale
        second = [engine.submit(
            prompts[1], max_new_tokens=6,
            sampling=SamplingParams(temperature=1.0, seed=1)
        ).result(timeout=300)["tokens"]]
    finally:
        engine.shutdown()
    assert _worst_row(entry, prompts[:1], first, rows) < EXACT_BAND
    assert _worst_row(entry, prompts[1:], second, rows,
                      first_id=2) > 100 * EXACT_BAND


# -- (b) each op against its formula -----------------------------------------

def _mixer_case(rng, t):
    heads, p, g, n = 4, 8, 2, 16
    d_inner, conv_dim = heads * p, heads * p + 2 * g * n
    draw = lambda *s: jnp.asarray(rng.randn(*s).astype("float32"))  # noqa
    params = {"conv_w": draw(4, conv_dim) * 0.5, "conv_b": draw(conv_dim),
              "dt_bias": draw(heads), "a_log": jnp.log(
                  jnp.asarray(rng.uniform(1, 16, heads).astype("float32"))),
              "d": jnp.ones((heads,)), "norm_w": draw(d_inner)}
    sizes = dict(heads=heads, head_dim=p, groups=g, n_state=n, eps=1e-5,
                 out_dtype=jnp.float32)
    return draw(t, 2 * d_inner + 2 * g * n + heads), params, sizes, (
        jnp.zeros((3, 3, conv_dim)), jnp.zeros((3, heads, p, n)))


@pytest.mark.parametrize("length", [13, 16, 5])
def test_chunked_scan_is_the_recurrence_is_repeated_updates(length):
    """One slot's prompt through the chunk form in chunks of 8 (the last
    padded), through one-token updates, and token by token in one scan:
    one ``y``, one final state, for lengths that are no multiple of the
    chunk or of the scan's own chunk (4)."""
    rng = np.random.RandomState(length)
    x, params, sizes, (conv0, ssm0) = _mixer_case(rng, length)
    slot, chunk = 1, 8
    conv, ssm, ys = conv0 + 1.0, ssm0 + 1.0, []   # dirt a reset must clear
    for lo in range(0, length, chunk):
        real = min(chunk, length - lo)
        piece = jnp.zeros((chunk, x.shape[1])).at[:real].set(x[lo:lo + real])
        y, conv, ssm = mamba.mixer_chunk(
            piece, params, conv, ssm, slot, jnp.arange(chunk) < real,
            lo == 0, chunk=4, **sizes)
        ys.append(y[:real])
    y_chunks = jnp.concatenate(ys)
    conv_s, ssm_s, ys = conv0, ssm0, []
    mask = jnp.arange(3) == slot
    for t in range(length):
        y, conv_s, ssm_s = mamba.mixer_step(
            jnp.tile(x[t][None], (3, 1)), params, conv_s, ssm_s, mask,
            **sizes)
        ys.append(y[slot])
    y_steps = jnp.stack(ys)
    y_whole, _c, ssm_w = mamba.mixer_chunk(
        x, params, conv0, ssm0, slot, jnp.ones((length,), bool), True,
        chunk=length, **sizes)
    np.testing.assert_allclose(y_chunks, y_steps, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(y_chunks, y_whole, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ssm[slot], ssm_s[slot], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ssm[slot], ssm_w[slot], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(conv[slot], conv_s[slot], rtol=1e-5,
                               atol=1e-6)
    # the other slots' rows: untouched by the chunks, bit for bit
    assert np.array_equal(np.asarray(ssm)[[0, 2]],
                          np.asarray(ssm0 + 1.0)[[0, 2]])
    assert np.array_equal(np.asarray(ssm_s)[[0, 2]], np.asarray(ssm0)[[0, 2]])


def test_the_scan_chunked_is_the_scan_token_by_token():
    rng = np.random.RandomState(3)
    t, h, p, g, n = 37, 8, 4, 2, 16
    x, dt = rng.randn(t, h, p), np.abs(rng.randn(t, h)) * 0.5
    a, b, c = -np.exp(rng.rand(h)), rng.randn(t, g, n), rng.randn(t, g, n)
    args = [jnp.asarray(v.astype("float32")) for v in (x, dt, a, b, c)]
    h0 = jnp.asarray(rng.randn(h, p, n).astype("float32"))
    y1, s1 = mamba.ssm_scan_sequential(*args, h0)
    y2, s2 = mamba.ssm_scan_chunked(*args, h0, 8)
    np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(s1, s2, rtol=1e-4, atol=1e-5)


def test_the_kernels_serve_the_engine_like_the_composites(exact):
    """The same model under ``interpret``: the grouped paged-attention,
    ``moe_experts`` and ``ssm_update`` kernels through the Pallas
    interpreter give the composites' tokens, and logits inside the exact
    band of the reference."""
    with kernels.scoped_mode("interpret"):
        engine, entry = _engine(_model(name="hybrid_kernels"))
        try:
            rows = _record_rows(entry)
            sampled = _serve_sampled(entry, engine, exact["prompts"])
            greedy = _serve_greedy(engine, exact["prompts"])
        finally:
            engine.shutdown()
    assert _worst_row(entry, exact["prompts"], sampled, rows) < EXACT_BAND
    assert greedy == exact["greedy"]


# -- (c) the shares add up ----------------------------------------------------

def test_the_eight_ranks_parts_and_the_shared_expert_once_are_the_layer():
    rng = np.random.RandomState(11)
    t, hidden, ffn, shared, ranks, held, k = 10, 32, 12, 20, 8, 2, 3
    everyone = ranks * held
    draw = lambda *s: jnp.asarray(rng.randn(*s).astype("float32"))  # noqa
    h, norm_w = draw(t, hidden), jnp.ones((hidden,))
    gate, select = draw(everyone, hidden), 0.1 * draw(everyone)
    w_up, w_down = (0.3 * draw(everyone, ffn, hidden),
                    0.3 * draw(everyone, ffn, hidden))
    sh_up, sh_down = 0.3 * draw(hidden, shared), 0.3 * draw(shared, hidden)
    sizes = dict(CONFIG, num_experts_per_tok=k)
    experts = reference._functions(
        tuple((key, sizes[key]) for key in reference._KEYS))[4]
    with jax.default_matmul_precision("highest"):
        whole = experts(h, norm_w, gate, select, w_up, w_down, sh_up,
                        sh_down, offset=0)[0] - h
        only_shared = experts(h, norm_w, gate, select, w_up[:0], w_down[:0],
                              sh_up, sh_down, offset=0)[0] - h
    normed = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + 1e-5)
    op = OpRegistry.get("moe_routed_experts").lower
    parts, counts = [], []
    for rank in range(ranks):
        mine = slice(rank * held, (rank + 1) * held)
        out = op({"X": [normed], "GateW": [gate], "SelectBias": [select],
                  "WUp": [w_up[mine]], "WDown": [w_down[mine]],
                  "WriteRows": [jnp.zeros((t,), jnp.int32)]},
                 {"k": k, "score_scale": 2.5, "normalize": True,
                  "expert_offset": rank * held, "num_rows": 1})
        parts.append(out["Out"][0])
        counts.append(np.asarray(out["Counts"][0]))
    np.testing.assert_allclose(sum(parts) + only_shared, whole, rtol=1e-4,
                               atol=1e-5)
    # every assignment landed on exactly one rank
    assert sum(c[1] for c in counts) == t * k == counts[0][0]
    assert not np.allclose(parts[0], 0) and not np.allclose(
        sum(parts[:4]) + only_shared, whole, atol=1e-3)


# -- (d) the engine ----------------------------------------------------------

def test_a_request_alone_and_among_others_gives_the_same_tokens(exact):
    """Slots reused after retirement start from zero state: each of the
    six, served alone on a fresh single-slot entry whose slot the ones
    before it dirtied, gives what it gave among the others."""
    engine, entry = _engine(_model(name="hybrid_alone", slots=1))
    try:
        alone = [_serve_greedy(engine, [p], [n])[0]
                 for p, n in zip(exact["prompts"], ANSWERS)]
    finally:
        engine.shutdown()
    assert alone == exact["greedy"]


def test_launch_ahead_on_and_off_give_the_same_tokens(exact):
    m = _model(name="hybrid_serial")
    serial = DecodeModel(
        decode_program=m.decode_program, prefill_program=None,
        inject_program=None, startup_program=m.startup_program,
        chunk_program=m.chunk_program, chunk_tokens=m.chunk_tokens,
        chunk_logits_fetch=m.chunk_logits_fetch, slots=m.slots,
        max_len=m.max_len, vocab_size=m.vocab_size, hidden=m.hidden,
        state_names=m.state_names, logits_fetch=m.logits_fetch,
        prefill_logits_fetch=None, prefill_kv_fetches=[],
        inject_kv_feeds=[], block_size=m.block_size,
        num_blocks=m.num_blocks, name=m.name, version=m.version,
        kv_width=m.kv_width, kv_dtype=m.kv_dtype,
        slot_states=m.slot_states)
    engine, entry = _engine(serial)
    try:
        tokens = _serve_greedy(engine, exact["prompts"])
    finally:
        engine.shutdown()
    assert entry.stats()["decode_steps_ahead"] == 0
    assert exact["entry"].stats()["decode_steps_ahead"] > 0
    assert tokens == exact["greedy"]


def test_a_slot_that_does_not_step_keeps_its_state_bit_for_bit():
    """Hand-stepped: two requests, one short. Once the short one has
    retired its slot's states (and a never-used slot's) stay the bytes they
    are while the other goes on stepping."""
    engine, entry = _engine(_model(name="hybrid_idle"), started=False)
    prompts = _prompts(5)
    long, short = (engine.submit(prompts[1], max_new_tokens=20),
                   engine.submit(prompts[0], max_new_tokens=3))
    while not short.done():
        entry._iterate()
    for _ in range(2):          # the step in flight at retirement lands
        entry._iterate()
    names = [n for n, _s, _d in entry.model.slot_states]
    busy = [s for s, st in enumerate(entry._slots) if st is not None]
    assert len(busy) == 1
    idle = [s for s in range(entry.model.slots) if s not in busy]
    before = {n: np.asarray(entry._scope.find_var(n)) for n in names}
    for _ in range(8):
        entry._iterate()
    assert not long.done()
    for n in names:
        after = np.asarray(entry._scope.find_var(n))
        assert np.array_equal(after[idle], before[n][idle]), n
        assert not np.array_equal(after[busy], before[n][busy]), n
    engine.start()
    assert len(long.result(timeout=300)["tokens"]) == 20
    engine.shutdown()


def test_the_steps_counters_come_back_with_its_tokens(exact):
    stats = exact["entry"].stats()
    k, layers = CONFIG["num_experts_per_tok"], 2
    # every delivered or wasted slot-step routed k experts in each expert
    # layer
    assert stats["moe_assignments"] % (layers * k) == 0
    assert stats["moe_assignments"] >= (
        stats["active_slot_steps"] * layers * k)
    assert 0 < stats["moe_held_assignments"] < stats["moe_assignments"]
    # a step touches at most every held expert of every expert layer
    assert (stats["moe_touched_experts"] <= stats["moe_held_assignments"]
            and stats["moe_touched_experts"] <= stats["step_launches"]
            * CONFIG["n_routed_experts"] * layers)
    # a greedy step's one fetch: S tokens and three counts, int32
    assert stats["decode_logits_fetch_steps"] < stats["decode_steps"]


# -- (e) what is refused ------------------------------------------------------

@pytest.mark.parametrize("options", [{"prefix_cache_size": 4,
                                      "host_tier_mb": 0},
                                     {"prefix_cache_size": 0,
                                      "host_tier_mb": 16}, {}],
                         ids=["prefix_cache", "host_tier", "defaults"])
def test_a_prefix_cache_or_a_tier_is_refused_at_registration(options):
    engine = GenerationEngine(**options)
    with pytest.raises(EnforceError, match="per-slot recurrent state.*"
                       "prefix_cache_size=0 and host_tier_mb=0"):
        engine.register_model(_model(name="hybrid_refused"))


def test_beam_search_and_speculation_are_refused_at_the_door(exact):
    with pytest.raises(RejectedError, match="recurrent state"):
        exact["engine"].submit([1, 2, 3], max_new_tokens=4, beam_width=2)
    with pytest.raises(RuntimeError, match="no stateless prefill"):
        exact["entry"].offline_decode([1, 2, 3], 4)


def test_the_hbm_gate_counts_both_kinds_of_state():
    m = _model(name="hybrid_bytes")
    rows = m.num_blocks * m.block_size
    kv = 2 * rows * 2 * 16 * 4                       # one attention layer
    conv = 4 * 3 * (64 + 2 * 2 * 16) * 4
    ssm = 4 * 8 * 8 * 16 * 4
    assert m.arena_bytes() == kv + 2 * (conv + ssm)
    engine = GenerationEngine(prefix_cache_size=0, host_tier_mb=0,
                              hbm_budget_mb=0.001)
    with pytest.raises(EnforceError, match="HBM budget"):
        engine.register_model(m)


# -- the comparison's readings (tools/check_hybrid_logits.py) ----------------

def _tool():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_hybrid_logits",
        os.path.join(ROOT, "tools", "check_hybrid_logits.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


def test_the_tool_counts_answers_as_the_cell_does():
    """An answer is wrong when one of its first ``check_tokens`` tokens is
    more than ``check_tolerance`` behind; a token past them is not held."""
    behind = np.zeros((4, 20))
    behind[0, 3], behind[1, 17], behind[2, 15], behind[3, 0] = (
        0.3, 0.9, 0.26, 0.25)
    out = _tool()._summary(behind, behind, {"check_tokens": 16,
                                            "check_tolerance": 0.25})
    assert (out["answers_wrong"], out["answers"]) == (2, 4)
    assert out["answers_worst_behind"] == [0.3, 0.0, 0.26, 0.25]
    assert out["behind"]["worst"] == 0.9
    assert out["behind"]["share_of_tokens_over"]["0.5"] == 1 / 80


def test_the_tool_counts_a_flip_by_layer_and_by_held_expert():
    """Two passes' ranked experts ``[layers, tokens, k + 1]``: the chosen
    SETS are compared (the order within them is not), and a flip counts for
    the held share when an expert of ids 4..7 comes or goes."""
    a = np.array([[[1, 2, 9], [4, 5, 9]], [[4, 6, 9], [0, 1, 9]]])
    b = np.array([[[2, 1, 8], [4, 3, 9]], [[6, 5, 9], [0, 1, 2]]])
    out = _tool()._flips([(a, None)], [(b, None)], (4, 8), 2)
    assert out == {"tokens_differing_by_layer": [1, 1],
                   "with_a_held_expert_by_layer": [1, 1]}
    same = _tool()._flips([(a, None)], [(a[..., ::1], None)], (4, 8), 2)
    assert same["tokens_differing_by_layer"] == [0, 0]


def test_the_reference_hands_out_its_routing_and_rounds_operands_on_request(
        exact):
    """``routing=True`` adds every expert layer's ranked experts (the k
    chosen, then the first loser) and their selection scores, in falling
    order; ``round_operands`` changes the logits a little and is off by
    default."""
    weights, prompt = _weights(exact["entry"]), exact["prompts"][3]
    at = range(len(prompt) - 3, len(prompt))
    plain = reference.logits(weights, CONFIG, prompt, at,
                             pad_to=len(prompt))
    rows, ranked, scores = reference.logits(
        weights, CONFIG, prompt, at, pad_to=len(prompt), routing=True)
    k = CONFIG["num_experts_per_tok"]
    assert np.array_equal(rows, plain)
    assert ranked.shape == scores.shape == (2, 3, k + 1)
    assert (np.diff(scores, axis=-1) <= 0).all()
    assert ((0 <= ranked) & (ranked < CONFIG["router_experts"])).all()
    rounded = reference.logits(weights, CONFIG, prompt, at,
                               pad_to=len(prompt),
                               round_operands="bfloat16")
    gap = np.abs(rounded - plain).max() / plain.std()
    assert 0 < gap < 0.1
