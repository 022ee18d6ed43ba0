"""The ``lfm2_moe`` hybrid decoder (gated short convolutions beside paged
grouped-query K/V rows with QK-norm and rotary positions, gated routed
experts of which a share is held) served through ``GenerationEngine``, at a
tiny size on the CPU, against its plain reference
(``benchmark/references/plain_lfm2.py``: float32, whole sequence, no cache).

Logits are compared, not tokens (with random weights the largest logit
changes on rounding): a sampled request makes the engine fetch every step's
row, and ``_choose_token`` is where each delivered row passes.
"""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.references import plain_lfm2 as reference  # noqa: E402
from paddle_tpu import kernels  # noqa: E402
from paddle_tpu.core.registry import OpRegistry  # noqa: E402
from paddle_tpu.kernels import attention, mamba, moe  # noqa: E402
from paddle_tpu.serving import GenerationEngine, build_lfm2_model  # noqa: E402
from paddle_tpu.serving.decode import SamplingParams  # noqa: E402
from paddle_tpu.serving.decode.model import DecodeModel  # noqa: E402

#: the published keys at a tiny size: one whole period (conv conv conv
#: attention) twice over, both dense layers, five expert layers
CONFIG = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=7,
    layer_types=["conv", "conv", "full_attention", "conv", "conv", "conv",
                 "full_attention"],
    num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
    num_dense_layers=2, num_experts=4, num_experts_per_tok=2,
    moe_intermediate_size=24, conv_L_cache=3, norm_eps=1e-5,
    norm_topk_prob=True, routed_scaling_factor=1,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"})
ROUTER = 8
GEOMETRY = dict(slots=4, max_len=48, block_size=4, chunk_tokens=8)
RANK = 1                    # experts 4..7 of 8 are held
PROMPT_LENS = (5, 13, 8, 20, 3, 9)      # under, over and at the chunk
ANSWERS = (6, 9, 4, 10, 12, 5)
#: float32 build against the float32 reference: summation order alone, and
#: the rotation's angles (float32 on both sides, positions under 48)
EXACT_BAND = 1e-4
#: bfloat16 build: parameters and each sub-layer's input rounded to 8 bits
#: of mantissa (2^-9 relative) through 14 sub-layers; measured 6.6e-3 of a
#: row's standard deviation (a router's choice flipped would read more)
BF16_BAND = 5e-2


def _sizes():
    skip = ("num_hidden_layers", "rope_parameters")
    return dict({k: v for k, v in CONFIG.items() if k not in skip},
                rope_theta=CONFIG["rope_parameters"]["rope_theta"],
                router_experts=ROUTER)


def _model(dtype="float32", name="lfm2", **over):
    m = build_lfm2_model(**_sizes(), **dict(GEOMETRY, **over), dtype=dtype,
                         expert_rank=RANK, name=name)
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
            expert_offset=RANK * CONFIG["num_experts"])
        got = np.stack(rows[first_id + i])
        worst = max(worst, float(
            (np.abs(got - want).max(1) / want.std(1)).max()))
    return worst


def _serve_sampled(engine, prompts, answers=ANSWERS):
    responses = [
        engine.submit(p, max_new_tokens=n,
                      sampling=SamplingParams(temperature=1.0, seed=i))
        for i, (p, n) in enumerate(zip(prompts, answers))]
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
    sampled = _serve_sampled(engine, prompts)
    greedy = _serve_greedy(engine, prompts)
    yield {"engine": engine, "entry": entry, "rows": rows,
           "prompts": prompts, "sampled": sampled, "greedy": greedy}
    engine.shutdown()


# -- (a) prefill by chunks, then decode, against the reference ---------------

def test_float32_build_gives_the_references_logits(exact):
    worst = _worst_row(exact["entry"], exact["prompts"], exact["sampled"],
                       exact["rows"])
    assert worst < EXACT_BAND, worst
    # six requests over four slots: slots were reused (joined and left);
    # prompts under, over and at the chunk size all went through the chunk
    # program, and 20 + 10 positions cross chunk and block boundaries
    stats = exact["entry"].stats()
    assert stats["chunk_runs"] >= len(PROMPT_LENS) + 2
    assert stats["prefills"] == 0


def test_bfloat16_build_is_inside_its_band_and_outside_the_exact_one():
    engine, entry = _engine(_model("bfloat16", name="lfm2_bf16"))
    try:
        rows = _record_rows(entry)
        prompts = _prompts()
        sampled = _serve_sampled(engine, prompts)
    finally:
        engine.shutdown()
    worst = _worst_row(entry, prompts, sampled, rows)
    assert EXACT_BAND * 10 < worst < BF16_BAND, worst


def _stale(entry, name):
    """``entry._run`` with the state ``name`` one decode step stale: what a
    step wrote there is put back to what the step read."""
    run, scope = entry._run, entry._scope

    def stale(kind, feeds, span=None):
        if kind != "step":
            return run(kind, feeds, span)
        before = np.asarray(scope.find_var(name))
        out = run(kind, feeds, span)
        scope.set(name, jnp.asarray(before, dtype=scope.find_var(name).dtype))
        return out

    return stale


def _shifted(entry, _name):
    """``entry._run`` with every decode step's positions one too far: the
    rotation alone reads them."""
    run = entry._run

    def shifted(kind, feeds, span=None):
        if kind == "step":
            feeds = dict(feeds)
            step = np.array(feeds[DecodeModel.DEC_STEP])
            step[:, 1] += 1
            feeds[DecodeModel.DEC_STEP] = step
        return run(kind, feeds, span)

    return shifted


@pytest.mark.parametrize("fault,state", [
    (_stale, "conv3"), (_stale, "kcache2"), (_shifted, None)],
    ids=["stale_convolution_tail", "stale_k_row", "position_off_by_one"])
def test_a_planted_fault_fails_the_exact_band(fault, state):
    """The controls: one conv layer's tail, or one attention layer's K
    arena, a decode step behind; the decode steps' positions off by one.
    Each leaves the prompt's logits sound and the decoded rows wrong."""
    engine, entry = _engine(_model(name=f"lfm2_{fault.__name__}", slots=1))
    try:
        rows = _record_rows(entry)
        prompts = _prompts()[1:3]
        sound = _serve_sampled(engine, prompts[:1], [6])
        entry._run = fault(entry, f"{entry.model.name}_v1.{state}")
        broken = _serve_sampled(engine, prompts[1:], [6])
    finally:
        engine.shutdown()
    assert _worst_row(entry, prompts[:1], sound, rows) < EXACT_BAND
    assert _worst_row(entry, prompts[1:], broken, rows,
                      first_id=2) > 100 * EXACT_BAND


def test_the_kernels_serve_the_engine_like_the_composites(exact):
    """The same model under ``interpret``: the grouped paged-attention and
    the gated ``moe_experts`` kernels through the Pallas interpreter give
    the composites' tokens, and logits inside the exact band."""
    with kernels.scoped_mode("interpret"):
        engine, entry = _engine(_model(name="lfm2_kernels"))
        try:
            rows = _record_rows(entry)
            prompts = exact["prompts"][1:4]     # 13, 8 and 20 tokens
            sampled = _serve_sampled(engine, prompts, ANSWERS[1:4])
            greedy = _serve_greedy(engine, prompts, ANSWERS[1:4])
        finally:
            engine.shutdown()
    assert _worst_row(entry, prompts, sampled, rows) < EXACT_BAND
    assert greedy == exact["greedy"][1:4]


def test_a_request_alone_and_among_others_gives_the_same_tokens(exact):
    """Slots reused after retirement start from a zero tail: each of four,
    served alone on a fresh single-slot entry whose slot the ones before it
    dirtied, gives what it gave among the others."""
    engine, _entry = _engine(_model(name="lfm2_alone", slots=1))
    try:
        alone = [_serve_greedy(engine, [p], [n])[0]
                 for p, n in zip(exact["prompts"][:4], ANSWERS)]
    finally:
        engine.shutdown()
    assert alone == exact["greedy"][:4]


def test_the_steps_counters_come_back_with_its_tokens(exact):
    stats = exact["entry"].stats()
    k, layers = CONFIG["num_experts_per_tok"], 5
    assert stats["moe_assignments"] % (layers * k) == 0
    assert stats["moe_assignments"] >= (
        stats["active_slot_steps"] * layers * k)
    assert 0 < stats["moe_held_assignments"] < stats["moe_assignments"]
    # the busiest held expert of a layer has at least the mean of the
    # touched ones' tokens and at most every token of the step
    assert (stats["moe_held_assignments"] / stats["moe_touched_experts"]
            <= stats["moe_peak_expert_tokens"] / stats["moe_touched_experts"]
            * CONFIG["num_experts"])
    assert 0 < stats["moe_peak_expert_tokens"] <= (
        stats["step_launches"] * layers * GEOMETRY["slots"])
    assert stats["moe_peak_expert_tokens"] <= stats["moe_held_assignments"]


# -- the operator against its formula -----------------------------------------

@pytest.mark.parametrize("length", [13, 16, 5])
def test_short_conv_by_chunks_is_by_steps_is_the_whole_sequence(length):
    rng = np.random.RandomState(length)
    d, taps, slot, chunk = 16, 3, 1, 8
    x = jnp.asarray(rng.randn(length, 3 * d).astype("float32"))
    w = jnp.asarray(rng.uniform(-0.5, 0.5, (taps, d)).astype("float32"))
    tail0 = jnp.zeros((3, taps - 1, d))
    tail, ys = tail0 + 1.0, []          # dirt a reset must clear
    for lo in range(0, length, chunk):
        real = min(chunk, length - lo)
        piece = jnp.zeros((chunk, 3 * d)).at[:real].set(x[lo:lo + real])
        y, tail = mamba.short_conv_chunk(
            piece, w, tail, slot, jnp.arange(chunk) < real, lo == 0,
            jnp.float32)
        ys.append(y[:real])
    by_chunks = jnp.concatenate(ys)
    tail_s, ys = tail0, []
    for t in range(length):
        y, tail_s = mamba.short_conv_step(
            jnp.tile(x[t][None], (3, 1)), w, tail_s, jnp.arange(3) == slot,
            jnp.float32)
        ys.append(y[slot])
    b, c, u = np.split(np.asarray(x), 3, axis=-1)
    ext = np.concatenate([np.zeros((taps - 1, d), "float32"), b * u])
    whole = c * sum(np.asarray(w)[k] * ext[k:k + length]
                    for k in range(taps))
    np.testing.assert_allclose(by_chunks, whole, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(jnp.stack(ys), whole, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(tail[slot], tail_s[slot], rtol=1e-6)
    np.testing.assert_allclose(tail[slot], ext[-(taps - 1):], rtol=1e-6)
    # the other slots' tails: untouched, bit for bit
    assert np.array_equal(np.asarray(tail)[[0, 2]],
                          np.asarray(tail0 + 1.0)[[0, 2]])
    assert np.array_equal(np.asarray(tail_s)[[0, 2]],
                          np.asarray(tail0)[[0, 2]])


def test_rotary_is_rotate_half_over_the_whole_head_in_complex_numbers():
    rng = np.random.RandomState(0)
    x = rng.randn(3, 2, 4, 8).astype("float32")
    pos = np.array([[0, 5], [17, 2047], [1, 2]])
    got = OpRegistry.get("rotary_embedding").lower(
        {"X": [jnp.asarray(x)], "Positions": [jnp.asarray(pos)]},
        {"theta": 1e6})["Out"][0]
    freq = 1e6 ** (-np.arange(4) / 4.0)
    turn = np.exp(1j * pos[..., None, None] * freq)
    want = (x[..., :4] + 1j * x[..., 4:]) * turn
    np.testing.assert_allclose(
        got, np.concatenate([want.real, want.imag], -1), rtol=1e-3,
        atol=1e-3)
    assert np.array_equal(np.asarray(got)[0, 0], x[0, 0])   # position 0


# -- (b) the shares add up ----------------------------------------------------

def test_the_eight_ranks_parts_are_the_uncut_layer():
    rng = np.random.RandomState(11)
    t, hidden, ffn, ranks, held, k = 10, 32, 12, 8, 2, 3
    everyone = ranks * held
    draw = lambda *s: jnp.asarray(rng.randn(*s).astype("float32"))  # noqa
    h, norm_w = draw(t, hidden), jnp.ones((hidden,))
    gate, select = draw(everyone, hidden), 0.1 * draw(everyone)
    w1, w3, w2 = (0.3 * draw(everyone, ffn, hidden) for _ in range(3))
    sizes = dict(CONFIG, num_experts_per_tok=k, rope_theta=1e6)
    experts = reference._functions(
        tuple((key, sizes[key]) for key in reference._KEYS)
        + (("rope_theta", 1e6),))[5]
    with jax.default_matmul_precision("highest"):
        whole = experts(h, norm_w, gate, select, w1, w3, w2, offset=0)[0] - h
    normed = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + 1e-5)
    op = OpRegistry.get("moe_routed_experts").lower
    parts, counts = [], []
    for rank in range(ranks):
        mine = slice(rank * held, (rank + 1) * held)
        out = op({"X": [normed], "GateW": [gate], "SelectBias": [select],
                  "WGate": [w1[mine]], "WUp": [w3[mine]],
                  "WDown": [w2[mine]],
                  "WriteRows": [jnp.zeros((t,), jnp.int32)]},
                 {"k": k, "score_scale": 1.0, "normalize": True,
                  "norm_epsilon": 1e-6, "expert_offset": rank * held,
                  "num_rows": 1})
        parts.append(out["Out"][0])
        counts.append(np.asarray(out["Counts"][0]))
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-4, atol=1e-5)
    # every assignment landed on exactly one rank
    assert sum(c[1] for c in counts) == t * k == counts[0][0]
    assert not np.allclose(parts[0], 0) and not np.allclose(
        sum(parts[:4]), whole, atol=1e-3)
    # the busiest held expert: at least the mean, at most every token
    for c in counts:
        assert c[1] / max(c[2], 1) <= c[3] <= t


# -- (c) the kernels at the published geometries -------------------------------

def _published(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name)) as f:
        return json.load(f)


LFM2 = _published("lfm2_24b_a2b.json")
NEMOTRON = _published("nemotron3_nano_30b_a3b.json")
#: (tokens, hidden, width, matrices an expert): LFM2's gated experts at a
#: small and at the cell's step, Nemotron's relu2 experts at its own
EXPERT_SHAPES = [
    (16, LFM2["hidden_size"], LFM2["moe_intermediate_size"], 3),
    (128, LFM2["hidden_size"], LFM2["moe_intermediate_size"], 3),
    (32, NEMOTRON["hidden_size"], NEMOTRON["moe_intermediate_size"], 2)]
#: (K/V heads, query heads a K/V head, head size)
HEAD_SHAPES = [
    (LFM2["num_key_value_heads"],
     LFM2["num_attention_heads"] // LFM2["num_key_value_heads"],
     LFM2["hidden_size"] // LFM2["num_attention_heads"]),
    (NEMOTRON["num_key_value_heads"],
     NEMOTRON["num_attention_heads"] // NEMOTRON["num_key_value_heads"],
     NEMOTRON["head_dim"])]


@pytest.mark.parametrize("t,hidden,ffn,matrices", EXPERT_SHAPES)
def test_moe_experts_interpreted_is_its_composite_at_published_widths(
        t, hidden, ffn, matrices):
    """bfloat16 operands, float32 accumulation on both sides: what parts
    them is the order of the hidden size's tiles in the up products' sums
    and the activation's rounding to bfloat16 where those sums differ in
    the last bit: 2^-8 of a few of the 1,536 terms of an output element."""
    rng = np.random.RandomState(t)
    held = 3
    bf16 = jnp.bfloat16
    x = jnp.asarray(rng.randn(t, hidden), bf16)
    ws = [jnp.asarray(0.02 * rng.randn(held, ffn, hidden), bf16)
          for _ in range(matrices)]
    c = np.where(rng.rand(t, held) < 0.5, rng.rand(t, held), 0.0)
    c[:, 1] = 0.0                       # an expert no token chose
    c = jnp.asarray(c, jnp.float32)
    order = [ws[0], ws[-1]] + ws[1:-1]          # up, down, then the gate
    assert moe.hidden_tile(t, hidden, ffn, bf16, matrices,
                           interpret=True) < hidden      # more than a tile
    got = jax.jit(lambda *a: moe.moe_experts(*a, interpret=True))(
        x, c, *order)
    want = moe.experts_composite(x, c, *order)
    scale = float(np.abs(np.asarray(want)).max())
    assert scale > 0
    np.testing.assert_allclose(got, want, atol=2e-3 * scale, rtol=0)


@pytest.mark.parametrize("g,per,d", HEAD_SHAPES)
def test_grouped_paged_attention_interpreted_is_its_composite(g, per, d):
    rng = np.random.RandomState(d)
    S, L, bs = 5, 64, 16
    lengths = [1, 15, 17, 64, 0]
    _q, k, v, rows, bias = kernels._paged_case(rng, S, L, bs, g * d, lengths)
    q = rng.randn(S, g * per * d).astype("float32")
    sm = 1.0 / float(np.sqrt(d))
    got = jax.jit(lambda *a: attention.paged_attention(
        *a, S, L, bs, sm, interpret=True, kv_heads=g))(q, k, v, rows, bias)
    want = attention.paged_attention_composite(q, k, v, rows, bias, S, L, sm,
                                               kv_heads=g)
    live = np.asarray(lengths) > 0
    # float32: an online softmax regroups the sums (kernels/attention.py)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(got)[~live].any()


# -- (d) no fallback by geometry ------------------------------------------------

@pytest.mark.parametrize("t,hidden,ffn,matrices", EXPERT_SHAPES)
def test_the_expert_kernel_takes_both_configurations_geometries(
        t, hidden, ffn, matrices):
    tile = moe.hidden_tile(t, hidden, ffn, "bfloat16", matrices)
    assert tile and hidden % tile == 0 and tile % 128 == 0
    # what it refuses: float32 weights, tokens in no whole sublane tile
    assert not moe.hidden_tile(t, hidden, ffn, "float32", matrices)
    assert not moe.hidden_tile(t + 1, hidden, ffn, "bfloat16", matrices)


def test_nemotrons_tile_is_the_one_it_had():
    assert moe.hidden_tile(32, 2688, 1856, "bfloat16", 2) == 384


@pytest.mark.parametrize("g,per,d", HEAD_SHAPES)
def test_the_grouped_attention_kernel_takes_both_geometries(g, per, d):
    pack, rows = attention.grouped_layout(g * d, g, g * per * d, "bfloat16")
    assert pack * d == 128 and rows % 16 == 0 and rows >= pack * per
    assert attention._mosaic_tiles(16, g * d, "bfloat16")
    # a head that no whole number of fills a lane tile runs the composite
    assert attention.grouped_layout(3 * 48, 3, 3 * 4 * 48, "bfloat16") == (
        0, 0)
