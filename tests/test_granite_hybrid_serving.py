"""The dense ``granitemoehybrid`` decoder (Mamba-2 or position-free
grouped-query mixers, a dense SwiGLU in every layer, four scalar
multipliers) served through ``GenerationEngine``, at a tiny size on the CPU,
against its plain reference (``benchmark/references/plain_granite_hybrid.py``:
float32, whole sequence, the recurrence token by token, no cache); and the
chunk prefill's two new parts: the mask the device makes of a chunk's span,
and the chunk kernel that reads a slot's live blocks alone.

Logits are compared, not tokens (with random weights the largest logit
changes on rounding): a sampled request makes the engine fetch every step's
row, and ``_choose_token`` is where each delivered row passes.
"""

import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.references import plain_granite_hybrid as reference  # noqa: E402
from paddle_tpu import kernels  # noqa: E402
from paddle_tpu.core.registry import OpRegistry  # noqa: E402
from paddle_tpu.kernels import attention  # noqa: E402
from paddle_tpu.serving import (  # noqa: E402
    GenerationEngine, build_decoder_model, build_granite_hybrid_model)
from paddle_tpu.serving.decode import SamplingParams  # noqa: E402
from paddle_tpu.serving.decode.model import DecodeModel  # noqa: E402

#: the published keys at a tiny size: both kinds of mixer on either side of
#: each other; a head is 64 / 4 = 16 wide and the scores are scaled by 1 /
#: 16, as the published 1 / 64 scales heads of 64
CONFIG = dict(
    vocab_size=96, hidden_size=64,
    layer_types=["mamba", "mamba", "attention", "mamba", "attention",
                 "mamba"],
    num_attention_heads=4, num_key_value_heads=2,
    shared_intermediate_size=96, mamba_n_heads=8, mamba_d_head=8,
    mamba_n_groups=1, mamba_d_state=16, mamba_d_conv=4, mamba_chunk_size=4,
    embedding_multiplier=12, attention_multiplier=0.0625,
    residual_multiplier=0.22, logits_scaling=8, rms_norm_eps=1e-5)
GEOMETRY = dict(slots=4, max_len=48, block_size=4, chunk_tokens=8)
#: under, over and at the chunk; 21 and 27 span three and four chunks and
#: six and seven blocks
PROMPT_LENS = (5, 21, 8, 27, 3, 17)
ANSWERS = (6, 9, 4, 10, 12, 5)
#: float32 build against the float32 reference: summation order alone
EXACT_BAND = 1e-4
#: bfloat16 build: parameters and each sub-layer's input rounded to 8 bits
#: of mantissa through 12 sub-layers, and a table that is both the embedding
#: (times 12) and the head; measured over the 46 delivered rows 0.029 of a
#: row's standard deviation in the median, 0.058 at the 90th percentile and
#: 0.087 at worst (the published size on the chip reads 0.083 in the median)
BF16_BAND = 2e-1


def _model(dtype="float32", name="granite", **over):
    # a wider draw than the published 0.02: at a hidden size of 64 under an
    # embedding times 12 the layers would have nothing to say
    m = build_granite_hybrid_model(
        **CONFIG, **dict(GEOMETRY, **over), dtype=dtype, name=name,
        initializer_range=0.5)
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


def _worst_row(entry, prompts, answers, rows, first_id=1, **read_as):
    """The worst delivered row's max |difference| from the reference's
    full forward over the served tokens, in standard deviations of the
    reference's row; ``read_as`` misreads a published key (a control)."""
    weights, worst = _weights(entry), 0.0
    for i, (prompt, out) in enumerate(zip(prompts, answers)):
        tokens = prompt + [int(t) for t in out[:-1]]
        want = reference.logits(
            weights, CONFIG, tokens,
            range(len(prompt) - 1, len(prompt) - 1 + len(out)),
            pad_to=GEOMETRY["max_len"], **read_as)
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
    recorded), then the same prompts greedy; every chunk launch's feeds."""
    engine, entry = _engine(_model())
    rows = _record_rows(entry)
    chunks, run = [], entry._run

    def noting(kind, feeds, span=None):
        if kind == "chunk":
            chunks.append(dict(feeds))
        return run(kind, feeds, span)

    entry._run = noting
    prompts = _prompts()
    sampled = _serve_sampled(engine, prompts)
    greedy = _serve_greedy(engine, prompts)
    yield {"engine": engine, "entry": entry, "rows": rows, "chunks": chunks,
           "prompts": prompts, "sampled": sampled, "greedy": greedy}
    engine.shutdown()


# -- (a) prefill by chunks, then decode, against the reference ---------------

def test_float32_build_gives_the_references_logits(exact):
    worst = _worst_row(exact["entry"], exact["prompts"], exact["sampled"],
                       exact["rows"])
    assert worst < EXACT_BAND, worst
    # six requests over four slots: slots were reused; prompts of three and
    # four chunks and six and seven blocks went through the chunk program,
    # then decode steps through the states and the rows they left
    stats = exact["entry"].stats()
    assert stats["chunk_runs"] >= 2 * sum(-(-n // 8) for n in PROMPT_LENS)
    assert stats["prefills"] == 0


def test_bfloat16_build_is_inside_its_band_and_outside_the_exact_one():
    engine, entry = _engine(_model("bfloat16", name="granite_bf16"))
    try:
        rows = _record_rows(entry)
        prompts = _prompts()
        sampled = _serve_sampled(engine, prompts)
    finally:
        engine.shutdown()
    worst = _worst_row(entry, prompts, sampled, rows)
    assert EXACT_BAND * 10 < worst < BF16_BAND, worst


@pytest.mark.parametrize("read_as", [
    {"embedding_multiplier": 1}, {"attention_multiplier": 1},
    {"attention_multiplier": 0.25}, {"residual_multiplier": 1},
    {"logits_scaling": 1},
    # the ONE group of state 16 misread as two groups of 8: half the heads
    # then read the other half of B and of C
    {"mamba_n_groups": 2, "mamba_d_state": 8}],
    ids=lambda r: "_".join(f"{k}_{v}" for k, v in r.items()))
def test_each_multiplier_and_the_one_group_matter(exact, read_as):
    """The controls: the reference with ONE published key misread (a
    multiplier as 1, the attention's as the usual 1 / sqrt(head), the one
    B/C group as two) leaves the exact band far behind on the rows the
    sound reference holds inside it."""
    worst = _worst_row(exact["entry"], exact["prompts"], exact["sampled"],
                       exact["rows"], **read_as)
    assert worst > 100 * EXACT_BAND, worst


def _stale(entry, name, kind):
    """``entry._run`` with the state ``name`` one launch of ``kind``
    stale: what the launch wrote there is put back to what it read."""
    run, scope = entry._run, entry._scope

    def stale(launched, feeds, span=None):
        if launched != kind:
            return run(launched, feeds, span)
        before = np.asarray(scope.find_var(name))
        out = run(launched, feeds, span)
        scope.set(name, jnp.asarray(before, dtype=scope.find_var(name).dtype))
        return out

    return stale


@pytest.mark.parametrize("state,kind", [
    ("ssm1", "step"), ("conv3", "step"), ("kcache2", "step"),
    ("ssm1", "chunk"), ("kcache4", "chunk")],
    ids=["stale_ssm_state", "stale_convolution_tail", "stale_k_row",
         "ssm_state_dropped_at_chunk_boundaries", "k_arena_a_chunk_stale"])
def test_a_planted_fault_fails_the_exact_band(state, kind):
    """The controls: one Mamba layer's state or tail, or one attention
    layer's K arena, a decode step behind; or the same a CHUNK behind (a
    long prompt's state dropped at its chunk boundaries, its K rows never
    landing). Each leaves a sound request's logits sound and the faulted
    request's wrong."""
    engine, entry = _engine(_model(name=f"granite_{state}_{kind}", slots=1))
    try:
        rows = _record_rows(entry)
        prompts = _prompts()[1:4:2]             # 21 and 27 tokens
        sound = _serve_sampled(engine, prompts[:1], [6])
        entry._run = _stale(entry, f"{entry.model.name}_v1.{state}", kind)
        broken = _serve_sampled(engine, prompts[1:], [6])
    finally:
        engine.shutdown()
    assert _worst_row(entry, prompts[:1], sound, rows) < EXACT_BAND
    assert _worst_row(entry, prompts[1:], broken, rows,
                      first_id=2) > 100 * EXACT_BAND


def test_the_kernels_serve_the_engine_like_the_composites(exact):
    """The same model under ``interpret``: the chunk kernel, the grouped
    paged-attention kernel and ``ssm_update`` through the Pallas
    interpreter give the composites' tokens, and logits inside the exact
    band, with no fallback."""
    before = kernels.fallback_counter().value
    with kernels.scoped_mode("interpret"):
        engine, entry = _engine(_model(name="granite_kernels"))
        try:
            rows = _record_rows(entry)
            prompts = exact["prompts"][1:4]     # 21, 8 and 27 tokens
            sampled = _serve_sampled(engine, prompts, ANSWERS[1:4])
            greedy = _serve_greedy(engine, prompts, ANSWERS[1:4])
        finally:
            engine.shutdown()
    assert _worst_row(entry, prompts, sampled, rows) < EXACT_BAND
    assert greedy == exact["greedy"][1:4]
    assert kernels.fallback_counter().value == before


def test_a_request_alone_and_among_others_gives_the_same_tokens(exact):
    """Slots reused after retirement start from zero states: each of four,
    served alone on a fresh single-slot entry whose slot the ones before it
    dirtied, gives what it gave among the others."""
    engine, _entry = _engine(_model(name="granite_alone", slots=1))
    try:
        alone = [_serve_greedy(engine, [p], [n])[0]
                 for p, n in zip(exact["prompts"][:4], ANSWERS)]
    finally:
        engine.shutdown()
    assert alone == exact["greedy"][:4]


# -- (b) the chunk's mask: made on the device, fed as two integers ------------

def test_no_chunk_program_is_fed_a_bias(exact):
    """A chunk launch's feeds are the tokens, the positions, the row map,
    the write rows, the slot and TWO integers: nothing of ``[1, C, L]``."""
    C, L = GEOMETRY["chunk_tokens"], GEOMETRY["max_len"]
    for model in (exact["entry"].model,
                  build_decoder_model(32, hidden=16, slots=2, max_len=L,
                                      block_size=4, chunk_tokens=C)):
        sig = {name: (shape, dtype)
               for name, shape, dtype in model.chunk_feed_sig()}
        assert sig[DecodeModel.CHU_SPAN] == ((2,), "int32")
        assert not any(len(shape) == 3 for shape, _dtype in sig.values())
        assert not hasattr(DecodeModel, "CHU_BIAS")
    assert exact["chunks"]
    for feeds in exact["chunks"]:
        start, real = (int(x) for x in feeds[DecodeModel.CHU_SPAN])
        assert feeds[DecodeModel.CHU_POSITIONS][0, 0] == start
        assert 1 <= real <= C
        assert max(np.asarray(v).size for v in feeds.values()) <= L


@pytest.mark.parametrize("block_len", [1, 4])
@pytest.mark.parametrize("start,real", [
    (0, 8), (0, 3), (8, 8), (16, 5), (40, 8), (44, 4), (12, 1)])
def test_the_device_makes_the_mask_the_rule_states(block_len, start, real):
    """``chunk_mask_bias`` (the op every chunk program's mask comes from)
    against ``DecodeModel.chunk_bias``, the rule in numpy: a position sees
    what lies at or before it and the whole of its own block; a query past
    the real ones sees nothing."""
    model = type("M", (), {
        "chunk_tokens": 8, "max_len": 48, "block_len": block_len})()
    want = DecodeModel.chunk_bias(model, start, real)
    span = DecodeModel.chunk_span(model, start, real)
    got = OpRegistry.get("chunk_mask_bias").lower(
        {"Span": [jnp.asarray(span)]},
        {"chunk": 8, "length": 48, "block_len": block_len})["Out"][0]
    assert got.shape == (1, 8, 48) and got.dtype == jnp.float32
    assert np.array_equal(np.asarray(got), want)
    horizon = np.asarray(attention.chunk_horizon(span, 8, 48, block_len))
    assert np.array_equal(horizon, (want[0] == 0.0).sum(1))


# -- (c) the chunk kernel against the composite --------------------------------

@pytest.mark.parametrize("heads", [(8, 4, 64), (2, 4, 128), (2, 2, 16)],
                         ids=lambda h: "g%dx%dx%d" % h)
@pytest.mark.parametrize("start,real", [
    (0, 32), (0, 15), (0, 17),          # the prompt's start, short chunks
    (224, 31), (224, 32), (240, 17),    # ending under, at and over a tile
    (32, 16), (33, 15), (288, 32)])     # at a block's edge; the slot's end
def test_the_chunk_kernel_is_the_composite(heads, start, real):
    """Interpret mode, a shuffled block table, 256-row copy tiles over a
    slot of 320 rows: the real queries within 2e-5 of the composite under
    the rule's bias, the others zeros."""
    G, per, D = heads
    rng = np.random.RandomState(start + real)
    args = kernels._chunk_case(rng, 32, 320, 16, G, per, D)
    kernels._assert_chunk_parity(args, start, real, 16, G, D)


@pytest.mark.parametrize("start,real", [(0, 28), (32, 32), (252, 8),
                                        (64, 4)])
def test_the_chunk_kernel_holds_the_block_mask(start, real):
    """``block_len`` 4: a position sees the whole of its own block, the
    rows after it in the block too."""
    rng = np.random.RandomState(start + real)
    args = kernels._chunk_case(rng, 32, 320, 16, 4, 8, 128)
    kernels._assert_chunk_parity(args, start, real, 16, 4, 128, block_len=4)


def test_the_chunk_kernel_reads_the_live_blocks_alone():
    """Rows past the chunk's horizon hold NaN: the kernel's answer does not
    change, so it never read them (the composite, which reads all ``L``
    rows, would give NaN)."""
    import jax

    rng = np.random.RandomState(3)
    q, k, v, rows = kernels._chunk_case(rng, 32, 320, 16, 8, 4, 64)
    span = np.array([64, 32], "int32")
    dead = np.ones(len(k), bool)
    dead[rows[:96]] = False                       # positions 0..95 are live
    run = jax.jit(lambda *a: attention.chunk_attention(
        *a, 16, 0.125, 8, interpret=True))
    clean = np.asarray(run(q, k, v, rows, span))
    k[dead], v[dead] = np.nan, np.nan
    assert np.array_equal(np.asarray(run(q, k, v, rows, span)), clean)
    assert np.isfinite(clean).all()


def test_short_geometries_keep_the_composite_by_shape():
    """Compiled (not interpreted), a geometry whose ``C x L`` is under
    ``CHUNK_KERNEL_MIN_WORK`` takes the composite without counting a
    fallback: the accepted cells' 128 x 2,048."""
    import jax

    assert 128 * 2048 < attention.CHUNK_KERNEL_MIN_WORK <= 512 * 16896
    rng = np.random.RandomState(5)
    q, k, v, rows = kernels._chunk_case(rng, 8, 48, 4, 2, 2, 16)
    before = kernels.fallback_counter().value
    text = jax.jit(lambda *a: attention.chunk_attention(
        *a, 4, 0.25, 2)).lower(q, k, v, rows,
                               np.array([8, 8], "int32")).as_text()
    assert "custom_call" not in text
    assert kernels.fallback_counter().value == before


def test_a_mamba_layer_is_one_loop_of_the_chunk_program_and_none_of_the_step():
    """What ``ssm_scan_device_share`` rests on: the reader matches events by
    NAME (no scope reaches it), so every ``while`` of either program has to
    be a Mamba layer's chunked scan. ``mixer_chunk`` lowers to exactly one,
    ``mixer_step`` and the chunk attention's composite to none; a loop added
    to either program turns this red before it is counted as scan time."""
    import jax

    from paddle_tpu.kernels import mamba

    H, P, N, T, S = 4, 8, 16, 8, 2
    width = 2 * H * P + 2 * N + H
    conv_dim = H * P + 2 * N
    params = {k: jnp.zeros(s, "float32") for k, s in (
        ("conv_w", (4, conv_dim)), ("conv_b", (conv_dim,)), ("dt_bias", (H,)),
        ("a_log", (H,)), ("d", (H,)), ("norm_w", (H * P,)))}
    states = (np.zeros((S, 3, conv_dim), "float32"),
              np.zeros((S, H, P, N), "float32"))
    how = dict(heads=H, head_dim=P, groups=1, n_state=N, eps=1e-5,
               out_dtype="float32")
    loops = lambda f, *a: jax.jit(f).lower(*a).as_text().count(  # noqa: E731
        "stablehlo.while")
    assert loops(
        lambda z, m: mamba.mixer_chunk(z, params, *states, 0, m, True,
                                       chunk=4, **how),
        np.zeros((T, width), "float32"), np.ones(T, bool)) == 1
    assert loops(
        lambda z, m: mamba.mixer_step(z, params, *states, m, **how),
        np.zeros((S, width), "float32"), np.ones(S, bool)) == 0
    rng = np.random.RandomState(7)
    assert loops(
        lambda *a: attention.chunk_attention_by_span(*a, 0.25, 2),
        *kernels._chunk_case(rng, 8, 48, 4, 2, 2, 16),
        np.array([8, 8], "int32")) == 0


# -- (d) what a chunk launch counts -------------------------------------------

def test_a_chunk_counts_its_tokens_its_context_and_its_pairs(exact):
    stats = exact["entry"].stats()
    spans = [tuple(int(x) for x in f[DecodeModel.CHU_SPAN])
             for f in exact["chunks"]]
    assert stats["chunk_tokens"] == sum(real for _s, real in spans)
    assert stats["chunk_context_rows"] == sum(start for start, _r in spans)
    assert stats["chunk_attended_rows"] == sum(
        sum(start + c + 1 for c in range(real)) for start, real in spans)
    assert stats["chunk_context_rows"] > 0
