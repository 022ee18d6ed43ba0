"""The ``mistral4`` latent-attention decoder (``serving/decode/hybrid.py
build_latent_moe_model``: ONE arena of ``[c | k^R]`` rows a layer, the step
absorbed, a chunk expanded, YaRN rotation with a
position-dependent query scale, softmax-routed experts beside a shared one)
served through ``GenerationEngine``, at a tiny size on the CPU, against its
plain reference (``benchmark/references/plain_mistral4.py``: float32, whole
sequence, EXPANDED attention, no cache); and its parts by hand: the
frequency table, the query scale, the chunk's expanded attention against
the step's absorbed one, the one-arena kernel, the grouped expert product,
the eight shares.

Logits are compared, not tokens (with random weights the largest logit
changes on rounding): a sampled request makes the engine fetch every step's
row, and ``_choose_token`` is where each delivered row passes.
"""

import math
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.references import plain_mistral4 as reference  # noqa: E402
from paddle_tpu.core.registry import OpRegistry  # noqa: E402
from paddle_tpu.kernels import attention, moe  # noqa: E402
from paddle_tpu.serving import (  # noqa: E402
    GenerationEngine, build_latent_moe_model)
from paddle_tpu.serving.decode import SamplingParams  # noqa: E402
from paddle_tpu.serving.decode.hybrid import yarn_frequencies  # noqa: E402

#: the published group with the original context cut to 16 positions, so
#: that prompts of 17-27 tokens stand past it: the stretched frequencies
#: and the query scale are both at work
ROPE = {"beta_fast": 32, "beta_slow": 1, "factor": 128,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 16, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn"}
#: the published keys at a tiny size; a head is 8 + 8 wide, values 16
CONFIG = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
    rope_parameters=ROPE, n_routed_experts=4, num_experts_per_tok=2,
    moe_intermediate_size=32, routed_scaling_factor=1.0,
    norm_topk_prob=True, rms_norm_eps=1e-6)
ROUTER = 8
GEOMETRY = dict(slots=4, max_len=48, block_size=4, chunk_tokens=8,
                num_blocks=30)
#: under, over and at the chunk; 21 and 27 span three and four chunks
PROMPT_LENS = (5, 21, 8, 27, 3, 17)
ANSWERS = (6, 9, 4, 10, 12, 5)
#: float32 build against the float32 reference: summation order alone
#: (the absorbed step regroups the same products); measured 2.7e-5
EXACT_BAND = 1e-4
#: bfloat16 build: parameters and each sub-layer's input rounded to 8 bits
#: of mantissa, and the step's absorbed query and context rounded once more
#: than the reference's expanded form; measured over the 46 delivered rows
#: 0.06 of a row's standard deviation in the median and 0.21 at the 90th
#: percentile (0.20 while the chunks of this size still ran absorbed: the
#: band, once 0.2, stood on that reading's edge), the band twice that; rows
#: where a top-2-of-8 router's choice flipped read up to 0.84 (a routed
#: model's worst row is the router's, not the arithmetic's)
BF16_BAND = 4e-1


def _model(dtype="float32", name="mistral4", expert_rank=1, **over):
    # a wider draw than the published 0.02: at a hidden size of 64 the
    # layers would have nothing to say beside the embedding
    m = build_latent_moe_model(
        **CONFIG, router_experts=ROUTER, **dict(GEOMETRY, **over),
        dtype=dtype, name=name, initializer_range=0.3,
        expert_rank=expert_rank)
    m.startup_program.random_seed = 7
    return m


def _engine(model):
    engine = GenerationEngine(prefix_cache_size=0, host_tier_mb=0)
    entry = engine.register_model(model)
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
    rows, choose = {}, entry._choose_token

    def recording(st, row, device_masked):
        rows.setdefault(st.request.id, []).append(np.array(row, np.float32))
        return choose(st, row, device_masked)

    entry._choose_token = recording
    return rows


def _row_errors(entry, prompts, answers, rows, offset, **read_as):
    """Every delivered row's max |difference| from the reference's full
    forward over the served tokens, in standard deviations of the
    reference's row; ``read_as`` misreads the description (a control)."""
    weights, out = _weights(entry), []
    for i, (prompt, served) in enumerate(zip(prompts, answers)):
        tokens = prompt + [int(t) for t in served[:-1]]
        want = reference.logits(
            weights, CONFIG, tokens,
            range(len(prompt) - 1, len(prompt) - 1 + len(served)),
            pad_to=GEOMETRY["max_len"], expert_offset=offset, **read_as)
        got = np.stack(rows[1 + i])
        out.extend(np.abs(got - want).max(1) / want.std(1))
    return np.asarray(out)


def _serve_sampled(engine, prompts):
    responses = [
        engine.submit(p, max_new_tokens=n,
                      sampling=SamplingParams(temperature=1.0, seed=i))
        for i, (p, n) in enumerate(zip(prompts, ANSWERS))]
    return [r.result(timeout=300)["tokens"] for r in responses]


@pytest.fixture(scope="module")
def exact():
    """The float32 build holding experts 4..7 of 8, served once."""
    engine, entry = _engine(_model())
    rows = _record_rows(entry)
    prompts = _prompts()
    sampled = _serve_sampled(engine, prompts)
    yield {"engine": engine, "entry": entry, "rows": rows,
           "prompts": prompts, "sampled": sampled}
    engine.shutdown()


# -- prefill by chunks, then decode through the latent cache -----------------

def test_float32_build_gives_the_references_logits(exact):
    errors = _row_errors(exact["entry"], exact["prompts"], exact["sampled"],
                         exact["rows"], offset=4)
    assert errors.max() < EXACT_BAND, errors.max()
    stats = exact["entry"].stats()
    assert stats["chunk_runs"] >= sum(-(-n // 8) for n in PROMPT_LENS)
    assert stats["prefills"] == 0
    # what the chunks' routed layers multiplied reached the host with the
    # steps: every real prompt token in every layer's router, an eighth of
    # their choices and more on the half of the experts held here
    layers, k = CONFIG["num_hidden_layers"], CONFIG["num_experts_per_tok"]
    assert 0 < stats["moe_grouped_pairs"] <= sum(PROMPT_LENS) * layers * k
    # a chunk of 8 tokens over 4 held experts is under the grouped
    # product's rule: every real token for every held expert
    assert stats["moe_grouped_rows"] == sum(PROMPT_LENS) * layers * 4
    assert 0 < stats["moe_grouped_experts"] <= stats["chunk_runs"] * layers * 4


def test_float32_build_served_by_the_kernels_gives_the_references_logits():
    """The same prompts with the kernels serving (interpreted): every chunk
    through ``latent_chunk_attention`` (a lowered call a layer of the ONE
    chunk program, no fallback among them), every step through the paged
    kernel handed one arena; the rows stay inside the exact band."""
    from paddle_tpu import kernels
    from paddle_tpu.kernels import registry

    served, fell = registry.latent_chunk_counter(), kernels.fallback_counter()
    before = served.value, fell.value
    with kernels.scoped_mode("interpret"):
        engine, entry = _engine(_model(name="mistral4_kernels"))
        try:
            rows = _record_rows(entry)
            prompts = _prompts()
            sampled = _serve_sampled(engine, prompts)
        finally:
            engine.shutdown()
    assert served.value - before[0] == CONFIG["num_hidden_layers"]
    assert fell.value == before[1]
    errors = _row_errors(entry, prompts, sampled, rows, offset=4)
    assert errors.max() < EXACT_BAND, errors.max()
    assert entry.stats()["prefills"] == 0


def test_bfloat16_build_is_inside_its_band_and_outside_the_exact_one():
    engine, entry = _engine(_model("bfloat16", name="mistral4_bf16"))
    try:
        rows = _record_rows(entry)
        prompts = _prompts()
        sampled = _serve_sampled(engine, prompts)
    finally:
        engine.shutdown()
    errors = _row_errors(entry, prompts, sampled, rows, offset=4)
    p90 = float(np.quantile(errors, 0.9))
    assert EXACT_BAND * 10 < p90 < BF16_BAND, (p90, errors.max())


@pytest.mark.parametrize("read_as", [
    {"rope_lanes": False}, {"llama_4_scaling_beta": 0.0},
    {"mscale_all_dim": 0.0}, {"factor": 1.0}, {"norm_topk_prob": False},
    {"routed_scaling_factor": 2.0}],
    ids=lambda r: "_".join(f"{k}_{v}" for k, v in r.items()))
def test_each_part_of_the_description_matters(exact, read_as):
    """The controls: the reference with ONE part misread (the rotary lanes
    left unrotated, step 4's scale left out, ``m^2`` left out of the
    softmax scale, YaRN's stretch left out, the router's renormalisation
    or scale) leaves the exact band far behind on the rows the sound
    reference holds inside it."""
    errors = _row_errors(exact["entry"], exact["prompts"], exact["sampled"],
                         exact["rows"], offset=4, **read_as)
    assert errors.max() > 100 * EXACT_BAND, errors.max()


def test_a_prompt_served_again_gives_the_same_logits():
    """This model has no per-slot state, so the pool shares a served
    prompt's full blocks with the next request of the same prompt, which
    prefills only what is left: for a prompt of whole blocks (8 tokens: two
    blocks of 4) its LAST token alone, whose row is already in place and
    whose write row is therefore the sentinel. It is a token all the same:
    the router routes a chunk's tokens by the chunk's span (left to the
    write row it was routed nowhere and every logit after it was wrong:
    found on the chip, where a fault's second serving of a 32,768-token
    prompt read 0.98 standard deviations behind)."""
    engine, entry = _engine(_model(name="again"))
    try:
        rows = _record_rows(entry)
        prompts = _prompts()
        first = _serve_sampled(engine, prompts)
        hits = entry.kv.pool.stats()["radix_hits"]
        again = _serve_sampled(engine, prompts)
        assert entry.kv.pool.stats()["radix_hits"] > hits
    finally:
        engine.shutdown()
    assert [list(map(int, a)) for a in again] == [
        list(map(int, a)) for a in first]
    for i in range(len(prompts)):
        rows[1 + i] = rows.pop(1 + len(prompts) + i)
    errors = _row_errors(entry, prompts, again, rows, offset=4)
    assert errors.max() < EXACT_BAND, errors.max()


@pytest.mark.parametrize("fault", ["kv", "kv_all", "chunk_kv"])
def test_a_stale_latent_arena_leaves_the_exact_band(fault):
    """The mechanism's own faults (``tools/check_hybrid_logits.py``): the
    FIRST layer's latent arena put back to what it was after every decode
    step (``kv``: a step's row never lands, so the next steps attend
    without it), EVERY layer's (``kv_all``), or the first layer's a chunk
    stale at the boundary before each prompt's last chunk (``chunk_kv``).
    On logits, in float32, at prompts of 3-27 tokens (where a missing row
    is a large share of what a query sees) each leaves the band the sound
    build holds by four orders: measured 4.7, 4.9 and 3.8 of a row's
    standard deviation at worst against the sound 2.7e-5 (the step faults
    1.1 in the median; ``chunk_kv`` moves only the three prompts longer
    than a chunk)."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import check_hybrid_logits as tool

    engine, entry = _engine(_model(name="stale_" + fault))
    try:
        rows = _record_rows(entry)
        prompts = _prompts()
        plant = tool._chunk_fault if fault.startswith("chunk") else tool._stale
        undo = plant(entry, fault)
        sampled = _serve_sampled(engine, prompts)
        undo()
    finally:
        engine.shutdown()
    errors = _row_errors(entry, prompts, sampled, rows, offset=4)
    assert errors.max() > 1e4 * EXACT_BAND, errors.max()


def test_one_arena_a_layer_and_its_bytes():
    """A layer's cache is ONE arena: ``rows x 384 lanes x 2 bytes`` at the
    published widths (256 of latent + 64 of rotary key, padded to whole
    128-lane tiles: the chip tiles an array's minor dimension by 128
    whatever its declared width; the counts take the 640 required)."""
    m = build_latent_moe_model(
        96, 64, 2, num_attention_heads=4, q_lora_rank=32, kv_lora_rank=256,
        qk_nope_head_dim=64, qk_rope_head_dim=64, v_head_dim=128,
        rope_parameters=ROPE, n_routed_experts=2, router_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=32, slots=2,
        max_len=32, block_size=16, num_blocks=3, chunk_tokens=16)
    assert m.state_names == [("mistral4_v1.lcache0",),
                             ("mistral4_v1.lcache1",)]
    assert m.arenas == 2 and m.kv_width == 384
    assert m.arena_bytes() == 48 * 384 * 2 * 2
    assert m.chunks_only and not m.recurrent
    two = _model(name="pairs")
    assert two.arena_bytes() == two.rows * 128 * 4 * 3


def test_the_pool_reserves_and_resets_one_arena_a_layer(exact):
    entry = exact["entry"]
    assert entry.kv.reserves
    # every arena of every layer is read whole by the one reader there is
    out, nbytes = entry.kv._read(lambda a: a[:2])
    assert [len(names) for names in out] == [1, 1, 1]
    assert nbytes == entry.model.arena_bytes()


# -- the rotation and the query's scale, by hand -----------------------------

def test_yarn_frequency_table_by_hand():
    """At the published widths (64 rope lanes, theta 10,000, factor 128,
    beta 32 / 1 over 8,192 positions) the correction range is ``floor(
    12.88) = 12`` to ``ceil(24.92) = 25``: pairs under 12 keep the base's
    frequency, pairs from 25 on are stretched 128-fold, and pair 18 stands
    6/13 of the way."""
    f = yarn_frequencies(64, 10000.0, 128.0, 32.0, 1.0, 8192)
    base = [10000.0 ** (-2.0 * j / 64) for j in range(32)]
    r = lambda b: 64 * math.log(8192 / (2 * math.pi * b)) / (  # noqa: E731
        2 * math.log(10000.0))
    assert (math.floor(r(32)), math.ceil(r(1))) == (12, 25)
    assert len(f) == 32
    np.testing.assert_allclose(f[:13], base[:13], rtol=1e-12)
    np.testing.assert_allclose(f[25:], [b / 128 for b in base[25:]],
                               rtol=1e-12)
    g = 6 / 13
    assert f[18] == pytest.approx((1 - g) * base[18] + g * base[18] / 128)
    np.testing.assert_allclose(
        f, reference.frequencies(64, dict(ROPE, **{
            "original_max_position_embeddings": 8192})), rtol=1e-12)


def test_rotary_table_and_interleaved_pairs_by_hand():
    rotary = OpRegistry.get("rotary_embedding").lower
    x = np.arange(2 * 3 * 8, dtype="float32").reshape(2, 1, 3, 8) / 7.0
    pos = np.array([[5], [8192]], "int64")
    freqs = [1.0, 0.5, 0.25, 0.125]
    got = np.asarray(rotary(
        {"X": [jnp.asarray(x)], "Positions": [jnp.asarray(pos)]},
        {"theta": 10000.0, "freqs": freqs, "interleaved": True})["Out"][0])
    want = np.empty_like(x)
    for t in range(2):
        for j, f in enumerate(freqs):
            a, b = x[t, 0, :, 2 * j], x[t, 0, :, 2 * j + 1]
            c, s = (np.cos(np.float32(pos[t, 0] * f)),
                    np.sin(np.float32(pos[t, 0] * f)))
            want[t, 0, :, 2 * j] = a * c - b * s
            want[t, 0, :, 2 * j + 1] = b * c + a * s
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the table alone, rotate-half pairing: lane i with lane i + 4
    half = np.asarray(rotary(
        {"X": [jnp.asarray(x)], "Positions": [jnp.asarray(pos)]},
        {"theta": 10000.0, "freqs": freqs})["Out"][0])
    c, s = np.cos(np.float32(5 * 0.5)), np.sin(np.float32(5 * 0.5))
    np.testing.assert_allclose(
        half[0, 0, :, 1], x[0, 0, :, 1] * c - x[0, 0, :, 5] * s, rtol=1e-5)
    # callers that name neither keep their bytes: theta's own table
    plain = {"X": [jnp.asarray(x)], "Positions": [jnp.asarray(pos)]}
    by_theta = np.asarray(rotary(plain, {"theta": 100.0})["Out"][0])
    by_table = np.asarray(rotary(plain, {
        "theta": 1.0, "freqs": [float(np.exp(np.float32(i) * (
            -np.log(np.float32(100.0)) / 4))) for i in range(4)]})["Out"][0])
    np.testing.assert_allclose(by_theta, by_table, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("position,scale", [
    (0, 1.0), (8191, 1.0), (8192, 1.0 + 0.1 * math.log(2.0)),
    (16384, 1.0 + 0.1 * math.log(3.0))])
def test_query_scale_by_hand(position, scale):
    op = OpRegistry.get("position_log_scale").lower
    x = np.arange(1 * 1 * 2 * 4, dtype="float32").reshape(1, 1, 2, 4) + 1.0
    got = np.asarray(op(
        {"X": [jnp.asarray(x)],
         "Positions": [jnp.asarray([[position]], jnp.int32)]},
        {"beta": 0.1, "period": 8192})["Out"][0])
    np.testing.assert_allclose(got, x * np.float32(scale), rtol=1e-6)


def test_split_by_sections_traces():
    """``split`` with ``sections`` under a trace (the layer's shape
    inference, a jitted program): its offsets are the program's own
    integers."""
    op = OpRegistry.get("split").lower
    x = jnp.arange(2 * 7, dtype=jnp.float32).reshape(2, 7)
    a, b, c = jax.jit(lambda x: op(
        {"X": [x]}, {"num": 0, "sections": [3, 2, 2], "axis": -1})["Out"])(x)
    assert (a.shape, b.shape, c.shape) == ((2, 3), (2, 2), (2, 2))
    np.testing.assert_array_equal(np.concatenate([a, b, c], -1), x)


# -- a chunk attends expanded, a step absorbed: the same numbers --------------

def _latent_case(rng, heads, nope, rope, value, latent, L, bs, queries):
    pool = L // bs + 3
    ids = rng.permutation(pool)[:L // bs]
    rows = jnp.asarray((ids[:, None] * bs + np.arange(bs)).reshape(-1))
    arena = np.zeros((pool * bs, 128), "float32")
    arena[:, :latent + rope] = rng.randn(pool * bs, latent + rope)
    w_uk = 0.3 * rng.randn(heads, nope, latent).astype("float32")
    w_uv = 0.3 * rng.randn(heads, latent, value).astype("float32")
    q = rng.randn(queries, heads * (nope + rope)).astype("float32")
    return q, w_uk, w_uv, jnp.asarray(arena), rows


def test_the_chunk_op_is_the_expanded_form_dense_kernel_or_loops():
    """``chunk_latent_attention`` has ONE form: its definition is the dense
    expanded composite; where kernels serve the program the same form
    through the ``latent_chunk_attention`` kernel (counted a lowered call),
    and through its fallback, XLA's loops by tiles of queries and of rows
    (counted a fallback), for an op that names no block size."""
    from paddle_tpu import kernels
    from paddle_tpu.kernels import registry

    rng = np.random.RandomState(6)
    q, w_uk, w_uv, arena, rows = _latent_case(rng, 4, 8, 8, 16, 32, 320, 16,
                                              32)
    op = OpRegistry.get("chunk_latent_attention")
    served, fell = registry.latent_chunk_counter(), kernels.fallback_counter()
    for start, real in ((0, 32), (224, 31)):
        ins = {"Q": [q], "WUK": [w_uk], "WUV": [w_uv], "Arena": [arena],
               "Rows": [rows], "Span": [np.array([start, real], "int32")]}
        attrs = {"sm_scale": 0.2, "rope": 8, "block_size": 16}
        want = np.asarray(attention.latent_chunk_expanded(
            q, w_uk, w_uv, arena, rows, ins["Span"][0], 0.2, 8))
        np.testing.assert_array_equal(op.lower(ins, attrs)["Out"][0], want)
        with kernels.scoped_mode("off"):
            off = op.lowering(True)(ins, attrs)["Out"][0]
        np.testing.assert_array_equal(off, want)
        for attrs, counted in ((attrs, served),
                               ({"sm_scale": 0.2, "rope": 8}, fell)):
            before = served.value, fell.value
            with kernels.scoped_mode("interpret"):
                got = np.asarray(op.lowering(True)(ins, attrs)["Out"][0])
            assert (served.value - before[0], fell.value - before[1]) == (
                (1, 0) if counted is served else (0, 1))
            np.testing.assert_allclose(got[:real], want[:real], rtol=2e-5,
                                       atol=2e-5)
            assert not got[real:].any()
    assert attention._EXPAND_QUERY_TILE == attention._EXPAND_TILE_ROWS == 512


def test_expanded_by_tiles_of_queries_is_the_dense_expanded():
    """A chunk of four query tiles, each in a loop over the rows ITS last
    query sees, gives the dense composite's numbers."""
    rng = np.random.RandomState(4)
    heads, nope, rope, value, latent, L, bs = 2, 4, 4, 8, 8, 96, 16
    rows = jnp.asarray(rng.permutation(L))
    arena = np.zeros((L, 128), "float32")
    arena[:, :latent + rope] = rng.randn(L, latent + rope)
    w_uk = 0.3 * rng.randn(heads, nope, latent).astype("float32")
    w_uv = 0.3 * rng.randn(heads, latent, value).astype("float32")
    q = rng.randn(32, heads * (nope + rope)).astype("float32")
    old = attention._EXPAND_QUERY_TILE
    attention._EXPAND_QUERY_TILE = 8
    try:
        for start, real in ((0, 32), (40, 19)):
            span = np.array([start, real], "int32")
            tiled = np.asarray(attention.latent_chunk_expanded(
                q, w_uk, w_uv, arena, rows, span, 0.3, rope, tile_rows=16))
            dense = np.asarray(attention.latent_chunk_expanded(
                q, w_uk, w_uv, arena, rows, span, 0.3, rope))
            np.testing.assert_allclose(tiled[:real], dense[:real],
                                       rtol=2e-5, atol=2e-5)
            assert not tiled[real:].any()
    finally:
        attention._EXPAND_QUERY_TILE = old


def test_every_loop_of_the_chunk_program_is_its_expanded_attention(
        monkeypatch):
    """What the latent attention's readings rest on: their readers match
    device events by NAME. Where the kernel serves (the programs lowered for
    the chip, at widths Mosaic tiles) the step program holds no ``while``
    and the chunk program ONE a layer, the loop over its groups of heads,
    whose body holds the one call named ``latent_chunk_attention``, counted
    in ``latent_chunk_kernel_lowerings_total`` and no fallback:
    ``latent_chunk_kernel_roofline`` and ``..._device_share`` find the
    calls by that name, ``latent_chunk_attention_roofline`` and
    ``latent_attention_device_share`` (``^%?while``) the loops around them.
    With the composites everywhere (``off``) the step program holds no loop
    and the chunk program one a layer, the dense definition; the fallback's
    loops are one a tile of queries; what the grouped product does outside
    its kernel (the pairs' sort, gather and scatter) holds none. A loop
    added to either program turns this red before it is counted as
    attention."""
    import paddle_tpu as fluid
    from paddle_tpu import kernels
    from paddle_tpu.core import lowering
    from paddle_tpu.kernels import registry
    from paddle_tpu.utils import hlo

    loops = lambda text: text.count("stablehlo.while")  # noqa: E731

    def lowered(m, scope, platform=None):
        """The step and the chunk program's StableHLO (for ``platform``: no
        compiler of the chip's is needed to LOWER for it)."""
        for program, sig, fetch in (
                (m.decode_program, m.decode_feed_sig(), m.counts_fetch),
                (m.chunk_program, m.chunk_feed_sig(), m.chunk_logits_fetch)):
            entry, _src = lowering.lower_step(
                program, scope, tuple((n, shape, str(np.dtype(dtype)))
                                      for n, shape, dtype in sorted(sig)),
                [fetch], donate=True, use_cache=False, persist=False,
                label="hlo")
            shapes = lambda names: tuple(  # noqa: E731
                hlo._sds_of(scope.find_var(n)) for n in names)
            yield entry.fn.trace(
                tuple(jax.ShapeDtypeStruct(shape, np.dtype(dtype))
                      for _n, shape, dtype in sorted(sig)),
                shapes(entry.donated), shapes(entry.readonly),
                hlo._sds_of(jax.random.PRNGKey(0))).lower(
                    lowering_platforms=platform and (platform,)).as_text()

    m = _model(name="loops")
    scope = fluid.Scope()
    with fluid.scope_guard(scope), kernels.scoped_mode("off"):
        fluid.Executor(fluid.CPUPlace()).run(m.startup_program)
        # (the dense definition is the fallback's loop at one trip: one a
        # layer, and the op alone accounts for it, below)
        step, chunk = lowered(m, scope)
        assert (loops(step), loops(chunk)) == (
            0, CONFIG["num_hidden_layers"])
    # widths Mosaic tiles: 16 heads (two groups of 8) of 64 + 64 | 128
    # over a latent of 128, blocks of 16 rows, a chunk of 128 (a sub-tile's
    # queries are lanes)
    wide = dict(CONFIG, hidden_size=128, num_attention_heads=16,
                kv_lora_rank=128, qk_nope_head_dim=64, qk_rope_head_dim=64,
                v_head_dim=128, moe_intermediate_size=128)
    m = build_latent_moe_model(
        **wide, router_experts=ROUTER, slots=4, max_len=256, block_size=16,
        chunk_tokens=128, num_blocks=70, dtype="bfloat16", name="served",
        initializer_range=0.3)
    scope = fluid.Scope()
    served, fell = registry.latent_chunk_counter(), kernels.fallback_counter()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(m.startup_program)
        monkeypatch.setattr(registry, "_on_tpu", lambda: True)
        programs = lowered(m, scope, "tpu")
        step = next(programs)
        before = served.value, fell.value
        chunk = next(programs)
        monkeypatch.undo()
    layers = wide["num_hidden_layers"]
    assert (loops(step), loops(chunk)) == (0, layers)
    names = lambda text: re.findall(  # noqa: E731
        r'kernel_name = "(\w+)"', text)
    # (beside the step's attention its routed experts' kernel: a step of
    # 4 tokens is padded to a whole sublane tile inside `moe_experts`)
    assert [n for n in names(step) if n != "moe_experts"] == [
        attention.LATENT_STEP_KERNEL] * layers
    assert names(chunk) == [attention.LATENT_CHUNK_KERNEL] * layers
    assert attention.LATENT_CHUNK_KERNEL == "latent_chunk_attention"
    assert (served.value - before[0], fell.value - before[1]) == (layers, 0)
    rng = np.random.RandomState(8)
    q, w_uk, w_uv, arena, rows = _latent_case(rng, 4, 8, 8, 16, 32, 320, 16,
                                              32)
    monkeypatch.setattr(attention, "_EXPAND_QUERY_TILE", 8)
    for tile_rows, tiles in ((None, 1), (16, 32 // 8)):
        assert loops(jax.jit(lambda q, span: attention.latent_chunk_expanded(
            q, w_uk, w_uv, arena, rows, span, 0.2, 8,
            tile_rows=tile_rows)).lower(
                q, np.array([224, 31], "int32")).as_text()) == tiles
    T, k, E = 64, 3, 4
    idx = jnp.asarray(rng.randint(0, 16, (T, k)))
    w = jnp.asarray(rng.rand(T, k).astype("float32"))
    mask = jnp.asarray(rng.rand(T) > 0.2)
    n = moe.grouped_rows(T, k, E, 8)

    def outside_the_kernel(idx, w, mask):
        return (moe.group_pairs(idx, w, mask, 4, E, n, 8),
                moe.grouped_counts(idx, mask, 4, E, 8))

    assert loops(jax.jit(outside_the_kernel).lower(
        idx, w, mask).as_text()) == 0


@pytest.mark.parametrize("family", [
    "nemotron_h", "lfm2", "ouro", "sdar", "granite_hybrid"])
def test_no_other_familys_program_holds_a_latent_op(family):
    """Who else runs the chunk's kernel: nobody. The latent ops (the one
    whose written form PR 57 changed: ``chunk_latent_attention`` names its
    ``block_size``; and the step's) are written by ``_hybrid_model(latent=)``
    alone, so the other five serving families' programs, at their tests'
    sizes, hold neither and are what they were (their ``to_bytes`` equal
    the parent commit's: PERF.md section 6, PR 57, compared by hand)."""
    import importlib

    m = importlib.import_module(f"test_{family}_serving")._model()
    for program in (m.decode_program, m.chunk_program, m.startup_program):
        types = {op.type for block in program.blocks for op in block.ops}
        assert not types & {"chunk_latent_attention",
                            "paged_latent_attention"}
    mine = _model(name="census")
    ops = [op for op in mine.chunk_program.global_block().ops
           if op.type == "chunk_latent_attention"]
    assert len(ops) == CONFIG["num_hidden_layers"]
    assert all(op.attrs["block_size"] == GEOMETRY["block_size"]
               for op in ops)


@pytest.mark.parametrize("start,real", [(0, 32), (0, 7), (224, 31),
                                        (288, 32)])
def test_absorbed_is_expanded(start, real):
    """The same numbers both ways: a chunk's queries EXPANDED (the chunk
    op's form: the dense definition, and the ``latent_chunk_attention``
    kernel that serves, interpreted) and the same queries as decode slots
    of one sequence, ABSORBED (the step op's form), through the composite
    and through the kernel handed one arena (interpreted)."""
    from paddle_tpu import kernels

    rng = np.random.RandomState(3)
    L, bs, rope = 320, 16, 8
    q, w_uk, w_uv, arena, rows = _latent_case(rng, 4, 8, rope, 16, 32, L, bs,
                                              32)
    span = np.array([start, real], "int32")
    want = np.asarray(attention.latent_chunk_expanded(
        q, w_uk, w_uv, arena, rows, span, 0.2, rope))[:real]
    assert np.abs(want).max() > 0.1
    # query c of the chunk stands at position start + c and sees 0..there
    sees = np.arange(L)[None, :] <= start + np.arange(real)[:, None]
    step = OpRegistry.get("paged_latent_attention")
    ins = {"Q": [q[:real]], "WUK": [w_uk], "WUV": [w_uv], "Arena": [arena],
           "Rows": [jnp.tile(rows, real)],
           "Bias": [jnp.asarray(np.where(sees, 0.0, -1e9)[:, None, :],
                                jnp.float32)]}
    attrs = {"sm_scale": 0.2, "rope": rope, "seqs": real, "length": L,
             "block_size": bs}
    before = kernels.fallback_counter().value
    with kernels.scoped_mode("interpret"):
        kernel = step.lowering(True)(ins, attrs)["Out"][0]
    assert kernels.fallback_counter().value == before
    chunk = OpRegistry.get("chunk_latent_attention")
    with kernels.scoped_mode("interpret"):
        served = chunk.lowering(True)(
            {"Q": [q], "WUK": [w_uk], "WUV": [w_uv], "Arena": [arena],
             "Rows": [rows], "Span": [span]},
            {"sm_scale": 0.2, "rope": rope, "block_size": bs})["Out"][0]
    assert kernels.fallback_counter().value == before
    np.testing.assert_allclose(served[:real], want, rtol=2e-5, atol=2e-5)
    for got in (step.lower(ins, attrs)["Out"][0], kernel):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(got, served[:real], rtol=2e-5, atol=2e-5)


# -- the grouped expert product ----------------------------------------------

def test_the_rule_of_the_grouped_product():
    """Grouped where the rows it multiplies under a balanced routing are a
    QUARTER of the dense product's or fewer, which is where the chip read
    it the faster one (2.07 ms against 2.30 at 512 tokens; at 3.4 times
    fewer rows it read slower): this model's chunks (512 tokens and more, 4
    of 128, 16 held) take it; 384 tokens and fewer (a third of the dense
    rows and more), the accepted routed cells' chunks of 128 tokens (16
    held experts would each get one row tile of 128: as many rows as the
    dense product) and every tiny size do not."""
    assert moe.takes_grouped(512, 4, 16, 128)
    assert moe.takes_grouped(1024, 4, 16, 128)
    assert moe.takes_grouped(2048, 4, 16, 128)
    assert not moe.takes_grouped(384, 4, 16, 128)
    assert not moe.takes_grouped(256, 4, 16, 128)
    assert not moe.takes_grouped(128, 6, 16, 128)      # nemotron's chunk
    assert not moe.takes_grouped(128, 4, 8, 64)        # lfm2's
    assert not moe.takes_grouped(128, 8, 16, 128)      # sdar's
    assert not moe.takes_grouped(8, 2, 4, 8)
    assert moe.grouped_rows(512, 4, 16) == (16 + 16) * 128


#: a routing's selection bias over the router's 16 experts (held: 0-3 or
#: 4-7), or the routing written out
_SELECT = {
    "balanced": np.zeros(16),
    "all_on_one": np.where(np.arange(16) == 5, 100.0, 0.0),
    "none_held": np.where(np.arange(16) < 8, -100.0, 0.0),
    # experts 1 and 6 are chosen by nobody: a held expert with no pair
    # between experts that have some
    "an_expert_with_none": np.where(np.isin(np.arange(16), (1, 6)),
                                    -100.0, 0.0),
    # (as balanced: what differs is the tokens, and the mask)
    "ragged_tokens": np.zeros(16),
    "masked_but_three": np.zeros(16),
}


def _routing(select, rng, x, gate, mask, k):
    """``(idx, w, mask)``: the router's under a selection bias, or written
    out: a token with two of its four choices among experts 0-3 and two
    among 4-7 (a rank that holds either four has two pairs a token, two
    elsewhere); every token masked but three."""
    T = x.shape[0]
    if select == "two_held_two_elsewhere":
        idx = np.stack([rng.randint(0, 2, T), rng.randint(2, 4, T),
                        rng.randint(4, 6, T), rng.randint(6, 8, T)], 1)
        w = rng.rand(T, 4).astype("float32") + 0.1
        return jnp.asarray(idx), jnp.asarray(w / w.sum(1, keepdims=True)), mask
    if select == "masked_but_three":
        mask = jnp.asarray(np.isin(np.arange(T), (0, T // 2, T - 1)))
    idx, w = moe.route(x, gate, jnp.asarray(_SELECT[select].astype(
        "float32")), k, 1.0, True, score="softmax")
    return idx, w, mask


@pytest.mark.parametrize("select", [
    "balanced", "all_on_one", "none_held", "an_expert_with_none",
    "two_held_two_elsewhere", "masked_but_three", "ragged_tokens"])
@pytest.mark.parametrize("gated", [True, False])
def test_grouped_product_is_the_dense_one_under_imbalance(select, gated):
    """The grouped product (interpreted) against the dense composite over
    the same routing, whatever the imbalance: the kernel picks its tokens'
    rows and sums a token's pairs itself, so every case goes through its
    pick and its adds. ``ragged_tokens``: 50 tokens, no multiple of either
    row tile nor of a lane tile (padded inside)."""
    rng = np.random.RandomState(5)
    T, H, F, E, EA = 50 if select == "ragged_tokens" else 64, 256, 40, 4, 16
    k = 4 if select == "two_held_two_elsewhere" else 3
    x = jnp.asarray(rng.randn(T, H).astype("float32"))
    gate = jnp.asarray(rng.randn(EA, H).astype("float32"))
    w_gate, w_up, w_down = (
        jnp.asarray(0.1 * rng.randn(E, F, H).astype("float32"))
        for _ in range(3))
    w_gate = w_gate if gated else None
    mask = jnp.asarray(rng.rand(T) > 0.2)
    idx, w, mask = _routing(select, rng, x, gate, mask, k)
    for offset in (0, 4):
        c = moe.held_weights(idx, w, mask, offset, E)
        want = moe.experts_composite(x, c, w_up, w_down, w_gate)
        per_expert = np.asarray((c != 0).sum(0))
        for tile in (8, 16):
            got = jax.jit(lambda *a: moe.moe_grouped(
                *a, offset, w_up, w_down, w_gate, interpret=True,
                row_tile=tile))(x, idx, w, mask)
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
            # the layout it multiplied: every pair a row of its expert's
            # tiles, each expert's rows from a multiple of the tile on
            rows = moe.grouped_rows(T, k, E, tile)
            dest, token, weight, tile_expert, tile_pairs, used = (
                np.asarray(a) for a in moe.group_pairs(
                    idx, w, mask, offset, E, rows, tile))
            at = dest[dest < rows]
            assert len(set(at)) == len(at) == per_expert.sum()
            assert used == (-(-per_expert // tile)).sum()
            # a tile's pairs are its first rows: its expert's, less the
            # whole tiles before it
            assert tile_pairs.sum() == per_expert.sum()
            assert (tile_pairs[used:] == 0).all()
            for i in range(used):
                assert (weight[i * tile:i * tile + tile_pairs[i]] != 0).all()
                assert not weight[i * tile + tile_pairs[i]:(i + 1) * tile].any()
            chosen = np.asarray(idx) - offset
            for t_, j in zip(*np.nonzero(dest < rows)):
                assert token[dest[t_, j]] == t_
                assert tile_expert[dest[t_, j] // tile] == chosen[t_, j]
            assert weight[np.setdiff1d(np.arange(rows), at)].sum() == 0
            pairs, padded, touched = np.asarray(
                moe.grouped_counts(idx, mask, offset, E, tile))
            assert pairs == per_expert.sum()
            assert padded == (-(-per_expert // tile) * tile).sum()
            assert touched == (per_expert > 0).sum()
        if select == "all_on_one" and offset == 4:
            assert per_expert.max() == int(np.asarray(mask).sum())
        if select == "none_held" and offset == 0:
            assert not per_expert.any() and not np.asarray(want).any()
        if select == "an_expert_with_none":
            assert (per_expert == 0).sum() == 1 and per_expert[-1]
        if select == "two_held_two_elsewhere":
            held = np.asarray((c != 0).sum(1))
            assert (held[np.asarray(mask)] == 2).all() and not held[
                ~np.asarray(mask)].any()
        if select == "masked_but_three":
            assert per_expert.sum() <= 3 * k


def test_the_grouped_product_keeps_resident_what_its_vmem_takes(monkeypatch):
    """The kernel keeps a call's tokens and their float32 sums in VMEM, so
    the wrapper reckons them against the limit it asks for, by geometry:
    both cells' chunk of 1,024 tokens (and 2,048 at Mistral's widths) is
    one call; under a limit that takes 128 tokens, 300 tokens go in three
    calls of 100, each over its own tokens' pairs, and still equal the
    composite; under one that takes none the composite runs, counted."""
    from paddle_tpu import kernels

    assert moe._grouped_resident(4096, 2048, 256, jnp.bfloat16, 3, 128) == 2048
    assert moe._grouped_resident(3072, 3072, 128, jnp.bfloat16, 3, 128) >= 2048
    rng = np.random.RandomState(13)
    T, H, F, E, EA, k, tile = 300, 256, 40, 4, 16, 3, 16
    x = jnp.asarray(rng.randn(T, H).astype("float32"))
    gate = jnp.asarray(rng.randn(EA, H).astype("float32"))
    w_gate, w_up, w_down = (
        jnp.asarray(0.1 * rng.randn(E, F, H).astype("float32"))
        for _ in range(3))
    mask = jnp.asarray(rng.rand(T) > 0.2)
    idx, w = moe.route(x, gate, jnp.zeros((EA,), jnp.float32), k, 1.0, True,
                       score="softmax")
    want = moe.experts_composite(x, moe.held_weights(idx, w, mask, 4, E),
                                 w_up, w_down, w_gate)
    # (a function of its own a trace: the limit is read while tracing)
    run = lambda: lambda *a: moe.moe_grouped(  # noqa: E731
        *a, 4, w_up, w_down, w_gate, interpret=True, row_tile=tile)
    calls = lambda: str(jax.make_jaxpr(run())(x, idx, w, mask)).count(  # noqa: E731
        "name=moe_grouped")
    fell = kernels.fallback_counter()
    assert calls() == 1
    # float32 here: a token's row and its sums, a column of the pick; a row
    # tile's down product, two up products, activation and weight blocks
    a_token = H * (4 + 4) + tile * 4
    a_tile = tile * (4 * H + F * (4 * 2 + 4)) + 2 * 3 * F * H * 4
    monkeypatch.setattr(moe, "_GROUPED_VMEM_SPARE", 0)
    for tokens, expect in ((128, 3), (0, 0)):
        monkeypatch.setattr(moe, "_GROUPED_VMEM_LIMIT",
                            a_tile + (tokens + 100) * a_token)
        assert moe._grouped_resident(H, F, H, jnp.float32, 3, tile) == tokens
        before = fell.value
        assert calls() == expect
        np.testing.assert_allclose(jax.jit(run())(x, idx, w, mask), want,
                                   rtol=1e-5, atol=1e-5)
        assert fell.value - before == 2 * (not tokens)


def test_the_op_takes_the_grouped_product_by_its_rule(monkeypatch):
    """``moe_routed_experts`` outside a step asks the rule: over 512 tokens
    choosing 2 of 64 with 4 held it runs the grouped kernel (interpreted)
    and counts its rows in whole tiles; over 16 tokens the composite, every
    token for every held expert; with ``kernel`` (a step) neither."""
    from paddle_tpu import kernels

    rng = np.random.RandomState(11)
    H, F, E, EA = 128, 24, 4, 64
    called = []
    grouped = moe.moe_grouped
    monkeypatch.setattr(moe, "moe_grouped", lambda *a, **kw: (
        called.append(a[0].shape[0]), grouped(*a, **kw))[1])
    op = OpRegistry.get("moe_routed_experts")
    weights = {
        "GateW": [jnp.asarray(rng.randn(EA, H).astype("float32"))],
        "SelectBias": [jnp.zeros((EA,), jnp.float32)],
        "WUp": [jnp.asarray(0.1 * rng.randn(E, F, H).astype("float32"))],
        "WDown": [jnp.asarray(0.1 * rng.randn(E, F, H).astype("float32"))],
        "WGate": [jnp.asarray(0.1 * rng.randn(E, F, H).astype("float32"))]}
    for T, takes in ((512, True), (16, False)):
        ins = dict(weights, X=[jnp.asarray(
            rng.randn(1, T, H).astype("float32"))],
            WriteRows=[jnp.arange(T) % 7])
        attrs = {"k": 2, "num_rows": 6, "score": "softmax",
                 "group_counts": True}
        want = op.lower(ins, attrs)
        with kernels.scoped_mode("interpret"):
            got = op.lowering(True)(ins, attrs)
            step = op.lowering(True)(ins, dict(attrs, kernel=True))
        assert bool(called) == takes
        called.clear()
        np.testing.assert_allclose(got["Out"][0], want["Out"][0],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(step["Out"][0], want["Out"][0],
                                   rtol=1e-5, atol=1e-5)
        assert not called
        pairs, rows, touched = np.asarray(got["GroupCounts"][0])
        assert np.array_equal(got["GroupCounts"][0], want["GroupCounts"][0])
        real = int((np.arange(T) % 7 < 6).sum())
        assert pairs == np.asarray(got["Counts"][0])[1] <= real * 2
        assert rows == (touched * 128 if takes else real * E)
        # a program that does not ask keeps the outputs it had
        assert "GroupCounts" not in op.lower(ins, {"k": 2, "num_rows": 6})


# -- the eight shares add up -------------------------------------------------

def test_the_eight_shares_add_up():
    """Each of 8 ranks computes the routed part of ITS experts (the served
    op, its share of the weights, its offset); their sum and the shared
    expert counted ONCE are the uncut layer, which is the reference handed
    all 8 experts."""
    rng = np.random.RandomState(9)
    T, H, F, EA, k = 24, 64, 32, 8, 2
    draw = lambda *shape: jnp.asarray(  # noqa: E731
        0.2 * rng.randn(*shape).astype("float32"))
    h = jnp.asarray(rng.randn(T, H).astype("float32"))
    norm_w = jnp.asarray(1.0 + 0.1 * rng.randn(H).astype("float32"))
    gate, w1, w3, w2 = (draw(EA, H), draw(EA, F, H), draw(EA, F, H),
                        draw(EA, F, H))
    s1, s3, s2 = draw(H, F), draw(H, F), draw(F, H)
    sizes = dict({k_: CONFIG[k_] for k_ in reference._KEYS},
                 num_experts_per_tok=k,
                 rope_parameters=tuple(sorted(
                     (k_, float(ROPE[k_])) for k_ in reference._ROPE_KEYS)),
                 rope_lanes=True)
    _e, _h, _a, experts = reference._functions(tuple(sorted(sizes.items())))
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(experts(h, norm_w, gate, w1, w3, w2, s1, s3, s2,
                                   np.int32(0)))
        x = reference._rms(h, norm_w, CONFIG["rms_norm_eps"])
        shared = np.asarray((jax.nn.silu(x @ s1) * (x @ s3)) @ s2)
    op = OpRegistry.get("moe_routed_experts").lower
    total, pairs = np.zeros((T, H), "float32"), 0
    for rank in range(8):
        out = op({"X": [x], "GateW": [gate],
                  "SelectBias": [jnp.zeros((EA,), jnp.float32)],
                  "WUp": [w3[rank:rank + 1]], "WDown": [w2[rank:rank + 1]],
                  "WGate": [w1[rank:rank + 1]],
                  "WriteRows": [jnp.zeros((T,), jnp.int32)]},
                 {"k": k, "num_rows": 1, "score": "softmax",
                  "expert_offset": rank, "group_counts": True})
        total += np.asarray(out["Out"][0])
        pairs += int(np.asarray(out["GroupCounts"][0])[0])
    assert pairs == T * k          # every choice landed on exactly one rank
    np.testing.assert_allclose(np.asarray(h) + total + shared, whole,
                               rtol=1e-4, atol=1e-4)
