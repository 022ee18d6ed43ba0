"""The ``ouro`` looped decoder (one stack of layers applied several times a
token with shared parameters, paged K/V rows per (pass, layer), an exit
gate) served through ``GenerationEngine``, at a tiny size on the CPU,
against its plain reference (``benchmark/references/plain_ouro.py``:
float32, whole sequence, no cache); and admission by reservation, which its
arena forces: a pool smaller than slots x length with no tier admits a
request against its whole block chain.

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

from benchmark.references import plain_ouro as reference  # noqa: E402
from decode_testing import without_token_fetch  # noqa: E402
from paddle_tpu import kernels, observability  # noqa: E402
from paddle_tpu.kernels import attention  # noqa: E402
from paddle_tpu.serving import GenerationEngine, build_ouro_model  # noqa: E402
from paddle_tpu.serving.decode import SamplingParams, hybrid  # noqa: E402
from paddle_tpu.serving.decode.model import DecodeModel  # noqa: E402
from paddle_tpu.serving.request import RequestError  # noqa: E402

#: the published keys at a tiny size: three layers, twice a token
CONFIG = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    intermediate_size=96, total_ut_steps=2, early_exit_threshold=1,
    rms_norm_eps=1e-6, rope_theta=1000000)
PASSES, LAYERS = CONFIG["total_ut_steps"], CONFIG["num_hidden_layers"]
GEOMETRY = dict(slots=4, max_len=48, block_size=4, chunk_tokens=8)
PROMPT_LENS = (5, 13, 8, 20, 3, 9)      # inside, across and on the chunk
ANSWERS = (6, 9, 4, 10, 12, 5)
#: float32 build against the float32 reference: summation order alone, and
#: the rotation's angles (float32 on both sides, positions under 48);
#: measured 3.1e-6 of a row's standard deviation
EXACT_BAND = 1e-4


def _model(dtype="float32", name="ouro", **over):
    m = build_ouro_model(**CONFIG, **dict(GEOMETRY, **over), dtype=dtype,
                         initializer_range=0.12, name=name)
    m.startup_program.random_seed = 7
    return m


def _engine(model, started=True):
    engine = GenerationEngine(prefix_cache_size=0, host_tier_mb=0)
    entry = engine.register_model(model)
    if started:
        engine.start()
    return engine, entry


def _prompts(lens=PROMPT_LENS, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, CONFIG["vocab_size"], n)]
            for n in lens]


def _weights(entry):
    scope, prefix = entry._scope, f"{entry.model.name}_v1."
    state = {n for kv in entry.model.state_names for n in kv}
    return {n[len(prefix):]: scope.find_var(n) for n in scope.var_names()
            if n.startswith(prefix) and n not in state}


def _record_rows(entry):
    """Every logits row the engine delivers, by request id."""
    rows, choose = {}, entry._choose_token

    def recording(st, row, device_masked):
        rows.setdefault(st.request.id, []).append(np.array(row, np.float32))
        return choose(st, row, device_masked)

    entry._choose_token = recording
    return rows


def _worst_row(entry, prompts, answers, rows, first_id=1, **control):
    """The worst delivered row's max |difference| from the reference's
    full forward over the served tokens, in standard deviations of the
    reference's row."""
    weights, worst = _weights(entry), 0.0
    for i, (prompt, out) in enumerate(zip(prompts, answers)):
        tokens = prompt + [int(t) for t in out[:-1]]
        want = reference.logits(
            weights, CONFIG, tokens,
            range(len(prompt) - 1, len(prompt) - 1 + len(out)),
            pad_to=GEOMETRY["max_len"], **control)
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
    """The float32 build over a full-size pool, served once: sampled
    requests (their rows recorded), then the same prompts greedy."""
    engine, entry = _engine(_model())
    rows = _record_rows(entry)
    prompts = _prompts()
    sampled = _serve_sampled(engine, prompts)
    greedy = _serve_greedy(engine, prompts)
    yield {"engine": engine, "entry": entry, "rows": rows,
           "prompts": prompts, "sampled": sampled, "greedy": greedy}
    engine.shutdown()


# -- the model: built once, run several times -------------------------------------

def test_parameters_exist_once_and_rows_once_a_pass_and_layer():
    m = _model(name="ouro_count")
    h, f, v = (CONFIG["hidden_size"], CONFIG["intermediate_size"],
               CONFIG["vocab_size"])
    layer = 4 * h * h + 3 * h * f + 4 * h
    want = LAYERS * layer + 2 * v * h + h + (h + 1)
    state = {n for kv in m.state_names for n in kv}
    block = m.startup_program.global_block()
    held = {n: int(np.prod(var.shape)) for n, var in block.vars.items()
            if var.persistable and n not in state}
    assert sum(held.values()) == want == 117633
    # every pass reads the SAME parameter: one variable a matrix, whatever
    # the passes, in the step program and in the chunk program alike
    for program in (m.decode_program, m.chunk_program):
        params = [n for n in program.global_block().vars if n in held]
        assert sorted(params) == sorted(held)
    assert len(m.state_names) == PASSES * LAYERS == len(set(state)) // 2
    assert m.state_names[LAYERS] == ("ouro_count_v1.kcache.p1.l0",
                                     "ouro_count_v1.vcache.p1.l0")
    assert m.passes == PASSES and m.chunks_only and not m.recurrent
    assert m.count_names == hybrid.LOOP_COUNTS
    # the published size by the same formula: 2.668 B, not four times the
    # layers' 2.467 B
    H, F, V, NL = 2048, 5632, 49152, 48
    assert (NL * (4 * H * H + 3 * H * F + 4 * H) + 2 * V * H + H + H + 1
            == 2_667_974_657)
    assert 4 * NL * 2 * H * 2 == 1_572_864          # K and V bytes a token


def test_a_threshold_under_one_is_refused():
    with pytest.raises(ValueError, match="early_exit_threshold"):
        build_ouro_model(**dict(CONFIG, early_exit_threshold=0.9),
                         **GEOMETRY)


def test_the_kernel_takes_sixteen_heads_of_128_with_one_query_head_each():
    assert attention.grouped_layout(2048, 16, 2048, "bfloat16") == (1, 16)
    assert attention._mosaic_tiles(16, 2048, "bfloat16")


# -- chunks, then steps, against the reference ------------------------------------

def test_float32_build_gives_the_references_logits(exact):
    worst = _worst_row(exact["entry"], exact["prompts"], exact["sampled"],
                       exact["rows"])
    assert worst < EXACT_BAND, worst
    # six requests over four slots: slots were reused; prompts inside,
    # across and on the chunk's edge all went through the chunk program,
    # and 20 + 10 positions cross chunk and block boundaries
    stats = exact["entry"].stats()
    assert stats["chunk_runs"] >= len(PROMPT_LENS) + 2
    assert stats["prefills"] == 0


def test_the_reference_run_one_pass_short_fails_the_band(exact):
    worst = _worst_row(exact["entry"], exact["prompts"], exact["sampled"],
                       exact["rows"], passes=PASSES - 1)
    assert worst > 100 * EXACT_BAND, worst


def test_rounded_operands_move_a_row_by_roundings_share_and_no_more(exact):
    """``round_operands`` is a diagnosis, off unless asked for: the
    reference then rounds its activations where the served bfloat16 program
    rounds them. Against the float32 build that moves a row well past the
    exact band (it is not a no-op: the rounding is not compiled away) and
    far less than a pass left out does."""
    entry, prompts = exact["entry"], exact["prompts"]
    rounded = _worst_row(entry, prompts, exact["sampled"], exact["rows"],
                         round_operands="bfloat16")
    short = _worst_row(entry, prompts, exact["sampled"], exact["rows"],
                       passes=PASSES - 1)
    assert 50 * EXACT_BAND < rounded < short / 5, (rounded, short)


def test_the_exit_gates_counts_are_the_references_distribution(exact):
    """One request alone: each of its decode steps adds ``passes`` to
    ``loop_pass_tokens`` and, to ``loop_exit_pass_milli``, the pass at
    which the reference's ``p_1 .. p_T`` expects to leave, in thousandths
    rounded to nearest."""
    entry, engine = exact["entry"], exact["engine"]
    prompt, n = _prompts((11,), seed=3)[0], 9
    before = entry.stats()
    (out,) = _serve_greedy(engine, [prompt], [n])
    after = entry.stats()
    at = range(len(prompt), len(prompt) + n - 1)     # the steps' positions
    _logits, exits = reference.forward(
        _weights(entry), CONFIG, prompt + out[:-1], at,
        pad_to=GEOMETRY["max_len"])
    assert exits.shape == (n - 1, PASSES)
    np.testing.assert_allclose(exits.sum(1), 1.0, rtol=1e-6)
    assert 0.02 < exits[:, 0].min() and exits[:, 0].max() < 0.98
    expected = (exits * np.arange(1, PASSES + 1)).sum(1)
    moved = after["loop_exit_pass_milli"] - before["loop_exit_pass_milli"]
    assert abs(moved - np.floor(1000 * expected + 0.5).sum()) <= 2
    assert (after["loop_pass_tokens"] - before["loop_pass_tokens"]
            == PASSES * (n - 1))
    assert (after["active_slot_steps"] - before["active_slot_steps"]
            == n - 1)


def test_a_pass_keeps_rows_of_its_own(exact):
    """After serving, layer 1's K arena of pass 0 is not its arena of
    pass 1, row for row, where rows were written."""
    scope, prefix = exact["entry"]._scope, "ouro_v1."
    first = np.asarray(scope.find_var(prefix + "kcache.p0.l1"))
    second = np.asarray(scope.find_var(prefix + "kcache.p1.l1"))
    written = np.abs(first).sum(1) > 0
    assert written.sum() > 40
    assert np.array_equal(written, np.abs(second).sum(1) > 0)
    gap = np.abs(first - second)[written].max(1)
    assert gap.min() > 1e-3


# -- the controls: each must fail the comparison ----------------------------------

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
    (_stale, "kcache.p1.l2"), (_shifted, None)],
    ids=["one_pass_and_layers_k_arena_stale", "position_off_by_one"])
def test_a_planted_fault_fails_the_exact_band(fault, state):
    """One of the six (pass, layer) K arenas a decode step behind; the
    decode steps' positions off by one. Each leaves the prompt's logits
    sound and the decoded rows wrong."""
    engine, entry = _engine(_model(name=f"ouro_{fault.__name__}", slots=1))
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


def test_passes_that_share_one_arena_pair_fail_the_band(monkeypatch):
    """The served model built with every pass of a layer on pass 0's arena
    pair (a token's older rows are then its last pass's, for every pass:
    the decode-time sharing the family's paper offers as an approximation,
    which nothing serves) is told from the model."""
    own = hybrid._Parts.arenas
    monkeypatch.setattr(
        hybrid._Parts, "arenas",
        lambda parts, program, key: own(parts, program, (0, key[1])))
    engine, entry = _engine(_model(name="ouro_shared"))
    try:
        rows = _record_rows(entry)
        prompts = _prompts()[:3]
        sampled = _serve_sampled(engine, prompts, ANSWERS[:3])
    finally:
        engine.shutdown()
    assert _worst_row(entry, prompts, sampled, rows) > 100 * EXACT_BAND


def test_the_kernel_serves_the_engine_like_the_composite(exact):
    """The same model under ``interpret``: the grouped paged-attention
    kernel with ONE query head a K/V head, through the Pallas interpreter,
    gives the composite's tokens, and logits inside the exact band."""
    with kernels.scoped_mode("interpret"):
        engine, entry = _engine(_model(name="ouro_kernels"))
        try:
            rows = _record_rows(entry)
            prompts = exact["prompts"][1:4]     # 13, 8 and 20 tokens
            sampled = _serve_sampled(engine, prompts, ANSWERS[1:4])
            greedy = _serve_greedy(engine, prompts, ANSWERS[1:4])
        finally:
            engine.shutdown()
    assert _worst_row(entry, prompts, sampled, rows) < EXACT_BAND
    assert greedy == exact["greedy"][1:4]


# -- admission by reservation ------------------------------------------------------

#: chains of 4 to 10 blocks of 4 rows; a pool of 16 (a third of 4 slots x
#: 12 blocks) holds two or three of them
CROWD_LENS = (13, 5, 20, 9, 17, 3, 11, 8, 15, 6)
CROWD_ANSWERS = (12, 14, 10, 20, 9, 16, 13, 18, 8, 11)


@pytest.fixture(scope="module")
def crowded():
    """Ten requests at once on a pool of a third of slots x length, with a
    step in flight; and on a full-size pool whose every step lands before
    the next is launched."""
    prompts = _prompts(CROWD_LENS, seed=5)
    small_engine, small = _engine(_model(name="ouro_small", num_blocks=16))
    try:
        tight = _serve_greedy(small_engine, prompts, CROWD_ANSWERS)
    finally:
        small_engine.shutdown()
    serial_engine, serial = _engine(
        without_token_fetch(_model(name="ouro_serial")))
    try:
        roomy = _serve_greedy(serial_engine, prompts, CROWD_ANSWERS)
    finally:
        serial_engine.shutdown()
    return {"small": small, "serial": serial, "tight": tight,
            "roomy": roomy, "prompts": prompts}


def test_an_oversubscribed_pool_serves_what_a_full_one_serves(crowded):
    assert crowded["tight"] == crowded["roomy"]
    assert [len(t) for t in crowded["tight"]] == list(CROWD_ANSWERS)
    # the tokens with a step in flight are the tokens without
    assert crowded["small"].stats()["decode_steps_ahead"] > 0
    assert crowded["serial"].stats()["decode_steps_ahead"] == 0


def test_the_pool_not_the_slots_made_requests_wait(crowded):
    small, serial = crowded["small"].stats(), crowded["serial"].stats()
    chains = [-(-(p + a) // 4) for p, a in zip(CROWD_LENS, CROWD_ANSWERS)]
    assert small["reserved_admissions"] == len(chains)
    assert small["blocks_reserved"] == sum(chains)
    assert small["admissions_deferred"] > 0
    for never in ("blocks_exhausted", "blocks_parked_total",
                  "blocks_failed_total", "sessions_parked", "failed"):
        assert small[never] == 0, never
    assert small["completed"] == len(chains)
    # a pool that gives every slot its full length reserves nothing
    assert serial["reserved_admissions"] == serial["admissions_deferred"] == 0
    pool = crowded["small"].block_pool
    assert pool.stats()["blocks_reserved"] == 0
    kept = pool.check_conservation()
    assert kept["blocks_live"] == 0
    assert kept["blocks_free"] + kept["blocks_cached"] == 16
    # never more promised than the pool had: at most the chains that fit
    assert small["pool_block_allocs"] <= sum(chains)


def test_a_chain_no_pool_could_hold_fails_loudly():
    engine, entry = _engine(_model(name="ouro_never", num_blocks=6))
    try:
        with pytest.raises(RequestError, match="can never fit"):
            engine.submit(_prompts((9,))[0],
                          max_new_tokens=20).result(timeout=120)
        # and the pool serves on
        (out,) = _serve_greedy(engine, _prompts((9,)), [8])
        assert len(out) == 8
    finally:
        engine.shutdown()
    assert entry.metrics.count("blocks_failed_total") == 1
    assert entry.block_pool.stats()["blocks_reserved"] == 0


def test_the_reservation_rides_in_the_launch_ahead_order(tmp_path):
    """Hand-stepped on a small pool: a greedy arrival whose chain the pool
    covers is admitted under the step in flight with no drain; one whose
    chain it cannot cover stays in the queue, and takes no slot. A decode
    step is ONE put and ONE fetch; the spans say what was reserved."""
    engine, entry = _engine(_model(name="ouro_hand", num_blocks=10),
                            started=False)
    path = str(tmp_path / "trace.json")
    with observability.tracing(path):
        first = engine.submit(_prompts((6,), 1)[0], max_new_tokens=14)  # 5
        for _ in range(4):
            entry._iterate()
        assert entry._launched is not None
        fits = engine.submit(_prompts((5,), 2)[0], max_new_tokens=6)    # 3
        waits = engine.submit(_prompts((7,), 3)[0], max_new_tokens=9)   # 4
        drains = dict(entry.metrics.drains())
        entry._iterate()
        assert entry.block_pool.stats()["blocks_reserved"] > 0
        assert dict(entry.metrics.drains()) == drains
        assert entry._pool.active_count == 2 and entry._queue.depth() == 1
        assert entry.stats()["admissions_deferred"] == 1
        fetches, fetch = [], entry._fetch
        entry._fetch = lambda value: (fetches.append(1), fetch(value))[1]
        launches = entry.stats()["step_launches"]
        for _ in range(3):
            entry._iterate()
        assert len(fetches) - 1 <= entry.stats()["step_launches"] - launches
        entry._fetch = fetch
        for _ in range(60):
            if first.done() and fits.done() and waits.done():
                break
            entry._iterate()
        spans = observability.get_tracer().spans()
    assert [len(r.result()["tokens"]) for r in (first, fits, waits)] == [
        14, 6, 9]
    assert entry.stats()["admissions_deferred"] == 1
    observability.get_tracer().clear()
    named = lambda name: [s["args"] for s in spans  # noqa: E731
                          if s["name"] == name]
    steps, chunks = named("decode::step"), named("decode::chunk")
    assert steps and all(a["puts"] == 1 and a["passes"] == PASSES
                         for a in steps)
    assert chunks and all(a["passes"] == PASSES for a in chunks)
    admits = [a for a in named("decode::admit")
              if a["outcome"] == "admitted"]
    assert [a["reserved"] for a in admits] == [5, 3, 4]
    assert admits[0]["free"] == 10 - 5 and admits[1]["free"] == 10 - 5 - 3
    engine.shutdown()
