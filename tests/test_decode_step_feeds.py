"""A decode step's ONE host feed (ISSUE 39).

Until ISSUE 39 `_step_feeds` built five arrays a step on the host and `_run`
put four of them: positions ``[S, 1]``, an additive bias ``[S, 1, L]``, a
row map ``[S * L]`` and write rows ``[S]``. Every value in them follows from
a slot's cursor and its block table, so the step is now fed those integers
alone (``dec_step``, `DecodeModel.step_feed` / `fill_step`) and its program's
first op (``paged_step_feeds``) makes the four arrays of them on the device.

Held here: that expansion equals, value for value, the arrays the parent
built — over seeded synthetic slot states through the real step programs of
the toy decoder and the toy hybrid (grouped-query), and at every step of
hand-stepped engines in the modes that rearrange block tables (copy-on-write,
a beam group, a park and a resume, a speculative draft whose ``write=False``
step rewrites nothing); that a step launch puts exactly one host array; and
that the served tokens equal the plain reference's with a step in flight.

One difference is not a difference: past a slot's LAST block the parent's row
map held whatever the host array held (zeros at first, a previous tenant's
rows after a resume), the expansion names the rows of block 0. Those
positions lie at or beyond the slot's length, their bias is ``-1e9``, the
kernel never reads them and the composite weighs them by exactly 0.0.
"""

import numpy as np
import pytest
from decode_testing import sharpen, without_token_fetch

import paddle_tpu as fluid
from paddle_tpu import observability as obs
from paddle_tpu.core.registry import OpRegistry
from paddle_tpu.serving.decode import (
    GenerationEngine,
    build_decoder_model,
    build_nemotron_h_model,
)
from paddle_tpu.serving.decode.kvstate import SeqKV
from paddle_tpu.serving.decode.model import NEG_INF, DecodeModel
from paddle_tpu.serving.decode.pool import Block

OUTPUTS = ("TokenOut", "Position", "Bias", "Rows", "WriteRows")

#: max_len 30 over blocks of 4: the last block of a full slot is cut short
DECODER = dict(vocab_size=32, hidden=8, num_layers=2, slots=4, max_len=30,
               block_size=4)
HYBRID = dict(
    vocab_size=96, hidden_size=64, hybrid_override_pattern="M*E",
    mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
    conv_kernel=4, chunk_size=8, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, n_routed_experts=4,
    router_experts=8, num_experts_per_tok=2, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=48, routed_scaling_factor=2.5,
    dtype="float32", slots=4, max_len=48, block_size=4, chunk_tokens=8)


def _build(kind, name):
    if kind == "decoder":
        return build_decoder_model(name=name, version="1", **DECODER)
    m = build_nemotron_h_model(name=name, **HYBRID)
    m.startup_program.random_seed = 7
    return m


def _expansion_op(m):
    ops = [op for op in m.decode_program.global_block().ops
           if op.type == "paged_step_feeds"]
    assert len(ops) == 1
    return ops[0]


# -- the arrays the parent built ---------------------------------------------

def _parent_arrays(m, stepping):
    """What the parent's `_step_feeds` (and its draft copy) built, from
    ``{slot: (token, position, row_map, write_row)}``: its lines, kept."""
    S, L, R = m.slots, m.max_len, m.rows
    tok = np.zeros((S, 1), "int64")
    pos = np.zeros((S, 1), "int64")
    bias = np.full((S, 1, L), NEG_INF, "float32")
    rows = np.zeros((S, L), "int64")
    wrows = np.full((S,), R, dtype="int64")
    for s, (token, p, row_map, write_row) in stepping.items():
        tok[s, 0] = token
        pos[s, 0] = p
        bias[s, 0, :p + 1] = 0.0
        rows[s] = row_map
        wrows[s] = write_row
    return tok, pos, bias, rows.reshape(-1), wrows


def _parent_row_map(m, block_ids):
    """The parent's `_rebuild_row_map` over a fresh slot."""
    bs = m.block_size
    row_map = np.zeros(m.max_len, dtype="int64")
    for i, b in enumerate(block_ids):
        lo = i * bs
        hi = min(lo + bs, m.max_len)
        row_map[lo:hi] = b * bs + np.arange(hi - lo)
    return row_map


def _assert_equal(m, got, want, covered, device_tokens=None, host=()):
    """``got``: the expansion's five outputs; ``want``: the parent's five
    arrays; ``covered[s]``: the positions slot ``s``'s blocks cover. With a
    step in flight (``device_tokens``: its output) every row takes that
    step's token but the slots of ``host``, new since its launch, which
    take their own from the host (ISSUE 42)."""
    S, L, bs = m.slots, m.max_len, m.block_size
    tok, pos, bias, rows, wrows = (np.asarray(a) for a in got)
    ptok, ppos, pbias, prows, pwrows = want
    assert pos.shape == (S, 1) and (pos == ppos).all()
    assert bias.shape == (S, 1, L) and bias.dtype == np.float32
    assert (bias == pbias).all()
    assert wrows.shape == (S,) and (wrows == pwrows).all()
    assert rows.shape == (S * L,)
    rows, prows = rows.reshape(S, L), prows.reshape(S, L)
    for s in range(S):
        n = covered.get(s, 0)
        assert (rows[s, :n] == prows[s, :n]).all(), s
        # past the last block: block 0's rows, closed by the bias
        assert (rows[s, n:] == np.arange(n, L) % bs).all(), s
        assert (bias[s, 0, n:] == np.float32(NEG_INF)).all(), s
    assert tok.shape == (S, 1)
    if device_tokens is None:
        live = sorted(covered)
        assert (tok[live] == ptok[live]).all()
    else:
        expected = np.array(device_tokens)
        for s in host:
            expected[s] = ptok[s]
        assert (tok == expected).all()


# -- synthetic slot states through the real step programs --------------------

def _states(case, m, rng):
    """``{slot: (token, position, block ids, write?)}`` of one case."""
    S, L, bs, per = m.slots, m.max_len, m.block_size, m.blocks_per_slot
    ids = [int(b) for b in rng.permutation(m.num_blocks)]

    def take(n):
        got = ids[:n]
        del ids[:n]
        return got

    def tok():
        return int(rng.integers(0, m.vocab_size))

    if case == "idle":
        return {}
    if case == "block_first_row":       # the cursor opens a block
        return {s: (tok(), k * bs, take(k + 1), True)
                for s, k in ((0, 1), (2, 3), (3, per - 1))}
    if case == "block_last_row":
        return {s: (tok(), k * bs + bs - 1, take(k + 1), True)
                for s, k in ((1, 0), (2, 2), (3, per - 2))}
    if case == "last_position":
        return {1: (tok(), L - 1, take(per), True),
                2: (tok(), 0, take(1), True)}
    if case == "after_copy_on_write":   # tables neither rising nor adjacent
        a, b = sorted(take(5), reverse=True), take(3)
        return {0: (tok(), 4 * bs + 1, a, True),
                3: (tok(), 2 * bs, [b[1], b[0], b[2]], True)}
    if case == "beam_group":            # one prefix, three private tails
        shared = take(2)
        return {s: (tok(), 2 * bs + 2, shared + take(1), True)
                for s in (0, 1, 3)}
    if case == "draft_no_write":        # a row that is right already
        return {2: (tok(), bs + 1, take(4), False),
                0: (tok(), 3 * bs, take(4), True)}
    assert case.startswith("seeded")
    out = {}
    for s in range(S):
        if rng.random() < 0.3:
            continue
        p = int(rng.integers(0, L))
        extra = int(rng.integers(0, 2))
        out[s] = (tok(), p, take(min(per, p // bs + 1 + extra)),
                  bool(rng.random() < 0.8))
    return out


CASES = ("idle", "block_first_row", "block_last_row", "last_position",
         "after_copy_on_write", "beam_group", "draft_no_write",
         "seeded0", "seeded1", "seeded2", "seeded3")


@pytest.fixture(scope="module", params=["decoder", "hybrid"])
def program(request):
    """The built model and a runner of its REAL decode program that fetches
    what the expansion op gives the layers."""
    m = _build(request.param, "feeds_" + request.param)
    op = _expansion_op(m)
    assert op.attrs["length"] == m.max_len
    assert op.attrs["block_size"] == m.block_size
    names = [op.outputs[slot][0] for slot in OUTPUTS]
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(m.startup_program)

    def run(step, token):
        with fluid.scope_guard(scope):
            return exe.run(m.decode_program, feed={
                DecodeModel.DEC_STEP: step, DecodeModel.DEC_TOKEN: token},
                fetch_list=names)

    return m, run


@pytest.mark.parametrize("case", CASES)
def test_the_expansion_equals_the_arrays_the_parent_built(program, case):
    m, run = program
    seed = CASES.index(case) + (1000 if m.recurrent else 0)
    states = _states(case, m, np.random.default_rng(seed))
    bs = m.block_size
    step = m.step_feed()
    assert step.dtype == np.int32
    assert step.shape == (m.slots, 4 + -(-m.max_len // bs))
    stepping, covered = {}, {}

    for s, (token, p, blocks, write) in states.items():
        row = blocks[p // bs] * bs + p % bs if write else m.rows
        kv = SeqKV(m, m.groups[0],
                   blocks=[Block(b, b * bs) for b in blocks])
        assert kv.table.tolist() == m.block_table(kv.blocks).tolist()
        m.fill_step(step, s, p, kv.groups, token, write)
        stepping[s] = (token, p, _parent_row_map(m, blocks), row)
        covered[s] = min(len(blocks) * bs, m.max_len)
    got = run(step, np.zeros((m.slots, 1), "int64"))
    _assert_equal(m, got, _parent_arrays(m, stepping), covered)
    # a slot that does not step: no open position, nothing written
    idle = [s for s in range(m.slots) if s not in states]
    assert (np.asarray(got[2])[idle] == np.float32(NEG_INF)).all()
    assert (np.asarray(got[4])[idle] == m.rows).all()


def test_a_token_of_minus_one_takes_the_device_feeds(program):
    """A launched-ahead step's tokens are the step before's output: slots
    that carry -1 read ``dec_token``, the others their own column."""
    m, run = program
    step = m.step_feed()
    groups = SeqKV(m, m.groups[0], blocks=[
        Block(b, b * m.block_size) for b in (0, 1)]).groups
    m.fill_step(step, 0, 3, groups)                     # token left at -1
    m.fill_step(step, 1, 5, groups, token=17)
    device = np.arange(10, 10 + m.slots, dtype="int64").reshape(-1, 1)
    tok = np.asarray(run(step, device)[0])
    assert tok[:, 0].tolist() == [10, 17] + list(range(12, 10 + m.slots))


# -- every step of a hand-stepped engine -------------------------------------

def _expand(m, feeds):
    lower = OpRegistry.get("paged_step_feeds").lower
    out = lower({"Packed": [np.asarray(feeds[DecodeModel.DEC_STEP])],
                 "Token": [feeds[DecodeModel.DEC_TOKEN]]},
                {"length": m.max_len, "block_size": m.block_size})
    return [out[slot][0] for slot in OUTPUTS]


def _watch_steps(entry, seen):
    """Compare every step's feed, as `_step_feeds` returns it, with the
    parent's arrays built from the live slots. ``seen`` gets a tuple of the
    stepping slots' block tables a step."""
    m, build = entry.model, entry._step_feeds

    def checked():
        launched = entry._launched
        built = build()
        if not isinstance(built, tuple):
            return built
        feeds, active, groups = built
        slots = list(active) + [s for g in groups for s in g.order]
        stepping, covered = {}, {}
        for s in slots:
            st = entry._slots[s]
            stepping[s] = (st.last_token, st.cursor, st.kv.row_map,
                           st.kv.row_of(st.cursor))
            covered[s] = min(len(st.kv.blocks) * m.block_size, m.max_len)
        assert set(feeds) == {DecodeModel.DEC_STEP, DecodeModel.DEC_TOKEN}
        assert isinstance(feeds[DecodeModel.DEC_STEP], np.ndarray)
        assert not isinstance(feeds[DecodeModel.DEC_TOKEN], np.ndarray)
        _assert_equal(
            m, _expand(m, feeds), _parent_arrays(m, stepping), covered,
            device_tokens=None if launched is None else launched.fetches[1],
            host=[s for s in slots if not entry._slots[s].ahead])
        seen.append(tuple(tuple(b.id for b in entry._slots[s].kv.blocks)
                          for s in slots))
        return built

    entry._step_feeds = checked


def _hand_step(entry, resps, iters=800):
    for _ in range(iters):
        if all(r.done() for r in resps):
            return
        entry._iterate()
    raise AssertionError("hand-stepped run did not converge")


def _tokens(resp):
    return [int(t) for t in resp.result(timeout=60)["tokens"]]


def _engine_copy_on_write():
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    entry = sharpen(engine.register_model(
        lambda: _build("decoder", "feeds_cow")))
    prompt = [7, 3, 9, 2, 11, 5]        # a full block and a shared tail
    seen = []
    _watch_steps(entry, seen)
    refs = [entry.offline_decode(prompt, 9)] * 2
    resps = [engine.submit(prompt, max_new_tokens=9) for _ in refs]
    _hand_step(entry, resps)
    assert entry.block_pool.stats()["cow_copies"] >= 1
    # the writer's table is not a run of adjacent blocks any more
    assert any(np.diff(t).tolist() != [1] * (len(t) - 1)
               for step in seen for t in step if len(t) > 1)
    return [_tokens(r) for r in resps] == refs, seen


def _engine_beam():
    from paddle_tpu.serving.decode import BeamParams

    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    entry = sharpen(engine.register_model(
        lambda: _build("decoder", "feeds_beam")))
    seen = []
    _watch_steps(entry, seen)
    prompt = [5, 6, 7, 8, 9]
    ref = entry.offline_beam(prompt, 8, BeamParams(3))
    resp = engine.submit(prompt, max_new_tokens=8, beam_width=3)
    _hand_step(entry, [resp])
    out = [[int(t) for t in h["tokens"]]
           for h in resp.result(timeout=60)["beams"]]
    # hypotheses of one group stepped together over a shared first block
    assert any(len(step) == 3 and len({t[0] for t in step}) == 1
               for step in seen)
    return out == [list(rt) for rt, _rs in ref], seen


def _engine_park_and_resume():
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0,
                              host_tier_mb=16)
    entry = sharpen(engine.register_model(lambda: build_decoder_model(
        vocab_size=32, hidden=8, num_layers=1, slots=2, max_len=16,
        block_size=2, num_blocks=6, name="feeds_park", version="1")))
    seen = []
    _watch_steps(entry, seen)
    prompts = [[1, 2, 3, 4], [5, 6, 7, 8], [3, 1, 3, 1], [2, 7, 1, 8]]
    refs = [entry.offline_decode(p, 7) for p in prompts]
    resps = [engine.submit(p, max_new_tokens=7) for p in prompts]
    _hand_step(entry, resps)
    st = entry.stats()
    assert st["sessions_parked"] == st["sessions_resumed"] >= 1
    return [_tokens(r) for r in resps] == refs, seen


def _engine_draft_no_write():
    """A draft that disagrees with its target: after a rejected proposal
    the catch-up step feeds a position whose draft row is written already
    (``write=False``: the sentinel row, a length short of the blocks)."""
    engine = GenerationEngine(queue_depth=32, breaker_threshold=0)
    geom = dict(DECODER, max_len=32)
    tgt = engine.register_model(lambda: build_decoder_model(
        name="feeds_spec_t", version="1", **geom))
    draft = engine.register_model(lambda: build_decoder_model(
        name="feeds_spec_d", version="1", **dict(geom, num_layers=1,
                                                 slots=2)))
    dm, calls, seen = draft.model, [], []
    step_kv, run = tgt._draft_step_kv, draft._run

    def noting(st, d, token, p, write):
        calls.append((st, token, p, write))
        return step_kv(st, d, token, p, write)

    def checking(kind, feeds, span=None):
        if kind == "step":
            st, token, p, write = calls.pop()
            b = st.draft_kv.blocks[p // dm.block_size]
            row = b.row0 + p % dm.block_size if write else dm.rows
            assert feeds[DecodeModel.DEC_STEP][st.d_slot, 3] == row
            _assert_equal(
                dm, _expand(dm, feeds),
                _parent_arrays(dm, {st.d_slot: (token, p, st.draft_kv.row_map,
                                                row)}),
                {st.d_slot: min(len(st.draft_kv.blocks) * dm.block_size,
                                dm.max_len)})
            seen.append(write)
        return run(kind, feeds, span)

    tgt._draft_step_kv, draft._run = noting, checking
    prompt = [9, 9, 8, 7]
    ref = tgt.offline_decode(prompt, 12)
    resp = engine.submit(prompt, model="feeds_spec_t", max_new_tokens=12,
                         draft_model="feeds_spec_d", spec_k=3)
    _hand_step(tgt, [resp])
    assert False in seen and True in seen
    return _tokens(resp) == ref, seen


def _engine_hybrid():
    engine = GenerationEngine(prefix_cache_size=0, host_tier_mb=0)
    entry = engine.register_model(_build("hybrid", "feeds_hybrid_engine"))
    seen = []
    _watch_steps(entry, seen)
    rng = np.random.default_rng(5)
    prompts = [[int(t) for t in rng.integers(1, 96, n)]
               for n in (5, 13, 8, 20, 3)]
    resps = [engine.submit(p, max_new_tokens=n)
             for p, n in zip(prompts, (6, 9, 4, 10, 12))]
    _hand_step(entry, resps)
    # greedy steps of a hybrid are launched ahead too: tokens of -1
    assert entry.stats()["decode_steps_ahead"] > 0
    return all(len(_tokens(r)) == n
               for r, n in zip(resps, (6, 9, 4, 10, 12))), seen


@pytest.mark.parametrize("scenario", [
    "copy_on_write", "beam", "park_and_resume", "draft_no_write", "hybrid"])
def test_every_step_of_an_engine_feeds_what_the_parent_fed(scenario):
    same, seen = globals()["_engine_" + scenario]()
    assert same, "served tokens differ from the reference's"
    assert len(seen) >= 4


# -- one put a step ---------------------------------------------------------

@pytest.mark.parametrize("order", ["ahead", "serial"])
def test_a_step_launch_puts_exactly_one_host_array(order):
    """``decode::step`` says ``puts=1`` and ``bytes`` = the packed array's,
    and ``serving_fed_bytes_total`` moves by as much, whether the step's
    tokens are the step before's output on the device (``ahead``) or come
    from the host (``serial``: they ride in the same array)."""
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    model = _build("decoder", "feeds_puts_" + order)
    entry = engine.register_model(
        model if order == "ahead" else without_token_fetch(model))
    nbytes = entry.model.step_feed().nbytes
    assert nbytes == 4 * 4 * (4 + 8)
    resps = [engine.submit(p, max_new_tokens=6)
             for p in ([3, 1, 4], [1, 5, 9, 2, 6, 5], [8, 9])]
    assert entry._admit_free_slots() == 3
    count = entry.metrics.count
    obs.get_tracer().clear()
    obs.enable_tracing()
    try:
        for _ in range(4):
            fed0, launches0 = count("fed_bytes"), count("step_launches")
            entry._step()
            assert count("step_launches") == launches0 + 1
            assert count("fed_bytes") - fed0 == nbytes
    finally:
        obs.disable_tracing()
    steps = [s for s in obs.get_tracer().spans()
             if s["name"] == "decode::step"]
    obs.get_tracer().clear()
    assert len(steps) == 4
    assert [s["args"]["puts"] for s in steps] == [1] * 4
    assert [s["args"]["bytes"] for s in steps] == [nbytes] * 4
    if order == "ahead":
        assert [s["args"]["ahead"] for s in steps] == [False] + [True] * 3
    _hand_step(entry, resps)


# -- the served tokens -------------------------------------------------------

def test_greedy_answers_equal_the_plain_reference_with_a_step_in_flight():
    """The toy decoder served by a started engine, whose greedy steps are
    launched ahead of the fetch before them, against the benchmark's plain
    whole-sequence reference fed the served weights: token for token."""
    from benchmark.references import plain_decoder

    engine = GenerationEngine(queue_depth=32, breaker_threshold=0)
    entry = sharpen(engine.register_model(
        lambda: _build("decoder", "feeds_plain")))
    prefix, scope = "feeds_plain_v1.", entry._scope
    weights = {n[len(prefix):]: scope.find_var(n) for n in scope.var_names()
               if n.startswith(prefix) and "cache" not in n}
    rng = np.random.default_rng(11)
    prompts = [[int(t) for t in rng.integers(0, 32, n)]
               for n in (3, 7, 1, 12, 5, 9)]
    answers = (10, 6, 14, 8, 12, 5)

    def reference(prompt, n):
        toks = list(prompt)
        for _ in range(n):
            row = plain_decoder.logits(
                weights, DECODER["num_layers"], toks, [len(toks) - 1],
                pad_to=DECODER["max_len"])[0]
            toks.append(int(np.argmax(row)))
        return toks[len(prompt):]

    refs = [reference(p, n) for p, n in zip(prompts, answers)]
    engine.start()
    try:
        resps = [engine.submit(p, max_new_tokens=n)
                 for p, n in zip(prompts, answers)]
        outs = [_tokens(r) for r in resps]
    finally:
        engine.shutdown()
    assert outs == refs
    assert entry.stats()["decode_steps_ahead"] > 0
