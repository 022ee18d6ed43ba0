"""Layer groups of the paged cache (serving/decode/model.py ``KVGroup``,
kvstate.py "Layer groups"): a window group's chain holds exactly the blocks
with a position inside the next query's window, the blocks behind it go
back to the group's pool, both pools conserve their blocks through admit /
chunk / step / abandon, admission waits for BOTH promises, the step's and
the chunk's feeds say what the group's table holds, and a model with one
group is fed what it ever was. Host code and one small op: no program runs.
"""

import numpy as np
import pytest

from paddle_tpu.serving.decode.kvstate import KVStore, SeqKV
from paddle_tpu.serving.decode.metrics import DecodeMetrics
from paddle_tpu.serving.decode.model import (
    DecodeModel, window_chunk_blocks, window_table_blocks)

W, BS, C, L, S = 8, 4, 8, 64, 4


def _model(num_blocks=40, window_num_blocks=24, window=W):
    from paddle_tpu.serving import build_afmoe_model

    return build_afmoe_model(
        96, 32, ["sliding_attention", "sliding_attention", "full_attention"],
        num_attention_heads=2, num_key_value_heads=1, head_dim=16,
        intermediate_size=48, num_dense_layers=1, num_experts=2,
        router_experts=4, num_experts_per_tok=2, moe_intermediate_size=16,
        sliding_window=window, dtype="float32", slots=S, max_len=L,
        block_size=BS, num_blocks=num_blocks,
        window_num_blocks=window_num_blocks, chunk_tokens=C, name="grp")


class _Req:
    beam = draft_key = None
    held_back = False

    def __init__(self, plen, max_new):
        self.prompt, self.max_new = list(range(plen)), max_new


def _store(model):
    metrics = DecodeMetrics(engine_label=f"t{id(model)}")
    return KVStore(model, 0, 0, metrics, run=None, fetch=None, scope=None,
                   device=None), metrics


def _walk(store, kv, plen, new):
    """A sequence's life as the scheduler drives it: chunks, then steps.
    Yields ``(kind, first position, stop)`` after each launch's feeds."""
    m = store._model
    for start in range(0, plen, m.chunk_tokens):
        stop = min(start + m.chunk_tokens, plen)
        for g in store.windowed:
            store.release_behind(kv, start, g)
        store.open_windows(kv, stop)
        yield "chunk", start, stop
    for p in range(plen, plen + new - 1):
        for g in store.windowed:
            store.release_behind(kv, p, g)
        assert store.open_block(kv, p)
        yield "step", p, p + 1


def test_the_geometry_of_a_window_groups_tables():
    # stepping at p the live blocks run from (p - W + 1) // BS to p // BS
    assert window_table_blocks(8, 4, 16) == 3
    assert window_table_blocks(4096, 16, 2112) == 257
    # a chunk from s over C positions: (s - W + 1) // BS to (s + C - 1) // BS
    assert window_chunk_blocks(8, 8, 4, 16) == 5
    assert window_chunk_blocks(4096, 1024, 16, 2112) == 321
    # never more than a slot has
    assert window_table_blocks(4096, 16, 12) == 12
    for w, c, bs in ((8, 8, 4), (5, 3, 2), (16, 4, 4), (7, 9, 4)):
        worst_step = max(p // bs - max(p - w + 1, 0) // bs + 1
                         for p in range(200))
        worst_chunk = max((s + c - 1) // bs - max(s - w + 1, 0) // bs + 1
                          for s in range(200))
        assert window_table_blocks(w, bs, 10 ** 6) == worst_step
        assert window_chunk_blocks(w, c, bs, 10 ** 6) == worst_chunk


@pytest.mark.parametrize("plen,new", [(3, 6), (8, 5), (11, 20), (37, 12),
                                      (52, 12)])
def test_a_window_chain_holds_its_window_and_nothing_behind_it(plen, new):
    m = _model()
    store, metrics = _store(m)
    kv = store.acquire(_Req(plen, new))
    _full, w = kv.groups
    _shared, pool = store.pools
    assert store.windowed == [1] and w.window == W and kv.window is None
    for kind, first, stop in _walk(store, kv, plen, new):
        held = range(w.first, w.first + len(w.blocks))
        # never a block wholly behind the first query's window ...
        assert w.first == max(first - W + 1, 0) // BS, (kind, first)
        # ... never one missing inside it or under the launch's own rows
        assert held[-1] == (stop - 1) // BS
        for p in range(max(first - W + 1, 0), stop):
            assert w.row_of(p) == w.blocks[p // BS - w.first].row0 + p % BS
        assert len(w.blocks) <= (m.window_chunk_blocks(m.window_groups[0])
                                 if kind == "chunk" else len(w.table))
        # what is promised and not opened follows what is held
        assert w.reserve == min(w.limit - len(w.blocks), w.left)
        assert pool.reserved == w.reserve
        pool.check_conservation()
        store.pool.check_conservation()
    released = metrics.count("kv_window_blocks_released")
    assert released == w.first
    assert (released > 0) == (plen + new - 2 - W + 1 >= BS)
    store.release(kv)
    for p in (store.pool, pool):
        assert p.check_conservation()["blocks_live"] == 0
        assert p.reserved == 0


def test_a_released_block_is_the_next_owners():
    m = _model(window_num_blocks=7)
    store, _metrics = _store(m)
    pool = store.pools[1]
    a = store.acquire(_Req(30, 2))
    w = a.groups[1]
    assert w.limit == 5 and pool.free_count == 2
    for _ in _walk(store, a, 30, 2):
        pass
    # 8 blocks were opened over the prompt's life out of a promise of 5
    assert pool.allocs == 8 and len(w.blocks) <= 3
    # with nothing left to open, what it gave back is anyone's
    assert w.reserve == w.left == 0
    b = store.acquire(_Req(5, 3))
    assert b is not None
    assert pool.free_count == 7 - len(w.blocks) - 2
    store.release(a)
    store.release(b)
    assert pool.check_conservation()["blocks_free"] == 7


@pytest.mark.parametrize("full,window,admitted", [
    (40, 24, True), (40, 8, False), (10, 24, False)])
def test_admission_waits_for_both_promises(full, window, admitted):
    m = _model(num_blocks=full, window_num_blocks=window)
    store, metrics = _store(m)
    assert store.reserves
    # one sequence is there already: 7 blocks whole, 5 in the window
    there = store.acquire(_Req(20, 5))
    assert there is not None
    req = _Req(20, 5)
    assert store.chain(req) == 7 and store._needs(7) == [7, 5]
    assert store.covers(req, 0) is admitted
    kv = store.acquire(req)
    assert (kv is not None) is admitted
    assert metrics.count("admissions_deferred") == (0 if admitted else 1)
    assert req.held_back is not admitted
    # a request held back leaves nothing promised in either pool
    assert store.pool.reserved + len(there.blocks) == (
        7 + (7 - len(kv.blocks) if admitted else 0))
    assert store.pools[1].reserved == (10 if admitted else 5)
    store.release(there)
    # what the first gave back is what the second waited for
    assert store.covers(req, 0)
    store.release(kv or store.acquire(req))
    for p in store.pools:
        assert p.reserved == 0 and p.check_conservation()["blocks_live"] == 0


def test_a_request_no_window_pool_could_hold_fails_loudly():
    m = _model(window_num_blocks=3)
    store, _metrics = _store(m)
    with pytest.raises(RuntimeError, match="layer group 'sliding' can never"):
        store.acquire(_Req(20, 5))


_BOTH = ("Host it on an engine with prefix_cache_size=0 and host_tier_mb=0 "
         "(got prefix_cache_size={prefix}, host_tier_mb={tier})")
# a fact of the state -> who refuses it, in what words, and for which sizes
REFUSALS = {
    "block_filling": (
        dict(fills_blocks=True), "ServingError", ("prefix", "tier"),
        "model m@1 fills its answer a block of 4 positions at a time: a "
        "block's K/V rows are rewritten by every pass and final only once "
        "it is committed, so neither the prefix cache nor the host KV tier "
        "may hold them. " + _BOTH),
    "recurrent": (
        dict(recurrent=True, chunks_only=True), "EnforceError",
        ("prefix", "tier"),
        "model m@1 keeps per-slot recurrent state, which the prefix cache "
        "and the host KV tier cannot carry: both key on K/V rows, a "
        "function of the token prefix alone, and hold no snapshot of a "
        "state. " + _BOTH),
    "window_group": (
        dict(window_groups=["g"], chunks_only=True), "EnforceError",
        ("prefix", "tier"),
        "model m@1 keeps attention layers in window groups, which give back "
        "the blocks behind a sequence's window: a block that was given back "
        "can be neither shared by a later prompt nor restored from the host "
        "KV tier, and the prefix cache and the tier hold whole prefixes. "
        + _BOTH),
    "indexer": (
        dict(index_names=["i"], chunks_only=True), "EnforceError",
        ("prefix", "tier"),
        "model m@1 keeps an indexer's keys in an arena of their own beside "
        "K and V: the prefix cache and the host KV tier hold K and V rows "
        "alone, so a prefix taken from either would come back without the "
        "keys its rows are chosen by. " + _BOTH),
    "no_inject_program": (
        dict(chunks_only=True), "EnforceError", ("tier",),
        "model m@1 has no inject program: what the host KV tier keeps (an "
        "evicted block's rows, a parked session's) could never be put back. "
        "Host it on an engine with host_tier_mb=0 (got {tier})"),
}


@pytest.mark.parametrize("size", ["prefix", "tier"])
@pytest.mark.parametrize("fact", sorted(REFUSALS))
def test_a_store_refuses_what_the_state_cannot_give_it(fact, size):
    import types

    facts, error, refused, words = REFUSALS[fact]
    model = types.SimpleNamespace(**dict(
        dict(label="m@1", block_len=4, fills_blocks=False, recurrent=False,
             chunks_only=False, window_groups=[], index_names=[]),
        **facts))
    tier, prefix = ((3 << 20, 0) if size == "tier" else (0, 4))
    if size in refused:
        with pytest.raises(RuntimeError) as caught:
            KVStore.check_carries(model, tier, prefix)
        assert type(caught.value).__name__ == error
        assert str(caught.value) == words.format(prefix=prefix,
                                                 tier=tier >> 20)
    else:
        KVStore.check_carries(model, tier, prefix)
    # with nothing to carry every state is hosted, and the store knows what
    # it may still do: share a prompt's blocks, put rows back
    assert KVStore.check_carries(model, 0, 0) == (
        fact in ("block_filling", "no_inject_program"),
        fact == "block_filling")
    real = _model()
    assert KVStore.check_carries(real) == (False, False)
    assert _store(real)[0].reserves


def test_the_steps_feed_names_the_live_blocks_and_masks_what_left():
    m = _model()
    store, _metrics = _store(m)
    kv = store.acquire(_Req(21, 4))
    for _kind, p, _stop in _walk(store, kv, 21, 4):
        pass
    w = kv.groups[1]
    assert p == 23 and w.first == 4       # position 16 is the window's first
    step = m.step_feed()
    assert step.shape == (S, m.step_width) == (S, 4 + 16 + 3 + 3)
    at = m.step_table + m.blocks_per_slot
    # a slot that does not step sees nothing and writes nowhere
    assert (step[:, at] == 0).all()
    assert (step[:, at + 2] == m.window_groups[0].num_blocks * BS).all()
    m.fill_step(step, 2, p, kv.groups)
    length, low, wrow = step[2, at:at + 3]
    assert (length, low) == (p + 1 - 16, 0) and wrow == w.row_of(p)
    assert list(step[2, at + 3:at + 3 + len(w.blocks)]) == [
        b.row0 // BS for b in w.blocks]
    # one position on the oldest block's first row has left the window
    low_at = 24
    assert store.release_behind(kv, low_at, 1) == 0 and w.first == 4
    assert store.open_block(kv, low_at)
    m.fill_step(step, 2, low_at, kv.groups)
    assert step[2, at + 1] == low_at - W + 1 - 16 == 1


def test_paged_window_feeds_by_hand():
    from paddle_tpu.core.registry import OpRegistry

    packed = np.zeros((3, 4 + 2 + 3 + 3), "int32")
    packed[0, 6:] = (6, 1, 21, 5, 2, 9)   # rows [1, 6) of blocks 5, 2
    packed[1, 6:] = (0, 0, 48, 0, 0, 0)   # does not step
    packed[2, 6:] = (12, 3, 30, 7, 8, 1)
    out = OpRegistry.get("paged_window_feeds").lower(
        {"Packed": [packed]}, {"column": 6, "blocks": 3, "block_size": 4})
    bias = np.asarray(out["Bias"][0])
    assert bias.shape == (3, 1, 12)
    assert (bias[0, 0] == 0.0).tolist() == [False] + [True] * 5 + [False] * 6
    assert (bias[1] < -1e8).all()
    assert (bias[2, 0] == 0.0).tolist() == [False] * 3 + [True] * 9
    rows = np.asarray(out["Rows"][0]).reshape(3, 12)
    assert rows[0].tolist() == [20, 21, 22, 23, 8, 9, 10, 11, 36, 37, 38, 39]
    assert np.asarray(out["WriteRows"][0]).tolist() == [21, 48, 30]


def test_the_chunks_feeds_count_from_the_first_live_block():
    m = _model()
    store, _metrics = _store(m)
    kv = store.acquire(_Req(30, 2))
    launches = list(_walk(store, kv, 30, 2))
    sig = dict((name, (shape, dtype)) for name, shape, dtype in
               m.chunk_feed_sig())
    span, rows, wrows = DecodeModel.chunk_group_feeds(0)
    assert (span, rows, wrows) == ("chu_span.g1", "chu_rows.g1",
                                   "chu_write_rows.g1")
    assert sig[rows] == ((5 * BS,), "int64") and sig[wrows] == ((C,), "int64")
    # the last chunk again, as its feeds were built: [24, 30)
    kv2 = store.acquire(_Req(30, 2))
    for kind, start, stop in _walk(store, kv2, 30, 2):
        if (kind, start) == ("chunk", 24):
            break
    feeds = m.chunk_feeds(24, 6, kv2.groups)
    w2 = kv2.groups[1]
    assert w2.first == (24 - W + 1) // BS == 4
    assert feeds[span].tolist() == [24 - 16, 6]
    assert feeds[rows] is w2.row_map
    got = feeds[wrows]
    assert got[:6].tolist() == [w2.row_of(p) for p in range(24, 30)]
    assert (got[6:] == m.window_groups[0].num_blocks * BS).all()
    assert launches[-1][0] == "step"


def _one_group_models():
    from paddle_tpu.serving import build_decoder_model, build_lfm2_model

    yield "decoder", build_decoder_model(
        32, hidden=16, num_layers=1, slots=2, max_len=16, block_size=4,
        chunk_tokens=4, name="one_a")
    yield "lfm2", build_lfm2_model(
        64, 32, ["conv", "full_attention"], num_attention_heads=2,
        num_key_value_heads=1, intermediate_size=48, num_dense_layers=1,
        num_experts=2, router_experts=4, num_experts_per_tok=2,
        moe_intermediate_size=16, dtype="float32", slots=2, max_len=16,
        block_size=4, chunk_tokens=4, name="one_b")


@pytest.mark.parametrize("kind,model", list(_one_group_models()),
                         ids=["decoder", "lfm2"])
def test_a_model_with_one_group_is_fed_what_it_ever_was(kind, model):
    m = model
    assert m.window_groups == [] and m.all_state_names == m.state_names
    assert m.step_width == m.step_table + m.blocks_per_slot == 8
    step = m.step_feed()
    assert step.shape == (2, 8)
    assert [n for n, _s, _d in m.decode_feed_sig()] == ["dec_token",
                                                        "dec_step"]
    assert m.decode_feed_sig()[1][1] == (2, 8)
    names = [n for n, _s, _d in m.chunk_feed_sig()]
    assert names == ["chu_tokens", "chu_positions", "chu_span", "chu_rows",
                     "chu_write_rows"] + (["chu_slot"] if m.recurrent
                                          else [])
    store, metrics = _store(m)
    assert store.window_pools == [] and not store.reserves
    assert store.pools == [store.pool] and store.windowed == []
    kv = store.acquire(_Req(6, 3))
    assert isinstance(kv, SeqKV) and kv.groups == (kv,)
    m.fill_step(step, 1, 6, kv.groups, write=len(kv.blocks) > 1)
    assert step[1, 4:].tolist() == kv.table.tolist()
    assert list(m.chunk_feeds(4, 2, kv.groups)) == names[2:5]
    store.open_windows(kv, 9)
    store.release(kv)
    assert store.pool.check_conservation()["blocks_live"] == 0
    for name in ("kv_window_blocks_released", "attention_rows_read_step"):
        assert metrics.count(name) == 0
    # no op and no attribute of a window in its programs
    for program in (m.decode_program, m.chunk_program):
        ops = program.global_block().ops
        assert "paged_window_feeds" not in [op.type for op in ops]
        assert not any("window" in op.attrs for op in ops)


def _by_hand(groups):
    """What the scheduler feeds of a sequence with the footings ``groups``
    in each case below, written out."""
    if groups == 2:
        # 21 prompt positions in three chunks, then the step at 21. The
        # first group holds blocks 0..5 of its pool of 40; the window group
        # opened 0, 1 then 2, 3 of its pool of 24, gave 0 and 1 back before
        # the chunk at 16 and took them again (in their order, since PR 64:
        # `BlockPool.release`), gave 2 back before the step
        nowhere = np.int64(24 * BS)
        chunk = {
            "chu_span": np.array([16, 5], "int32"),
            "chu_rows": np.array(list(range(24)) + [0] * 40, "int64"),
            "chu_write_rows": np.array(
                [16, 17, 18, 19, 20] + [40 * BS] * 3, "int64"),
            "chu_span.g1": np.array([8, 5], "int32"),
            "chu_rows.g1": np.array(
                list(range(8, 16)) + [0, 1, 2, 3, 4, 5, 6, 7] + [0] * 4,
                "int64"),
            "chu_write_rows.g1": np.array([0, 1, 2, 3, 4] + [nowhere] * 3,
                                          "int64"),
        }
        idle = [-1, 0, 0, 40 * BS] + [0] * 16 + [0, 0, nowhere, 0, 0, 0]
        step = np.array([idle, idle, [
            7, 21, 22, 21, 0, 1, 2, 3, 4, 5] + [0] * 10 + [
            # ten rows from position 12, two of them behind the window
            10, 2, 5, 3, 0, 1], idle], "int32")
        return chunk, step
    chunk = {
        "chu_span": np.array([4, 2], "int32"),
        "chu_rows": np.array(list(range(8)) + [0] * 8, "int64"),
        "chu_write_rows": np.array([4, 5, 32, 32], "int64"),
    }
    step = np.array([[-1, 0, 0, 32, 0, 0, 0, 0],
                     [-1, 6, 7, 6, 0, 1, 0, 0]], "int32")
    return chunk, step


@pytest.mark.parametrize("groups", [2, 1])
def test_the_folded_fillers_feed_the_arrays_written_out_by_hand(groups):
    if groups == 2:
        m, (plen, new), (slot, token) = _model(), (21, 4), (2, 7)
    else:
        (_kind, m), _ = _one_group_models()
        (plen, new), (slot, token) = (6, 3), (1, -1)
    store, _metrics = _store(m)
    kv = store.acquire(_Req(plen, new))
    assert len(kv.groups) == groups == len(store.pools)
    want_chunk, want_step = _by_hand(groups)
    got = {}
    for kind, first, stop in _walk(store, kv, plen, new):
        if kind == "chunk" and stop == plen:
            got = {name: np.array(a) for name, a in
                   m.chunk_feeds(first, stop - first, kv.groups).items()}
        if (kind, first) == ("step", plen):
            break
    step = m.step_feed()
    m.fill_step(step, slot, plen, kv.groups, token)
    sig = {name: (shape, dtype) for name, shape, dtype in m.chunk_feed_sig()}
    assert list(got) == list(want_chunk)
    for name, want in want_chunk.items():
        assert (got[name].shape, str(got[name].dtype)) == sig[name], name
        assert got[name].dtype == want.dtype, name
        assert got[name].tolist() == want.tolist(), name
    assert step.dtype == want_step.dtype == np.int32
    assert step.shape == m.decode_feed_sig()[1][1]
    assert step.tolist() == want_step.tolist()
