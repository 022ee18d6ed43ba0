"""`serving/decode/kvstate.py` with no engine: a `KVStore` over a numpy arena
in a dict, launched through a fake ``run`` that does what the inject program
does (scatter feed row ``j`` to arena row ``inj_rows[j]``, the sentinel
dropped). Nothing is compiled; the model is a real `DecodeModel`, for its
geometry and names alone."""

import types

import numpy as np
import pytest

from paddle_tpu.serving import build_decoder_model
from paddle_tpu.serving.decode.kvstate import (
    ArenaInvalidError,
    KVStore,
    SeqKV,
)
from paddle_tpu.serving.decode.model import DecodeModel
from paddle_tpu.serving.decode.pool import block_hashes

GEOM = dict(vocab_size=32, hidden=8, num_layers=2, slots=4, max_len=32,
            block_size=4, version="1")


class Counts(dict):
    def incr(self, name, n=1):
        self[name] = self.get(name, 0) + n


class Scope:
    def __init__(self, arenas):
        self.arenas = arenas

    def find_var(self, name):
        return self.arenas[name]

    def set(self, name, value):
        self.arenas[name] = np.asarray(value)


class Harness:
    """A store, its arena, and every launch and fetch it made."""

    def __init__(self, name, tier_bytes=1 << 20, prefix=4, **geom):
        m = self.model = build_decoder_model(name=name, **dict(GEOM, **geom))
        self.scope = Scope({n: np.zeros((m.rows, m.kv_width), m.kv_dtype)
                            for pair in m.state_names for n in pair})
        self.counts = Counts()
        self.launches = []      # (kind, feeds, span)
        self.fail = None
        self.store = KVStore(m, tier_bytes, prefix, self.counts,
                             run=self.run, fetch=self.fetch,
                             scope=lambda: self.scope, device=None)

    def run(self, kind, feeds, span):
        self.launches.append((kind, feeds, span))
        if self.fail is not None:
            raise self.fail
        assert kind == "inject"
        m = self.model
        rows = feeds[DecodeModel.INJ_ROWS]
        live = rows < m.rows
        for names, feed_names in zip(m.state_names, m.inject_kv_feeds):
            for arena, feed in zip(names, feed_names):
                self.scope.arenas[arena][rows[live]] = np.asarray(
                    feeds[feed])[0, live]

    def fetch(self, value):
        a = np.asarray(value)
        self.counts.incr("fetched_bytes", a.nbytes)
        return a

    def arena_bytes(self):
        return sum(a.nbytes for a in self.scope.arenas.values())

    def rows_of(self, kv, lo, hi):
        """What the arena holds at a sequence's positions, per pair."""
        idx = kv.row_map[lo:hi]
        return [(self.scope.arenas[k][idx], self.scope.arenas[v][idx])
                for k, v in self.model.state_names]


def _pairs(m, n, seed):
    rng = np.random.RandomState(seed)
    return [(rng.rand(n, m.hidden).astype("float32"),
             rng.rand(n, m.hidden).astype("float32"))
            for _ in m.state_names]


def _request(prompt, max_new, **kw):
    return types.SimpleNamespace(
        prompt=list(prompt), max_new=max_new, beam=None, draft_key=None,
        held_back=False, **kw)


# -- the one inject, at the six former sites' shapes --------------------------

def _site(h, site):
    """``(target, lo, hi, source, rows that must land)`` as each former
    site of the inject idiom handed them over."""
    m, store = h.model, h.store
    L = m.max_len
    if site in ("cow_partial", "beam_tail"):
        kv = store.acquire_rows(9)
        block = kv.blocks[2 if site == "cow_partial" else 1]
        n = 1 if site == "cow_partial" else 3
        want = _pairs(m, n, 5)
        return block, 0, n, want, want
    if site == "resume_whole":
        kv = store.acquire_rows(11)
        want = _pairs(m, 11, 1)
        return kv, 0, 11, want, want
    if site == "tier_blocks":
        kv = store.acquire_rows(14)
        kv.shared_len = 4
        want = _pairs(m, 8, 2)
        return kv, 4, 12, want, want
    kv = store.acquire_rows(10)
    if site == "prefix_suffix_hit":
        # the prefix cache's entry: ONE [2 * pairs, P, H] array from 0
        kv.shared_len = 4
        live = np.random.RandomState(3).rand(
            2 * len(m.state_names), 16, m.hidden).astype("float32")
        want = [(live[i, 4:10], live[i + 1, 4:10])
                for i in range(0, len(live), 2)]
        return kv, 4, 10, live, want
    outs = [np.random.RandomState(4 + i).rand(1, L, m.hidden).astype(
        "float32") for i in range(2 * len(m.state_names))]
    want = [(outs[i][0, :10], outs[i + 1][0, :10])
            for i in range(0, len(outs), 2)]
    if site == "prefix_suffix_miss":
        # the prefill program's outputs, handed over as they are
        return kv, 0, 10, outs, want
    assert site == "draft_prompt"
    return kv, 0, 10, store.host_rows(outs, 10), want


SITES = ["prefix_suffix_hit", "prefix_suffix_miss", "resume_whole",
         "tier_blocks", "cow_partial", "beam_tail", "draft_prompt"]


@pytest.mark.parametrize("site", SITES)
def test_write_rows_feeds_the_given_rows_at_the_mapped_rows(site):
    h = Harness(f"kvs_w_{site}")
    m = h.model
    target, lo, hi, source, want = _site(h, site)
    h.store.write_rows(target, lo, hi, source, None)
    (kind, feeds, _span), = h.launches
    assert kind == "inject"
    rows = feeds[DecodeModel.INJ_ROWS]
    assert rows.shape == (m.max_len,) and rows.dtype == np.int64
    named = (target.row_map[lo:hi] if isinstance(target, SeqKV)
             else target.row0 + np.arange(lo, hi))
    assert (rows[lo:hi] == named).all()
    # every other row of the feed names the sentinel and lands nowhere
    assert (np.delete(rows, np.s_[lo:hi]) == m.rows).all()
    assert set(feeds) == {DecodeModel.INJ_ROWS} | {
        n for pair in m.inject_kv_feeds for n in pair}
    for (kn, vn), (k, v) in zip(m.inject_kv_feeds, want):
        for name, given in ((kn, k), (vn, v)):
            feed = feeds[name]
            assert feed.shape == (1, m.max_len, m.hidden)
            assert feed.dtype == np.float32
            assert (feed[0, lo:hi] == given).all()
            if site != "prefix_suffix_miss":    # host rows: zero padding
                assert not np.delete(feed[0], np.s_[lo:hi], axis=0).any()
    if site == "prefix_suffix_miss":
        # device outputs are not copied
        assert all(feeds[n] is s for n, s in zip(
            [n for pair in m.inject_kv_feeds for n in pair], source))
    # and the arena holds them there, and nothing anywhere else
    for (ka, va), (k, v) in zip(m.state_names, want):
        for arena, given in ((ka, k), (va, v)):
            a = h.scope.arenas[arena]
            assert (a[named] == given).all()
            assert not np.delete(a, named, axis=0).any()


def test_a_failed_inject_raises_one_exception_and_rejects_nobody():
    h = Harness("kvs_fail")
    kv = h.store.acquire_rows(5)
    h.fail = ValueError("donated call died")
    with pytest.raises(ArenaInvalidError, match="donated call died"):
        h.store.write_rows(kv, 0, 5, _pairs(h.model, 5, 0), "decode::inject",
                           request=3)
    # the store gave nothing back: the blocks are still the caller's
    assert h.store.pool.check_conservation()["blocks_live"] == 2


def test_open_block_copies_a_shared_tail_before_the_write():
    """Two sequences share a registered partial tail; the first to append
    gets a private copy, filled by the one inject, and remaps."""
    h = Harness("kvs_cow")
    m, store = h.model, h.store
    prompt = [3, 1, 4, 1, 5, 9]
    live = np.random.RandomState(7).rand(
        2 * len(m.state_names), 8, m.hidden).astype("float32")
    a = store.acquire(_request(prompt, 4))
    store.write_rows(a, 0, 6, live, None)
    store.register(a, prompt, live)
    b = store.acquire(_request(prompt, 4))
    assert b.shared_len == 6 and b.blocks[1] is a.blocks[1]
    before = len(h.launches)
    assert store.open_block(b, 6) is True
    assert b.blocks[1] is not a.blocks[1]
    assert b.table[1] == b.blocks[1].id and b.row_of(4) == b.blocks[1].row0
    (kind, feeds, _), = h.launches[before:]
    assert (feeds[DecodeModel.INJ_ROWS][:2]
            == b.blocks[1].row0 + np.arange(2)).all()
    for (k, v), i in zip(h.rows_of(b, 4, 6), range(0, len(live), 2)):
        assert (k == live[i, 4:6]).all() and (v == live[i + 1, 4:6]).all()
    assert store.stats()["block_pool"]["cow_copies"] == 1


def test_open_block_says_when_the_pool_is_empty():
    h = Harness("kvs_empty", num_blocks=2)
    kv = h.store.acquire_rows(8)
    assert h.store.free_blocks == 0
    blocks = list(kv.blocks)
    assert h.store.open_block(kv, 8) is False
    assert kv.blocks == blocks
    assert h.store.acquire_rows(1) is None


# -- spill and restore --------------------------------------------------------

def test_spill_then_restore_is_byte_for_byte():
    h = Harness("kvs_spill")
    m, store = h.model, h.store
    kv = store.acquire_rows(9)
    rows = _pairs(m, 9, 11)
    store.write_rows(kv, 0, 9, rows, None)
    key, nbytes = store.spill(kv, 9, 41, 0, list(range(9)))
    assert key is not None and key in store.tier
    # every arena came to the host WHOLE, and was counted twice over
    assert nbytes == h.arena_bytes() == h.counts["arena_read_bytes"]
    assert h.counts["fetched_bytes"] == nbytes
    store.release(kv)
    for a in h.scope.arenas.values():
        a[:] = 0
    back = store.acquire_rows(9)

    def never():
        raise AssertionError("a clean entry is not recomputed")

    store.restore(back, key, 9, never)
    for (k, v), (k0, v0) in zip(h.rows_of(back, 0, 9), rows):
        assert k.tobytes() == k0.tobytes() and v.tobytes() == v0.tobytes()
    assert key not in store.tier and "resume_replays" not in h.counts


def test_a_corrupt_spill_is_recomputed_not_served():
    h = Harness("kvs_crc")
    m, store = h.model, h.store
    kv = store.acquire_rows(6)
    store.write_rows(kv, 0, 6, _pairs(m, 6, 12), None)
    key, _ = store.spill(kv, 6, 42, 0, list(range(6)))
    assert store.tier.corrupt_entry(key)
    outs = [np.random.RandomState(20 + i).rand(1, m.max_len, m.hidden)
            .astype("float32") for i in range(2 * len(m.state_names))]
    store.restore(kv, key, 6, lambda: outs)
    for (k, v), i in zip(h.rows_of(kv, 0, 6), range(0, len(outs), 2)):
        assert (k == outs[i][0, :6]).all() and (v == outs[i + 1][0, :6]).all()
    assert h.counts["resume_replays"] == 1
    assert store.stats()["host_tier"]["corrupt_dropped"] == 1


def test_a_tier_that_cannot_take_the_rows_says_so_and_keeps_nothing():
    h = Harness("kvs_small_tier", tier_bytes=64)
    kv = h.store.acquire_rows(9)
    key, nbytes = h.store.spill(kv, 9, 43, 0, list(range(9)))
    assert key is None and nbytes == h.arena_bytes()
    assert len(h.store.tier) == 0
    h.store.drop_spilled(["park:43:0", "park:43:1"])    # harmless


def test_restore_prefix_takes_written_back_blocks_from_a_boundary():
    h = Harness("kvs_prefix")
    m, store = h.model, h.store
    bs = m.block_size
    prompt = list(range(1, 15))
    hashes = block_hashes(prompt, bs)
    stored = {}
    for i in (1, 2):
        stored[i] = _pairs(m, bs, 30 + i)
        store.tier.put("blk:" + hashes[i], stored[i], bs,
                       tokens=prompt[i * bs:(i + 1) * bs])
    kv = store.acquire_rows(len(prompt))
    kv.shared_len = bs + 1      # a shared partial tail: not from a boundary
    assert store.restore_prefix(kv, prompt, request=1) == 0
    kv.shared_len = bs
    assert store.restore_prefix(kv, prompt, request=1) == 3 * bs
    assert h.counts["tier_hits"] == 2
    for i in (1, 2):
        for (k, v), (k0, v0) in zip(h.rows_of(kv, i * bs, (i + 1) * bs),
                                    stored[i]):
            assert (k == k0).all() and (v == v0).all()
    assert not any(h.rows_of(kv, 0, bs)[0][0].ravel())


# -- a beam fork --------------------------------------------------------------

def test_a_forks_tail_copy_is_counted_as_an_arena_read():
    h = Harness("kvs_fork")
    m, store = h.model, h.store
    parent = store.acquire_rows(6)
    rows = _pairs(m, 6, 13)
    store.write_rows(parent, 0, 6, rows, None)
    assert "arena_read_bytes" not in h.counts
    child = store.fork(parent, 6)
    assert h.counts["arena_read_bytes"] == h.arena_bytes()
    assert h.counts["fetched_bytes"] == h.arena_bytes()
    assert child.blocks[0] is parent.blocks[0]
    assert child.blocks[0].refcount == 2
    assert child.blocks[1] is not parent.blocks[1]
    for (k, v), (k0, v0) in zip(h.rows_of(child, 0, 6), rows):
        assert (k == k0).all() and (v == v0).all()
    store.pool.check_conservation()
    # an aligned fork copies nothing
    store.fork(parent, 4)
    assert h.counts["arena_read_bytes"] == h.arena_bytes()


def test_a_fork_the_pool_cannot_cover_raises():
    h = Harness("kvs_fork_full", num_blocks=2)
    parent = h.store.acquire_rows(6)
    with pytest.raises(RuntimeError, match="exhausted forking a beam"):
        h.store.fork(parent, 6)


# -- admission by reservation -------------------------------------------------

def test_acquire_promises_a_whole_chain_or_nothing():
    h = Harness("kvs_reserve", tier_bytes=0, num_blocks=8)
    store = h.store
    assert store.reserves
    first = _request([1, 2, 3, 4, 5], 11)           # 16 rows: 4 blocks
    assert store.chain(first) == store.admission_blocks(first) == 4
    kv = store.acquire(first)
    assert len(kv.blocks) == 2 and kv.reserve == 2
    assert store.free_blocks == 4
    assert h.counts["blocks_reserved"] == 4
    second = _request([9, 8, 7], 15)                # 18 rows: 5 blocks
    assert not store.covers(second, 0) and second.held_back
    assert store.acquire(second) is None
    assert store.acquire(second) is None
    assert h.counts["admissions_deferred"] == 1     # once a request
    # the promise is what the sequence opens its blocks out of
    assert store.open_block(kv, 8) is True
    assert kv.reserve == 1 and len(kv.blocks) == 3
    assert store.free_blocks == 4
    assert store.open_block(kv, 9) is True          # same block: no change
    assert kv.reserve == 1
    # and release hands back the part never opened
    store.release(kv)
    assert store.free_blocks == 8 and store.pool.reserved == 0
    assert store.acquire(second).reserve == 4
    assert store.stats()["block_pool"]["blocks_reserved"] == 4


def test_a_chain_no_pool_could_hold_fails_loudly():
    h = Harness("kvs_never", tier_bytes=0, num_blocks=3)
    with pytest.raises(RuntimeError, match="can never fit"):
        h.store.acquire(_request([1, 2, 3, 4, 5], 11))
    assert h.counts["blocks_failed_total"] == 1
    assert h.store.covers(_request([1, 2, 3, 4, 5], 11), 0)  # goes on, to fail


@pytest.mark.parametrize("kind", ["beam", "speculative", "with_a_tier"])
def test_what_is_served_from_what_is_promised_to_nobody(kind):
    tier = 1 << 20 if kind == "with_a_tier" else 0
    h = Harness(f"kvs_nochain_{kind}", tier_bytes=tier, num_blocks=8)
    req = _request([1, 2, 3, 4, 5], 11)
    if kind == "beam":
        req.beam = object()
    elif kind == "speculative":
        req.draft_key = ("d", "1")
    assert h.store.chain(req) == 0
    assert h.store.admission_blocks(req) == 2
    kv = h.store.acquire(req)
    assert kv.reserve == 0 and h.store.pool.reserved == 0


def test_an_exhausted_pool_without_reservation_gives_none_then_blocks():
    h = Harness("kvs_exhaust", num_blocks=3)
    kv = h.store.acquire(_request([1] * 8, 4))
    assert h.store.acquire(_request([2] * 8, 4)) is None
    assert "blocks_exhausted" not in h.counts       # the scheduler's count
    with pytest.raises(RuntimeError, match="can never fit"):
        h.store.acquire(_request([3] * 13, 4))
    h.store.release(kv)
    assert h.store.acquire(_request([2] * 8, 4)) is not None


# -- the rest of the surface --------------------------------------------------

def test_chunk_write_rows_skip_what_is_shared_and_pad_with_the_sentinel():
    h = Harness("kvs_chunk")
    m = h.model
    kv = h.store.acquire_rows(11)
    kv.shared_len = 6
    rows = kv.chunk_write_rows(4, 11, 8)
    assert rows.shape == (8,) and rows.dtype == np.int64
    assert (rows[:2] == m.rows).all() and rows[7] == m.rows
    assert (rows[2:7] == kv.row_map[6:11]).all()
    assert (kv.chunk_write_rows(0, 4, 8) == m.rows).all()


def test_the_prefix_cache_and_registration_share_through_the_store():
    h = Harness("kvs_share")
    m, store = h.model, h.store
    prompt = [5, 6, 7, 8, 9]
    key, entry = store.prefix_get(prompt)
    assert entry is None
    live = np.ones((2 * len(m.state_names), 8, m.hidden), "float32")
    store.prefix_put(key, live, np.arange(m.vocab_size, dtype="float32"))
    key2, entry = store.prefix_get(prompt)
    assert key2 == key and (entry[0] == live).all()
    kv = store.acquire(_request(prompt, 3))
    store.register(kv, prompt, live)
    again = store.acquire(_request(prompt, 3))
    assert again.shared_len == 5 and again.blocks == kv.blocks
    stats = store.stats()
    assert stats["prefix_hits"] == 1 and stats["prefix_misses"] == 1
    assert stats["prefix_cache_entries"] == 1
    assert stats["block_dedup_ratio"] == stats["block_pool"]["dedup_ratio"]
    assert stats["block_dedup_ratio"] > 1


def test_reset_zeroes_the_arenas_and_empties_the_pool():
    h = Harness("kvs_reset")
    kv = h.store.acquire_rows(9)
    h.store.write_rows(kv, 0, 9, _pairs(h.model, 9, 2), None)
    assert any(a.any() for a in h.scope.arenas.values())
    h.store.reset()
    assert not any(np.asarray(a).any() for a in h.scope.arenas.values())
    assert h.store.free_blocks == h.model.num_blocks
