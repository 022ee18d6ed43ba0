"""The KV block pool and the host tier account for themselves (ISSUE 38).

A pool of 32 blocks (what PERF.md's count of the stall used) served one
unique prompt after another, hand-stepped: a retired prompt's full blocks
stay registered in the LRU, so the free list runs out and every further
block evicts a cached one, whose rows the pool writes back to the host tier
through the engine's reader: every K and V arena brought to the host WHOLE.
What the pool alone sees is counted where it happens, through the sink the
engine hands it (``DecodeMetrics.incr``): the counters equal the pool's own
attributes, the bytes are fetches like any other, each write-back is a
``decode::writeback`` span, and a parked session's ``decode::spill`` says
what it read.
"""

import numpy as np
import pytest
from decode_testing import sharpen

from paddle_tpu import observability as obs
from paddle_tpu.serving.decode import GenerationEngine, build_decoder_model
from paddle_tpu.serving.decode.pool import BlockPool

BLOCKS, BLOCK, LAYERS = 32, 4, 2
PROMPT, MAX_NEW, REQUESTS = 8, 3, 24     # two full blocks and a tail each

FETCH_SPANS = ("decode::step_fetch", "decode::prefill_fetch",
               "decode::chunk_fetch", "decode::writeback")


def _arenas_nbytes(model):
    """What one read of every K and V arena brings to the host."""
    return 2 * len(model.state_names) * model.rows * model.kv_width * 4


def _pool_numbers(entry):
    st = entry.stats()
    return dict(st["block_pool"], **{k: st[k] for k in (
        "pool_block_allocs", "pool_evictions", "tier_writebacks",
        "arena_read_bytes", "fetched_bytes")})


@pytest.fixture(scope="module")
def churned():
    """``REQUESTS`` unique prompts, one at a time, traced; the pool's
    numbers after each."""
    obs.get_tracer().clear()
    engine = GenerationEngine(queue_depth=8, breaker_threshold=0)
    entry = engine.register_model(lambda: build_decoder_model(
        vocab_size=32, hidden=8, num_layers=LAYERS, slots=2, max_len=16,
        block_size=BLOCK, num_blocks=BLOCKS, chunk_tokens=4,
        name="pool32", version="1"))
    rng = np.random.RandomState(5)
    after = []
    obs.enable_tracing()
    try:
        for _ in range(REQUESTS):
            resp = engine.submit([int(t) for t in rng.randint(0, 32, PROMPT)],
                                 max_new_tokens=MAX_NEW)
            for _ in range(200):
                if resp.done():
                    break
                entry._iterate()
            assert resp.error() is None
            after.append(_pool_numbers(entry))
    finally:
        obs.disable_tracing()
    spans = obs.get_tracer().spans()
    obs.get_tracer().clear()
    return entry, after, spans


def test_the_first_eviction_comes_when_the_free_list_is_empty(churned):
    _entry, after, _spans = churned
    per_request = -(-(PROMPT + MAX_NEW) // BLOCK)
    first = next(i for i, n in enumerate(after) if n["evictions"])
    # until then every block handed out came off the free list, which the
    # requests before left too short for this one
    assert all(n["evictions"] == 0 for n in after[:first])
    assert after[first - 1]["blocks_free"] < per_request
    assert after[first - 1]["blocks_cached"] \
        == (PROMPT // BLOCK) * first           # each prompt's full blocks
    # and from then on every request evicts: nothing comes back but a tail
    evicted = [n["evictions"] for n in after[first:]]
    assert all(b > a for a, b in zip(evicted, evicted[1:]))
    for n in after:
        assert n["blocks_free"] + n["blocks_cached"] + n["blocks_live"] \
            == BLOCKS
    assert after[-1]["allocs"] == REQUESTS * per_request


@pytest.mark.parametrize("counter, attribute", [
    ("pool_block_allocs", "allocs"),
    ("pool_evictions", "evictions"),
    ("tier_writebacks", "tier_writebacks"),
])
def test_a_pool_counter_equals_the_pools_own_attribute(
        churned, counter, attribute):
    entry, after, _spans = churned
    for n in after:
        assert n[counter] == n[attribute]
    assert after[-1][counter] > 0
    assert entry.metrics.count(counter) \
        == getattr(entry.block_pool, attribute) \
        == entry.block_pool.stats()[attribute]
    # the registry's series, which the benchmark's snapshots hold
    family = f"serving_{counter}_total"
    line = [ln for ln in obs.scrape_text().splitlines()
            if ln.startswith(family) and entry.metrics.engine_label in ln]
    assert [float(ln.rsplit(" ", 1)[1]) for ln in line] \
        == [after[-1][counter]]


def test_an_eviction_reads_every_arena_whole_and_says_so(churned):
    entry, after, spans = churned
    whole = _arenas_nbytes(entry.model)
    assert whole == 2 * LAYERS * BLOCKS * BLOCK * 8 * 4
    # every evicted block here is a prompt's full block: one read each
    for n in after:
        assert n["arena_read_bytes"] == n["evictions"] * whole
        assert n["tier_writebacks"] == n["evictions"]
    writebacks = [s for s in spans if s["name"] == "decode::writeback"]
    assert len(writebacks) == after[-1]["evictions"]
    for s in writebacks:
        assert s["args"]["bytes"] == whole and s["args"]["rows"] == BLOCK
        assert 0 <= s["args"]["block"] < BLOCKS
    # the blocks go in LRU order: the oldest prompt's first
    assert [s["args"]["block"] for s in writebacks[:2]] == [0, 1]


def test_the_arena_reads_are_fetches_like_any_other(churned):
    """``serving_fetched_bytes_total`` is every fetch brought to the
    host: it moves by the arena reads too, and over the run it is the sum
    of the bytes its spans say."""
    entry, after, spans = churned
    first = next(i for i, n in enumerate(after) if n["evictions"])
    quiet = after[1]["fetched_bytes"] - after[0]["fetched_bytes"]
    for a, b in zip(after, after[1:]):
        assert (b["fetched_bytes"] - a["fetched_bytes"]
                == quiet + b["arena_read_bytes"] - a["arena_read_bytes"])
    assert after[first]["arena_read_bytes"] > 0
    assert entry.metrics.count("fetched_bytes") == sum(
        s["args"]["bytes"] for s in spans if s["name"] in FETCH_SPANS)


def test_a_pool_without_a_sink_counts_in_its_attributes_alone():
    pool = BlockPool(4, 2)
    blocks = pool.acquire_rows(8)
    assert len(blocks) == 4 and pool.allocs == 4 and pool.evictions == 0
    assert pool.acquire_rows(2) is None and pool.allocs == 4
    seen = []
    sunk = BlockPool(4, 2, count=lambda name, n=1: seen.append((name, n)))
    sunk.acquire_rows(3)
    assert seen == [("pool_block_allocs", 1)] * 2


def test_a_parked_sessions_spill_says_what_it_read():
    """Two sessions against a 12-row pool: one parks mid-generation. Its
    ``decode::spill`` read every arena whole for the rows it keeps, and
    says so; the counter holds the same bytes."""
    obs.get_tracer().clear()
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    entry = sharpen(engine.register_model(lambda: build_decoder_model(
        vocab_size=32, hidden=8, num_layers=1, slots=2, max_len=16,
        block_size=2, num_blocks=6, name="pool_park", version="1")))
    prompts = [[1, 2, 3, 4], [5, 6, 7, 8]]
    refs = [entry.offline_decode(p, 6) for p in prompts]
    before = entry.metrics.count("fetched_bytes")
    obs.enable_tracing()
    try:
        resps = [engine.submit(p, max_new_tokens=6) for p in prompts]
        for _ in range(800):
            if all(r.done() for r in resps):
                break
            entry._iterate()
    finally:
        obs.disable_tracing()
    spans = obs.get_tracer().spans()
    obs.get_tracer().clear()
    assert [[int(t) for t in r.result()["tokens"]] for r in resps] == refs
    st = entry.stats()
    assert st["sessions_parked"] == st["sessions_resumed"] >= 1
    spills = [s for s in spans if s["name"] == "decode::spill"]
    assert len(spills) == st["sessions_parked"]
    whole = _arenas_nbytes(entry.model)
    assert [s["args"]["bytes"] for s in spills] == [whole] * len(spills)
    read = sum(s["args"]["bytes"] for s in spans
               if s["name"] in ("decode::spill", "decode::writeback"))
    assert st["arena_read_bytes"] == read > 0
    assert st["fetched_bytes"] - before == read + sum(
        s["args"]["bytes"] for s in spans if s["name"] in FETCH_SPANS[:3])


# -- the order chains come back in (ISSUE 64) ------------------------------

def _ids(blocks):
    return [b.id for b in blocks]


def _is_one_run(ids):
    return all(b - a == 1 for a, b in zip(ids, ids[1:]))


def test_a_chain_released_and_opened_again_comes_back_ascending():
    """`release` pushes a chain's private blocks so that the free list
    hands them out in the chain's order again: blocks that lay side by
    side in the arena come back side by side (the paged kernels copy such
    a run in one descriptor), not reversed."""
    pool = BlockPool(96, 4)
    first = pool.acquire_rows(4 * 20)
    second = pool.acquire_rows(4 * 30)
    assert _ids(first) == list(range(20))
    assert _ids(second) == list(range(20, 50))
    pool.release(first)
    again = pool.acquire_rows(4 * 20)
    assert _ids(again) == list(range(20))
    # the later chain released first, then the earlier one: each is a run
    pool.release(second)
    pool.release(again)
    a, b = pool.acquire_rows(4 * 20), pool.acquire_rows(4 * 30)
    assert _ids(a) == list(range(20)) and _ids(b) == list(range(20, 50))
    pool.check_conservation()


@pytest.mark.parametrize("churn", [0, 3, 9])
def test_a_chunks_open_promised_off_a_recycled_pool_is_one_run(churn):
    """A chunk of 1,024 tokens opens 64 blocks in one call: off a pool
    whose chains of 64 and more came and went ``churn`` times it is ONE
    ascending run, as off a fresh one."""
    pool = BlockPool(512, 16)
    rng = np.random.RandomState(churn)
    held = []
    for _ in range(churn):
        assert pool.reserve(128)
        held.append(pool.open_promised(64) + pool.open_promised(64))
        if len(held) > 2:
            pool.release(held.pop(int(rng.randint(len(held)))))
    for chain in held:
        pool.release(chain)
    assert pool.reserve(64)
    chunk = pool.open_promised(64)
    assert all(b.size_used == 16 for b in chunk)
    assert _is_one_run(_ids(chunk)), _ids(chunk)
    assert pool.reserved == 0
    pool.check_conservation()


def test_a_windows_recycled_blocks_come_back_in_their_order():
    """`recycle` (what lies behind a window) is `release` for an owner
    that goes on: the blocks it gives back are the next it opens, in the
    order it held them."""
    pool = BlockPool(40, 4)
    assert pool.reserve(12)
    chain = pool.open_promised(12)
    pool.recycle(chain[:8], keep=8)
    assert pool.reserved == 8
    assert _ids(pool.open_promised(8)) == _ids(chain[:8])


def test_the_pools_order_leaves_eviction_reservations_and_stats_alone():
    """What `release` changed is the ORDER of the free list alone: the LRU
    still evicts a retired chain's registered blocks head first, a
    reservation is still what `reserve` promised less what was opened, and
    `stats` counts what it counted."""
    pool = BlockPool(12, 2)
    prompts = [[1, 2, 3, 4, 5, 6], [7, 8, 9, 10, 11, 12]]
    chains = []
    for toks in prompts:
        blocks, shared = pool.acquire_for_prompt(toks)
        assert shared == 0
        pool.register_prompt_blocks(blocks, toks)
        chains.append(blocks)
    private = pool.acquire_rows(8)
    assert _ids(chains[0] + chains[1] + private) == list(range(10))
    for chain in chains:
        pool.release(chain)
    pool.release(private)
    st = pool.stats()
    assert (st["blocks_free"], st["blocks_cached"], st["blocks_live"]) == (
        6, 6, 0)
    assert st["allocs"] == 10 and st["evictions"] == 0
    # the free list first (the private chain in its order, then the
    # blocks never handed out), then the LRU: the first chain's head first
    assert pool.reserve(12) and not pool.reserve(1)
    opened = _ids(pool.open_promised(12))
    assert opened == [6, 7, 8, 9, 10, 11, 0, 1, 2, 3, 4, 5]
    assert pool.reserved == 0 and pool.evictions == 6
    assert pool.stats()["radix_entries"] == 0
    pool.check_conservation()
