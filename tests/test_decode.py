"""Continuous-batching decode engine (paddle_tpu/serving/decode).

The acceptance contract (ISSUE 10, extended by ISSUE 13 to the paged
rebuild): generation through the iteration-level scheduler is
bit-identical to offline whole-sequence decode for the same prompts
REGARDLESS of admission order, slot assignment, what the other slots
are doing, or MODE — paged block storage, chunked prefill, speculative
decoding with greedy acceptance; prompts sharing a prefix share
PHYSICAL blocks (radix tree, copy-on-write at divergence); a killed
replica is re-admitted by the circuit breaker as an AOT-warmed
replacement with zero recompiles; a fresh process restores all three
default executables (decode step / prefill / inject) from the
compile-cache disk tier with zero traces — subprocess-asserted like
tests/test_compile_cache.py; and the paged-decode claims (static
peak-HBM paged-vs-slotted, block dedup, speculative steps-per-token
with zero retraces) hold live.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest
from decode_testing import (
    SPEC_MAX_NEW,
    jits,
    sharpen,
    spec_leg,
    without_token_fetch,
)

from paddle_tpu.resilience import faults
from paddle_tpu.serving.decode import (
    GenerationEngine,
    GenerationRequest,
    build_decoder_model,
)
from paddle_tpu.serving.queue import RequestQueue
from paddle_tpu.serving.request import (
    DeadlineExceededError,
    Priority,
    RejectedError,
    RequestError,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "decode_worker.py")


def _small_model(name="dec", version="1", slots=4, max_len=16, eos_id=None):
    return build_decoder_model(
        vocab_size=32, hidden=8, num_layers=2, slots=slots,
        max_len=max_len, eos_id=eos_id, name=name, version=version,
    )


@pytest.fixture(scope="module")
def served():
    """One warm engine + entry shared by the read-mostly tests."""
    engine = GenerationEngine(queue_depth=64, breaker_threshold=0)
    entry = engine.register_model(
        lambda: _small_model(name="shared", slots=4, max_len=16))
    engine.start()
    yield engine, entry
    engine.shutdown()


# ---------------------------------------------------------------------------
# bit-exactness: continuous == offline under arbitrary interleavings
# ---------------------------------------------------------------------------


def test_continuous_decode_matches_offline_any_admission_order(served):
    """10 mixed-length prompts, submitted in shuffled orders with jittered
    arrivals and mixed priorities over a 4-slot batch: every request's
    tokens equal the offline whole-sequence reference, although slot
    assignment and batchmates differ per round (retirement order
    permutes the free-slot list between rounds)."""
    engine, entry = served
    rng = np.random.RandomState(7)
    prompts = [list(rng.randint(0, 32, size=rng.randint(1, 7)))
               for _ in range(10)]
    max_news = [int(rng.randint(1, 9)) for _ in range(10)]
    refs = [entry.offline_decode(p, n) for p, n in zip(prompts, max_news)]

    for round_seed in (0, 1):
        order = np.random.RandomState(round_seed).permutation(10)
        resps = {}
        for i in order:
            resps[int(i)] = engine.submit(
                prompts[i], max_new_tokens=max_news[i],
                priority=int(i) % 3,
            )
            if int(i) % 3 == 0:
                time.sleep(0.002)  # stagger arrivals across iterations
        for i, r in resps.items():
            got = [int(t) for t in r.result(timeout=120)["tokens"]]
            assert got == refs[i], (
                f"round {round_seed} prompt {i}: continuous {got} != "
                f"offline {refs[i]}")


def test_decode_modes_tokens_equal_kernels_on_vs_off():
    """Paged, chunked, and speculative decode under the kernel registry's
    "interpret" mode (the blocked paged-attention kernel through the
    Pallas interpreter) vs "off" (the composite), with SHUFFLED admission
    orders: every request's tokens equal the offline reference in both
    modes, so the two modes' tokens equal each other. The kernel is an
    online softmax, within 1e-5 of the composite and not its bytes
    (tests/test_kernels.py; the logits are held to that tolerance, and
    replay to bytes, in tests/test_paged_kernel_engine.py)."""
    from paddle_tpu import kernels

    rng = np.random.RandomState(11)
    prompts = [list(int(t) for t in rng.randint(0, 32, size=n))
               for n in (9, 8, 2, 12, 5)]
    max_news = [5, 6, 4, 5, 6]

    def drive(mode, order_seed):
        with kernels.scoped_mode(mode):
            engine = GenerationEngine(queue_depth=32, breaker_threshold=0)
            entry = engine.register_model(lambda: build_decoder_model(
                vocab_size=32, hidden=8, num_layers=2, slots=4,
                max_len=24, block_size=4, chunk_tokens=4,
                name="kmode", version="1"))
            engine.register_model(lambda: build_decoder_model(
                vocab_size=32, hidden=8, num_layers=2, slots=4,
                max_len=24, block_size=4, name="kmode_d", version="1"))
            refs = [entry.offline_decode(p, n)
                    for p, n in zip(prompts, max_news)]
            order = np.random.RandomState(order_seed).permutation(
                len(prompts))
            resps = {}
            for i in order:
                resps[int(i)] = engine.submit(
                    prompts[i], max_new_tokens=max_news[i], model="kmode")
            spec = engine.submit(prompts[0], max_new_tokens=5,
                                 model="kmode", draft_model="kmode_d",
                                 spec_k=2)
            for _ in range(300):
                if spec.done() and all(r.done() for r in resps.values()):
                    break
                entry._iterate()
            outs = [
                [int(t) for t in resps[i].result(timeout=120)["tokens"]]
                for i in range(len(prompts))
            ]
            assert outs == refs, f"mode {mode}: continuous != offline"
            outs.append(
                [int(t) for t in spec.result(timeout=120)["tokens"]])
            engine.shutdown()
            return outs

    # different admission orders per mode pair: the tokens must not depend
    # on slot assignment or batchmates (the PR-13 property)
    assert drive("off", 0) == drive("interpret", 1)
    assert drive("interpret", 2) == drive("off", 3)


def test_eos_and_arena_edge_finish_rules_match_offline():
    """eos stop and prompt-fills-arena edge both fire identically in the
    continuous and offline paths (the finish rules are the contract,
    not an implementation detail). The eos token is probed from what the
    greedy head ACTUALLY generates (eos_id is host-side policy, so the
    probe model and the served model share byte-identical programs and
    weights under the same (name, version) prefix)."""
    prompt = [1, 2, 3]
    probe = GenerationEngine(queue_depth=16, breaker_threshold=0)
    free_run = probe.register_model(
        lambda: _small_model(name="eos", slots=2, max_len=10)
    ).offline_decode(prompt, 6)
    assert len(free_run) == 6  # nothing stops it without an eos rule
    # first token whose first occurrence is mid-stream: stopping on it is
    # observable (shorter than the free run) and unambiguous (index 0 of
    # that token IS the stop point)
    eos_at = next((j for j in range(1, len(free_run) - 1)
                   if free_run[j] not in free_run[:j]), 0)
    eos_id = free_run[eos_at]

    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    entry = engine.register_model(
        lambda: _small_model(name="eos", slots=2, max_len=10, eos_id=eos_id))
    engine.start()
    try:
        want = entry.offline_decode(prompt, 6)
        assert want == free_run[:eos_at + 1]  # stopped early, ON the eos
        got = [int(t) for t in engine.submit(
            prompt, max_new_tokens=6).result(timeout=120)["tokens"]]
        assert got == want and got[-1] == eos_id
        # arena edge: prompt + max_new fills the KV arena exactly
        edge = [4, 5, 6, 7]
        assert engine.submit(edge, max_new_tokens=6).result(
            timeout=120)["tokens"].shape[0] <= 6
        assert [int(t) for t in engine.submit(edge, max_new_tokens=6)
                .result(timeout=120)["tokens"]] == entry.offline_decode(edge, 6)
    finally:
        engine.shutdown()


def test_prefix_cache_dedups_prefill_bit_exactly(served):
    """Two requests with the same prompt pay ONE prefill forward; the
    cache-hit admission generates the same tokens as the miss."""
    engine, entry = served
    prompt = [9, 9, 8, 7]
    hits0 = entry.prefix_cache.hits
    prefills0 = entry.metrics.count("prefills")
    r1 = engine.submit(prompt, max_new_tokens=5)
    out1 = [int(t) for t in r1.result(timeout=120)["tokens"]]
    r2 = engine.submit(prompt, max_new_tokens=5)
    out2 = [int(t) for t in r2.result(timeout=120)["tokens"]]
    assert out1 == out2 == entry.offline_decode(prompt, 5)
    assert entry.prefix_cache.hits >= hits0 + 1
    assert entry.metrics.count("prefills") == prefills0 + 1


# ---------------------------------------------------------------------------
# a one-shot admission moves no bulk bytes across the host link (ISSUE 37)
# ---------------------------------------------------------------------------

ONE_SHOT = dict(vocab_size=32, hidden=8, num_layers=2, slots=4, max_len=16,
                block_size=4)


@pytest.fixture(scope="module", params=["chunk_program", "no_chunk_program"])
def one_shot(request):
    """A sharpened entry, never started, with a chunk program (prompts of
    up to ``chunk_tokens`` = 8 are one-shot: P = 8) or without (every
    prompt is: P = ``max_len`` = 16)."""
    chunked = request.param == "chunk_program"
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    entry = sharpen(engine.register_model(lambda: build_decoder_model(
        name="oneshot_" + request.param, version="1",
        chunk_tokens=8 if chunked else None, **ONE_SHOT)))
    return engine, entry, 8 if chunked else ONE_SHOT["max_len"]


@pytest.mark.parametrize("plen", [1, 4, 5, 8], ids=[
    "one_token", "block_multiple", "block_multiple_plus_1", "chunk_tokens"])
def test_one_shot_admission_fetches_one_row_and_the_live_rows(one_shot, plen):
    """On a prefix-cache miss the host takes the ``[V]`` logits row and
    ONE ``[2 * layers, P, H]`` array of K/V rows, and feeds the prefill
    program's tokens and positions and the inject program's row map:
    neither the causal bias (a constant on the device since
    registration) nor any K/V (the prefill program's outputs are the
    inject program's feeds). The prefix cache keeps those P rows a layer,
    and the served tokens equal the offline reference's."""
    engine, entry, P = one_shot
    V, H, L = (ONE_SHOT[k] for k in ("vocab_size", "hidden", "max_len"))
    layers = ONE_SHOT["num_layers"]
    prompt = [20 + plen] + [int(t) for t in range(3, 2 + plen)]
    ref = entry.offline_decode(prompt, 4)
    count = entry.metrics.count
    fed0, fetched0 = count("fed_bytes"), count("fetched_bytes")
    injects0, entries0 = count("prefill_device_injects"), len(
        entry.prefix_cache)
    resp = engine.submit(prompt, max_new_tokens=4)
    assert entry._admit_free_slots() == 1
    assert count("fetched_bytes") - fetched0 \
        == V * 4 + 2 * layers * P * H * 4
    assert count("fed_bytes") - fed0 == 3 * L * 8
    assert count("prefill_device_injects") - injects0 == 1
    assert len(entry.prefix_cache) == entries0 + 1
    live, row = entry.prefix_cache.get(next(reversed(
        entry.prefix_cache._map)))
    assert live.shape == (2 * layers, P, H)
    assert live.nbytes == 2 * layers * P * H * 4 and row.shape == (V,)
    for _ in range(8):
        entry._iterate()
    assert [int(t) for t in resp.result(timeout=60)["tokens"]] == ref
    # the same prompt again is a hit: nothing prefilled, nothing fetched
    # but its steps' tokens, and the same answer from the trimmed rows
    fetched0, prefills0 = count("fetched_bytes"), count("prefills")
    resp = engine.submit(prompt, max_new_tokens=4)
    assert entry._admit_free_slots() == 1
    assert count("fetched_bytes") == fetched0
    assert count("prefills") == prefills0
    assert count("prefill_device_injects") - injects0 == 1
    for _ in range(8):
        entry._iterate()
    assert [int(t) for t in resp.result(timeout=60)["tokens"]] == ref


def test_nothing_compiles_after_register_model():
    """Twenty admissions of twenty distinct prompt lengths, one-shot
    (1..8) and chunked (9..20), served to the end: jax's backend-compile
    events and the repo's own counters, counted by the benchmark's own
    ``Compiles`` (``chip_smoke.py``'s arithmetic), stand still, as
    ``compiles_in_window`` must on the chip. The position of the logits
    row is an operand of the picker, not a slice baked into a program."""
    import jax

    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from benchmark.compiles import Compiles

    engine = GenerationEngine(queue_depth=32, breaker_threshold=0)
    entry = engine.register_model(lambda: build_decoder_model(
        vocab_size=32, hidden=8, num_layers=2, slots=4, max_len=32,
        block_size=4, chunk_tokens=8, name="nocompile", version="1"))
    rng = np.random.RandomState(37)
    prompts = [[int(t) for t in rng.randint(0, 32, size=n)]
               for n in range(1, 21)]
    compiles = Compiles()
    before = compiles.snapshot()
    resps = [engine.submit(p, max_new_tokens=3) for p in prompts]
    for _ in range(400):
        if all(r.done() for r in resps):
            break
        entry._iterate()
    assert all(len(r.result(timeout=60)["tokens"]) == 3 for r in resps)
    assert entry.metrics.count("prefills") == 8
    assert entry.metrics.count("prefill_device_injects") == 8
    assert Compiles.moved(before, compiles.snapshot()) == 0, \
        "an admission compiled after registration"
    # the count is live: a computation jax has not seen moves it
    jax.jit(lambda x: x * 37 + 1)(np.arange(37))
    assert Compiles.moved(before, compiles.snapshot()) > 0


# ---------------------------------------------------------------------------
# multi-tenant registry + weighted-fair scheduling
# ---------------------------------------------------------------------------


def _queued(queue, rid, tenant, priority=Priority.NORMAL):
    req = GenerationRequest(rid, [1], 4, tenant, priority, None)
    queue.put(req)
    return req


def _pick_locked(engine, queue):
    """_pick's documented contract: the caller holds queue.lock (the
    scheduler calls it under its dispatch Condition). The lockdep witness
    enforces the declared serving.queue -> decode.tenant order, so the
    hand-stepped tests must honor the contract too."""
    with queue.lock:
        return engine._pick(queue)


def test_weighted_fair_pick_honors_stride_shares():
    """Under contention, a weight-2 tenant wins two slots for every one a
    weight-1 tenant wins (deterministic stride scheduling on the picker,
    no engine threads involved)."""
    engine = GenerationEngine(breaker_threshold=0)
    engine.set_tenant("a", weight=2.0)
    engine.set_tenant("b", weight=1.0)
    queue = RequestQueue(max_depth=256)
    for i in range(60):
        _queued(queue, i, "a" if i % 2 == 0 else "b")
    wins = {"a": 0, "b": 0}
    for _ in range(30):
        wins[_pick_locked(engine, queue).tenant] += 1
    assert wins["a"] == 20 and wins["b"] == 10, wins


def test_pick_strict_priority_lanes_before_fairness():
    """Lane order dominates: a HIGH request dispatches before NORMAL
    traffic even when its tenant is far behind on virtual time."""
    engine = GenerationEngine(breaker_threshold=0)
    engine.set_tenant("busy", weight=1.0)
    queue = RequestQueue(max_depth=64)
    for i in range(4):
        _queued(queue, i, "busy")
        _pick_locked(engine, queue)  # banks virtual time for 'busy'
    _queued(queue, 100, "fresh")                      # NORMAL lane
    _queued(queue, 101, "busy", priority=Priority.HIGH)
    assert _pick_locked(engine, queue).id == 101


def test_pick_skips_tenant_at_in_flight_cap():
    engine = GenerationEngine(breaker_threshold=0)
    engine.set_tenant("capped", weight=10.0, max_in_flight=1)
    engine._tenant("capped").in_flight = 1
    queue = RequestQueue(max_depth=64)
    _queued(queue, 1, "capped")
    _queued(queue, 2, "other")
    assert _pick_locked(engine, queue).tenant == "other"
    # only the capped tenant queued -> nothing admissible, req stays queued
    assert _pick_locked(engine, queue) is None
    engine._tenant("capped").in_flight = 0
    assert _pick_locked(engine, queue).tenant == "capped"


def test_pick_reserves_in_flight_so_one_round_cannot_exceed_cap():
    """An admission round with several free slots calls _pick repeatedly
    BEFORE any prefill runs; the cap must be charged at pick time or one
    round admits a capped tenant twice."""
    engine = GenerationEngine(breaker_threshold=0)
    engine.set_tenant("capped", weight=1.0, max_in_flight=1)
    queue = RequestQueue(max_depth=64)
    _queued(queue, 1, "capped")
    _queued(queue, 2, "capped")
    first = _pick_locked(engine, queue)
    assert first.tenant == "capped"
    assert engine._tenant("capped").in_flight == 1
    # same round, second free slot: the reservation blocks the pick
    assert _pick_locked(engine, queue) is None
    # retire the first -> the second request becomes admissible
    engine._tenant_unflight("capped")
    assert _pick_locked(engine, queue).id == 2


def test_idle_tenant_reenters_at_vtime_floor():
    """A long-idle tenant must not burn banked lag into a burst that
    starves everyone else: it re-enters at the current floor and still
    alternates with the active tenant."""
    engine = GenerationEngine(breaker_threshold=0)
    engine.set_tenant("active", weight=1.0)
    engine.set_tenant("idle", weight=1.0)
    queue = RequestQueue(max_depth=256)
    for i in range(10):
        _queued(queue, i, "active")
        _pick_locked(engine, queue)  # active's vtime climbs to 10
    for i in range(10, 18):
        _queued(queue, i, "active" if i % 2 == 0 else "idle")
    picks = [_pick_locked(engine, queue).tenant for _ in range(8)]
    # never more than 2 consecutive wins for the returning tenant
    for k in range(len(picks) - 2):
        assert len(set(picks[k:k + 3])) > 1, picks


def test_quota_reject_on_live_engine_does_not_deadlock():
    """Over-quota submits while the scheduler loop is dispatching: the
    quota path must estimate retry-after OUTSIDE _tenant_lock (the loop
    acquires queue-lock -> tenant-lock; holding tenant-lock while taking
    the queue lock was an ABBA deadlock)."""
    engine = GenerationEngine(queue_depth=64, breaker_threshold=0)
    entry = engine.register_model(
        lambda: _small_model(name="livequota", slots=1, max_len=32))
    engine.set_tenant("q", max_queued=1)
    engine.start()
    try:
        keep = [engine.submit([1, 2], tenant="q", max_new_tokens=24)]
        rejected = 0
        for _ in range(200):  # race the scheduler's admission scans
            try:
                keep.append(engine.submit([1, 2], tenant="q",
                                          max_new_tokens=2))
            except RejectedError as e:
                assert e.retry_after_s > 0.0
                rejected += 1
        assert rejected > 0
        for r in keep:
            r.result(timeout=120)
    finally:
        engine.shutdown()
    assert entry.metrics.count("rejected_quota") == rejected


def test_inject_failure_invalidates_arena_and_recovers():
    """A failed DONATED inject is replica health, not a request error:
    the admitting request and every in-flight sequence fail loudly, the
    arena resets, and the next request generates bit-identically."""
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    entry = engine.register_model(
        lambda: _small_model(name="inj", slots=2, max_len=32))
    ref = entry.offline_decode([5, 6], 4)
    engine.start()
    try:
        victim = engine.submit([1, 2], max_new_tokens=24)  # holds slot 0
        deadline = time.time() + 30
        # ``admitted`` moves after the victim's own inject; its slot is
        # active BEFORE it, and a fault armed in between hit the victim
        while entry.stats()["admitted"] < 1:
            assert time.time() < deadline
            time.sleep(0.002)
        faults.configure([{"site": "decode.inject", "action": "raise",
                           "times": 1}])
        doomed = engine.submit([3, 4], max_new_tokens=4)
        with pytest.raises(RequestError, match="failed in inject"):
            doomed.result(timeout=120)
        with pytest.raises(RequestError, match="arena failure"):
            victim.result(timeout=120)
        out = engine.submit([5, 6], max_new_tokens=4).result(timeout=120)
        assert [int(t) for t in out["tokens"]] == ref
    finally:
        engine.shutdown()
        faults.reset()
    assert entry.stats()["step_failures"] == 1


def test_arena_failure_mid_admission_still_admits_remaining_picked():
    """When the FIRST of several picked requests invalidates the arena,
    the rest must still admit into the reset arena — dropping them would
    abandon their futures forever and leak tenant queued counters."""
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    entry = engine.register_model(
        lambda: _small_model(name="multi", slots=2, max_len=16))
    ref = entry.offline_decode([5, 6], 4)
    # both queued BEFORE start: one admission round picks both
    doomed = engine.submit([1, 2], max_new_tokens=4)
    survivor = engine.submit([5, 6], max_new_tokens=4)
    faults.configure([{"site": "decode.inject", "action": "raise",
                       "times": 1}])
    engine.start()
    try:
        with pytest.raises(RequestError, match="failed in inject"):
            doomed.result(timeout=120)
        got = [int(t) for t in survivor.result(timeout=120)["tokens"]]
        assert got == ref
    finally:
        engine.shutdown()
        faults.reset()
    assert engine.stats()["tenants"]["default"]["queued"] == 0


def test_half_open_breaker_relaunches_once_while_idle():
    """An open breaker whose cooldown lapses with NO traffic must not
    rebuild the replica on every scheduler tick: one relaunch per
    half-open episode, then the probe STEP decides close/reopen."""
    engine = GenerationEngine(queue_depth=16, breaker_threshold=1,
                              breaker_cooldown_s=0.05)
    entry = engine.register_model(
        lambda: _small_model(name="idleprobe", slots=2, max_len=16))
    faults.configure([{"site": "decode.step", "action": "raise",
                       "times": 1}])
    engine.start()
    try:
        with pytest.raises(RequestError):
            engine.submit([5, 6], max_new_tokens=4).result(timeout=120)
        time.sleep(0.6)  # many loop ticks past cooldown, zero traffic
        st = entry.stats()
        assert st["relaunches"] == 1, st["relaunches"]
        assert st["breaker_probes"] == 1, st["breaker_probes"]
        # the probe step closes the breaker and serves correctly
        out = engine.submit([5, 6], max_new_tokens=4).result(timeout=120)
        assert [int(t) for t in out["tokens"]] == entry.offline_decode(
            [5, 6], 4)
    finally:
        engine.shutdown()
        faults.reset()
    assert entry.stats()["breaker_state"] == "closed"


def test_tenant_admission_quota_rejects_with_measured_backoff():
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    entry = engine.register_model(
        lambda: _small_model(name="quota", slots=2, max_len=8))
    engine.set_tenant("small", max_queued=2)
    # engine NOT started: submissions stay queued
    engine.submit([1, 2], tenant="small", max_new_tokens=2)
    engine.submit([1, 2], tenant="small", max_new_tokens=2)
    with pytest.raises(RejectedError) as exc:
        engine.submit([1, 2], tenant="small", max_new_tokens=2)
    assert "quota" in str(exc.value)
    assert exc.value.retry_after_s > 0.0
    assert entry.metrics.count("rejected_quota") == 1
    assert engine.stats()["tenants"]["small"]["queued"] == 2


def test_model_registry_resolution_and_versioning():
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    engine.register_model(
        lambda: _small_model(name="m", version="1", slots=2, max_len=8))
    e2 = engine.register_model(
        lambda: _small_model(name="m", version="2", slots=2, max_len=8))
    assert engine.models() == [("m", "1"), ("m", "2")]
    assert engine.entry("m") is e2                # latest version wins
    assert engine.entry("m", "2") is e2
    with pytest.raises(RejectedError, match="must name one"):
        engine.submit([1], max_new_tokens=1)      # ambiguous: 2 hosted
    with pytest.raises(RejectedError, match="no model"):
        engine.submit([1], model="ghost", max_new_tokens=1)
    engine.start()
    try:
        out = engine.submit([3, 4], model="m", version="1",
                            max_new_tokens=3).result(timeout=120)
        ref = engine.entry("m", "1").offline_decode([3, 4], 3)
        assert [int(t) for t in out["tokens"]] == ref
    finally:
        engine.shutdown()


def test_submit_validation_rejects_inadmissible_requests(served):
    engine, entry = served
    m = entry.model
    with pytest.raises(RejectedError, match="empty"):
        engine.submit([], max_new_tokens=2)
    with pytest.raises(RejectedError, match="out of range"):
        engine.submit([m.vocab_size], max_new_tokens=2)
    with pytest.raises(RejectedError, match="max_new_tokens"):
        engine.submit([1], max_new_tokens=0)
    with pytest.raises(RejectedError, match="exceeds the KV arena"):
        engine.submit(list(range(1, 16)), max_new_tokens=8)
    with pytest.raises(RejectedError, match="priority"):
        engine.submit([1], priority=99, max_new_tokens=2)


# ---------------------------------------------------------------------------
# satellite: queue drain-rate backoff + expired-vs-rejected split
# ---------------------------------------------------------------------------


class _Row:
    _seq = 0

    def __init__(self, rows=1, priority=Priority.NORMAL, dead=False):
        _Row._seq += 1
        self.id = _Row._seq
        self.rows = rows
        self.priority = priority
        self._dead = dead

    def expired(self, now=None):
        return self._dead


def test_retry_after_tracks_measured_drain_rate():
    q = RequestQueue(max_depth=4)
    for _ in range(4):
        q.put(_Row())
    # cold start: no drain observed yet -> the seed hint
    with pytest.raises(RejectedError) as exc:
        q.put(_Row())
    assert exc.value.retry_after_s == pytest.approx(0.05)
    # drain 3 rows at a measured ~100 rows/s
    for r in list(q.lane(Priority.NORMAL))[:3]:
        time.sleep(0.01)
        q.remove([r])
    est = q.retry_after_estimate(rows=4)
    # 3 rows of overflow at O(100) rows/s: an order-of-magnitude window,
    # not a fixed hint (the EWMA smooths scheduler jitter)
    assert 0.005 <= est <= 1.0
    assert q.stats()["drain_rate_rows_per_s"] > 0
    # caller floor: reported hint is max(measured, caller estimate)
    q.put(_Row(rows=3))
    with pytest.raises(RejectedError) as exc:
        q.put(_Row(), retry_after_s=4.5)
    assert exc.value.retry_after_s == pytest.approx(4.5)


def test_queue_counts_expiry_separately_from_admission_rejects():
    q = RequestQueue(max_depth=2)
    q.put(_Row(dead=True))
    q.put(_Row())
    with pytest.raises(RejectedError):
        q.put(_Row())                      # rejected at admission
    dead = q.expire()
    assert len(dead) == 1                  # expired while queued
    s = q.stats()
    assert s["rejected_at_admission"] == 1
    assert s["expired_in_queue"] == 1
    assert s["depth"] == 1
    assert s["lane_depths"][Priority.NORMAL] == 1


def test_drain_rate_ignores_idle_gaps_between_bursts():
    """Only back-to-back drains of a busy queue are service-rate samples.
    A drain after the queue sat empty spans the idle gap — sampling it
    would converge the EWMA to the ARRIVAL rate, so the first rejection
    of a burst hitting a long-idle queue would back off ~100x too long."""
    q = RequestQueue(max_depth=8)
    for _ in range(4):
        q.put(_Row())
    for r in list(q.lane(Priority.NORMAL)):
        time.sleep(0.005)
        q.remove([r])                  # the last remove empties the queue
    busy = q.stats()["drain_rate_rows_per_s"]
    assert busy > 20.0
    time.sleep(0.3)                    # idle gap: ~3 rows/s if mis-sampled
    q.put(_Row())
    q.remove(list(q.lane(Priority.NORMAL)))
    assert q.stats()["drain_rate_rows_per_s"] == pytest.approx(busy)


def test_pick_rounds_sample_drain_rate_once_per_round():
    """_pick removes one request per call in a tight loop; sampling each
    pick would measure the loop's microsecond gaps (~1e6 rows/s) and
    collapse every retry-after hint to its floor. The round's picks are
    deferred and note_drained() samples them as ONE drain event."""
    engine = GenerationEngine(breaker_threshold=0)
    q = RequestQueue(max_depth=64)
    for i in range(8):
        _queued(q, i, "t")
    for _ in range(4):                 # admission round 1 (4 free slots)
        assert _pick_locked(engine, q) is not None
    q.note_drained()
    time.sleep(0.02)
    for _ in range(4):                 # admission round 2
        assert _pick_locked(engine, q) is not None
    q.note_drained()
    rate = q.stats()["drain_rate_rows_per_s"]
    # 4 rows per ~20ms round is O(200) rows/s; per-pick sampling would
    # have pushed the EWMA toward 1e6
    assert 0 < rate < 5000, rate


def test_finished_generation_delivered_even_if_deadline_lapses_same_step():
    """The device already paid for a COMPLETE generation: 'finished' wins
    over 'expired' on the iteration that lands the final token, matching
    the prefill fast path (which retires without an expiry check).
    Thread-less — the worker is stepped by hand for determinism."""
    engine = GenerationEngine(breaker_threshold=0)
    entry = engine.register_model(
        lambda: _small_model(name="dlwin", slots=1, max_len=16))
    resp = engine.submit([1, 2, 3], max_new_tokens=2, deadline_ms=60000)
    assert entry._admit_free_slots() == 1
    req = entry._slots[0].request
    entry._step()                      # token 1 of 2: mid-flight
    req.deadline = 0.0                 # lapses before the FINAL iteration
    entry._step()                      # token 2: finished AND expired
    got = [int(t) for t in resp.result(timeout=5)["tokens"]]
    assert got == entry.offline_decode([1, 2, 3], 2)


def test_deadline_expires_in_queue_while_slots_are_busy():
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    entry = engine.register_model(
        lambda: _small_model(name="dl", slots=1, max_len=32))
    engine.start()
    try:
        long = engine.submit([1, 2], max_new_tokens=20)   # holds the slot
        doomed = engine.submit([3, 4], max_new_tokens=4, deadline_ms=1.0)
        with pytest.raises(DeadlineExceededError):
            doomed.result(timeout=120)
        long.result(timeout=120)
        assert entry.metrics.count("deadline_missed") >= 1
        assert entry.stats()["queue_expired_in_queue"] >= 1
    finally:
        engine.shutdown()


# ---------------------------------------------------------------------------
# kill a replica mid-decode: breaker re-admits an AOT-warmed replacement
# ---------------------------------------------------------------------------


def test_breaker_relaunches_warm_replica_with_zero_recompiles():
    """An injected decode-step crash loses the in-flight batch (failed
    loudly), opens the breaker, and the cooldown probe relaunches the
    replica — whose three executables ALL come from the in-process
    compile-cache tier (zero new traces), after which generation is
    bit-identical to the offline reference again."""
    engine = GenerationEngine(queue_depth=16, breaker_threshold=1,
                              breaker_cooldown_s=0.05)
    entry = engine.register_model(
        lambda: _small_model(name="kill", slots=2, max_len=16))
    assert entry.compile_sources["trace"] == 3
    ref = entry.offline_decode([5, 6, 7], 6)
    faults.configure([{"site": "decode.step", "action": "raise",
                       "times": 1}])
    engine.start()
    try:
        doomed = engine.submit([5, 6, 7], max_new_tokens=6)
        with pytest.raises(RequestError, match="decode-step failure"):
            doomed.result(timeout=120)
        # the replacement replica serves the SAME request correctly
        out = engine.submit([5, 6, 7], max_new_tokens=6).result(timeout=120)
        assert [int(t) for t in out["tokens"]] == ref
    finally:
        engine.shutdown()
        faults.reset()
    st = entry.stats()
    assert st["step_failures"] == 1
    assert st["relaunches"] == 1
    assert st["breaker_probes"] >= 1
    # zero recompiles: the relaunch re-lowered all three programs from
    # the memory tier; the trace count never moved
    assert entry.compile_sources["trace"] == 3
    assert entry.compile_sources["memory"] >= 3


# ---------------------------------------------------------------------------
# AOT warm start across processes (the cold-replica acceptance gate)
# ---------------------------------------------------------------------------


def _run_worker(cache_dir):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["JAX_PLATFORMS"] = "cpu"
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    proc = subprocess.run(
        [sys.executable, WORKER], env=env, capture_output=True,
        text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_fresh_process_restores_all_executables_with_zero_compiles(tmp_path):
    """A cold replica with a populated cache dir reaches full decode/
    prefill/inject coverage from the jax.export disk tier: zero traces,
    all three entries disk-sourced, bit-identical generations."""
    cache = tmp_path / "cache"
    cold = _run_worker(cache)
    assert cold["compile_sources"]["trace"] == 3
    warm = _run_worker(cache)
    assert warm["compile_sources"] == {"trace": 0, "disk": 3, "memory": 0}, \
        warm
    assert warm["persistent_hits"] >= 3
    assert warm["persistent_errors"] == 0
    assert warm["tokens"] == cold["tokens"]


# ---------------------------------------------------------------------------
# HBM budget gate + observability surface
# ---------------------------------------------------------------------------


def test_arena_sized_against_hbm_budget_before_compile():
    tiny = GenerationEngine(breaker_threshold=0, hbm_budget_mb=0.001)
    from paddle_tpu.utils.enforce import EnforceError

    with pytest.raises(EnforceError, match="budget"):
        tiny.register_model(
            lambda: _small_model(name="oom", slots=4, max_len=16))
    roomy = GenerationEngine(breaker_threshold=0, hbm_budget_mb=64)
    entry = roomy.register_model(
        lambda: _small_model(name="fits", slots=2, max_len=8))
    assert entry.model.arena_bytes() < 64 * 2**20


def test_stats_surface_has_decode_and_tenant_series(served):
    engine, entry = served
    out = engine.submit([2, 4, 6], tenant="acme",
                        max_new_tokens=3).result(timeout=120)
    assert len(out["tokens"]) == 3
    st = entry.stats()
    assert st["occupancy"] > 0.0
    # a decode-step quantity: the prefill-derived first token of each
    # admission is counted apart (prefill_tokens), so <= S always holds
    assert 0.0 < st["tokens_per_step"] <= st["slots"]
    assert st["prefill_tokens"] == st["admitted"]
    assert st["compile_sources"]["trace"] == 3
    assert st["arena_mib"] == pytest.approx(
        entry.model.arena_bytes() / 2**20)
    for key in ("latency_p99_s", "queue_wait_p99_s", "decode_step_p99_s",
                "prefill_p99_s", "queue_drain_rate_rows_per_s",
                "queue_rejected_at_admission", "queue_expired_in_queue"):
        assert key in st, key
    assert set(st["queue_lane_depths"]) == {"high", "normal", "low"}
    assert st["tenant_tokens"].get("acme", 0) >= 3
    top = engine.stats()
    assert top["tenants"]["acme"]["in_flight"] == 0
    assert any(h.startswith("shared@") for h in top["hosted"])
    # the per-tenant counters are real registry series (scrapable), not
    # snapshot-only bookkeeping
    from paddle_tpu.observability import metrics as obs_metrics

    text = obs_metrics.registry().to_text()
    assert "serving_tenant_tokens_total" in text
    assert "serving_queue_lane_depth" in text


# ---------------------------------------------------------------------------
# r13: paged arena — block sharing, copy-on-write, exhaustion
# ---------------------------------------------------------------------------


def test_two_requests_share_physical_blocks():
    """Storage dedup, not just prefill dedup: two prompts sharing a
    full-block prefix reference the SAME physical blocks (radix tree
    over chained block hashes) — logical rows exceed physical rows while
    both are live — and still generate bit-identically. Hand-stepped
    (engine not started) so the mid-flight pool state is sampleable."""
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    entry = engine.register_model(lambda: build_decoder_model(
        vocab_size=32, hidden=8, num_layers=2, slots=4, max_len=32,
        block_size=4, name="dedup", version="1"))
    prefix = [7, 3, 9, 2, 11, 5, 8, 1]          # exactly 2 full blocks
    p1, p2 = prefix + [4, 6], prefix + [13]
    refs = [entry.offline_decode(p, 6) for p in (p1, p2)]
    r1 = engine.submit(p1, max_new_tokens=6)
    r2 = engine.submit(p2, max_new_tokens=6)
    assert entry._admit_free_slots() == 2
    bp = entry.block_pool.stats()
    assert bp["dedup_ratio"] > 1.0, bp
    assert bp["rows_logical"] > bp["rows_live"], bp
    assert bp["radix_hits"] >= 2                 # p2 referenced 2 shared blocks
    for _ in range(8):
        entry._step()
    assert [int(t) for t in r1.result(timeout=5)["tokens"]] == refs[0]
    assert [int(t) for t in r2.result(timeout=5)["tokens"]] == refs[1]


def test_cow_on_divergent_append_preserves_bit_identity():
    """Two IDENTICAL prompts share every block including the partial
    tail; the first generated token diverges the sequences, so the
    writer pays a copy-on-write (fresh block + host-row re-inject)
    instead of mutating rows its sharer reads. Both outputs stay
    bit-identical to the offline reference."""
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    entry = engine.register_model(lambda: build_decoder_model(
        vocab_size=32, hidden=8, num_layers=2, slots=4, max_len=32,
        block_size=4, name="cow", version="1"))
    prompt = [7, 3, 9, 2, 11, 5]                 # 1 full block + partial tail
    ref = entry.offline_decode(prompt, 6)
    r1 = engine.submit(prompt, max_new_tokens=6)
    r2 = engine.submit(prompt, max_new_tokens=6)
    assert entry._admit_free_slots() == 2
    bp = entry.block_pool.stats()
    assert bp["dedup_ratio"] > 1.0, bp           # tail shared too
    entry._step()
    assert entry.block_pool.stats()["cow_copies"] >= 1
    for _ in range(8):
        entry._step()
    assert [int(t) for t in r1.result(timeout=5)["tokens"]] == ref
    assert [int(t) for t in r2.result(timeout=5)["tokens"]] == ref
    # the pool never leaks: both retired -> no live blocks
    done = entry.block_pool.stats()
    assert done["blocks_live"] == 0, done


def test_block_pool_exhaustion_fails_loudly_and_recovers():
    """An undersized pool rejects the request that cannot fit — a loud
    request-attributed failure, not an arena loss — and keeps serving
    requests that do fit. Retired registered blocks are evicted on
    demand (LRU) to make room."""
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    entry = engine.register_model(lambda: build_decoder_model(
        vocab_size=32, hidden=8, num_layers=1, slots=2, max_len=16,
        block_size=4, num_blocks=3, name="tightpool", version="1"))
    ref = entry.offline_decode([1, 2], 4)
    engine.start()
    try:
        # 12 rows of pool; a 10-token prompt fills all 3 blocks by its
        # second generated token and the fourth block does not exist
        with pytest.raises(RequestError, match="block pool exhausted"):
            engine.submit(list(range(1, 11)),
                          max_new_tokens=4).result(timeout=120)
        out = engine.submit([1, 2], max_new_tokens=4).result(timeout=120)
        assert [int(t) for t in out["tokens"]] == ref
        # the retired request's registered blocks were cached; admitting
        # fresh prompts evicts them instead of failing
        out2 = engine.submit([3, 4], max_new_tokens=4).result(timeout=120)
        assert [int(t) for t in out2["tokens"]] == \
            entry.offline_decode([3, 4], 4)
    finally:
        engine.shutdown()
    assert entry.metrics.count("blocks_exhausted") >= 1


# ---------------------------------------------------------------------------
# r13: chunked prefill — fairness + bit-identity to unchunked
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("order", ["ahead", "serial"])
def test_chunked_prefill_interleaves_and_matches_unchunked(order):
    """A long prompt admits through the [1, C] chunk program ONE chunk
    per engine iteration: the in-flight decode slot gains a token EVERY
    iteration of the admission window (never stalls longer than the
    chunk budget), and the chunked generation is bit-identical to the
    offline (unchunked, whole-sequence) reference. Hand-stepped through
    entry._iterate() for a deterministic interleaving record. Two orders
    of a step's fetch give the same record: ``serial`` (a model without
    ``token_fetch``: every step lands in the iteration that launched
    it) and ``ahead`` (the token an iteration delivers is the step's
    that the iteration BEFORE launched; the admission and every chunk,
    the last too, run under a step in flight)."""
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    model = build_decoder_model(
        vocab_size=32, hidden=8, num_layers=2, slots=2, max_len=32,
        block_size=4, chunk_tokens=5, name="chunkfair", version="1")
    entry = engine.register_model(
        model if order == "ahead" else without_token_fetch(model))
    rng = np.random.RandomState(11)
    long_prompt = [int(t) for t in rng.randint(0, 32, size=17)]
    ref_long = entry.offline_decode(long_prompt, 5)
    ref_short = entry.offline_decode([1, 2], 20)
    short = engine.submit([1, 2], max_new_tokens=20)
    assert entry._admit_free_slots() == 1
    entry._step()                              # short is mid-generation
    # ahead: that step's token is still on the device
    assert len(entry._slots[0].generated) == (1 if order == "ahead" else 2)
    assert (entry._launched is not None) == (order == "ahead")
    lng = engine.submit(long_prompt, max_new_tokens=5)
    progress = []
    in_flight = []
    for _ in range(40):
        before = len(entry._slots[0].generated)
        if entry._iterate():
            break
        after = (len(entry._slots[0].generated)
                 if entry._slots[0] is not None else before + 1)
        prefilling = any(
            st is not None and st.mode == "prefill" for st in entry._slots)
        progress.append((prefilling, after - before))
        in_flight.append(entry._launched is not None)
        if short.done() and lng.done():
            break
    # fairness: during EVERY iteration the long admission was chunking,
    # the in-flight decode slot still advanced
    chunk_iters = [p for p in progress if p[0]]
    assert len(chunk_iters) >= 2, progress     # 17 tokens / C=5 -> >= 3 chunks
    assert all(delta >= 1 for _p, delta in chunk_iters), progress
    assert [int(t) for t in lng.result(timeout=5)["tokens"]] == ref_long
    assert [int(t) for t in short.result(timeout=5)["tokens"]] == ref_short
    assert entry.metrics.count("chunk_runs") >= 3
    assert entry.metrics.count("chunk_tokens") >= 16
    m = entry.metrics
    assert m.count("step_launches") == m.count("decode_steps")
    if order == "serial":
        assert m.count("decode_steps_ahead") == 0 and not any(in_flight)
    else:
        # every iteration but the last leaves a step in flight, and all
        # launches but the hand-made first were ahead of a fetch: since
        # ISSUE 42 neither the chunked admission nor its last chunk
        # drains (three launches were not ahead before)
        assert all(in_flight[:-1]) and not in_flight[-1]
        assert m.count("decode_steps") - m.count("decode_steps_ahead") == 1
        assert m.drains() == dict.fromkeys(m.drains(), 0) | {"idle": 1}
        assert m.count("chunk_launches_ahead") == m.count("chunk_runs")


def test_chunked_prefill_skips_radix_shared_chunks():
    """A second long prompt sharing the radix chain chunk-prefills ONLY
    its final chunk (the shared blocks already hold byte-identical rows)
    and still matches the offline reference."""
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    entry = engine.register_model(lambda: build_decoder_model(
        vocab_size=32, hidden=8, num_layers=2, slots=2, max_len=32,
        block_size=4, chunk_tokens=5, name="chunkshare", version="1"))
    rng = np.random.RandomState(12)
    prompt = [int(t) for t in rng.randint(0, 32, size=16)]  # 4 full blocks
    ref = entry.offline_decode(prompt, 4)
    engine.start()
    try:
        out1 = engine.submit(prompt, max_new_tokens=4).result(timeout=120)
        runs_after_first = entry.metrics.count("chunk_runs")
        out2 = engine.submit(prompt, max_new_tokens=4).result(timeout=120)
        runs_after_second = entry.metrics.count("chunk_runs")
    finally:
        engine.shutdown()
    assert [int(t) for t in out1["tokens"]] == ref
    assert [int(t) for t in out2["tokens"]] == ref
    assert runs_after_first >= 4                 # 16 tokens / C=5 -> 4 chunks
    # the re-admission paid ONE chunk (the final-logits chunk), not four
    assert runs_after_second - runs_after_first == 1, (
        runs_after_first, runs_after_second)


@pytest.mark.slow
def test_chunked_prefill_32k_prompt_never_stalls_decode():
    """The satellite's literal claim at production scale: a 32k-token
    prompt admission streams through the chunk program without EVER
    stalling the in-flight decode slot for more than one chunk per
    iteration. (The offline [L, L]-bias reference is unbuildable at 32k
    — 4 GiB per feed — which is exactly why chunked prefill exists; the
    bit-identity of chunk-vs-unchunked is pinned at small scale by
    test_chunked_prefill_interleaves_and_matches_unchunked, and run-to-
    run determinism is asserted here.)"""
    L, C, BS = 32768, 1024, 512
    plen = 32000

    def build():
        return build_decoder_model(
            vocab_size=16, hidden=4, num_layers=1, slots=2, max_len=L,
            block_size=BS, num_blocks=2 * (plen // BS + 4),
            chunk_tokens=C, name="chunk32k", version="1")

    engine = GenerationEngine(queue_depth=8, breaker_threshold=0)
    entry = engine.register_model(build)
    rng = np.random.RandomState(13)
    long_prompt = [int(t) for t in rng.randint(0, 16, size=plen)]
    short = engine.submit([1, 2], max_new_tokens=48)
    assert entry._admit_free_slots() == 1
    entry._step()
    lng = engine.submit(long_prompt, max_new_tokens=4)
    stalls = 0
    toks = []
    while not lng.done():
        st0 = entry._slots[0]
        before = len(st0.generated) if st0 is not None else None
        assert not entry._iterate()
        st0 = entry._slots[0]
        if before is not None and st0 is not None:
            if len(st0.generated) - before < 1:
                stalls += 1
        if short.done() and not any(
                s is not None and s.mode == "prefill"
                for s in entry._slots):
            # short finished before the long prompt landed: keep going
            while not lng.done():
                assert not entry._iterate()
            break
    assert stalls == 0, f"{stalls} iterations stalled the decode slot"
    toks = [int(t) for t in lng.result(timeout=5)["tokens"]]
    assert len(toks) == 4
    assert entry.metrics.count("chunk_runs") >= plen // C
    # run-to-run determinism: a fresh engine reproduces the same bytes
    engine2 = GenerationEngine(queue_depth=8, breaker_threshold=0)
    entry2 = engine2.register_model(build)
    lng2 = engine2.submit(long_prompt, max_new_tokens=4)
    assert entry2._admit_free_slots() == 1
    while not lng2.done():
        assert not entry2._iterate()
    assert [int(t) for t in lng2.result(timeout=5)["tokens"]] == toks


# ---------------------------------------------------------------------------
# r13: speculative decoding — greedy acceptance, bit-identity, steps/token
# ---------------------------------------------------------------------------


def _spec_pair(name, draft_layers=2, **over):
    """Target + draft entries in one engine. Same geometry => the
    deterministic init makes the weights byte-identical (the acceptance
    upper bound); fewer draft layers => a genuinely different model."""
    engine = GenerationEngine(queue_depth=32, breaker_threshold=0)
    tgt = engine.register_model(lambda: build_decoder_model(
        vocab_size=32, hidden=8, num_layers=2, slots=4, max_len=32,
        block_size=4, name=f"{name}_t", version="1", **over))
    engine.register_model(lambda: build_decoder_model(
        vocab_size=32, hidden=8, num_layers=draft_layers, slots=2,
        max_len=32, block_size=4, name=f"{name}_d", version="1", **over))
    return engine, tgt


def test_speculative_decode_bit_identical_any_admission_order():
    """Speculative requests interleaved with normal decode traffic in
    shuffled admission orders: EVERY request's tokens equal the offline
    whole-sequence reference — greedy acceptance makes speculation an
    execution strategy, not a sampling change."""
    engine, tgt = _spec_pair("specmix")
    rng = np.random.RandomState(21)
    prompts = [list(rng.randint(0, 32, size=rng.randint(1, 6)))
               for _ in range(8)]
    max_news = [int(rng.randint(2, 9)) for _ in range(8)]
    refs = [tgt.offline_decode(p, n) for p, n in zip(prompts, max_news)]
    engine.start()
    try:
        for round_seed in (0, 1):
            order = np.random.RandomState(round_seed).permutation(8)
            resps = {}
            for i in order:
                spec = int(i) % 2 == 0
                resps[int(i)] = engine.submit(
                    prompts[i], model="specmix_t",
                    max_new_tokens=max_news[i],
                    draft_model="specmix_d" if spec else None,
                    spec_k=3)
            for i, r in resps.items():
                got = [int(t) for t in r.result(timeout=120)["tokens"]]
                assert got == refs[i], (
                    f"round {round_seed} prompt {i} (spec={i % 2 == 0}): "
                    f"{got} != {refs[i]}")
    finally:
        engine.shutdown()
    st = tgt.stats()
    assert st["spec_emitted_tokens"] > 0
    assert st["spec_target_steps"] < st["spec_emitted_tokens"]


def test_speculative_steps_per_token_below_target():
    """With a byte-identical draft (same geometry, deterministic init)
    acceptance is 1.0 and the measured target-steps-per-emitted-token
    hits the 1/(k+1) floor — and the whole run retraces NOTHING after
    warmup (every mode lives on the already-compiled programs)."""
    engine, tgt = _spec_pair("specsame")
    refs = {}
    prompt = [3, 1, 4, 1, 5]
    refs["a"] = tgt.offline_decode(prompt, 12)
    engine.start()
    j0 = jits()
    try:
        out = engine.submit(prompt, model="specsame_t", max_new_tokens=12,
                            draft_model="specsame_d",
                            spec_k=3).result(timeout=120)
    finally:
        engine.shutdown()
    assert [int(t) for t in out["tokens"]] == refs["a"]
    st = tgt.stats()
    assert st["spec_acceptance_rate"] == 1.0, st["spec_acceptance_rate"]
    assert st["spec_steps_per_token"] <= 0.7, st["spec_steps_per_token"]
    assert st["spec_steps_per_token"] == pytest.approx(
        st["spec_target_steps"] / st["spec_emitted_tokens"])
    assert jits() == j0, "speculative path must not retrace"


def test_speculative_with_distinct_draft_still_bit_identical():
    """A draft that genuinely disagrees with the target (fewer layers,
    different weights) lowers acceptance but can NEVER change the
    output: every emitted token is the target's own greedy argmax."""
    engine, tgt = _spec_pair("specdiff", draft_layers=1)
    prompt = [9, 9, 8, 7]
    ref = tgt.offline_decode(prompt, 10)
    engine.start()
    try:
        out = engine.submit(prompt, model="specdiff_t", max_new_tokens=10,
                            draft_model="specdiff_d",
                            spec_k=3).result(timeout=120)
    finally:
        engine.shutdown()
    assert [int(t) for t in out["tokens"]] == ref
    st = tgt.stats()
    # the ratio is measured, not assumed: it can only beat 1.0 when the
    # draft earns acceptances
    assert st["spec_target_steps"] <= st["spec_emitted_tokens"]


def test_speculative_validation_rejects_bad_drafts():
    engine, tgt = _spec_pair("specval")
    with pytest.raises(RejectedError, match="draft"):
        engine.submit([1], model="specval_t", max_new_tokens=2,
                      draft_model="specval_t")      # draft == target
    with pytest.raises(RejectedError, match="no model"):
        engine.submit([1], model="specval_t", max_new_tokens=2,
                      draft_model="ghost")
    with pytest.raises(RejectedError, match="spec_k"):
        engine.submit([1], model="specval_t", max_new_tokens=2,
                      draft_model="specval_d", spec_k=0)


# ---------------------------------------------------------------------------
# the paged-decode claims, each held live (no committed file)
# ---------------------------------------------------------------------------


def test_paged_arena_static_peak_hbm_4x_under_slotted():
    """analysis/memory.py over the SAME decode geometry (8 slots, 32k
    context, 16 layers): a paged pool sized for ~2k used tokens a slot
    peaks at least 4x under the dense slotted arena (block_size =
    max_len). Programs are built and analyzed, never compiled."""
    from paddle_tpu.analysis.memory import estimate_peak_hbm

    geom = dict(vocab_size=32000, hidden=64, num_layers=16, slots=8,
                max_len=32768)
    peak = {}
    for tag, kw in (("slotted", dict(block_size=32768, num_blocks=8)),
                    ("paged", dict(block_size=64, num_blocks=320))):
        m = build_decoder_model(name=f"hbm_{tag}", version="1", **geom, **kw)
        peak[tag] = estimate_peak_hbm(
            m.decode_program,
            feed_shapes={n: shp for n, shp, _d in m.decode_feed_sig()},
            fetch_names=[m.logits_fetch]).peak_total_bytes
        assert peak[tag] > m.arena_bytes()
    assert peak["slotted"] >= 4.0 * peak["paged"], peak


def test_block_dedup_admission_bit_identical_to_undeduped():
    """Three prompts sharing a two-block prefix (two of them identical),
    hand-stepped: while live the logical rows exceed the physical ones,
    the identical pair pays a copy-on-write at divergence, every
    generation equals the undeduped offline reference, and the pool is
    empty and conserved afterwards."""
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    entry = sharpen(engine.register_model(lambda: build_decoder_model(
        vocab_size=32, hidden=8, num_layers=2, slots=4, max_len=32,
        block_size=4, name="dedup3", version="1")))
    prefix = [7, 3, 9, 2, 11, 5, 8, 1]          # two full blocks
    prompts = [prefix + [4, 6], prefix + [13], prefix + [4, 6]]
    refs = [entry.offline_decode(p, 6) for p in prompts]
    resps = [engine.submit(p, max_new_tokens=6) for p in prompts]
    assert entry._admit_free_slots() == 3
    mid = entry.block_pool.stats()
    assert mid["dedup_ratio"] > 1.0, mid
    assert mid["rows_logical"] > mid["rows_live"], mid
    assert mid["radix_hits"] >= 4, mid           # 2 shared blocks, 2 sharers
    for _ in range(32):
        if all(r.done() for r in resps):
            break
        entry._step()
    outs = [[int(t) for t in r.result(timeout=60)["tokens"]] for r in resps]
    assert outs == refs
    done = entry.block_pool.stats()
    assert done["cow_copies"] >= 1, done
    assert done["blocks_live"] == 0, done
    entry.block_pool.check_conservation()


def test_speculative_replay_leg_steps_per_token_and_zero_retraces():
    """Three requests under the replay-proposal path (draft_kv=False)
    with a byte-identical draft: at most 0.7 target steps per emitted
    token, NOTHING compiled after registration, tokens equal
    target-only decode."""
    st, retraces, same = spec_leg("specleg", draft_kv=False)
    assert same
    assert st["spec_emitted_tokens"] == sum(SPEC_MAX_NEW), st
    assert st["spec_steps_per_token"] <= 0.7, st
    assert st["spec_draft_kv_steps"] == 0, st    # the replay path ran
    assert retraces == 0, "the speculative leg compiled after warm-up"


def test_pool_capacity_check_excludes_blocks_being_shared():
    """Review r13: the admission capacity check must not count cached
    blocks the SAME admission re-references as shared — they stop being
    evictable the moment the commit refs them. Pre-fix this crashed
    mid-commit (None block) and leaked the refcounts forever; post-fix
    it is a clean loud refusal, and the pool still serves afterwards."""
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    entry = engine.register_model(lambda: build_decoder_model(
        vocab_size=32, hidden=8, num_layers=1, slots=2, max_len=24,
        block_size=4, num_blocks=3, name="capcheck", version="1"))
    base = [5, 1, 7, 2, 9, 3, 8, 6]              # exactly 2 full blocks
    engine.start()
    try:
        # leaves both full blocks registered+cached, generated block freed
        out = engine.submit(base, max_new_tokens=2).result(timeout=120)
        assert [int(t) for t in out["tokens"]] == \
            entry.offline_decode(base, 2)
        # 16-token prompt shares those 2 cached blocks and needs 2 MORE:
        # free=1 + evictable=0 (both cached blocks are the shared ones)
        with pytest.raises(RequestError, match="block pool exhausted"):
            engine.submit(base + [4, 4, 4, 4, 2, 2, 2, 2],
                          max_new_tokens=2).result(timeout=120)
        # nothing leaked: the shared-prefix prompt still admits + serves
        out2 = engine.submit(base, max_new_tokens=2).result(timeout=120)
        assert [int(t) for t in out2["tokens"]] == \
            entry.offline_decode(base, 2)
    finally:
        engine.shutdown()
    assert entry.block_pool.stats()["blocks_live"] == 0
