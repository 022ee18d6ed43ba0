"""Graceful degradation under pressure (ISSUE 18).

The acceptance contract: block-pool exhaustion NEVER silently loses or
needlessly fails work — sessions that cannot keep their arena rows park
(KV spilled to the host-RAM tier, slot freed) and later resume
byte-identical to an uninterrupted run, in every generation mode and
for every victim-selection policy; a corrupted host-tier entry is
quarantined by its CRC and the resume recomputes the KV from the token
history instead of reading garbage; admission defers (measured
retry-after) rather than hard-failing unless the request can NEVER fit;
the brownout ladder escalates immediately, de-escalates hysteretically,
and its two REJECT rungs (L4 shed, L3 beam cap) only fire while live
pressure confirms the severity. Every scenario is hand-stepped (no
scheduler thread), so its park/resume schedule is a function of the code.
"""

import pytest
from decode_testing import sharpen

from paddle_tpu.serving.brownout import BrownoutController
from paddle_tpu.serving.decode import (
    BeamParams,
    GenerationEngine,
    SamplingParams,
    build_decoder_model,
)
from paddle_tpu.serving.decode.tier import HostKVTier
from paddle_tpu.serving.request import (
    Priority,
    RejectedError,
    RequestError,
)

def _tight_model(name, slots=2, num_blocks=6, max_len=16, block_size=2):
    return build_decoder_model(
        vocab_size=32, hidden=8, num_layers=1, slots=slots,
        max_len=max_len, block_size=block_size, num_blocks=num_blocks,
        name=name, version="1")


def _drain(entry, resps, iters=800):
    for _ in range(iters):
        if all(r.done() for r in resps):
            return
        entry._iterate()
    raise AssertionError("hand-stepped drain did not converge")


# ---------------------------------------------------------------------------
# host KV tier (unit)
# ---------------------------------------------------------------------------


def test_host_tier_put_get_lru_and_capacity():
    import numpy as np

    tier = HostKVTier(capacity_bytes=1024)   # 4 entries of 256 B
    rows = [(np.ones((4, 8), "float32"), np.ones((4, 8), "float32"))]
    assert tier.put("blk:a", rows, 4, tokens=(1, 2, 3, 4))
    assert "blk:a" in tier and len(tier) == 1
    ent = tier.get("blk:a")
    assert ent is not None and ent.size_used == 4
    assert np.array_equal(ent.kv_rows[0][0], rows[0][0])
    # LRU: filling past capacity evicts the stalest entry, never errors
    for i in range(8):
        assert tier.put(f"blk:{i}", rows, 4, tokens=(i,))
    assert "blk:a" not in tier
    assert tier.stats()["evictions"] >= 1
    # an entry that ALONE exceeds the budget is the only refusal
    tiny = HostKVTier(capacity_bytes=8)
    assert not tiny.put("blk:x", rows, 4, tokens=(1,))
    assert tiny.stats()["rejected"] == 1


def test_host_tier_crc_quarantines_corruption():
    import numpy as np

    tier = HostKVTier(capacity_bytes=1 << 20)
    rows = [(np.arange(32, dtype="float32").reshape(4, 8),
             np.zeros((4, 8), "float32"))]
    tier.put("park:7:0", rows, 4, tokens=(1, 2, 3, 4))
    assert tier.stats()["spills"] == 1       # park: keys count as spills
    tier.corrupt_entry("park:7:0")
    # a corrupt entry reads as a MISS, never as wrong bytes
    assert tier.pop("park:7:0") is None
    st = tier.stats()
    assert st["corrupt_dropped"] == 1 and st["misses"] == 1
    assert "park:7:0" not in tier


# ---------------------------------------------------------------------------
# brownout controller (unit, hand-stepped, no threads)
# ---------------------------------------------------------------------------


def test_brownout_escalates_immediately_to_highest_rung():
    ctl = BrownoutController()
    assert ctl.step(occupancy=0.2) == 0
    assert ctl.step(occupancy=0.97) == 4     # straight to L4, no ladder
    (t,) = ctl.transitions
    assert t["from"] == 0 and t["to"] == 4
    assert t["trigger"] == "occupancy" and t["value"] == 0.97


def test_brownout_deescalates_one_level_per_hold_window():
    ctl = BrownoutController(hold=3)
    ctl.step(occupancy=0.97)
    for expect in (4, 4, 3):                 # 3 clear steps -> one level
        assert ctl.step(occupancy=0.1) == expect
    for expect in (3, 3, 2):
        assert ctl.step(occupancy=0.1) == expect


def test_brownout_hysteresis_band_holds_without_flapping():
    ctl = BrownoutController()               # enter[2]=0.85, exit[2]=0.70
    ctl.step(occupancy=0.9)                  # -> L3
    assert ctl.level == 3
    for _ in range(10):                      # inside the band: no motion
        assert ctl.step(occupancy=0.75) == 3
    assert len(ctl.transitions) == 1


def test_brownout_clear_streak_resets_on_pressure_blip():
    ctl = BrownoutController(hold=3)
    ctl.step(occupancy=0.97)
    ctl.step(occupancy=0.1)
    ctl.step(occupancy=0.1)
    ctl.step(occupancy=0.9)                  # blip: streak must reset
    for expect in (4, 4, 3):
        assert ctl.step(occupancy=0.1) == expect


def test_brownout_trigger_names_the_binding_signal():
    ctl = BrownoutController()
    ctl.step(occupancy=0.3, queue_seconds=0.96, deadline=0.5)
    assert ctl.transitions[-1]["trigger"] == "queue_seconds"


# ---------------------------------------------------------------------------
# preemption / resume
# ---------------------------------------------------------------------------


def _victim_policies():
    return {
        "default": None,                         # newest admission
        "oldest": lambda cands: min(cands, key=lambda s: s.seq),
        "shuffled": lambda cands: sorted(
            cands, key=lambda s: (s.seq * 2654435761) % 97)[0],
    }


@pytest.mark.parametrize("policy", sorted(_victim_policies()))
def test_preempt_resume_bit_identity_any_victim(policy):
    """Four sessions against a pool that serves ~two: whichever victim
    the policy picks, every stream finishes byte-identical to the
    uninterrupted offline reference, nothing fails, and the pool
    conserves."""
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    entry = sharpen(engine.register_model(
        lambda: _tight_model(f"ov_vic_{policy}", slots=3, num_blocks=8)))
    entry.victim_policy = _victim_policies()[policy]
    prompts = [[1 + i, 2 + i, 3 + i, 4 + i] for i in range(4)]
    refs = [entry.offline_decode(p, 6) for p in prompts]
    resps = [engine.submit(p, max_new_tokens=6) for p in prompts]
    _drain(entry, resps)
    outs = [[int(t) for t in r.result(timeout=60)["tokens"]]
            for r in resps]
    st = entry.stats()
    engine.shutdown()
    assert outs == refs
    assert st["failed"] == 0
    assert st["sessions_parked"] >= 1
    assert st["sessions_parked"] == st["sessions_resumed"]
    entry.block_pool.check_conservation()


def _tokens(resp):
    return [int(t) for t in resp.result(timeout=60)["tokens"]]


def _park_greedy(sampling=None):
    """Two sessions against a 12-row pool: both fit alone, not together
    — one parks mid-generation and resumes after the other retires."""
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    entry = sharpen(engine.register_model(lambda: _tight_model(
        "ov_greedy" if sampling is None else "ov_sampled")))
    prompts = [[1, 2, 3, 4], [5, 6, 7, 8]]
    refs = [entry.offline_decode(p, 6, sampling=sampling) for p in prompts]
    resps = [engine.submit(p, max_new_tokens=6, sampling=sampling)
             for p in prompts]
    _drain(entry, resps)
    return engine, entry, [_tokens(r) for r in resps] == refs


def _park_sampled():
    # the committed threefry stream is keyed per (seed, emitted index):
    # a park/resume must not advance or rewind a single draw
    return _park_greedy(SamplingParams(temperature=0.8, top_k=6, seed=7))


def _park_beam():
    """A width-2 beam group and a greedy competitor against a 20-row
    pool: either fits alone, not both — the exhausted one parks (the
    beam group spills per hypothesis) and resumes to the same ranked
    hypotheses."""
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    entry = sharpen(engine.register_model(
        lambda: _tight_model("ov_beam", slots=3, num_blocks=10)))
    comp_ref = entry.offline_decode([1, 2, 3, 4], 8)
    beam_ref = entry.offline_beam([5, 6, 7, 8], 6, BeamParams(2))
    comp = engine.submit([1, 2, 3, 4], max_new_tokens=8)
    beam = engine.submit([5, 6, 7, 8], max_new_tokens=6, beam_width=2)
    _drain(entry, [comp, beam])
    beam_out = [[int(t) for t in h["tokens"]]
                for h in beam.result(timeout=60)["beams"]]
    same = (_tokens(comp) == comp_ref
            and beam_out == [list(rt) for rt, _rs in beam_ref])
    return engine, entry, same


def _park_spec():
    """A speculative session (no target-arena footprint) beside two
    greedy competitors whose joint demand oversubscribes the pool: they
    park and resume around it. Whether anything parks depends on the
    brownout level at admission (wall clock), so only equality is held."""
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    entry = sharpen(engine.register_model(
        lambda: _tight_model("ov_spec_t", slots=3, num_blocks=8)))
    engine.register_model(
        lambda: _tight_model("ov_spec_d", slots=2, num_blocks=16))
    prompts = [[1, 2, 3, 4], [5, 6, 7, 8], [3, 1, 3, 1]]
    refs = [entry.offline_decode(p, 6) for p in prompts]
    resps = [engine.submit(p, max_new_tokens=6, model="ov_spec_t")
             for p in prompts[:2]]
    resps.append(engine.submit(prompts[2], max_new_tokens=6,
                               model="ov_spec_t", draft_model="ov_spec_d",
                               spec_k=2))
    _drain(entry, resps)
    return engine, entry, [_tokens(r) for r in resps] == refs


@pytest.mark.parametrize("mode", ["greedy", "sampled", "beam", "spec"])
def test_resumed_after_park_equals_uninterrupted(mode):
    """In every generation mode a session that parks (K/V spilled to the
    host tier) and resumes finishes with the tokens of the uninterrupted
    offline run; each park is matched by a resume, nothing fails, and
    the block pool is conserved and empty at the end."""
    engine, entry, same = globals()[f"_park_{mode}"]()
    st = entry.stats()
    engine.shutdown()
    assert same, f"{mode}: tokens differ across park/resume"
    assert st["failed"] == 0, st
    assert st["sessions_parked"] == st["sessions_resumed"], st
    if mode != "spec":
        assert st["sessions_parked"] >= 1, "nothing parked: proved nothing"
        assert st["host_tier"]["spills"] >= 1, st
    entry.block_pool.check_conservation()
    assert entry.block_pool.stats()["blocks_live"] == 0


def test_zero_loss_ledger_under_2x_burst():
    """Eight requests against a pool that serves two at a time: accepted
    == completed, none failed — parks make overload a latency event,
    never a loss event — and every stream equals its offline run."""
    engine = GenerationEngine(queue_depth=32, breaker_threshold=0)
    entry = sharpen(engine.register_model(lambda: _tight_model("ov_ledger")))
    prompts = [[(3 * i + j) % 32 for j in range(1, 5)] for i in range(8)]
    refs = [entry.offline_decode(p, 6) for p in prompts]
    resps = [engine.submit(p, max_new_tokens=6) for p in prompts]
    _drain(entry, resps, iters=1200)
    outs = [_tokens(r) for r in resps]
    st = entry.stats()
    engine.shutdown()
    assert outs == refs
    assert st["completed"] == len(resps) and st["failed"] == 0, st
    assert st["sessions_parked"] == st["sessions_resumed"], st
    entry.block_pool.check_conservation()


def test_brownout_ladder_over_a_scripted_trace():
    """The controller is clockless, so the ladder over a scripted trace
    is exact: a spike goes straight to L4, a value inside L3's
    hysteresis band (0.72 between exit 0.70 and enter 0.85) holds L3,
    and the clear tail walks down one level per hold window to L0."""
    ctl = BrownoutController()
    trace = ([("occupancy", 0.2)] * 2 + [("occupancy", 0.97)]
             + [("queue_seconds", 0.9)] * 2 + [("occupancy", 0.72)] * 8
             + [("occupancy", 0.3)] * 12)
    levels = [ctl.step(**{sig: val}) for sig, val in trace]
    assert levels[:3] == [0, 0, 4]
    assert max(levels) == 4 and levels[-1] == 0
    assert levels[12] == 3                       # the band's last step
    moves = [(t["from"], t["to"]) for t in ctl.snapshot()["transitions"]]
    assert moves[0] == (0, 4)
    assert all(a - b == 1 for a, b in moves[1:]) and len(moves) == 5, moves


def test_corruption_walkback_recomputes_not_garbage():
    """Flip one byte of a parked session's host-tier entry: the CRC
    quarantine must turn the resume into a replay-recompute
    (``resume_replays``) — same bytes out, one counter up."""
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    entry = sharpen(engine.register_model(lambda: _tight_model("ov_crc_t")))
    prompts = [[1, 2, 3, 4], [5, 6, 7, 8]]
    refs = [entry.offline_decode(p, 6) for p in prompts]
    resps = [engine.submit(p, max_new_tokens=6) for p in prompts]
    corrupted = False
    for _ in range(800):
        if all(r.done() for r in resps):
            break
        if entry._parked and not corrupted:
            for key in entry._parked[0].keys:
                entry.kv.tier.corrupt_entry(key)
            corrupted = True
        entry._iterate()
    outs = [[int(t) for t in r.result(timeout=60)["tokens"]]
            for r in resps]
    st = entry.stats()
    engine.shutdown()
    assert corrupted, "no session ever parked — the test proved nothing"
    assert outs == refs
    assert st["resume_replays"] >= 1
    assert st["host_tier"]["corrupt_dropped"] >= 1


def test_admission_defers_until_capacity_then_completes():
    """2x-capacity burst: every accepted request completes — exhaustion
    parks or defers, it never fails a request that can fit."""
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    entry = sharpen(engine.register_model(lambda: _tight_model("ov_defer")))
    prompts = [[1 + i, 2 + i, 3 + i, 4 + i] for i in range(4)]
    refs = [entry.offline_decode(p, 6) for p in prompts]
    resps = [engine.submit(p, max_new_tokens=6) for p in prompts]
    _drain(entry, resps)
    outs = [[int(t) for t in r.result(timeout=60)["tokens"]]
            for r in resps]
    st = entry.stats()
    engine.shutdown()
    assert outs == refs
    assert st["failed"] == 0 and st["completed"] == len(prompts)
    assert st["blocks_failed_total"] == 0


def test_never_fit_prompt_still_fails_loudly():
    """The ONE legitimate hard failure: a prompt whose blocks exceed
    the whole pool can never be served — parking everyone else would
    not help, so it fails loudly at admission, attributed."""
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    entry = engine.register_model(lambda: _tight_model("ov_neverfit"))
    with pytest.raises(RequestError, match="can never fit"):
        r = engine.submit(list(range(1, 14)), max_new_tokens=2)
        _drain(entry, [r])
        r.result(timeout=60)
    assert entry.metrics.count("blocks_failed_total") == 1
    engine.shutdown()


# ---------------------------------------------------------------------------
# the two REJECT rungs: stale severity must not shed
# ---------------------------------------------------------------------------


def test_l4_shed_requires_live_pressure():
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    entry = engine.register_model(lambda: _tight_model("ov_shed"))
    entry._brownout.level = 4
    # severity says shed, but the engine is idle: admission must pass
    r = engine.submit([1, 2], max_new_tokens=2)
    _drain(entry, [r])
    assert [int(t) for t in r.result(timeout=60)["tokens"]]
    # now live pressure confirms it: non-HIGH is turned away with a
    # measured retry-after, HIGH still lands
    entry._pending.append(object())
    try:
        with pytest.raises(RejectedError) as exc:
            engine.submit([1, 2], max_new_tokens=2)
        assert exc.value.retry_after_s is not None
        assert entry.metrics.count("brownout_shed") == 1
        high = engine.submit([1, 2], max_new_tokens=2,
                             priority=Priority.HIGH)
    finally:
        entry._pending.pop()
    entry._brownout.level = 0
    _drain(entry, [high])
    assert [int(t) for t in high.result(timeout=60)["tokens"]]
    engine.shutdown()


def test_l3_beam_cap_requires_live_pressure():
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    entry = engine.register_model(
        lambda: _tight_model("ov_cap", slots=3, num_blocks=12))
    entry._brownout.level = 3
    # idle engine: a wide beam admits despite the stale severity
    r = engine.submit([1, 2], max_new_tokens=2, beam_width=3)
    _drain(entry, [r])
    assert r.result(timeout=60)["beams"]
    entry._pending.append(object())
    try:
        with pytest.raises(RejectedError, match="beam width capped"):
            engine.submit([1, 2], max_new_tokens=2, beam_width=3)
        # at or under the cap still admits
        ok = engine.submit([1, 2], max_new_tokens=2, beam_width=2)
    finally:
        entry._pending.pop()
    entry._brownout.level = 0
    _drain(entry, [ok])
    assert ok.result(timeout=60)["beams"]
    engine.shutdown()
