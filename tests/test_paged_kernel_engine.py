"""The blocked paged-attention kernel held against the real engine (ISSUE
29), at the rehearsal geometry, with the kernel run through the Pallas
interpreter ("interpret") and the composite as the reference ("off").

The contract is a TOLERANCE between the two paths (an online softmax
regroups float32 sums) and BYTES within one path: the same compiled
program replayed under another admission order, or resumed after a park,
gives the same logits bit for bit. And the counter that says how much of
the arena a step's attention has to read is held to the script.
"""

import numpy as np
import pytest
from decode_testing import record_step_logits

from paddle_tpu import kernels
from paddle_tpu.serving.decode import GenerationEngine, build_decoder_model

GEOM = dict(vocab_size=32, hidden=8, num_layers=2, slots=4, max_len=24,
            block_size=4)
PROMPTS = [[3, 1, 4, 1, 5, 9, 2, 6, 5], [3, 1, 4], [9, 2], [7, 7, 1, 8, 2]]
MAX_NEW = [6, 7, 5, 6]


def _serve(mode, name, submit, order=None, geom=GEOM):
    """Hand-step one engine under ``mode``; ``submit(engine, i)`` sends
    request ``i`` of ``order``. Returns (results by request index, logits
    rows by request index, stats)."""
    with kernels.scoped_mode(mode):
        engine = GenerationEngine(queue_depth=32, breaker_threshold=0)
        entry = engine.register_model(lambda: build_decoder_model(
            name=name, version="1", **geom))
        engine.register_model(lambda: build_decoder_model(
            name=name + "_d", version="1", **geom))
        rows = {}
        record_step_logits(entry, rows)
        order = list(range(len(PROMPTS))) if order is None else order
        resps = {i: submit(engine, i) for i in order}
        for _ in range(800):
            if all(r.done() for r in resps.values()):
                break
            entry._iterate()
        tokens = {i: r.result(timeout=60) for i, r in resps.items()}
        logits = {i: rows.get(id(r), []) for i, r in resps.items()}
        stats = entry.stats()
        engine.shutdown()
    return tokens, logits, stats


def _greedy(name):
    return lambda engine, i: engine.submit(
        PROMPTS[i], max_new_tokens=MAX_NEW[i], model=name)


def _assert_rows_close(a, b, what):
    assert len(a) == len(b) and len(a) > 0, what
    for x, y in zip(a, b):
        scale = max(1.0, float(np.abs(y).max()))
        kernels._assert_close_both_ways(x, y, what, 1e-4, 1e-5 * scale)


@pytest.mark.parametrize("kind", ["greedy", "beam", "draft"])
def test_served_tokens_and_logits_within_tolerance_of_the_composite(kind):
    """What used to be a byte comparison between the two paths of this op
    is a tolerance now: every stepping slot's logits row within 1e-4
    relative (1e-5 of the row's scale absolute), and the tokens, which
    sit far further apart than that here, equal."""
    name = f"pk_{kind}"

    def submit(engine, i):
        if kind == "beam" and i == 0:
            return engine.submit(PROMPTS[i], max_new_tokens=MAX_NEW[i],
                                 model=name, beam_width=2)
        if kind == "draft" and i == 0:
            return engine.submit(PROMPTS[i], max_new_tokens=MAX_NEW[i],
                                 model=name, draft_model=name + "_d",
                                 spec_k=2)
        return _greedy(name)(engine, i)

    order = [0, 1, 2] if kind == "beam" else None
    off_t, off_l, _ = _serve("off", name, submit, order)
    on_t, on_l, st = _serve("interpret", name, submit, order)
    for i in off_t:
        assert [int(t) for t in on_t[i]["tokens"]] == \
            [int(t) for t in off_t[i]["tokens"]], (kind, i)
        if kind == "draft" and i == 0:
            continue                 # a spec slot steps the DRAFT's program
        _assert_rows_close(on_l[i], off_l[i], f"{kind} request {i}")
    if kind == "beam":
        assert [[int(t) for t in b["tokens"]] for b in on_t[0]["beams"]] \
            == [[int(t) for t in b["tokens"]] for b in off_t[0]["beams"]]
    if kind == "draft":
        assert st["spec_draft_kv_steps"] > 0
    assert st["failed"] == 0


def test_replay_under_a_shuffled_admission_order_is_bit_identical():
    """One compiled program, the kernel serving it: whichever slot a
    request lands in and whoever steps beside it, its logits rows are the
    same bytes (a slot's blocks are reduced in its own grid step, in the
    order its length gives)."""
    name = "pk_replay"
    a_t, a_l, _ = _serve("interpret", name, _greedy(name), [0, 1, 2, 3])
    b_t, b_l, _ = _serve("interpret", name, _greedy(name), [3, 1, 0, 2])
    for i in range(len(PROMPTS)):
        assert list(a_t[i]["tokens"]) == list(b_t[i]["tokens"])
        assert len(a_l[i]) == len(b_l[i]) == MAX_NEW[i] - 1
        for x, y in zip(a_l[i], b_l[i]):
            assert x.tobytes() == y.tobytes(), f"request {i}"


def test_resume_after_park_is_bit_identical_with_the_unparked_run():
    """A pool that holds about two of four sessions parks the others
    (K/V spilled to the host tier) and resumes them: tokens and logits
    rows equal, byte for byte, those of a run with room for everyone."""
    def serve(num_blocks):
        geom = dict(GEOM, slots=3, num_blocks=num_blocks, num_layers=1,
                    max_len=16, block_size=2)
        return _serve(
            "interpret", "pk_park",
            lambda engine, i: engine.submit(
                [1 + i, 2 + i, 3 + i, 4 + i], max_new_tokens=6,
                model="pk_park"),
            geom=geom)

    roomy_t, roomy_l, st0 = serve(24)
    parked_t, parked_l, st = serve(8)
    assert st0["sessions_parked"] == 0
    assert st["sessions_parked"] >= 1
    assert st["sessions_parked"] == st["sessions_resumed"]
    assert st["failed"] == 0
    for i in roomy_t:
        assert list(roomy_t[i]["tokens"]) == list(parked_t[i]["tokens"])
        assert len(roomy_l[i]) == 5
        assert [x.tobytes() for x in roomy_l[i]] == \
            [x.tobytes() for x in parked_l[i]]


def test_live_block_counters_follow_the_script():
    """``serving_decode_live_blocks_total`` is the sum over stepping slots
    of ceil((cursor + 1) / block); ``serving_decode_block_slots_total``
    is slots x blocks per slot, a step. Four requests admitted at once:
    request i steps MAX_NEW[i] - 1 times (its first token is the
    prefill's), at cursors len(prompt) + t."""
    from paddle_tpu.observability import metrics as obs_metrics

    name = "pk_count"
    bs, per_slot = GEOM["block_size"], -(-GEOM["max_len"] // GEOM["block_size"])
    _tokens, logits, st = _serve("off", name, _greedy(name))
    steps = max(MAX_NEW) - 1
    want = sum(-(-(len(p) + t + 1) // bs)
               for p, n in zip(PROMPTS, MAX_NEW) for t in range(n - 1))
    assert [len(logits[i]) for i in range(4)] == [n - 1 for n in MAX_NEW]
    assert st["decode_steps"] == steps
    assert st["decode_live_blocks"] == want
    assert st["decode_block_slots"] == steps * GEOM["slots"] * per_slot
    families = obs_metrics.registry().snapshot()
    for family in ("serving_decode_live_blocks_total",
                   "serving_decode_block_slots_total"):
        assert family in families, family


def test_copy_unit_counters_follow_the_script(monkeypatch):
    """``serving_paged_copy_units_total`` is the sum over stepping slots of
    ceil(live blocks / unit), the unit from the ``_paged_group`` the kernel
    calls (taken once, when the model is registered);
    ``serving_paged_copy_units_ahead_total`` all of a step's units but its
    first (the next live unit, the next live SLOT's first one too, is in
    flight while the one before it is reduced). 512 positions in blocks of
    4 with a unit held to one 128-row tile: 32 blocks."""
    from paddle_tpu.kernels import attention as A
    from paddle_tpu.observability import metrics as obs_metrics

    geom = dict(GEOM, max_len=512)
    bs, per_slot = geom["block_size"], 512 // geom["block_size"]
    monkeypatch.setattr(A, "_UNIT_BYTES", 2 * 128 * geom["hidden"] * 4)
    unit = A._paged_group(bs, per_slot, geom["hidden"], "float32")
    assert unit == 32 < per_slot
    prompts = [[1 + i % 9 for i in range(n)] for n in (126, 3, 250, 5)]
    max_new = [6, 5, 3, 4]
    name = "pk_units"
    _tokens, _logits, st = _serve(
        "off", name, lambda engine, i: engine.submit(
            prompts[i], max_new_tokens=max_new[i], model=name), geom=geom)
    # request i steps max_new[i] - 1 times, at cursors len(prompt) + t:
    # the first crosses from one unit to two at its third step
    per_step = [[-(-(-(-(len(p) + t + 1) // bs)) // unit)
                 for p, n in zip(prompts, max_new) if t < n - 1]
                for t in range(max(max_new) - 1)]
    assert per_step == [[1, 1, 2, 1], [1, 1, 2, 1], [2, 1, 1], [2, 1], [2]]
    assert st["decode_steps"] == len(per_step)
    assert st["paged_copy_units"] == sum(map(sum, per_step)) == 19
    assert st["paged_copy_units_ahead"] == 19 - len(per_step)
    # (the parent's schedule started all but one unit a live SLOT ahead:
    # 19 - 14 = 5 of them)
    families = obs_metrics.registry().snapshot()
    for family in ("serving_paged_copy_units_total",
                   "serving_paged_copy_units_ahead_total"):
        assert family in families, family
