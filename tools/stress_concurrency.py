#!/usr/bin/env python
"""Deterministic-interleaving concurrency stress harness.

Drives the repo's hottest threaded paths — GenerationEngine
admission/retire, RequestQueue admission/expiry, EmbeddingEngine
write-back, and the dataio pipeline — under SEEDED stall injection at
lock boundaries, with the runtime lockdep witness armed. Two seams
perturb thread interleavings:

  * the lockdep stall hook: whether acquisition #n of lock class L
    stalls (and for how long) is a pure function of (seed, L, n) —
    replaying a seed replays the exact stall schedule;
  * ``resilience.faults`` stall rules at the existing sites
    (decode.step/prefill/inject/sample/spill/resume, lookup.pull/push,
    dataio.read)
    with per-rule seeded probability.

Every scenario asserts a BIT-EXACT property against an unstressed
serial reference (decode tokens == offline decode, embedding host tier
== reference run, dataio stream digest == worker-count-0 digest) plus
counter-consistency invariants — so "the schedule changed the answer"
is a failure, not noise. A failing seed replays with::

    python tools/stress_concurrency.py --scenario decode --seed 17

CI contract: exit 0 = clean, 1 = failures, 2 = internal error;
``--smoke`` runs every scenario once on the default seed (wired into
tier-1 by tests/test_concurrency.py); ``--json`` machine summary.

``lockdep_pass()`` is a DETERMINISTIC single-threaded lockdep pass over
the decode + serving + embedding + checkpoint + dataio + fleet drivers;
it returns the witnessed lock-order hierarchy (e.g. ``serving.queue ->
decode.tenant``), which tests/test_concurrency.py holds against the
chains declared in code.
"""

import argparse
import hashlib
import json
import os
import random
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXIT_CLEAN, EXIT_FINDINGS, EXIT_INTERNAL = 0, 1, 2

os.environ.setdefault("JAX_PLATFORMS", "cpu")

SCENARIOS = ("queue", "decode", "embedding", "dataio")


class StallSchedule:
    """Deterministic stall decisions at lock boundaries. Install with
    ``lockdep.set_stall_hook(schedule)``; every enabled acquisition of
    lock class L consults ``(seed, L, n)`` — no wall clock, no thread
    identity — so one seed IS one schedule."""

    def __init__(self, seed, prob=0.2, delay_s=0.002):
        self.seed = int(seed)
        self.prob = float(prob)
        self.delay_s = float(delay_s)
        self._mu = threading.Lock()  # hook runs on every scenario thread
        self._stalls = 0

    @property
    def stalls(self):
        with self._mu:
            return self._stalls

    def __call__(self, name, n):
        r = random.Random(f"{self.seed}:{name}:{n}").random()
        if r < self.prob:
            with self._mu:
                self._stalls += 1
            time.sleep(self.delay_s)


def _stall_rules(seed, sites, prob=0.35, delay_s=0.003):
    return [{"site": s, "action": "stall", "delay_s": delay_s,
             "prob": prob, "seed": seed + i, "times": -1}
            for i, s in enumerate(sites)]


# ---------------------------------------------------------------------------
# scenario: RequestQueue admission / expiry / stats under contention
# ---------------------------------------------------------------------------


def scenario_queue(seed, n_per_thread=60, threads=4):
    from paddle_tpu.serving.decode.engine import GenerationRequest
    from paddle_tpu.serving.queue import RequestQueue
    from paddle_tpu.serving.request import Priority, RejectedError

    q = RequestQueue(max_depth=48)
    errors = []
    admitted = [0] * threads
    rejected = [0] * threads
    removed = [0]
    stop = threading.Event()

    def submitter(k):
        rng = random.Random(f"{seed}:submit:{k}")
        try:
            for i in range(n_per_thread):
                deadline = (time.perf_counter() + 0.005
                            if rng.random() < 0.3 else None)
                req = GenerationRequest(
                    k * 10_000 + i, [1], 4, f"t{k % 2}",
                    rng.choice(Priority.LANES), deadline)
                try:
                    q.put(req)
                    admitted[k] += 1
                except RejectedError:
                    rejected[k] += 1
                if rng.random() < 0.2:
                    time.sleep(0.0005)
        except BaseException as e:
            errors.append(e)

    def reaper():
        try:
            while not stop.is_set():
                q.expire()
                with q.lock:
                    head = q.head()
                    if head is not None:
                        q.remove([head])
                        removed[0] += 1
                q.stats()
                q.lane_depths()
                time.sleep(0.0005)
        except BaseException as e:
            errors.append(e)

    ts = [threading.Thread(target=submitter, args=(k,), daemon=True)
          for k in range(threads)]
    rp = threading.Thread(target=reaper, daemon=True)
    rp.start()
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
    # drain what's left, then stop the reaper
    deadline = time.time() + 10
    while not q.empty() and time.time() < deadline:
        q.expire()
        with q.lock:
            head = q.head()
            if head is not None:
                q.remove([head])
                removed[0] += 1
    stop.set()
    rp.join(10)
    assert not errors, f"queue scenario raised: {errors[:3]}"
    st = q.stats()
    assert q.empty() and st["depth"] == 0, st
    # conservation: every admitted row left via remove or expiry
    total_admitted = sum(admitted)
    accounted = removed[0] + st["expired_in_queue"]
    assert accounted == total_admitted, (
        f"row accounting broke: admitted {total_admitted} != removed "
        f"{removed[0]} + expired {st['expired_in_queue']}")
    assert st["rejected_at_admission"] == sum(rejected)
    return {"admitted": total_admitted, "removed": removed[0],
            "expired": st["expired_in_queue"], "rejected": sum(rejected)}


# ---------------------------------------------------------------------------
# scenario: continuous-batching decode vs offline reference
# ---------------------------------------------------------------------------


def _small_decode_model(name, slots=2, max_len=10, **kw):
    from paddle_tpu.serving.decode import build_decoder_model

    return build_decoder_model(
        vocab_size=16, hidden=8, num_layers=1, slots=slots,
        max_len=max_len, eos_id=None, name=name, version="1", **kw,
    )


def scenario_decode(seed, n_requests=6):
    from paddle_tpu.resilience import faults
    from paddle_tpu.serving.decode import (
        BeamParams,
        GenerationEngine,
        SamplingParams,
    )

    rng = random.Random(f"{seed}:decode")
    prompts = [[rng.randrange(16) for _ in range(rng.randrange(1, 5))]
               for _ in range(n_requests)]
    max_news = [rng.randrange(1, 5) for _ in range(n_requests)]
    # odd requests run the r17 committed-sampling policy: the stream is
    # keyed per (seed, emitted-index), so the stall schedule must not be
    # able to change a single byte of it
    samplings = [SamplingParams(temperature=0.8, top_k=6, seed=seed + i)
                 if i % 2 else None for i in range(n_requests)]

    engine = GenerationEngine(queue_depth=32, breaker_threshold=0)
    engine.set_tenant("a", weight=2.0)
    engine.set_tenant("b", weight=1.0, max_in_flight=1)
    entry = engine.register_model(
        lambda: _small_decode_model(f"stress{seed}"))
    refs = [entry.offline_decode(p, n, sampling=sp)
            for p, n, sp in zip(prompts, max_news, samplings)]
    beam_ref = entry.offline_beam(prompts[0], 3, BeamParams(2))

    faults.configure(_stall_rules(
        seed, ["decode.step", "decode.prefill", "decode.inject",
               "decode.sample", "decode.spill", "decode.resume"]))
    try:
        engine.start()
        resps = {}
        errors = []

        def submit_half(k):
            try:
                for i in range(k, n_requests, 2):
                    resps[i] = engine.submit(
                        prompts[i], max_new_tokens=max_news[i],
                        sampling=samplings[i],
                        tenant="a" if i % 3 else "b")
                    time.sleep(0.001 * ((seed + i) % 3))
            except BaseException as e:
                errors.append(e)

        ts = [threading.Thread(target=submit_half, args=(k,), daemon=True)
              for k in (0, 1)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert not errors, f"decode submit raised: {errors[:3]}"
        for i, resp in resps.items():
            got = [int(t) for t in resp.result(timeout=120)["tokens"]]
            assert got == refs[i], (
                f"seed {seed} request {i}: continuous {got} != offline "
                f"{refs[i]} — schedule changed the answer")
        # COW beam search under the same stall schedule: ranked
        # hypotheses byte-equal the offline reference, pool conserved
        beam = engine.submit(prompts[0], max_new_tokens=3,
                             beam_width=2).result(timeout=120)
        got_beams = [[int(t) for t in h["tokens"]] for h in beam["beams"]]
        assert got_beams == [list(rt) for rt, _rs in beam_ref], (
            f"seed {seed} beam: {got_beams} != {beam_ref}")
        entry.block_pool.check_conservation()
    finally:
        faults.reset()
        engine.shutdown()
    st = entry.stats()
    assert st["completed"] == n_requests + 1, st["completed"]
    assert st["failed"] == 0 and st["step_failures"] == 0
    assert st["sampled_tokens"] > 0
    overload = _decode_overload_leg(seed)
    return {"requests": n_requests + 1,
            "decode_steps": st["decode_steps"],
            "sampled_tokens": st["sampled_tokens"],
            "beam_forks": st["beam_forks"],
            "occupancy": round(st["occupancy"], 3),
            "parked": overload["parked"],
            "resumed": overload["resumed"]}


def _decode_overload_leg(seed):
    """r18 preemption under the stall schedule: an undersized block
    pool forces one of two in-flight sessions to park (KV rows spill to
    the host tier through decode.spill) and resume (decode.resume) —
    stalls inside the spill/re-inject window must not change a byte of
    either stream."""
    from paddle_tpu.resilience import faults
    from paddle_tpu.serving.decode import GenerationEngine

    rng = random.Random(f"{seed}:overload")
    prompts = [[rng.randrange(16) for _ in range(4)] for _ in range(2)]
    engine = GenerationEngine(queue_depth=8, breaker_threshold=0)
    entry = engine.register_model(lambda: _small_decode_model(
        f"stress_ov{seed}", slots=2, max_len=16, block_size=2,
        num_blocks=6))
    refs = [entry.offline_decode(p, 6) for p in prompts]
    faults.configure(_stall_rules(
        seed, ["decode.step", "decode.prefill", "decode.inject",
               "decode.sample", "decode.spill", "decode.resume"]))
    try:
        engine.start()
        resps = [engine.submit(p, max_new_tokens=6) for p in prompts]
        for i, resp in enumerate(resps):
            got = [int(t) for t in resp.result(timeout=120)["tokens"]]
            assert got == refs[i], (
                f"seed {seed} overload request {i}: {got} != {refs[i]} "
                f"— a spill/resume interleaving changed the answer")
        entry.block_pool.check_conservation()
    finally:
        faults.reset()
        engine.shutdown()
    st = entry.stats()
    # both prompts decode to 10 tokens against a 12-row pool: mid-gen
    # exhaustion parks (never fails) — the pool CAN fit each alone
    assert st["sessions_parked"] >= 1 and st["sessions_resumed"] >= 1, st
    assert st["failed"] == 0, st
    assert st["host_tier"]["spills"] >= 1, st["host_tier"]
    return {"parked": st["sessions_parked"],
            "resumed": st["sessions_resumed"]}


# ---------------------------------------------------------------------------
# scenario: embedding write-back vs serial reference (bit-exact tiers)
# ---------------------------------------------------------------------------


def _embedding_stream(seed, steps=30, batch=6, id_space=40):
    rng = random.Random(f"{seed}:embedding")
    return [[rng.randrange(id_space) for _ in range(batch)]
            for _ in range(steps)]


def _run_embedding(seed, stream, stressed):
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.embedding.store import EmbeddingEngine
    from paddle_tpu.embedding.table import TableConfig
    from paddle_tpu.resilience import faults

    scope = fluid.Scope()
    engine = EmbeddingEngine(scope=scope, push_workers=2)
    rt = engine.register(TableConfig(f"stress{seed}", 4, capacity=16, ep=2))
    stop = threading.Event()
    errors = []

    def poller():
        try:
            while not stop.is_set():
                rt.stats()
                len(rt.store)
                time.sleep(0.0005)
        except BaseException as e:
            errors.append(e)

    if stressed:
        faults.configure(_stall_rules(seed, ["lookup.pull", "lookup.push"]))
        th = threading.Thread(target=poller, daemon=True)
        th.start()
    try:
        for step, ids in enumerate(stream):
            arr = np.asarray(ids, dtype=np.int64)
            slots, _inv = rt.lookup(arr, train=True)
            # simulated train update: a pure function of (id, step), so
            # the final host tier is schedule-independent by contract
            slab = np.array(rt.slab_host())
            for idv in sorted(set(ids)):
                slab[rt._slot[idv]] += np.float32(
                    ((idv * 31 + step) % 7) * 0.125)
            scope.set(rt.cfg.slab_name, slab)
        engine.flush()
    finally:
        if stressed:
            stop.set()
            th.join(10)
            faults.reset()
        engine.close()
    assert not errors, f"embedding poller raised: {errors[:3]}"
    return rt.store.snapshot_blocks()


def scenario_embedding(seed):
    import numpy as np

    stream = _embedding_stream(seed)
    ref = _run_embedding(seed, stream, stressed=False)
    got = _run_embedding(seed, stream, stressed=True)
    assert len(ref) == len(got)
    rows = 0
    for (rid, rrow), (gid, grow) in zip(ref, got):
        assert np.array_equal(rid, gid), "host-tier id sets diverged"
        assert np.array_equal(rrow, grow), (
            f"seed {seed}: write-back order changed row VALUES — the "
            f"stale-read/marker contract is broken")
        rows += len(rid)
    return {"steps": len(stream), "host_rows": rows}


# ---------------------------------------------------------------------------
# scenario: dataio pipeline determinism under read stalls
# ---------------------------------------------------------------------------


def _dataio_digest(seed, num_workers, prefetch):
    import numpy as np

    from paddle_tpu.dataio.engine import DataEngine
    from paddle_tpu.dataio.prefetch import DevicePrefetcher
    from paddle_tpu.dataio.source import ListSource

    def transform(item, rng):
        return np.asarray([item * 3 + 1, rng.randrange(1000)],
                          dtype=np.int64)

    engine = DataEngine(
        ListSource(list(range(96)), seed=seed), transform=transform,
        batch_size=8, num_workers=num_workers, name=f"stress{seed}",
    )
    it = DevicePrefetcher(engine, depth=2) if prefetch else engine
    h = hashlib.sha256()
    for batch in it:
        # canonical int64 view: device placement narrows to int32 under
        # jax's default x64-off config — a dtype artifact, not a stream
        # property, so the digest compares VALUES
        h.update(np.ascontiguousarray(
            np.asarray(batch, dtype=np.int64)).tobytes())
    return h.hexdigest()


def scenario_dataio(seed):
    from paddle_tpu.resilience import faults

    ref = _dataio_digest(seed, num_workers=0, prefetch=False)
    faults.configure(_stall_rules(seed, ["dataio.read"], prob=0.3,
                                  delay_s=0.002))
    try:
        got = _dataio_digest(seed, num_workers=3, prefetch=True)
    finally:
        faults.reset()
    assert got == ref, (
        f"seed {seed}: dataio stream digest {got[:12]} != serial "
        f"reference {ref[:12]} — worker timing leaked into the stream")
    return {"digest": ref[:12]}


_SCENARIO_FNS = {
    "queue": scenario_queue,
    "decode": scenario_decode,
    "embedding": scenario_embedding,
    "dataio": scenario_dataio,
}


# ---------------------------------------------------------------------------
# deterministic drivers (single-threaded lockdep pass)
# ---------------------------------------------------------------------------


def _drive_decode():
    """Decode + serving-queue exercise with NO scheduler thread: submit,
    expire, admit (prefill+inject), step, retire — every acquisition on
    this thread, so the discovered edge set is a pure function of the
    code."""
    from paddle_tpu.serving.decode import GenerationEngine

    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    engine.set_tenant("a", weight=2.0)
    entry = engine.register_model(
        lambda: _small_decode_model("lockpass", slots=2, max_len=8))
    r1 = engine.submit([1, 2], max_new_tokens=2, tenant="a")
    r2 = engine.submit([3], max_new_tokens=2, tenant="b")
    dead = engine.submit([4], max_new_tokens=2, tenant="a",
                         deadline_ms=0.001)
    time.sleep(0.002)
    with entry._cond:
        for r in entry._queue.expire():
            entry._reject_expired(r)
    entry._admit_free_slots()
    for _ in range(4):
        entry._step()
    assert r1.done() and r2.done() and dead.done()
    assert entry.stats()["completed"] == 2
    # r17 generation modes on this same thread: beam fork/prune walks
    # blocks-under-slot chains, draft-KV walks decode.draft ->
    # decode.blocks (the declared proposal-slot chain)
    engine.register_model(
        lambda: _small_decode_model("lockpass_d", slots=2, max_len=8))
    b = engine.submit([1, 2], max_new_tokens=2, model="lockpass",
                      beam_width=2)
    s = engine.submit([3, 1], max_new_tokens=2, model="lockpass",
                      draft_model="lockpass_d", spec_k=2)
    for _ in range(12):
        if b.done() and s.done():
            break
        entry._iterate()
    assert b.done() and s.done()
    entry.block_pool.check_conservation()
    # r18 overload on this same thread: an undersized pool parks one of
    # two in-flight sessions — the spill write-back runs tier.put under
    # decode.blocks, witnessing the declared decode.blocks ->
    # decode.tier edge; the resume walks it again via the host tier
    ov = engine.register_model(
        lambda: _small_decode_model("lockpass_ov", slots=2, max_len=16,
                                    block_size=2, num_blocks=6))
    o1 = engine.submit([1, 2, 3, 4], max_new_tokens=6,
                       model="lockpass_ov")
    o2 = engine.submit([5, 6, 7, 8], max_new_tokens=6,
                       model="lockpass_ov")
    for _ in range(40):
        if o1.done() and o2.done():
            break
        ov._iterate()
    assert o1.done() and o2.done()
    assert o1.error() is None and o2.error() is None
    ost = ov.stats()
    assert ost["sessions_parked"] >= 1 and ost["sessions_resumed"] >= 1
    ov.block_pool.check_conservation()
    engine.stats()


def _drive_queue():
    from paddle_tpu.serving.decode.engine import GenerationRequest
    from paddle_tpu.serving.queue import RequestQueue
    from paddle_tpu.serving.request import Priority

    q = RequestQueue(max_depth=8)
    for i in range(4):
        q.put(GenerationRequest(i, [1], 2, "t", Priority.NORMAL, None))
    q.stats()          # re-entrant lane_depths under the RLock
    q.expire()
    with q.lock:
        head = q.head()
        q.remove([head])
    q.note_drained()


def _drive_embedding(tmpdir):
    """Embedding write-back + a checkpoint save through extra_state: the
    manifest/table/pending hierarchy in one deterministic pass."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.embedding.store import EmbeddingEngine
    from paddle_tpu.embedding.table import TableConfig
    from paddle_tpu.incubate.checkpoint import AutoCheckpoint

    scope = fluid.Scope()
    engine = EmbeddingEngine(scope=scope, push_workers=1)
    rt = engine.register(TableConfig("lockpass", 4, capacity=16, ep=2))
    for step in range(6):
        ids = np.asarray([(step * 5 + j) % 24 for j in range(6)], np.int64)
        rt.lookup(ids, train=True)
    ckpt = AutoCheckpoint(None, fluid.Program(), tmpdir,
                          save_interval_steps=1, scope=scope,
                          extra_state=engine)
    ckpt.save(0, blocking=True)
    ckpt.close()
    engine.flush()
    engine.close()


def _drive_metrics():
    from paddle_tpu.observability import metrics as obs_metrics
    from paddle_tpu.serving.metrics import ServingMetrics

    m = ServingMetrics(engine_label="lockdep-pass")
    m.tenant_incr("tokens", "a")
    m.tenant_counts("tokens")
    obs_metrics.scrape_text()


def _drive_dataio():
    _dataio_digest(0, num_workers=2, prefetch=True)


def _drive_fleet():
    """Fleet router + local replicas with NO pump or scheduler threads:
    submit (routing reads the replica queue depth under fleet.router —
    the hierarchy's top edge), a replica death, the parked re-dispatch,
    a hand-stepped completion, and the delivering tick — every
    acquisition on this thread."""
    from paddle_tpu.serving.decode import GenerationEngine
    from paddle_tpu.serving.fleet import FleetRouter, LocalReplica

    router = FleetRouter(health_interval_s=0.0)  # health pass each tick
    for i in range(2):
        engine = GenerationEngine(queue_depth=8, breaker_threshold=0,
                                  label=f"lockdep-fleet-{i}")
        engine.register_model(
            lambda: _small_decode_model("lockpass", slots=2, max_len=8))
        router.add_replica(LocalReplica(f"r{i}", i, engine))
    resp = router.submit([1, 2], max_new_tokens=1)
    (rr,) = router._inflight.values()
    victim = rr.replica
    router._replicas[victim].kill()
    router._mark_dead(victim, "lockpass")
    router._tick()          # health pass + re-dispatch of the parked rr
    assert rr.replica is not None and rr.replica != victim
    entry = router._replicas[rr.replica].engine.entry("lockpass", "1")
    entry._admit_free_slots()   # prefill fast path finishes max_new=1
    router._tick()              # poll + deliver
    assert resp.done() and resp.error() is None
    router.stats()


def lockdep_pass(tmpdir=None):
    """Run every deterministic driver under an armed, reset lockdep and
    return its snapshot (edges, declared chains, cycles, violations)."""
    import tempfile

    from paddle_tpu.observability import lockdep

    was = lockdep.enabled()
    hook = lockdep.get_stall_hook()
    own_tmp = None
    if tmpdir is None:
        own_tmp = tempfile.TemporaryDirectory(prefix="lockdep_pass_")
        tmpdir = own_tmp.name
    try:
        lockdep.set_stall_hook(None)
        lockdep.enable()
        lockdep.reset()
        _drive_queue()
        _drive_decode()
        _drive_embedding(tmpdir)
        _drive_metrics()
        _drive_dataio()
        _drive_fleet()
        return lockdep.snapshot()
    finally:
        lockdep.reset()
        lockdep.enable(was)
        lockdep.set_stall_hook(hook)
        if own_tmp is not None:
            own_tmp.cleanup()


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _run_scenarios(names, seed, as_json):
    import logging

    from paddle_tpu.observability import lockdep

    # injected stalls are the POINT here — one warning per stall would
    # drown the scenario summaries
    logging.getLogger("paddle_tpu.resilience.faults").setLevel(
        logging.ERROR)
    failures = []
    results = {}
    total_stalls = 0
    was = lockdep.enabled()
    try:
        lockdep.enable()
        for name in names:
            # FRESH witness state + stall schedule per scenario: the
            # (seed, lock, nth-acquisition) stall decisions must start
            # from zero so `--scenario X --seed N` replays exactly what
            # this scenario saw inside a --smoke run
            lockdep.reset()
            schedule = StallSchedule(seed)
            lockdep.set_stall_hook(schedule)
            t0 = time.perf_counter()
            try:
                results[name] = _SCENARIO_FNS[name](seed)
                results[name]["seconds"] = round(
                    time.perf_counter() - t0, 2)
                results[name]["stalls"] = schedule.stalls
                snap = lockdep.snapshot()
                if snap["cycles"] or snap["violations"]:
                    raise AssertionError(
                        f"lockdep reported cycles={snap['cycles']} "
                        f"violations={snap['violations']}")
                print(f"stress: {name} ok (seed {seed}): {results[name]}")
            # LockOrderError IS a finding (exit 1), not a harness error
            # (exit 2): the witness raising is the primary signal here
            except (AssertionError, lockdep.LockOrderError) as e:
                failures.append(f"{name}: {e}")
                print(f"STRESS FAIL {name} (replay: python tools/"
                      f"stress_concurrency.py --scenario {name} "
                      f"--seed {seed}): {e}", file=sys.stderr)
            total_stalls += schedule.stalls
    finally:
        lockdep.set_stall_hook(None)
        lockdep.reset()
        lockdep.enable(was)
        from paddle_tpu.resilience import faults

        faults.reset()
    if not failures:
        print(f"stress: all scenarios bit-exact under seed {seed} "
              f"({total_stalls} lock-boundary stalls injected, "
              f"lockdep clean)")
    if as_json:
        print(json.dumps({"pass": not failures, "seed": seed,
                          "stalls": total_stalls,
                          "results": results, "failures": failures}))
    return EXIT_FINDINGS if failures else EXIT_CLEAN


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="deterministic concurrency stress harness")
    ap.add_argument("--scenario", choices=SCENARIOS, action="append",
                    help="run one scenario (repeatable; default all)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="tier-1 gate: all scenarios once on the seed")
    ap.add_argument("--json", action="store_true", dest="as_json")
    try:
        args = ap.parse_args(argv)
        if args.smoke and args.scenario:
            print("--smoke is the ALL-scenarios tier-1 gate; drop "
                  "--scenario (use --scenario/--seed alone to replay)",
                  file=sys.stderr)
            return EXIT_INTERNAL
        names = list(SCENARIOS) if args.smoke \
            else (args.scenario or list(SCENARIOS))
        return _run_scenarios(names, args.seed, args.as_json)
    except SystemExit as e:
        raise SystemExit(EXIT_INTERNAL if e.code not in (0, 1, 2)
                         else e.code)
    except Exception:
        import traceback

        traceback.print_exc()
        return EXIT_INTERNAL


if __name__ == "__main__":
    sys.exit(main())
