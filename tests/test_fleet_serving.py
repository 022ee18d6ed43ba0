"""Fleet router tests (r12): replica health, chaos-proven failover,
prefix-affinity routing, load shedding, elasticity, rolling deploys.

The core property, asserted every way this file can reach it: once the
fleet ACCEPTS a request, exactly one answer is delivered and — because
decode is bit-deterministic — it is byte-identical to the single-replica
offline reference, no matter which replicas died, quarantined, or
drained along the way.
"""

import argparse
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from paddle_tpu.observability import lockdep
from paddle_tpu.resilience import faults
from paddle_tpu.serving.decode import GenerationEngine, build_decoder_model
from paddle_tpu.serving.fleet import (
    FleetRouter,
    LocalReplica,
    SubprocessReplica,
)
from paddle_tpu.serving.fleet.replica import error_from_dict
from paddle_tpu.serving.queue import RequestQueue
from paddle_tpu.serving.request import (
    DeadlineExceededError,
    Priority,
    RejectedError,
    ReplicaLostError,
    RequestError,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one geometry for the whole file: the first build traces, everything
# after hits the process-wide compile cache
GEOM = dict(vocab_size=24, hidden=8, num_layers=1, slots=2, max_len=16)


def _builder(name="fleet_t", version="1", **over):
    kw = {**GEOM, **over}

    def b():
        return build_decoder_model(name=name, version=version, **kw)

    return b


def _local_factory(builder=None, queue_depth=64):
    b = builder or _builder()

    def factory(index):
        return LocalReplica.create(f"r{index}", index, b,
                                   queue_depth=queue_depth)

    return factory


@pytest.fixture(autouse=True)
def clean_faults():
    """Chaos landmine: the injector parses PADDLE_TPU_FAULTS lazily ONCE
    — reset around every test so schedules never leak."""
    faults.reset()
    yield
    faults.reset()


class _FakeHandle:
    """Routing-surface stub (load/index/models only) for the pure
    routing-policy unit tests — no engine, no threads."""

    transport = "fake"

    def __init__(self, rid, index, load=0):
        self.rid = rid
        self.index = index
        self._load = load

    def load(self):
        return self._load

    def models(self):
        return [("m", "1")]

    def trace_count(self):
        return 0

    def close(self, timeout=0):
        pass


def _route_of(router, prompt):
    from paddle_tpu.serving.fleet.router import RoutedRequest

    rr = RoutedRequest(0, prompt, 4, "t", Priority.NORMAL, None, "m", "1")
    with router._lock:
        return router._route(rr, set())


# ---------------------------------------------------------------------------
# routing policy (pure units over fake handles)
# ---------------------------------------------------------------------------


def test_rendezvous_affinity_stable_under_membership_change():
    """Same prompt prefix -> same replica; removing an UNRELATED replica
    never moves the key (rendezvous property: only keys owned by the
    departed replica move); removing the target reassigns it."""
    router = FleetRouter(affinity_prefix=4)
    for i in range(3):
        router.add_replica(_FakeHandle(f"r{i}", i))
    prompt = [3, 1, 4, 1, 5]
    target = _route_of(router, prompt)
    assert _route_of(router, prompt) == target
    # same prefix, different tail: same affinity bucket
    assert _route_of(router, prompt[:4] + [9]) == target
    other = next(r for r in router._replicas if r != target)
    with router._lock:
        del router._replicas[other]
        del router._health[other]
    assert _route_of(router, prompt) == target
    with router._lock:
        del router._replicas[target]
        del router._health[target]
    moved = _route_of(router, prompt)
    assert moved is not None and moved != target


def test_affinity_spills_to_least_loaded_when_saturated():
    router = FleetRouter(affinity_prefix=4, saturation_rows=5)
    router.add_replica(_FakeHandle("r0", 0, load=10))
    router.add_replica(_FakeHandle("r1", 1, load=0))
    router.add_replica(_FakeHandle("r2", 2, load=10))
    for seed in range(8):
        prompt = [seed, seed + 1, 2, 3]
        assert _route_of(router, prompt) == "r1", (
            "saturated affinity target must spill to the least-loaded "
            "healthy replica")


def test_dead_and_draining_replicas_leave_the_routing_set():
    router = FleetRouter()
    for i in range(2):
        router.add_replica(_FakeHandle(f"r{i}", i))
    with router._lock:
        router._health["r0"].mark_dead("test")
        router._draining.add("r1")
        assert router._routable() == []
    with router._lock:
        router._draining.discard("r1")
        assert router._routable() == ["r1"]


# ---------------------------------------------------------------------------
# end-to-end over local replicas
# ---------------------------------------------------------------------------


def test_fleet_serves_bit_identical_to_offline_reference():
    router = FleetRouter(health_interval_s=0.05)
    factory = _local_factory()
    for i in range(2):
        router.add_replica(factory(i))
    router.start()
    try:
        prompts = [[3, 1, 4], [1, 5], [3, 1, 4], [9, 2, 6, 5]]
        entry = router._replicas["r0"].engine.entry("fleet_t", "1")
        refs = [entry.offline_decode(p, 5) for p in prompts]
        resps = [router.submit(p, max_new_tokens=5) for p in prompts]
        outs = [[int(t) for t in r.result(timeout=120)["tokens"]]
                for r in resps]
        assert outs == refs
        st = router.stats()
        assert st["accepted"] == 4 and st["completed"] == 4
        assert st["failed"] == 0 and st["replica_deaths"] == 0
    finally:
        router.shutdown()


def test_kill_mid_flight_redispatches_bit_identical(clean_faults):
    """THE failover property: a replica dies (replica.kill fault site)
    while holding live work; every accepted request still completes,
    byte-identical to the offline reference, and the re-dispatches are
    counted."""
    router = FleetRouter(health_interval_s=0.01)
    factory = _local_factory()
    for i in range(3):
        router.add_replica(factory(i))
    router.start()
    try:
        import random

        rng = random.Random(3)
        prompts = [[rng.randrange(GEOM["vocab_size"])
                    for _ in range(rng.randrange(1, 5))] for _ in range(12)]
        entry = router._replicas["r0"].engine.entry("fleet_t", "1")
        refs = [entry.offline_decode(p, 6) for p in prompts]
        resps = []
        armed = False
        for i, p in enumerate(prompts):
            resps.append(router.submit(p, max_new_tokens=6))
            if not armed:
                with router._lock:
                    holding = sum(
                        1 for rr in router._inflight.values()
                        if rr.replica == "r1" and rr.state == "inflight")
                if holding >= 1 or i == len(prompts) - 1:
                    faults.configure([{"site": "replica.kill",
                                       "action": "raise", "rank": 1}])
                    armed = True
            time.sleep(0.002)
        outs = [[int(t) for t in r.result(timeout=120)["tokens"]]
                for r in resps]
        assert outs == refs, "failover changed the bytes"
        st = router.stats()
        assert st["accepted"] == 12 and st["completed"] == 12
        assert st["replica_deaths"] == 1
        assert st["replicas"]["r1"]["state"] == "dead"
        assert st["rerouted"] >= 1
    finally:
        router.shutdown()


def test_injected_dispatch_fault_fails_over_invisibly(clean_faults):
    """A transient fleet.dispatch fault on one replica: the request
    lands elsewhere; the caller never sees it."""
    router = FleetRouter(health_interval_s=0.05)
    factory = _local_factory()
    for i in range(2):
        router.add_replica(factory(i))
    router.start()
    try:
        faults.configure([{"site": "fleet.dispatch", "action": "raise",
                           "rank": 0, "times": 1, "id": "d0"}])
        outs = []
        for k in range(6):
            r = router.submit([k + 1, 2, 3], max_new_tokens=3)
            outs.append(r.result(timeout=120)["tokens"])
        inj = faults.get_injector()
        assert inj.rule_stats()["d0"]["fired"] == 1
        st = router.stats()
        assert st["dispatch_faults"] == 1
        assert st["accepted"] == 6 and st["completed"] == 6
        # the faulted replica is still healthy (one transient failure
        # is below the breaker threshold)
        assert st["replicas"]["r0"]["state"] in ("closed", "half_open")
    finally:
        router.shutdown()


def test_health_fault_quarantines_then_readmits(clean_faults):
    """Consecutive heartbeat-probe failures open the replica's breaker
    (quarantine: no routing); once probes succeed again, the cooldown
    probe re-admits it — the PR-2 breaker contract at fleet scope."""
    router = FleetRouter(health_interval_s=0.01, breaker_threshold=2,
                         breaker_cooldown_s=0.03)
    factory = _local_factory()
    for i in range(2):
        router.add_replica(factory(i))
    router.start()
    try:
        faults.configure([{"site": "fleet.health", "action": "raise",
                           "rank": 0, "times": 2, "id": "h0"}])
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if router.metrics.count("breaker_opened") >= 1:
                break
            time.sleep(0.005)
        assert router.metrics.count("breaker_opened") >= 1, \
            "probe failures never opened the breaker"
        with router._lock:
            assert "r0" not in router._routable()
        # schedule exhausted -> probes succeed -> breaker closes
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if router.replicas()["r0"] == "closed":
                break
            time.sleep(0.005)
        assert router.replicas()["r0"] == "closed", \
            "replica never re-admitted after cooldown probe"
        assert router.metrics.count("breaker_closed") >= 1
        # quarantine was never an outage: the other replica serves
        r = router.submit([1, 2], max_new_tokens=3)
        assert len(r.result(timeout=120)["tokens"]) == 3
    finally:
        router.shutdown()


# ---------------------------------------------------------------------------
# deadline-budget propagation through re-dispatch (hand-stepped)
# ---------------------------------------------------------------------------


def _unstarted_local(rid, index, builder):
    """A LocalReplica whose engine scheduler is NOT running: submissions
    sit in the queue, so dispatch state is fully deterministic."""
    engine = GenerationEngine(queue_depth=64, breaker_threshold=0,
                              label=f"fleet-hand-{rid}")
    engine.register_model(builder)
    return LocalReplica(rid, index, engine)


def test_redispatch_preserves_original_deadline():
    """The satellite contract: a re-dispatched request carries its
    ORIGINAL absolute deadline — the retry inherits the remaining
    budget, never a fresh one (queue.py reroute + engine deadline_at)."""
    router = FleetRouter(health_interval_s=1e9)  # hand-stepped: no pump
    b = _builder()
    for i in range(2):
        router.add_replica(_unstarted_local(f"r{i}", i, b))
    resp = router.submit([1, 2, 3], max_new_tokens=4, deadline_ms=60000)
    (rr,) = router._inflight.values()
    victim = rr.replica
    original = rr.deadline_at
    assert original is not None
    inner_q = router._replicas[victim].engine.entry(
        "fleet_t", "1")._queue
    assert inner_q.iter_requests()[0].deadline == original
    # the replica dies; the pump re-dispatches under the SAME deadline
    router._replicas[victim].kill()
    router._mark_dead(victim, "test")
    assert rr.state == "parked"
    router._tick()
    assert rr.state == "inflight" and rr.replica != victim
    assert rr.deadline_at == original, "re-dispatch refreshed the budget"
    survivor_q = router._replicas[rr.replica].engine.entry(
        "fleet_t", "1")._queue
    inner = survivor_q.iter_requests()[0]
    assert inner.deadline == original, (
        "inner request on the failover replica must carry the original "
        "absolute deadline")
    assert not resp.done()


def test_expired_budget_completes_deadline_not_lost():
    """A request whose budget ran out while parked completes with
    DeadlineExceededError (a visible structured outcome — the zero-loss
    ledger's 'deadline' bucket, never a silent drop)."""
    router = FleetRouter(health_interval_s=1e9)
    b = _builder()
    for i in range(2):
        router.add_replica(_unstarted_local(f"r{i}", i, b))
    resp = router.submit([1, 2], max_new_tokens=4, deadline_ms=5)
    (rr,) = router._inflight.values()
    router._replicas[rr.replica].kill()
    router._mark_dead(rr.replica, "test")
    time.sleep(0.01)  # past the 5ms budget
    router._tick()
    assert resp.done()
    with pytest.raises(DeadlineExceededError):
        resp.result()
    assert router.metrics.count("deadline_missed") == 1
    assert router.metrics.count("rerouted") == 0


def test_parked_request_for_retired_version_completes_structured():
    """A parked request whose (model, version) can never be served
    again (retired fleet-wide) must complete with the structured
    rejection — not busy-spin re-dispatching forever. Backpressure
    rejections (retry_after > 0) keep it parked instead."""
    router = FleetRouter(health_interval_s=1e9)
    b = _builder(name="dd", version="1")
    for i in range(2):
        router.add_replica(_unstarted_local(f"r{i}", i, b))
    resp = router.submit([1, 2], max_new_tokens=3)
    (rr,) = router._inflight.values()
    victim = rr.replica
    router._replicas[victim].kill()
    router._mark_dead(victim, "test")
    survivor = next(r for r in router._replicas if r != victim)
    router._replicas[survivor].engine.unregister_model("dd", "1")
    router._tick()
    assert resp.done()
    with pytest.raises(RejectedError):
        resp.result()
    assert rr.id not in router._inflight


def test_queue_reroute_counts_apart_from_rejections():
    from paddle_tpu.serving.decode.engine import GenerationRequest

    q = RequestQueue(max_depth=3)
    reqs = [GenerationRequest(i, [1], 2, "t", Priority.NORMAL, None)
            for i in range(3)]
    for r in reqs:
        q.put(r)
    with pytest.raises(RejectedError):
        q.put(GenerationRequest(9, [1], 2, "t", Priority.NORMAL, None))
    q.reroute(reqs[:2])
    st = q.stats()
    assert st["rerouted"] == 2
    assert st["rejected_at_admission"] == 1
    assert st["expired_in_queue"] == 0
    assert st["depth"] == 1


def test_engine_reroute_queued_and_unregister():
    """Engine-side drain primitives the router composes: reroute_queued
    empties the admission queue (tenant counters released, rerouted
    counted); unregister_model drain-retires an entry and `latest`
    falls back in registration order."""
    engine = GenerationEngine(queue_depth=64, breaker_threshold=0,
                              label="fleet-reroute-unit")
    engine.register_model(_builder(name="ru", version="1"))
    for k in range(3):
        engine.submit([k + 1, 2], max_new_tokens=3, tenant="a")
    stolen = engine.reroute_queued("ru", "1")
    assert len(stolen) == 3
    entry = engine.entry("ru", "1")
    assert entry._queue.depth() == 0
    assert entry._queue.stats()["rerouted"] == 3
    assert engine.stats()["tenants"]["a"]["queued"] == 0
    # registry: v2 becomes latest, retiring it falls back to v1
    engine.register_model(_builder(name="ru", version="2"))
    assert engine.entry("ru").model.version == "2"
    engine.unregister_model("ru", "2")
    assert engine.entry("ru").model.version == "1"
    engine.unregister_model("ru", "1")
    with pytest.raises(RejectedError):
        engine.entry("ru")


# ---------------------------------------------------------------------------
# load shedding
# ---------------------------------------------------------------------------


def test_fleet_sheds_with_measured_retry_after_when_saturated():
    """Every replica full -> the router rejects (the request was never
    accepted) with the fleet's soonest retry-after; the accepted ones
    are all still accounted."""
    router = FleetRouter(health_interval_s=1e9)
    b = _builder()
    for i in range(2):
        h = LocalReplica(f"r{i}", i, GenerationEngine(
            queue_depth=1, breaker_threshold=0, label=f"fleet-shed-{i}"))
        h.engine.register_model(b)
        router.add_replica(h)
    accepted = 0
    shed = None
    for k in range(4):
        try:
            router.submit([k + 1, 2], max_new_tokens=3)
            accepted += 1
        except RejectedError as e:
            shed = e
    assert accepted == 2  # one row per replica queue
    assert shed is not None and shed.retry_after_s > 0
    assert router.metrics.count("rejected_shed") == 2
    assert router.metrics.count("accepted") == 2


# ---------------------------------------------------------------------------
# elasticity + rolling deploys
# ---------------------------------------------------------------------------


def test_scale_up_zero_traces_and_scale_down_drains():
    factory = _local_factory()
    router = FleetRouter(replica_factory=factory, health_interval_s=0.05)
    for i in range(2):
        router.add_replica(factory(i))
    router.start()
    try:
        new = router.scale_up()
        assert new.trace_count() == 0, (
            "scale-up replica must warm from the compile cache, not XLA")
        assert router.last_scaleup_traces == 0
        assert len(router.replicas()) == 3
        r = router.submit([1, 2, 3], max_new_tokens=3)
        r.result(timeout=120)
        retired = router.scale_down()
        assert retired is not None
        assert len(router.replicas()) == 2
        st = router.stats()
        assert st["scale_ups"] == 1 and st["scale_downs"] == 1
    finally:
        router.shutdown()


def test_rolling_deploy_pins_until_complete_then_flips():
    """Two-pass roll: unversioned traffic stays on the pinned OLD
    version until every replica hosts the new one; after the flip the
    old version is drain-retired everywhere and explicit requests for
    it shed with a structured rejection."""
    router = FleetRouter(health_interval_s=0.05)
    factory = _local_factory()
    for i in range(2):
        router.add_replica(factory(i))
    router.start()
    try:
        p = [3, 1, 4]
        ref_v1 = router._replicas["r0"].engine.entry(
            "fleet_t", "1").offline_decode(p, 4)
        stop = False
        mid_roll = []

        def traffic():
            while not stop:
                try:
                    r = router.submit(p, max_new_tokens=4)
                    mid_roll.append(
                        [int(t) for t in r.result(60)["tokens"]])
                except Exception as e:  # any error mid-roll is a finding
                    mid_roll.append(("ERR", str(e)))
                time.sleep(0.004)

        t = threading.Thread(target=traffic, daemon=True)
        t.start()
        # v2 has different geometry -> provably different bytes
        router.deploy(_builder(name="fleet_t", version="2", num_layers=2),
                      version="2")
        stop = True
        t.join(30)
        ref_v2 = router._replicas["r0"].engine.entry(
            "fleet_t", "2").offline_decode(p, 4)
        assert ref_v2 != ref_v1
        # every mid-roll answer is a CLEAN version's bytes (no errors,
        # no torn outputs), and the unversioned stream switches v1 -> v2
        # exactly once: v1 until the atomic pin flip, v2 after — a v1
        # answer after a v2 one would mean a request raced the roll.
        # (deploy() keeps draining pass 2 AFTER the flip, so traffic
        # legitimately sees v2 before deploy returns.)
        bad = [x for x in mid_roll
               if not (isinstance(x, list) and x in (ref_v1, ref_v2))]
        assert not bad, f"mid-roll traffic disturbed: {bad[:3]}"
        switches = [a != b for a, b in zip(mid_roll, mid_roll[1:])]
        assert sum(switches) <= 1, "mid-roll traffic flapped versions"
        assert mid_roll and mid_roll[0] == ref_v1, \
            "traffic saw v2 before the flip"
        got = [int(t) for t in
               router.submit(p, max_new_tokens=4).result(60)["tokens"]]
        assert got == ref_v2
        for rid in ("r0", "r1"):
            assert router._replicas[rid].models() == [("fleet_t", "2")]
        st = router.stats()
        assert st["pinned_versions"] == {"fleet_t": "2"}
        assert st["deploys"] == 1
        with pytest.raises(RejectedError):
            router.submit(p, max_new_tokens=4, version="1")
    finally:
        router.shutdown()


class _FakeReplaceableHandle(_FakeHandle):
    """A subprocess-shaped handle: deploys by replacement, retires over
    the 'wire'. Tracks the protocol calls the router must make."""

    transport = "fake-subprocess"

    def __init__(self, rid, index, hosted=None, log=None):
        super().__init__(rid, index)
        self.hosted = hosted or [("m", "1")]
        self.log = log if log is not None else []
        self.closed = False

    def models(self):
        return list(self.hosted)

    def deploy(self, builder, name, new_version):
        raise AssertionError("in-place deploy must not be used on a "
                             "replacement-capable handle")

    def spawn_replacement(self, new_spec, startup_timeout=0):
        self.log.append(("spawn_replacement", self.rid, new_spec["name"],
                         new_spec["version"]))
        return _FakeReplaceableHandle(
            self.rid, self.index,
            hosted=self.hosted + [(new_spec["name"],
                                   str(new_spec["version"]))],
            log=self.log)

    def steal_queued(self):
        self.log.append(("steal", self.rid))
        return []

    def retire(self, name, version, timeout=0):
        self.log.append(("retire", self.rid, name, str(version)))
        self.hosted = [m for m in self.hosted
                       if m != (name, str(version))]

    def close(self, timeout=0):
        self.log.append(("close", self.rid))
        self.closed = True


def test_deploy_by_replacement_protocol_order():
    """ROADMAP 3(b) unit: a replacement-capable replica deploys by
    spawn-replacement -> steal backlog -> swap into the same slot ->
    close old; pass 2 retires the old version from the REPLACEMENT over
    the wire. worker_spec is mandatory for such replicas."""
    router = FleetRouter(health_interval_s=1e9)
    log = []
    old = _FakeReplaceableHandle("r0", 0, log=log)
    router.add_replica(old)

    class _LocalFake(_FakeHandle):
        deploys = []

        def deploy(self, builder, name, version):
            self.deploys.append((name, version))

    local = _LocalFake("r1", 1)
    router.add_replica(local)
    # precondition fires up front: ZERO replicas touched (a
    # half-registered pass 1 could never be retried)
    with pytest.raises(RuntimeError, match="worker_spec"):
        router.deploy(None, version="2", name="m")
    assert not old.closed and router._replicas["r0"] is old
    assert not log and not local.deploys
    with router._lock:
        del router._replicas["r1"]
        del router._health["r1"]

    router.deploy(None, version="2", name="m",
                  worker_spec={"hidden": 8})
    new = router._replicas["r0"]
    assert new is not old and old.closed and not new.closed
    # replacement hosted both until pass 2 retired the old version
    assert new.models() == [("m", "2")]
    assert router.stats()["pinned_versions"]["m"] == "2"
    assert router.metrics.count("replaced_deploys") == 1
    assert router.metrics.count("deploys") == 1
    spawn_i = log.index(("spawn_replacement", "r0", "m", "2"))
    steal_i = log.index(("steal", "r0"))
    close_i = log.index(("close", "r0"))
    retire_i = log.index(("retire", "r0", "m", "1"))
    assert spawn_i < steal_i < close_i < retire_i


@pytest.mark.slow
def test_subprocess_rolling_deploy_by_replacement(tmp_path):
    """ROADMAP 3(b) with a REAL subprocess: the router rolls a new
    (model, version) onto a SubprocessReplica by spawning a replacement
    worker hosting old+new, draining the old worker out of its slot,
    flipping the pin, and retiring the old version over the RPC wire.
    v2 has different geometry, so the version switch is provable in the
    output bytes."""
    cache = str(tmp_path / "cache")
    margs = {**GEOM, "name": "flt_roll", "version": "1"}
    r0 = SubprocessReplica.spawn(
        "r0", 0, margs, extra_env={"JAX_COMPILATION_CACHE_DIR": cache})
    old_pid = r0.proc.pid

    # in-process references: deterministic init = byte-identical weights
    engine = GenerationEngine(breaker_threshold=0, label="roll-ref")
    e1 = engine.register_model(_builder(name="flt_roll", version="1"))
    e2 = engine.register_model(_builder(name="flt_roll", version="2",
                                        num_layers=2))
    p = [3, 1, 4]
    ref_v1 = e1.offline_decode(p, 4)
    ref_v2 = e2.offline_decode(p, 4)
    assert ref_v1 != ref_v2

    router = FleetRouter(health_interval_s=0.02)
    router.add_replica(r0)
    router.start()
    try:
        got = [int(t) for t in
               router.submit(p, max_new_tokens=4).result(240)["tokens"]]
        assert got == ref_v1
        router.deploy(None, version="2", name="flt_roll",
                      worker_spec={**GEOM, "num_layers": 2})
        new = router._replicas["r0"]
        assert isinstance(new, SubprocessReplica)
        assert new.proc.pid != old_pid, "no replacement worker spawned"
        assert r0.proc.poll() is not None, "old worker still running"
        # pass 2 retired v1 over the wire: only v2 remains hosted
        assert new.models() == [("flt_roll", "2")]
        got = [int(t) for t in
               router.submit(p, max_new_tokens=4).result(240)["tokens"]]
        assert got == ref_v2, "unversioned traffic not on the new version"
        st = router.stats()
        assert st["replaced_deploys"] == 1 and st["deploys"] == 1
        assert st["pinned_versions"]["flt_roll"] == "2"
        with pytest.raises(RejectedError):
            router.submit(p, max_new_tokens=4, version="1")
    finally:
        router.shutdown()


# ---------------------------------------------------------------------------
# error taxonomy
# ---------------------------------------------------------------------------


def test_router_rejects_invalid_submissions_structured():
    router = FleetRouter(health_interval_s=1e9)
    router.add_replica(_FakeHandle("r0", 0))
    for bad_call in (
        lambda: router.submit([], max_new_tokens=3),
        lambda: router.submit("nope", max_new_tokens=3),
        lambda: router.submit([1, 2], max_new_tokens=0),
    ):
        with pytest.raises(RejectedError):
            bad_call()
    assert router.metrics.count("rejected_invalid") == 3
    assert router.metrics.count("accepted") == 0


def test_replica_lost_error_classifies_for_failover():
    assert issubclass(ReplicaLostError, RequestError)
    assert ReplicaLostError("x").code == "replica_lost"
    # wire round-trip (subprocess transport) preserves the class
    e = error_from_dict(ReplicaLostError("lost mid-step").to_dict())
    assert isinstance(e, ReplicaLostError)
    e = error_from_dict(RejectedError("full", retry_after_s=0.5).to_dict())
    assert isinstance(e, RejectedError) and e.retry_after_s == 0.5


# ---------------------------------------------------------------------------
# supervisor: replica-grained restart
# ---------------------------------------------------------------------------


def test_supervisor_restart_single_rank(tmp_path):
    from paddle_tpu.resilience.supervisor import GangSupervisor

    script = tmp_path / "sleepy.py"
    script.write_text("import time, sys\ntime.sleep(30)\nsys.exit(0)\n")
    sup = GangSupervisor([str(script)], nproc=3)
    procs = sup.launch()
    pids = [p.pid for p in procs]
    try:
        sup.restart(1)
        assert sup._procs[1].pid != pids[1]
        # the other ranks were NOT disturbed
        assert sup._procs[0].pid == pids[0] and procs[0].poll() is None
        assert sup._procs[2].pid == pids[2] and procs[2].poll() is None
        assert sup.rank_restarts == {1: 1}
        ev = [e for e in sup.events if e["kind"] == "rank_restart"]
        assert len(ev) == 1 and ev[0]["rank"] == 1
        from paddle_tpu import observability
        c = observability.registry().get(
            "resilience_events_total", labels={"kind": "rank_restart"})
        assert c is not None and c.value >= 1
    finally:
        sup.terminate()
    assert all(p.poll() is not None for p in sup.procs())


# ---------------------------------------------------------------------------
# the race-class hammer (PR 11 pattern, armed witness)
# ---------------------------------------------------------------------------


@pytest.fixture
def armed_lockdep():
    was = lockdep.enabled()
    lockdep.enable()
    lockdep.reset()
    yield lockdep
    lockdep.reset()
    lockdep.enable(was)


def test_router_hammer_8_threads_under_lockdep(armed_lockdep):
    """8 submit threads race the pump's failover/health passes and
    stats readers while a replica dies mid-hammer: totals must stay
    exact (accepted == completed: no deadlines in play), the witness
    must stay silent, and every future must resolve."""
    router = FleetRouter(health_interval_s=0.01)
    factory = _local_factory()
    for i in range(3):
        router.add_replica(factory(i))
    router.start()
    errors = []
    responses = []
    resp_lock = threading.Lock()
    stop = threading.Event()
    N = 12

    def submitter(k):
        try:
            for i in range(N):
                r = router.submit([((k * N + i) % 23) + 1, 2],
                                  max_new_tokens=3, tenant=f"t{k % 3}")
                with resp_lock:
                    responses.append(r)
                time.sleep(0.001)
        except BaseException as e:
            errors.append(e)

    def reader():
        try:
            last = 0
            while not stop.is_set():
                st = router.stats()
                assert st["completed"] >= last
                last = st["completed"]
                router.replicas()
        except BaseException as e:
            errors.append(e)

    threads = [threading.Thread(target=submitter, args=(k,), daemon=True)
               for k in range(8)]
    threads.append(threading.Thread(target=reader, daemon=True))
    for t in threads:
        t.start()
    time.sleep(0.05)
    router._replicas["r2"].kill()  # die mid-hammer
    for t in threads[:-1]:
        t.join(120)
    stop.set()
    threads[-1].join(10)
    assert not errors, f"hammer raised: {errors[:3]}"
    outs = [r.result(timeout=120) for r in responses]
    assert all(len(o["tokens"]) == 3 for o in outs)
    st = router.stats()
    assert st["accepted"] == 8 * N
    assert st["completed"] == 8 * N, (
        f"zero-loss violated under the hammer: {st}")
    snap = lockdep.snapshot()
    assert snap["violations"] == [] and snap["cycles"] == []
    # the hierarchy was actually exercised top-down
    assert ["fleet.router", "serving.queue"] in snap["edges"]
    router.shutdown()


# ---------------------------------------------------------------------------
# subprocess transport: kill a real process (slow tier)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_subprocess_kill_a_replica_bit_identical(tmp_path):
    """The full story with real processes: two workers over the RPC
    transport (second warms zero-trace from the jax.export disk cache),
    a schedule-driven ``replica.kill`` hard-exits one mid-traffic
    (exit_code 43, no flushes), the router re-dispatches its work
    bit-identically, and a scale-up worker replaces it — also with
    zero traces."""
    cache = str(tmp_path / "cache")
    margs = {**GEOM, "name": "flt", "version": "1"}
    kill_sched = json.dumps([{
        "site": "replica.kill", "action": "kill", "at_call": 6,
        "rank": 1, "id": "sub-kill",
    }])

    def spawn(index, fault=False):
        env = {"JAX_COMPILATION_CACHE_DIR": cache}
        if fault:
            env["PADDLE_TPU_FAULTS"] = kill_sched
        return SubprocessReplica.spawn(f"r{index}", index, margs,
                                       extra_env=env)

    # in-process offline reference: deterministic init means the
    # subprocess workers hold byte-identical weights
    engine = GenerationEngine(breaker_threshold=0, label="sub-ref")
    entry = engine.register_model(_builder(name="flt", version="1"))
    import random

    rng = random.Random(1)
    prompts = [[rng.randrange(GEOM["vocab_size"])
                for _ in range(rng.randrange(1, 5))] for _ in range(10)]
    refs = [entry.offline_decode(p, 6) for p in prompts]

    r0 = spawn(0)
    assert r0.trace_count() == 3  # cold: populates the disk tier
    r1 = spawn(1, fault=True)
    assert r1.trace_count() == 0, "disk-tier warm start broken"

    router = FleetRouter(replica_factory=lambda i: spawn(i),
                         health_interval_s=0.02)
    router.add_replica(r0)
    router.add_replica(r1)
    router.start()
    try:
        resps = [router.submit(p, max_new_tokens=6) for p in prompts]
        outs = [[int(t) for t in r.result(timeout=240)["tokens"]]
                for r in resps]
        assert outs == refs, "cross-process failover changed the bytes"
        # the worker died the hard way, mid-service
        assert r1.proc.wait(timeout=60) == 43
        st = router.stats()
        assert st["accepted"] == 10 and st["completed"] == 10
        assert st["replica_deaths"] == 1
        assert st["replicas"]["r1"]["state"] == "dead"
        # replacement worker: serving-ready, ZERO traces
        new = router.scale_up()
        assert new.trace_count() == 0
        r = router.submit(prompts[0], max_new_tokens=6)
        assert [int(t) for t in r.result(timeout=240)["tokens"]] \
            == refs[0]
    finally:
        router.shutdown()


# ---------------------------------------------------------------------------
# the chaos scenario in-process + CLI smoke (tier-1 wiring)
# ---------------------------------------------------------------------------


def _load_tool(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_replica_kill_loses_nothing_and_changes_no_token():
    """tools/chaos_serve.py's scenario in-process (3 replicas, 18
    requests, 13 unique prompts, replica 1 killed while holding work):
    accepted == completed, nothing lost, the kill fired once and took
    one replica, the replacement served with zero traces, and every
    token equals both the offline reference's and the unkilled leg's."""
    import logging

    cs = _load_tool("chaos_serve")
    logging.getLogger("paddle_tpu.resilience.faults").setLevel(
        logging.ERROR)
    cfg = cs.default_cfg(argparse.Namespace(
        replicas=3, requests=18, max_new=6, kill_replica=1, seed=2,
        arrival_s=0.002))
    report = cs.run_scenario(cfg)
    assert report["failures"] == [], report["failures"]
    inv = report["invariants"]
    assert inv["accepted"] == inv["completed"] == 18 and inv["lost"] == 0
    assert inv["kill_fired"] and inv["replica_deaths"] == 1
    assert inv["bit_identical"] and inv["tokens_equal_unkilled"]
    assert inv["scaleup_traces"] == 0
    assert inv["unique_prompts"] < 18            # repeats: affinity fires
    assert report["measured"]["rerouted"] >= 1


def test_chaos_serve_smoke_cli():
    """Fast-tier gate: the chaos scenario end-to-end through the CLI —
    kill one of three replicas, zero loss, bit-identity, rerouted
    counter moved, zero-trace scale-up, bounded p99."""
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos_serve.py"),
         "--smoke", "--json"],
        capture_output=True, text=True, timeout=600, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
    payload = json.loads(
        [l for l in res.stdout.splitlines() if l.startswith("{")][-1])
    assert payload["pass"] and payload["failures"] == []
    assert payload["invariants"]["lost"] == 0
    assert payload["invariants"]["bit_identical"] is True
    assert payload["measured"]["rerouted"] >= 1


# ---------------------------------------------------------------------------
# r13 satellites: block-hash affinity + router-initiated supervisor restart
# ---------------------------------------------------------------------------


def test_affinity_key_is_block_hash_chain():
    """The affinity key is the chained hash of the prompt's first KV
    block (`pool.block_hashes` with affinity_prefix as the block size)
    — the SAME digest family the paged engine's radix tree keys
    physical blocks by. Every prompt sharing its first full block lands
    on one replica regardless of tail; a sub-block prompt falls back to
    the whole-prompt hash."""
    from paddle_tpu.serving.decode.pool import block_hashes

    router = FleetRouter(affinity_prefix=4)
    for i in range(4):
        router.add_replica(_FakeHandle(f"r{i}", i))
    first_block = [9, 2, 7, 4]
    targets = {_route_of(router, first_block + list(tail))
               for tail in ([], [1], [3, 3, 3], list(range(8)))}
    assert len(targets) == 1, targets
    # the chain hash, not the raw tokens, is the key: identical first
    # chunk => identical chain head
    h1 = block_hashes(first_block + [1, 2], 4)[0]
    h2 = block_hashes(first_block + [8], 4)[0]
    assert h1 == h2
    # sub-block prompts still route deterministically (whole-prompt key)
    assert (_route_of(router, [1, 2]) == _route_of(router, [1, 2]))


def test_dead_replica_restarts_in_place_via_supervisor():
    """ROADMAP item 3 (d): a DEAD replica whose rank a GangSupervisor
    owns is terminated+respawned INTO ITS OWN endpoint slot
    (supervisor.restart(rank), counted in
    resilience_events_total{kind=rank_restart}) and re-enters routing
    via revive_replica — autoscale replacement never fires. Hand-stepped
    (no pump thread) for determinism."""
    from paddle_tpu.distributed.launch import terminate_gang
    from paddle_tpu.observability import metrics as obs_metrics
    from paddle_tpu.resilience.supervisor import GangSupervisor

    sup = GangSupervisor(["-c", "import time; time.sleep(600)"],
                         nproc=1, grace_s=0.5)
    sup.launch()
    factory = _local_factory()

    def revive_factory(rid, index):
        assert rid == "r0" and index == 0
        return factory(index)

    router = FleetRouter(
        replica_factory=factory, autoscale=True, min_replicas=1,
        health_interval_s=1e9, supervisor=sup,
        revive_factory=revive_factory)
    handle = router.add_replica(factory(0))
    rank_restart_counter = obs_metrics.registry().counter(
        "resilience_events_total", "gang supervisor decisions",
        labels={"kind": "rank_restart"})
    before = rank_restart_counter.value
    old_pid = sup.procs()[0].pid
    try:
        handle.kill()
        router._health_pass()             # transport loss -> DEAD latch
        assert router.replicas()["r0"] == "dead"
        router._tick()                    # revive runs BEFORE autoscale
        assert sup.rank_restarts == {0: 1}
        assert rank_restart_counter.value == before + 1
        assert sup.procs()[0].pid != old_pid          # same slot, new proc
        assert router._metrics._counts["supervisor_restarts"].value == 1
        assert router._metrics._counts["scale_ups"].value == 0, \
            "restart-in-place must preempt scale-up replacement"
        assert router.replicas()["r0"] != "dead"
        # the revived slot serves — and a second tick doesn't restart again
        router._tick()
        assert sup.rank_restarts == {0: 1}
        resp = router.submit([1, 2, 3], max_new_tokens=3, model="fleet_t",
                             version="1")
        router._tick()
        deadline = time.time() + 60
        while not resp.done() and time.time() < deadline:
            router._tick()
            time.sleep(0.005)
        ref = router._replicas["r0"].engine.entry(
            "fleet_t", "1").offline_decode([1, 2, 3], 3)
        assert [int(t) for t in resp.result(timeout=5)["tokens"]] == ref
    finally:
        terminate_gang(sup.procs(), grace_s=0.5)
        for h in router._replicas.values():
            h.close()
