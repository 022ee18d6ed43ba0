"""Concurrency analyzer gates (ISSUE 11).

Three layers, each proven LIVE (positive controls fire) and CLEAN (the
repo passes):

* static lint (analysis/concurrency.py): lock inventory, the
  may-acquire-while-holding graph, cycle / blocking-under-lock /
  unguarded-mutation findings with file:line + held-chain attribution;
* runtime lockdep witness (observability/lockdep.py): named lock
  classes, cycle + declared-hierarchy violations raised at acquire time
  from a SINGLE-threaded pass;
* the lock hierarchy witnessed live by a deterministic pass over the
  decode + serving + embedding + checkpoint + dataio + fleet drivers:
  no cycle, no violation, every edge consistent with the declared chains.

Plus the PR-10 race-class regression: tenant counters, queue stats, and
registry scrape hammered from 8 threads under the armed witness.
"""

import importlib.util
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from paddle_tpu.analysis.concurrency import scan_paths, scan_sources
from paddle_tpu.observability import lockdep

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def armed_lockdep():
    """Enable + reset the witness for a test, restoring prior state (the
    graph is process-global; declared chains survive by design)."""
    was = lockdep.enabled()
    lockdep.enable()
    lockdep.reset()
    yield lockdep
    lockdep.reset()
    lockdep.enable(was)


# ---------------------------------------------------------------------------
# runtime witness unit behavior
# ---------------------------------------------------------------------------


def test_witness_raises_on_cycle_closing_edge(armed_lockdep):
    a = lockdep.named_lock("tw.a")
    b = lockdep.named_lock("tw.b", rlock=True)
    with a:
        with b:
            pass
    with pytest.raises(lockdep.LockOrderError) as ei:
        with b:
            with a:
                pass
    msg = str(ei.value)
    # attribution: both classes, the held chain, and where the opposite
    # order was first witnessed
    assert "tw.a" in msg and "tw.b" in msg
    assert "held chain: tw.b" in msg and "first seen at" in msg
    assert lockdep.violations()


def test_witness_enforces_declared_hierarchy(armed_lockdep):
    import paddle_tpu.serving.decode.engine  # noqa: F401 - declares order

    q = lockdep.named_lock("serving.queue", rlock=True)
    t = lockdep.named_lock("decode.tenant")
    with q:
        with t:  # declared direction: fine
            pass
    with pytest.raises(lockdep.LockOrderError) as ei:
        with t:
            with q:
                pass
    # the error names the declared RULE, not just the observed inversion
    assert "declared lock order 'serving.queue -> decode.tenant'" \
        in str(ei.value)


def test_witness_reentrant_and_condition_protocol(armed_lockdep):
    """RLock reentrancy adds no edges; Condition(named_lock) fully
    releases/restores the witness record across wait()."""
    q = lockdep.named_lock("tw.cond", rlock=True)
    cond = threading.Condition(q)
    woke = []

    def waiter():
        with cond:
            woke.append(cond.wait(timeout=5))

    th = threading.Thread(target=waiter, daemon=True)
    th.start()
    time.sleep(0.05)
    with cond:
        with q:  # re-entrant: no self-edge, no error
            pass
        cond.notify_all()
    th.join(5)
    assert woke == [True]
    snap = lockdep.snapshot()
    assert snap["cycles"] == [] and snap["violations"] == []


def test_witness_same_class_nesting_raises(armed_lockdep):
    """Two DIFFERENT instances of one lock class nested is a same-class
    ABBA waiting to happen (Linux lockdep's 'possible recursive
    locking') — only SAME-instance re-entrancy is silent."""
    a1 = lockdep.named_lock("tw.same")
    a2 = lockdep.named_lock("tw.same")
    with a1:
        with pytest.raises(lockdep.LockOrderError) as ei:
            with a2:
                pass
    assert "same-class nesting" in str(ei.value)


def test_witness_toggle_mid_hold_keeps_stack_consistent():
    """Disabling the witness between acquire and release must still pop
    the held record, or re-arming fabricates phantom held-chains."""
    was = lockdep.enabled()
    try:
        lockdep.enable()
        lockdep.reset()
        lk = lockdep.named_lock("tw.toggle")
        lk.acquire()
        lockdep.enable(False)
        lk.release()
        lockdep.enable(True)
        with lockdep.named_lock("tw.toggle.other"):
            pass  # no phantom 'tw.toggle' edge may appear
        snap = lockdep.snapshot()
        assert snap["edges"] == [] and snap["violations"] == []
    finally:
        lockdep.reset()
        lockdep.enable(was)


def test_witness_condition_restore_violation_surfaces_cleanly(
        armed_lockdep):
    """A declared-order violation detected while RESTORING the condition
    lock after wait() must surface as LockOrderError with the lock
    properly reacquired — not as 'cannot release un-acquired lock'."""
    import paddle_tpu.serving.decode.engine  # noqa: F401 - declares order

    q = lockdep.named_lock("serving.queue", rlock=True)
    t = lockdep.named_lock("decode.tenant")
    cond = threading.Condition(q)
    err = []

    def waiter():
        try:
            with cond:
                with t:  # declared direction going in: fine
                    # wake-up reacquires serving.queue while decode.tenant
                    # is held — the declared rule fires on restore
                    cond.wait(timeout=5)
        except BaseException as e:
            err.append(e)

    th = threading.Thread(target=waiter, daemon=True)
    th.start()
    time.sleep(0.05)
    with cond:
        cond.notify_all()
    th.join(5)
    assert len(err) == 1 and isinstance(err[0], lockdep.LockOrderError), err
    assert "declared lock order" in str(err[0])


def test_witness_disabled_is_inert():
    was = lockdep.enabled()
    lockdep.enable(False)
    try:
        a = lockdep.named_lock("tw.off.a")
        b = lockdep.named_lock("tw.off.b")
        with a:
            with b:
                pass
        with b:
            with a:  # would raise when armed
                pass
    finally:
        lockdep.enable(was)


# ---------------------------------------------------------------------------
# static lint: positive controls + repo-wide cleanliness
# ---------------------------------------------------------------------------


def test_static_controls_fire_with_attribution():
    lint = _load_tool("lint_concurrency")
    rep = scan_sources({"<control-abba>": lint.ABBA_CONTROL})
    cyc = [f for f in rep.findings if f.kind == "lock-order-cycle"]
    assert len(cyc) == 1
    assert cyc[0].file == "<control-abba>" and cyc[0].line in lint.ABBA_LINES
    assert all(str(line) in cyc[0].message for line in lint.ABBA_LINES)
    assert "holding" in cyc[0].message

    rep = scan_sources({"<control-unguarded>": lint.UNGUARDED_CONTROL})
    mut = [f for f in rep.findings if f.kind == "unguarded-shared-mutation"]
    assert len(mut) == 1 and mut[0].line == lint.UNGUARDED_LINE
    assert "counts" in mut[0].message and "_loop" in mut[0].message

    rep = scan_sources({"<control-blocking>": lint.BLOCKING_CONTROL})
    blk = [f for f in rep.findings if f.kind == "blocking-under-lock"]
    assert len(blk) == 1 and blk[0].line == lint.BLOCKING_LINE
    assert blk[0].held == ("<control-blocking>.Blocker._lock",)


def test_static_suppression_syntax_attributes_reason():
    lint = _load_tool("lint_concurrency")
    src = lint.UNGUARDED_CONTROL.replace(
        'self.counts["ticks"] = self.counts.get("ticks", 0) + 1',
        'self.counts["ticks"] = 1  # lockdep: ok(single writer by design)')
    rep = scan_sources({"<c>": src})
    assert not [f for f in rep.findings
                if f.kind == "unguarded-shared-mutation"]
    sup = [f for f in rep.suppressed
           if f.kind == "unguarded-shared-mutation"]
    assert len(sup) == 1
    assert sup[0].suppress_reason == "single writer by design"


def test_static_cross_file_cycle_suppression_and_paren_reasons():
    """A cycle spanning two files must be suppressible from EITHER
    file's edge line, and reasons containing '()' survive intact."""
    file_a = (
        "from paddle_tpu.observability.lockdep import named_lock\n\n\n"
        "class A:\n"
        "    def __init__(self):\n"
        "        self._x = named_lock('xf.a')\n"
        "        self._y = named_lock('xf.b')\n\n"
        "    def m(self):\n"
        "        with self._x:\n"
        "            with self._y:\n"
        "                pass\n")
    file_b = (
        "from paddle_tpu.observability.lockdep import named_lock\n\n\n"
        "class B:\n"
        "    def __init__(self):\n"
        "        self._x = named_lock('xf.a')\n"
        "        self._y = named_lock('xf.b')\n\n"
        "    def m(self):\n"
        "        with self._y:\n"
        "            # lockdep: ok(B.m never runs while A.m holds xf.a (guarded by setup()))\n"
        "            with self._x:\n"
        "                pass\n")
    rep = scan_sources({"a.py": file_a, "b.py": file_b})
    assert not [f for f in rep.findings if f.kind == "lock-order-cycle"]
    sup = [f for f in rep.suppressed if f.kind == "lock-order-cycle"]
    assert len(sup) == 1
    # greedy match: the parenthesized clause inside the reason survives
    assert sup[0].suppress_reason.endswith("(guarded by setup())")


def test_static_lint_repo_clean_and_hierarchy_acyclic():
    """The acceptance gate: zero unsuppressed findings over paddle_tpu/,
    every suppression attributed, and the static hold-graph has no
    cycles (the decode queue->tenant edge must be PRESENT — an empty
    graph would mean the interprocedural resolution died)."""
    rep = scan_paths([os.path.join(REPO, "paddle_tpu")])
    assert rep.files > 150
    assert not rep.findings, [str(f) for f in rep.findings]
    assert rep.cycles == []
    assert all(f.suppress_reason for f in rep.suppressed)
    edges = {(e.a, e.b) for e in rep.edges}
    assert ("serving.queue", "decode.tenant") in edges


# ---------------------------------------------------------------------------
# PR-10 race class regression: 8-thread hammer under the witness
# ---------------------------------------------------------------------------


def test_pr10_race_class_hammer_under_lockdep(armed_lockdep):
    """tenant_counts()/tenant_incr, queue.stats()/lane_depths(), and
    registry scrape-vs-incr from 8 threads: no exception, counters
    monotone, exact totals. (PR 10 fixed a dict-resize race in
    tenant_counts and a stats shadow — this pins the whole class.)"""
    from paddle_tpu.observability import metrics as obs_metrics
    from paddle_tpu.serving.decode.engine import GenerationRequest
    from paddle_tpu.serving.metrics import ServingMetrics
    from paddle_tpu.serving.queue import RequestQueue
    from paddle_tpu.serving.request import Priority, RejectedError

    sm = ServingMetrics(engine_label="hammer-r11")
    q = RequestQueue(max_depth=128)
    reg = obs_metrics.registry()
    errors = []
    stop = threading.Event()
    N = 200

    def incr_worker(k):
        try:
            for i in range(N):
                sm.tenant_incr("tokens", f"t{(k + i) % 5}")
                c = reg.counter("r11_hammer_total",
                                labels={"w": str(k % 3)})
                c.inc()
        except BaseException as e:
            errors.append(e)

    def queue_worker(k):
        try:
            for i in range(N):
                try:
                    q.put(GenerationRequest(
                        k * 1000 + i, [1], 1, f"t{k}",
                        Priority.LANES[i % 3], None))
                except RejectedError:
                    pass
                if i % 3 == 0:
                    with q.lock:
                        head = q.head()
                        if head is not None:
                            q.remove([head])
        except BaseException as e:
            errors.append(e)

    def reader():
        last_tokens = 0
        last_sum = 0.0
        try:
            while not stop.is_set():
                counts = sm.tenant_counts("tokens")
                total = sum(counts.values())
                assert total >= last_tokens, "tenant counter went backward"
                last_tokens = total
                st = q.stats()
                assert st["depth"] >= 0
                q.lane_depths()
                text = obs_metrics.scrape_text()
                assert "r11_hammer_total" in text or last_sum == 0.0
                vals = [m.value for m in reg.collect()
                        if m.name == "r11_hammer_total"]
                s = sum(vals)
                assert s >= last_sum, "registry counter went backward"
                last_sum = s
        except BaseException as e:
            errors.append(e)

    workers = [threading.Thread(target=incr_worker, args=(k,), daemon=True)
               for k in range(3)]
    workers += [threading.Thread(target=queue_worker, args=(k,),
                                 daemon=True) for k in range(3)]
    readers = [threading.Thread(target=reader, daemon=True)
               for _ in range(2)]
    for t in readers + workers:
        t.start()
    for t in workers:
        t.join(60)
    stop.set()
    for t in readers:
        t.join(10)
    assert not errors, f"hammer raised: {errors[:3]}"
    assert sum(sm.tenant_counts("tokens").values()) == 3 * N
    total = sum(m.value for m in reg.collect()
                if m.name == "r11_hammer_total")
    assert total == 3 * N
    snap = lockdep.snapshot()
    assert snap["cycles"] == [] and snap["violations"] == []


# ---------------------------------------------------------------------------
# background-thread shutdown audit
# ---------------------------------------------------------------------------


def test_periodic_threads_stop_bounded_and_idempotent():
    from paddle_tpu.observability.fetcher import (
        FetchHandlerMonitor,
        PeriodicMetricsDump,
    )

    class H:
        period_secs = 0.01

        def __init__(self):
            self.got = []

        def handler(self, d):
            self.got.append(d)

    h = H()
    mon = FetchHandlerMonitor(h).start()
    mon.start()  # idempotent: one thread
    mon.update({"loss": 1.0})
    time.sleep(0.05)
    t0 = time.perf_counter()
    mon.stop()
    mon.stop()  # idempotent
    assert time.perf_counter() - t0 < 6.0
    assert mon.deliveries >= 1 and h.got

    seen = []
    dump = PeriodicMetricsDump(seen.append, period_secs=0.01).start()
    time.sleep(0.03)
    dump.stop()
    dump.stop()
    assert dump.dumps >= 1 and seen


def test_device_prefetcher_joins_producer_on_abandon():
    from paddle_tpu.dataio.prefetch import DevicePrefetcher

    before = {t.ident for t in threading.enumerate()}
    pre = DevicePrefetcher(
        ({"x": np.full((4,), i)} for i in range(10_000)), depth=2)
    it = iter(pre)
    next(it)
    it.close()  # abandon mid-stream: producer must stop AND be joined
    time.sleep(0.05)
    leaked = [t for t in threading.enumerate()
              if t.ident not in before and t.is_alive()
              and "prefetch" in t.name]
    assert not leaked, f"prefetch producer leaked: {leaked}"


def test_heartbeat_monitor_start_stop_idempotent():
    from paddle_tpu.incubate.checkpoint import HeartBeatMonitor

    class C:
        def heartbeat(self, wid):
            return {}

    mon = HeartBeatMonitor(C(), worker_id=0, worker_num=1, timeout=10,
                           period=0.01)
    mon.start()
    first = mon._thread
    mon.start()
    assert mon._thread is first  # no second thread
    mon.stop()
    assert mon._thread is None
    mon.stop()  # idempotent


def test_heartbeat_monitor_restarts_after_loop_death():
    """A loop that self-terminated (heartbeat RPC failure) leaves a dead
    _thread behind; start() must spawn a replacement, not no-op. The dying
    thread is JOINED, and the server is back before the restart: a
    replacement that met the same dead server died as fast as the first,
    and whether the assertion below saw it alive was a race that six
    loaded workers lost."""
    from paddle_tpu.incubate.checkpoint import HeartBeatMonitor

    class Flaky:
        gone = True
        answered = threading.Event()

        def heartbeat(self, wid):
            if self.gone:
                raise ConnectionError("server gone")
            self.answered.set()
            return {}

    client = Flaky()
    mon = HeartBeatMonitor(client, worker_id=0, worker_num=1,
                           timeout=10, period=0.01)
    mon.start()
    first = mon._thread
    first.join(timeout=60)
    assert not first.is_alive()
    client.gone = False
    mon.start()
    assert mon._thread is not first
    assert client.answered.wait(timeout=60)
    assert mon._thread.is_alive()
    mon.stop()
    assert mon._thread is None


# ---------------------------------------------------------------------------
# the witnessed hierarchy + CLI smokes (tier-1 wiring)
# ---------------------------------------------------------------------------


def test_witnessed_lock_hierarchy_has_no_cycle_and_obeys_declared_chains(
        tmp_path):
    """The deterministic single-threaded lockdep pass over the decode +
    serving + embedding + checkpoint + dataio + fleet drivers
    (tools/stress_concurrency.py lockdep_pass) witnesses no cycle and no
    violation, sees the queue -> tenant and router -> queue edges, and
    no witnessed edge runs against a chain declared in code."""
    snap = _load_tool("stress_concurrency").lockdep_pass(
        tmpdir=str(tmp_path))
    assert snap["cycles"] == [] and snap["violations"] == []
    edges = [tuple(e) for e in snap["edges"]]
    assert ("serving.queue", "decode.tenant") in edges
    assert ("fleet.router", "serving.queue") in edges
    assert ("decode.blocks", "decode.radix") in edges
    chains = [list(c) for c in snap["declared"]]
    assert ["serving.queue", "decode.tenant"] in chains
    for a, b in edges:
        for chain in chains:
            if a in chain and b in chain:
                assert chain.index(a) < chain.index(b), (a, b, chain)


def _run_cli(tool, *args, timeout=600):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", f"{tool}.py"),
         *args],
        capture_output=True, text=True, timeout=timeout,
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


def test_lint_concurrency_smoke_cli():
    """Fast-tier gate: repo-wide static lint clean, all positive
    controls fire. Exit-code contract 0/1/2."""
    res = _run_cli("lint_concurrency", "--smoke", "--json")
    assert res.returncode == 0, res.stdout + res.stderr
    payload = json.loads(res.stdout.strip().splitlines()[-1])
    assert payload["pass"] and payload["failures"] == []
    # contract: findings exit 1 (probe with a synthetic dirty tree is
    # covered by the control assertions; here check bad usage exits 2)
    bad = _run_cli("lint_concurrency", "--no-such-flag")
    assert bad.returncode == 2


def test_stress_concurrency_smoke_cli():
    """Tier-1 wiring for the stress harness: every scenario bit-exact
    on the default seed with the witness armed and stalls injected."""
    res = _run_cli("stress_concurrency", "--smoke", "--json")
    assert res.returncode == 0, res.stdout + res.stderr
    payload = json.loads(res.stdout.strip().splitlines()[-1])
    assert payload["pass"] and payload["failures"] == []
    assert set(payload["results"]) == {"queue", "decode", "embedding",
                                       "dataio"}
    assert payload["stalls"] > 0  # stalls actually injected
