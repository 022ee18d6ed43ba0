"""Fault-tolerant training runtime (paddle_tpu/resilience + hardened
checkpoint/launcher/serving paths).

Covers: the deterministic fault-injection harness itself; the shared
retry policy; crash-consistent checkpoints (CRC manifests, fallback
chain walking, *.corrupt quarantine, close() error surfacing); the
fail-fast gang launcher and the supervised-restart loop (crash, budget
exhaustion, heartbeat-declared hangs); the robust reader decorator; the
lookup-path retry; the serving replica circuit breaker; and the
tools/chaos_train.py --smoke CI hook (worker kill + checkpoint
corruption -> supervised auto-resume, bit-identical to an uninterrupted
reference).
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core.ir import Program, program_guard
from paddle_tpu.incubate.checkpoint import (
    AutoCheckpoint,
    CheckpointCorruptError,
    load_checkpoint,
    newest_valid_checkpoint,
    verify_checkpoint,
)
from paddle_tpu.resilience import (
    FaultInjector,
    GangFailedError,
    GangSupervisor,
    InjectedFault,
    RetryPolicy,
    TransientFault,
    corrupt_file,
    faults,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


# ---------------------------------------------------------------------------
# fault injection harness
# ---------------------------------------------------------------------------


def test_fault_rule_matching_at_call_and_times():
    inj = FaultInjector([
        {"site": "a", "action": "raise", "at_call": 2},
        {"site": "b", "action": "raise", "times": 2},
    ])
    inj.fire("a")  # call 1: no fire
    with pytest.raises(TransientFault):
        inj.fire("a")
    inj.fire("a")  # times=1 exhausted
    for _ in range(2):
        with pytest.raises(TransientFault):
            inj.fire("b")
    inj.fire("b")  # times=2 exhausted
    assert inj.rule_stats()["a:0"]["fired"] == 1
    assert inj.rule_stats()["b:1"]["fired"] == 2


def test_fault_at_call_counts_calls_consumed_by_earlier_rules():
    """A firing rule must not hide the call from later rules' at_call
    counters — the written schedule IS the replayed timeline."""
    inj = FaultInjector([
        {"site": "s", "action": "raise", "times": 1},
        {"site": "s", "action": "raise", "at_call": 2, "exc": "fault"},
    ])
    with pytest.raises(TransientFault):
        inj.fire("s")  # call 1: rule 0 fires
    with pytest.raises(InjectedFault):
        inj.fire("s")  # call 2: rule 1 fires ON THE SECOND CALL


def test_fault_rule_step_rank_and_exc_class():
    inj = FaultInjector([
        {"site": "train.step", "at_step": 3, "rank": 1, "exc": "fault"},
    ])
    inj.fire("train.step", step=3, rank=0)  # wrong rank
    inj.fire("train.step", step=2, rank=1)  # wrong step
    with pytest.raises(InjectedFault):
        inj.fire("train.step", step=3, rank=1)


def test_fault_env_configuration(monkeypatch):
    monkeypatch.setenv(
        faults.FAULTS_ENV,
        json.dumps([{"site": "x", "action": "raise"}]),
    )
    faults.reset()  # force env re-parse
    with pytest.raises(TransientFault):
        faults.fire("x")
    faults.fire("x")  # one-shot
    faults.reset()
    monkeypatch.delenv(faults.FAULTS_ENV)
    faults.fire("x")  # inert again


def test_fault_state_dir_survives_process_restart(tmp_path):
    """The cross-process one-shot marker: a 'restarted' injector replaying
    the same schedule must not re-fire."""
    spec = [{"site": "s", "action": "raise", "id": "once"}]
    inj1 = FaultInjector(spec, state_dir=str(tmp_path))
    with pytest.raises(TransientFault):
        inj1.fire("s")
    inj2 = FaultInjector(spec, state_dir=str(tmp_path))  # "restart"
    inj2.fire("s")  # marker present: no fire
    assert inj2.rule_stats()["once"]["fired"] == 0


def test_fault_state_dir_only_pins_one_shot_rules(tmp_path):
    """Multi-fire rules (times>1 or unlimited) must KEEP firing across a
    process restart — only times=1 rules record cross-process markers."""
    spec = [{"site": "s", "action": "raise", "times": -1, "id": "forever"}]
    inj1 = FaultInjector(spec, state_dir=str(tmp_path))
    for _ in range(3):
        with pytest.raises(TransientFault):
            inj1.fire("s")
    inj2 = FaultInjector(spec, state_dir=str(tmp_path))  # "restart"
    with pytest.raises(TransientFault):
        inj2.fire("s")


def test_verify_checkpoint_bad_meta_types_quarantine(tmp_path):
    """meta.json that is valid JSON but has a non-numeric step must be
    treated as corruption (walk-back), not crash resume()."""
    _saved_checkpoints(tmp_path, steps=2)
    with open(tmp_path / "ckpt_1" / "meta.json", "w") as f:
        json.dump({"step": None}, f)
    with pytest.raises(CheckpointCorruptError, match="bad meta.json"):
        verify_checkpoint(str(tmp_path / "ckpt_1"))
    assert newest_valid_checkpoint(str(tmp_path), quarantine=False) == "ckpt_0"


def test_corrupt_file_flip_and_truncate(tmp_path):
    p = str(tmp_path / "f.bin")
    payload = bytes(range(256)) * 4
    with open(p, "wb") as f:
        f.write(payload)
    n = corrupt_file(p, mode="flip", nbytes=8)
    assert n == 8
    with open(p, "rb") as f:
        got = f.read()
    assert len(got) == len(payload) and got != payload
    corrupt_file(p, mode="truncate")
    assert os.path.getsize(p) == len(payload) // 2


# ---------------------------------------------------------------------------
# retry policy
# ---------------------------------------------------------------------------


def test_retry_succeeds_after_transients():
    sleeps = []
    p = RetryPolicy(max_attempts=4, base_delay_s=0.01, jitter=0.0,
                    sleep=sleeps.append)
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise TransientFault("blip")
        return "ok"

    assert p.call(flaky) == "ok"
    assert len(calls) == 3
    assert sleeps == [0.01, 0.02]  # capped exponential, jitter off


def test_retry_does_not_mask_real_errors():
    p = RetryPolicy(max_attempts=5, base_delay_s=0.0, sleep=lambda s: None)
    calls = []

    def broken():
        calls.append(1)
        raise ValueError("logic bug")

    with pytest.raises(ValueError):
        p.call(broken)
    assert len(calls) == 1  # not retried


def test_retry_deadline_and_exhaustion():
    p = RetryPolicy(max_attempts=3, base_delay_s=0.0, sleep=lambda s: None)
    with pytest.raises(TransientFault):
        p.call(lambda: (_ for _ in ()).throw(TransientFault("always")))
    # deadline: a huge backoff would blow the budget -> raise immediately
    p2 = RetryPolicy(max_attempts=10, base_delay_s=100.0, deadline_s=0.5,
                     sleep=lambda s: None)
    calls = []

    def fail():
        calls.append(1)
        raise TransientFault("x")

    with pytest.raises(TransientFault):
        p2.call(fail)
    assert len(calls) == 1


def test_retry_jitter_deterministic_with_seed():
    a = RetryPolicy(max_attempts=5, base_delay_s=0.1, seed=42,
                    sleep=lambda s: None)
    b = RetryPolicy(max_attempts=5, base_delay_s=0.1, seed=42,
                    sleep=lambda s: None)
    assert [a.delay(i) for i in range(1, 5)] == [
        b.delay(i) for i in range(1, 5)
    ]


def test_retry_on_retry_hook_runs_between_attempts():
    seen = []
    p = RetryPolicy(max_attempts=3, base_delay_s=0.0, sleep=lambda s: None)
    state = {"n": 0}

    def fn():
        state["n"] += 1
        if state["n"] < 3:
            raise ConnectionError("reset")
        return state["n"]

    assert p.call(fn, on_retry=lambda e, a: seen.append(a)) == 3
    assert seen == [1, 2]


# ---------------------------------------------------------------------------
# robust reader decorator (fluid.io.robust)
# ---------------------------------------------------------------------------


class _FlakyIter:
    """Class-based (resumable) iterator: record 3 raises, others yield."""

    def __init__(self, n):
        self.i = -1
        self.n = n

    def __iter__(self):
        return self

    def __next__(self):
        self.i += 1
        if self.i >= self.n:
            raise StopIteration
        if self.i == 3:
            raise IOError("bad record")
        return self.i


def test_robust_reader_skips_bad_record_resumable_iterator():
    reader = fluid.io.robust(lambda: _FlakyIter(6), max_skips=2)
    assert list(reader()) == [0, 1, 2, 4, 5]


def test_robust_reader_restarts_dead_generator():
    attempts = []

    def gen_reader():
        attempts.append(1)
        for i in range(6):
            if i == 3 and len(attempts) == 1:  # first pass only
                raise IOError("torn read")
            yield i

    reader = fluid.io.robust(gen_reader, max_skips=2, max_restarts=2)
    # the generator dies at record 3; the decorator restarts the reader
    # and fast-forwards past the 3 consumed + 1 bad record
    assert list(reader()) == [0, 1, 2, 4, 5]
    assert len(attempts) == 2


def test_robust_reader_bad_trailing_record_ends_epoch_cleanly():
    """A class-based iterator whose LAST record is bad: the skip is
    followed by a genuine StopIteration, which must end the epoch —
    not be misread as generator death."""
    reader = fluid.io.robust(lambda: _FlakyIter(4), max_skips=2)
    assert list(reader()) == [0, 1, 2]  # record 3 skipped, clean end


def test_robust_reader_deterministic_generator_failure_raises_loudly():
    """A generator record that fails EVERY replay can't be skipped
    (fast-forward re-executes it); the restart budget must end in the
    original error, never a silent epoch truncation."""

    def gen_reader():
        for i in range(6):
            if i == 3:  # deterministic: fails on every replay
                raise IOError("permanently bad record")
            yield i

    reader = fluid.io.robust(gen_reader, max_skips=100, max_restarts=3)
    it = reader()
    assert [next(it) for _ in range(3)] == [0, 1, 2]
    with pytest.raises(IOError, match="permanently bad"):
        list(it)


def test_robust_reader_bounded_failures_reraise():
    def all_bad():
        def it():
            raise IOError("dead source")
            yield  # pragma: no cover

        return it()

    reader = fluid.io.robust(all_bad, max_skips=3, max_restarts=100)
    with pytest.raises(IOError):
        list(reader())


# ---------------------------------------------------------------------------
# crash-consistent checkpoints
# ---------------------------------------------------------------------------


def _ckpt_model():
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", shape=[-1, 4])
        pred = fluid.layers.fc(x, size=3, num_flatten_dims=1)
    return main, startup, pred


def _saved_checkpoints(tmp_path, steps=3):
    main, startup, _ = _ckpt_model()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        ck = AutoCheckpoint(exe, main, str(tmp_path), save_interval_steps=1,
                            max_to_keep=10)
        snaps = {}
        for step in range(steps):
            # mutate a param so each checkpoint is distinguishable
            name = ck._persistable_names()[0]
            arr = np.asarray(scope.find_var(name)).copy()
            arr += 1.0
            scope.set(name, arr)
            snaps[step] = arr.copy()
            ck.save(step, blocking=True)
        ck.close()
    return main, snaps


def test_checkpoint_manifest_written_and_verifies(tmp_path):
    _saved_checkpoints(tmp_path, steps=2)
    d = str(tmp_path / "ckpt_1")
    assert os.path.exists(os.path.join(d, "manifest.json"))
    step, arrays = verify_checkpoint(d)
    assert step == 1 and arrays
    with open(os.path.join(d, "manifest.json")) as f:
        man = json.load(f)
    assert set(man["arrays"]) == set(arrays)
    assert man["files"]["state.npz"]["size"] == os.path.getsize(
        os.path.join(d, "state.npz")
    )


def test_corrupted_latest_falls_back_and_quarantines(tmp_path):
    """Satellite: `latest` points at a corrupted checkpoint; resume()
    must quarantine it and restore the previous valid one."""
    main, snaps = _saved_checkpoints(tmp_path, steps=3)
    corrupt_file(str(tmp_path / "ckpt_2" / "state.npz"))
    with pytest.raises(CheckpointCorruptError):
        verify_checkpoint(str(tmp_path / "ckpt_2"))
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        ck = AutoCheckpoint(None, main, str(tmp_path))
        start = ck.resume()
        assert start == 2  # fell back to ckpt_1
        pname = [v.name for v in main.global_block().vars.values()
                 if v.persistable][0]
        np.testing.assert_array_equal(
            np.asarray(scope.find_var(pname)), snaps[1]
        )
    assert os.path.isdir(str(tmp_path / "ckpt_2.corrupt"))
    assert not os.path.exists(str(tmp_path / "ckpt_2"))


def test_truncated_state_detected_as_torn_write(tmp_path):
    _saved_checkpoints(tmp_path, steps=2)
    corrupt_file(str(tmp_path / "ckpt_1" / "state.npz"), mode="truncate")
    with pytest.raises(CheckpointCorruptError, match="torn write"):
        verify_checkpoint(str(tmp_path / "ckpt_1"))
    assert newest_valid_checkpoint(str(tmp_path), quarantine=False) == "ckpt_0"


def test_crash_between_state_write_and_latest_update(tmp_path):
    """Satellite: a crash AFTER the checkpoint dir is complete but BEFORE
    the `latest` pointer swings. The pointer update is the COMMIT point:
    resume() falls back to the previous valid (committed) checkpoint and
    the uncommitted new entry is ignored — never half-trusted."""
    main, snaps = _saved_checkpoints(tmp_path, steps=2)
    faults.configure([
        {"site": "checkpoint.before_latest", "action": "raise",
         "exc": "fault"},
    ])
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        ck = AutoCheckpoint(exe, main, str(tmp_path), save_interval_steps=1)
        start = ck.resume()
        assert start == 2
        with pytest.raises(InjectedFault):
            ck.save(5, blocking=True)  # "crash" at the worst moment
    faults.reset()
    # the pointer still names ckpt_1 (the save never committed); the new
    # dir is complete on disk but resume honors the commit point
    with open(tmp_path / "latest") as f:
        assert f.read().strip() == "ckpt_1"
    assert verify_checkpoint(str(tmp_path / "ckpt_5"))[0] == 5
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        assert load_checkpoint(str(tmp_path), scope=scope2) == 2
    # but when the POINTER TARGET is lost too (the torn-latest case),
    # the chain walk recovers the newest complete entry instead of
    # starting from scratch
    import shutil

    shutil.rmtree(str(tmp_path / "ckpt_1"))
    scope3 = fluid.Scope()
    with fluid.scope_guard(scope3):
        assert load_checkpoint(str(tmp_path), scope=scope3) == 6


def test_crash_mid_state_write_leaves_only_tmp_debris(tmp_path):
    """A crash DURING the state write leaves a .tmp dir the chain never
    considers; resume() uses the previous checkpoint untouched."""
    main, snaps = _saved_checkpoints(tmp_path, steps=2)
    faults.configure([
        {"site": "checkpoint.before_rename", "action": "raise",
         "exc": "fault"},
    ])
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        ck = AutoCheckpoint(exe, main, str(tmp_path), save_interval_steps=1)
        ck.resume()
        with pytest.raises(InjectedFault):
            ck.save(7, blocking=True)
    faults.reset()
    assert os.path.isdir(str(tmp_path / "ckpt_7.tmp"))
    assert not os.path.isdir(str(tmp_path / "ckpt_7"))
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        assert load_checkpoint(str(tmp_path), scope=scope2) == 2


def test_autocheckpoint_close_surfaces_async_failure(tmp_path):
    """Satellite: a failed async write must raise at close() — and when
    the snapshot is still in memory, close() first retries it as a
    final blocking save (only raising if that fails too)."""
    main, _ = _saved_checkpoints(tmp_path, steps=1)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())

    # (a) transient failure: close() recovers via the final blocking save
    faults.configure([{"site": "checkpoint.io", "action": "raise",
                       "times": 1}])
    with fluid.scope_guard(scope):
        ck = AutoCheckpoint(exe, main, str(tmp_path), save_interval_steps=1,
                            retry=RetryPolicy(max_attempts=1))
        ck.resume()
        ck.save(10)  # async write fails once
        ck._join()
        assert ck._last_error is not None
        ck.close()  # retries blocking -> succeeds, no raise
    faults.reset()
    with open(tmp_path / "latest") as f:
        assert f.read().strip() == "ckpt_10"

    # (b) persistent failure: close() must raise, not swallow
    faults.configure([{"site": "checkpoint.io", "action": "raise",
                       "times": -1}])
    with fluid.scope_guard(scope):
        ck2 = AutoCheckpoint(exe, main, str(tmp_path), save_interval_steps=1,
                             retry=RetryPolicy(max_attempts=1))
        ck2.save(11)
        with pytest.raises(RuntimeError, match="checkpoint write failed"):
            ck2.close()
    faults.reset()


def test_checkpoint_io_retries_transient_faults(tmp_path):
    """The default retry policy absorbs a transient IO failure without
    surfacing anything."""
    main, _ = _saved_checkpoints(tmp_path, steps=1)
    faults.configure([{"site": "checkpoint.io", "action": "raise",
                       "times": 1}])
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        ck = AutoCheckpoint(
            exe, main, str(tmp_path), save_interval_steps=1,
            retry=RetryPolicy(max_attempts=3, base_delay_s=0.001),
        )
        ck.resume()
        ck.save(20, blocking=True)  # retried internally, no raise
        ck.close()
    faults.reset()
    assert verify_checkpoint(str(tmp_path / "ckpt_20"))[0] == 20


# ---------------------------------------------------------------------------
# io.py separate-files CRC manifest
# ---------------------------------------------------------------------------


def test_save_load_vars_crc_detects_corruption(tmp_path):
    main, startup, pred = _ckpt_model()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    d = str(tmp_path / "vars")
    with fluid.scope_guard(scope):
        exe.run(startup)
        names = fluid.io.save_persistables(exe, d, main_program=main)
        assert names
        with open(os.path.join(d, "__manifest__.json")) as f:
            man = json.load(f)
        assert set(man["crc32"]) == set(names)
        # clean round trip passes verification
        fluid.io.load_persistables(exe, d, main_program=main)
        # flip payload bytes in one .npy: load must fail naming the var
        victim = names[0]
        corrupt_file(
            os.path.join(d, victim.replace("/", "_") + ".npy"),
            offset=200,  # past the .npy header, inside the payload
        )
        with pytest.raises(fluid.EnforceError, match=victim):
            fluid.io.load_persistables(exe, d, main_program=main)


# ---------------------------------------------------------------------------
# fail-fast gang launcher
# ---------------------------------------------------------------------------


def _write_script(tmp_path, name, body):
    p = str(tmp_path / name)
    with open(p, "w") as f:
        f.write(textwrap.dedent(body))
    return p


def test_launch_procs_fail_fast_terminates_survivors(tmp_path):
    """Satellite: rank 1 crashes immediately; the old sequential wait
    would block 60s on rank 0 — fail-fast must terminate it at once."""
    from paddle_tpu.distributed.launch import launch_procs

    script = _write_script(tmp_path, "gang.py", """
        import os, sys, time
        rank = int(os.environ["PADDLE_TRAINER_ID"])
        if rank == 1:
            sys.exit(3)
        time.sleep(60)
    """)
    t0 = time.monotonic()
    codes = launch_procs([script], nproc=2)
    wall = time.monotonic() - t0
    assert wall < 30, f"fail-fast took {wall:.1f}s"
    assert codes[1] == 3
    assert codes[0] != 0  # terminated, not completed


def test_launch_procs_clean_gang_unchanged(tmp_path):
    from paddle_tpu.distributed.launch import launch_procs

    script = _write_script(tmp_path, "ok.py", """
        import sys
        sys.exit(0)
    """)
    assert launch_procs([script], nproc=2) == [0, 0]


# ---------------------------------------------------------------------------
# gang supervisor
# ---------------------------------------------------------------------------


def test_supervisor_restarts_crashed_gang(tmp_path):
    marker = str(tmp_path / "crashed_once")
    script = _write_script(tmp_path, "worker.py", """
        import os, sys
        marker = sys.argv[1]
        if not os.path.exists(marker):
            open(marker, "w").close()
            sys.exit(7)
        sys.exit(0)
    """)
    sup = GangSupervisor([script, marker], nproc=1, max_restarts=2,
                         restart_backoff_s=0.05)
    codes = sup.run()
    assert codes == [0]
    assert sup.restarts == 1
    kinds = [e["kind"] for e in sup.events]
    assert kinds == ["gang_start", "rank_exit", "restart", "gang_start",
                     "gang_ok"]
    exit_ev = next(e for e in sup.events if e["kind"] == "rank_exit")
    assert exit_ev["rank"] == 0 and exit_ev["code"] == 7


def test_supervisor_restart_budget_exhausted(tmp_path):
    script = _write_script(tmp_path, "always_dies.py", """
        import sys
        sys.exit(5)
    """)
    sup = GangSupervisor([script], nproc=1, max_restarts=1,
                         restart_backoff_s=0.05)
    with pytest.raises(GangFailedError) as ei:
        sup.run()
    assert ei.value.codes == [5]
    kinds = [e["kind"] for e in ei.value.events]
    assert kinds.count("rank_exit") == 2  # initial + 1 restart
    assert kinds[-1] == "gang_failed"


def test_supervisor_detects_hang_via_heartbeat(tmp_path):
    """First incarnation ticks once then wedges; the supervisor declares
    the hang after hang_timeout_s and restarts; the second incarnation
    exits cleanly."""
    marker = str(tmp_path / "hung_once")
    script = _write_script(tmp_path, "hang.py", """
        import os, sys, time
        marker = sys.argv[1]
        hb = os.environ["PADDLE_RESILIENCE_HEARTBEAT_DIR"]
        rank = os.environ["PADDLE_TRAINER_ID"]
        with open(os.path.join(hb, "hb_" + rank), "w") as f:
            f.write("tick")
        if not os.path.exists(marker):
            open(marker, "w").close()
            time.sleep(60)  # wedge: no further ticks
        sys.exit(0)
    """)
    sup = GangSupervisor([script, marker], nproc=1, max_restarts=1,
                         restart_backoff_s=0.05, hang_timeout_s=1.0,
                         heartbeat_dir=str(tmp_path / "hb"))
    t0 = time.monotonic()
    codes = sup.run()
    assert codes == [0]
    assert time.monotonic() - t0 < 30
    hang_ev = next(e for e in sup.events if e["kind"] == "hang")
    assert hang_ev["rank"] == 0 and hang_ev["age_s"] >= 1.0


def test_heartbeat_tick_helper(tmp_path, monkeypatch):
    from paddle_tpu.resilience.supervisor import (
        HEARTBEAT_DIR_ENV,
        heartbeat_tick,
    )

    monkeypatch.delenv(HEARTBEAT_DIR_ENV, raising=False)
    assert heartbeat_tick() is False  # no supervisor: inert
    monkeypatch.setenv(HEARTBEAT_DIR_ENV, str(tmp_path))
    monkeypatch.setenv("PADDLE_TRAINER_ID", "3")
    assert heartbeat_tick() is True
    assert os.path.exists(str(tmp_path / "hb_3"))


# ---------------------------------------------------------------------------
# lookup-path retry
# ---------------------------------------------------------------------------


def test_lookup_pull_push_retry_transient_faults():
    from paddle_tpu.distributed.lookup import RemoteLookupContext

    class FakeClient:
        def __init__(self):
            self.pulls = 0
            self.pushes = 0

        def pull_sparse(self, table_id, ids, dim):
            self.pulls += 1
            if self.pulls < 3:
                raise ConnectionError("blip")
            return np.arange(len(ids) * dim, dtype=np.float32).reshape(
                len(ids), dim
            )

        def push_sparse(self, table_id, ids, grads, lr):
            self.pushes += 1
            if self.pushes < 2:
                raise ConnectionError("blip")

    client = FakeClient()
    ctx = RemoteLookupContext(client)
    ctx.register("emb", table_id=0, dim=4)
    rows = ctx.pull("emb", np.array([5, 9], dtype=np.int64))
    assert rows.shape == (2, 4)
    assert client.pulls == 3  # two transient failures retried
    ctx.push("emb", np.array([5], dtype=np.int64),
             np.ones((1, 4), dtype=np.float32))
    assert client.pushes == 2
    assert ctx.stats["pushes"] == 1
    ctx.close()


# ---------------------------------------------------------------------------
# serving replica circuit breaker
# ---------------------------------------------------------------------------


def _breaker_engine(tmp_path, rng, threshold=2, cooldown_s=0.4):
    from paddle_tpu import inference
    from paddle_tpu.serving import BucketLattice, ServingEngine

    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", [-1, 4])
        pred = fluid.layers.fc(x, 3)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        model_dir = os.path.join(str(tmp_path), "model")
        fluid.io.save_inference_model(model_dir, ["x"], [pred], exe,
                                      main_program=main)
    config = inference.Config(model_dir)
    config.disable_tpu()
    lattice = BucketLattice([1, 2])
    config.set_serving_buckets(lattice.batch_sizes, lattice.seq_lens)
    return ServingEngine(
        config, lattice=lattice, num_replicas=1, max_wait_ms=1.0,
        breaker_threshold=threshold, breaker_cooldown_s=cooldown_s,
    )


def test_serving_breaker_quarantines_and_readmits(tmp_path, rng):
    """Acceptance: force the predictor to fail K times -> the breaker
    opens (quarantine); after the cooldown the next batch is a probe
    that re-admits the replica; every lifecycle counter matches
    exactly and flows through stats() (the C ABI JSON surface)."""
    from paddle_tpu.serving import RequestError

    K = 2
    engine = _breaker_engine(tmp_path, rng, threshold=K, cooldown_s=0.4)
    engine.start()
    try:
        rep = engine._replicas[0]
        healthy_run = rep.run_batch

        def broken(feeds):
            raise RuntimeError("forced replica failure")

        x = rng.randn(1, 4).astype("float32")

        # phase A: K consecutive batch failures open the breaker
        rep.run_batch = broken
        for _ in range(K):
            with pytest.raises(RequestError):
                engine.submit({"x": x}).result(timeout=30)
        stats = engine.stats()
        assert stats["batch_failures"] == K
        assert stats["breaker_opened"] == 1
        assert stats["breaker_states"] == ["open"]
        assert stats["breaker_open_replicas"] == 1
        assert stats["failed"] == K

        # phase B: heal the replica; a request submitted DURING the
        # cooldown waits, is served by the probe, and closes the breaker
        rep.run_batch = healthy_run
        t0 = time.perf_counter()
        resp = engine.submit({"x": x})
        out = resp.result(timeout=30)
        waited = time.perf_counter() - t0
        assert waited >= 0.2  # sat out (most of) the cooldown
        np.testing.assert_array_equal(
            out[engine.predictor.get_output_names()[0]],
            engine.predictor.run([x])[0],
        )
        stats = engine.stats()
        assert stats["breaker_probes"] == 1
        assert stats["breaker_closed"] == 1
        assert stats["breaker_states"] == ["closed"]
        assert stats["breaker_open_replicas"] == 0
        assert stats["completed"] == 1

        # phase C: relapse -> reopen via a FAILED probe
        rep.run_batch = broken
        for _ in range(K):
            with pytest.raises(RequestError):
                engine.submit({"x": x}).result(timeout=30)
        assert engine.stats()["breaker_opened"] == 2
        with pytest.raises(RequestError):
            engine.submit({"x": x}).result(timeout=30)  # failing probe
        stats = engine.stats()
        assert stats["breaker_probes"] == 2
        assert stats["breaker_reopened"] == 1
        assert stats["breaker_states"] == ["open"]

        # phase D: heal again; cooldown probe re-admits
        rep.run_batch = healthy_run
        engine.submit({"x": x}).result(timeout=30)
        stats = engine.stats()
        assert stats["breaker_probes"] == 3
        assert stats["breaker_closed"] == 2
        assert stats["breaker_states"] == ["closed"]
    finally:
        engine.shutdown()


def test_serving_breaker_counters_in_capi_stats_json(tmp_path, rng):
    """The C ABI surface (serving_stats_json) carries the breaker
    counters — C/Go front-ends see quarantine state without new ABI."""
    engine = _breaker_engine(tmp_path, rng)
    engine.start()
    try:
        from paddle_tpu.inference import capi_bridge as bridge

        handle = bridge._ServingHandle(engine)
        stats = json.loads(bridge.serving_stats_json(handle))
        for key in ("batch_failures", "breaker_opened", "breaker_probes",
                    "breaker_closed", "breaker_reopened",
                    "breaker_open_replicas", "breaker_states"):
            assert key in stats, key
    finally:
        engine.shutdown()


def test_serving_faults_site_forces_batch_failure(tmp_path, rng):
    """The chaos harness can break serving without monkeypatching: the
    serving.run_batch fault site fails the batch AND the isolation
    re-run, so the request fails and the breaker counts one batch
    failure."""
    from paddle_tpu.serving import RequestError

    engine = _breaker_engine(tmp_path, rng, threshold=5)
    engine.start()
    try:
        faults.configure([
            {"site": "serving.run_batch", "action": "raise", "times": 2},
        ])
        x = rng.randn(1, 4).astype("float32")
        with pytest.raises(RequestError):
            engine.submit({"x": x}).result(timeout=30)
        assert engine.stats()["batch_failures"] == 1
        faults.reset()
        out = engine.submit({"x": x}).result(timeout=30)
        assert out is not None
    finally:
        faults.reset()
        engine.shutdown()


# ---------------------------------------------------------------------------
# chaos CI hook
# ---------------------------------------------------------------------------


def test_chaos_train_smoke_cli():
    """tools/chaos_train.py --smoke: injected worker kill + corrupted
    newest checkpoint -> supervised auto-restart, quarantine, resume,
    and bit-identical final parameters vs the uninterrupted reference."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PADDLE_TPU_FAULTS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos_train.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=560, env=env,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "CHAOS_OK" in proc.stdout
    report = json.loads(
        [l for l in proc.stdout.splitlines() if l.startswith("{")][0]
    )
    extra = report["extra"]
    assert extra["injected_kills"] == 1
    assert extra["restarts"] >= 1
    assert extra["quarantined"]
    assert extra["bit_identical_to_reference"] is True


# ---------------------------------------------------------------------------
# multi-host format-2 manifest merge (PR 8 satellite): rank 0 folds every
# host's shard index into the manifest; a missing host fails LOUDLY at
# save (index never published) or at verify (file listed but absent)
# ---------------------------------------------------------------------------


def _two_host_shard_snaps(dim=4):
    """One [4, dim] array split rows 0-1 (host 0) / 2-3 (host 1)."""
    from paddle_tpu.incubate.checkpoint import _ShardSnap

    full = np.arange(4 * dim, dtype=np.float32).reshape(4, dim)
    host0 = _ShardSnap((4, dim), "float32", "ep(2)",
                       [((0, 0), (2, dim), full[:2])])
    host1 = _ShardSnap((4, dim), "float32", "ep(2)",
                       [((2, 0), (4, dim), full[2:])])
    return full, host0, host1


def _multihost_save(tmp_path, monkeypatch, write_host1_index=True,
                    timeout="1"):
    """Simulate a 2-host save: pre-place host 1's shard file + index in
    the tmp dir (hosts share the checkpoint FS), then run the rank-0
    save which must merge host 1's index into the manifest."""
    from paddle_tpu.incubate import checkpoint as ckpt_mod
    from paddle_tpu.incubate.checkpoint import _write_shard_file

    main, startup = Program(), Program()
    with program_guard(main, startup):
        fluid.data("x", shape=[-1, 2])
    full, host0_snap, host1_snap = _two_host_shard_snaps()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        monkeypatch.setattr(ckpt_mod, "_process_count", lambda: 2)
        monkeypatch.setenv("PADDLE_TPU_CKPT_MERGE_TIMEOUT", timeout)
        tmp = str(tmp_path / "ckpt_0.tmp")
        os.makedirs(tmp, exist_ok=True)
        if write_host1_index:
            _write_shard_file(tmp, {"big": host1_snap}, 1,
                              write_index=True)
        ck = AutoCheckpoint(exe, main, str(tmp_path),
                            save_interval_steps=1, scope=scope)
        # rank 0 contributes its own shard of the same array
        snap = {"w0": np.ones(2, "f"), "big": host0_snap}
        ck._write(0, snap)
    return full


def test_multihost_manifest_merge_roundtrip(tmp_path, monkeypatch):
    full = _multihost_save(tmp_path, monkeypatch)
    d = str(tmp_path / "ckpt_0")
    with open(os.path.join(d, "manifest.json")) as f:
        man = json.load(f)
    assert man["format"] == 2
    # both hosts' files are manifest-listed with CRCs; the array's
    # shard list carries blocks from BOTH hosts
    assert {"shards_p0.npz", "shards_p1.npz"} <= set(man["files"])
    files = {s["file"] for s in man["sharded"]["big"]["shards"]}
    assert files == {"shards_p0.npz", "shards_p1.npz"}
    # the merged index sidecar is not part of the committed checkpoint
    assert not any(n.endswith(".index.json") for n in os.listdir(d))
    step, arrays = verify_checkpoint(d)
    assert step == 0
    np.testing.assert_array_equal(arrays["big"], full)


def test_multihost_missing_host_fails_save_loudly(tmp_path, monkeypatch):
    with pytest.raises(CheckpointCorruptError, match="host 1/2"):
        _multihost_save(tmp_path, monkeypatch, write_host1_index=False)
    # nothing committed: no ckpt_0, no latest pointer
    assert not os.path.exists(tmp_path / "ckpt_0")
    assert not os.path.exists(tmp_path / "latest")


def test_multihost_lost_shard_file_fails_verification(tmp_path,
                                                      monkeypatch):
    """The merged manifest lists host 1's file — losing it after commit
    is DETECTED, never silently-thinned coverage."""
    _multihost_save(tmp_path, monkeypatch)
    d = str(tmp_path / "ckpt_0")
    os.remove(os.path.join(d, "shards_p1.npz"))
    with pytest.raises(CheckpointCorruptError, match="shards_p1.npz"):
        verify_checkpoint(d)


def test_nonchief_host_writes_shards_and_index_only(tmp_path,
                                                    monkeypatch):
    from paddle_tpu.incubate import checkpoint as ckpt_mod

    main, startup = Program(), Program()
    with program_guard(main, startup):
        fluid.data("x", shape=[-1, 2])
    _full, _h0, host1_snap = _two_host_shard_snaps()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        monkeypatch.setattr(ckpt_mod, "_process_index", lambda: 1)
        monkeypatch.setattr(ckpt_mod, "_process_count", lambda: 2)
        ck = AutoCheckpoint(exe, main, str(tmp_path),
                            save_interval_steps=1, scope=scope)
        ck._write(3, {"w0": np.ones(2, "f"), "big": host1_snap})
    tmp = tmp_path / "ckpt_3.tmp"
    assert sorted(os.listdir(tmp)) == ["shards_p1.index.json",
                                       "shards_p1.npz"]
    # no manifest, no meta, no rename, no latest — the chief owns those
    assert not os.path.exists(tmp_path / "ckpt_3")
    assert not os.path.exists(tmp_path / "latest")
