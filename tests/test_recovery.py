"""Failure-detection / recovery tests (SURVEY §5.3).

reference: paddle/fluid/operators/distributed/heart_beat_monitor.h:54
(worker-lost detection), checkpoint_notify_op.cc + io.py:405 (checkpoint-
based recovery). Covers: async auto-checkpoint + resume continuity, the
kill-a-worker scenario over the real TCP PS, and monitor-driven lost-worker
logging.
"""

import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

pytestmark = pytest.mark.slow

import paddle_tpu as fluid
from paddle_tpu.core.ir import Program, program_guard
from paddle_tpu.incubate.checkpoint import AutoCheckpoint, HeartBeatMonitor


def _model():
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", shape=[-1, 8])
        y = fluid.data("y", shape=[-1, 1])
        pred = fluid.layers.fc(x, size=1, num_flatten_dims=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.Adam(learning_rate=0.05).minimize(loss)
    return main, startup, loss


def test_auto_checkpoint_resume(tmp_path, rng):
    """Crash after step k, restart, resume: the restarted run continues the
    ORIGINAL loss curve (params + optimizer accumulators restored)."""
    feed = {"x": rng.randn(16, 8).astype("float32"),
            "y": rng.randn(16, 1).astype("float32")}
    ckdir = str(tmp_path / "ck")

    # run A: 10 steps, checkpoint every 2, record the full curve; the
    # in-memory scope after step 5 is then DISCARDED (the "crash") and the
    # tail is replayed from disk
    main, startup, loss = _model()
    exe = fluid.Executor(fluid.CPUPlace())
    s1 = fluid.Scope()
    with fluid.scope_guard(s1):
        exe.run(startup)
        ck = AutoCheckpoint(exe, main, ckdir, save_interval_steps=2,
                            max_to_keep=3)
        assert ck.resume() == 0
        full = []
        for step in range(10):
            full.append(
                float(exe.run(main, feed=feed, fetch_list=[loss])[0][0])
            )
            ck.maybe_save(step, blocking=(step == 5))
        ck.close()

    # restart from the step-5 checkpoint: fresh scope, resume from disk
    s2 = fluid.Scope()
    with fluid.scope_guard(s2):
        exe.run(startup)
        ck2 = AutoCheckpoint(exe, main, ckdir, save_interval_steps=2)
        start = ck2.resume()
        # newest complete checkpoint on disk is ckpt_9, but the crash story
        # needs ckpt_5 — point `latest` back at it the way an operator
        # rolling back would
        with open(os.path.join(ckdir, "latest"), "w") as f:
            f.write("ckpt_5")
        start = ck2.resume()
        assert start == 6
        rest = [float(exe.run(main, feed=feed, fetch_list=[loss])[0][0])
                for _ in range(start, 10)]
    # deterministic model/feed: the replayed tail equals the original run
    # (no dropout, so the unchekpointed executor rng counter is inert)
    np.testing.assert_allclose(rest, full[6:], rtol=1e-5, atol=1e-7)


def test_checkpoint_gc_and_latest(tmp_path, rng):
    main, startup, loss = _model()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        ck = AutoCheckpoint(exe, main, str(tmp_path), save_interval_steps=1,
                            max_to_keep=2)
        for step in range(5):
            ck.save(step, blocking=True)
    kept = sorted(d for d in os.listdir(tmp_path) if d.startswith("ckpt_"))
    assert kept == ["ckpt_3", "ckpt_4"]
    with open(tmp_path / "latest") as f:
        assert f.read().strip() == "ckpt_4"


def test_heartbeat_monitor_detects_lost_worker():
    """Two heartbeating 'workers' (threads); one stops; the monitor flags
    exactly that one."""
    from paddle_tpu.distributed.ps import PSClient, PSServer

    srv = PSServer()
    try:
        client = PSClient([srv.endpoint])
        stop1 = False
        import threading

        def beat(wid, should_stop):
            while not should_stop():
                client.heartbeat(wid)
                time.sleep(0.1)

        t1 = threading.Thread(
            target=beat, args=(1, lambda: stop1), daemon=True
        )
        t1.start()
        client.heartbeat(2)  # worker 2 beats once, then goes silent
        lost = []
        mon = HeartBeatMonitor(
            client, worker_id=0, worker_num=2, timeout=1.0, period=0.2,
            on_lost=lambda wid, age: lost.append(wid),
        ).start()
        time.sleep(2.5)
        mon.stop()
        stop1 = True
        t1.join(timeout=2)
        assert 2 in mon.lost
        assert 1 not in mon.lost
        assert lost and lost[0] == 2
    finally:
        srv.stop()


def _run_ckpt_worker(tmp_path, ckdir, fault_spec, steps=2):
    """Subprocess that trains `steps` steps with blocking per-step saves
    under a fault schedule — the real-crash (os._exit) counterpart of the
    in-process raise-based tests in test_resilience.py."""
    script = os.path.join(str(tmp_path), "ckpt_worker.py")
    with open(script, "w") as f:
        f.write(
            """
import os, sys
import numpy as np
import paddle_tpu as fluid
from paddle_tpu.core.ir import Program, program_guard
from paddle_tpu.incubate.checkpoint import AutoCheckpoint

ckdir, steps = sys.argv[1], int(sys.argv[2])
main, startup = Program(), Program()
with program_guard(main, startup):
    x = fluid.data("x", shape=[-1, 8])
    y = fluid.data("y", shape=[-1, 1])
    pred = fluid.layers.fc(x, size=1, num_flatten_dims=1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.Adam(learning_rate=0.05).minimize(loss)
exe = fluid.Executor(fluid.CPUPlace())
rng = np.random.RandomState(7)
feed = {"x": rng.randn(8, 8).astype("float32"),
        "y": rng.randn(8, 1).astype("float32")}
scope = fluid.Scope()
with fluid.scope_guard(scope):
    exe.run(startup)
    ck = AutoCheckpoint(exe, main, ckdir, save_interval_steps=1)
    start = ck.resume()
    for step in range(start, steps):
        exe.run(main, feed=feed, fetch_list=[loss])
        ck.save(step, blocking=True)
    ck.close()
print("WORKER_DONE", start)
"""
        )
    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    if fault_spec is not None:
        import json

        env["PADDLE_TPU_FAULTS"] = json.dumps(fault_spec)
    else:
        env.pop("PADDLE_TPU_FAULTS", None)
    return subprocess.run(
        [sys.executable, script, ckdir, str(steps)],
        env=env, capture_output=True, text=True, timeout=300,
    )


def test_kill_between_state_write_and_latest_pointer(tmp_path):
    """A worker is HARD-KILLED (os._exit, no cleanup) between writing
    state.npz and updating `latest`: the pointer is the commit point, so
    a restarted worker resumes from the previous valid checkpoint."""
    from paddle_tpu.incubate.checkpoint import load_checkpoint, verify_checkpoint

    ckdir = str(tmp_path / "ck")
    proc = _run_ckpt_worker(
        tmp_path, ckdir,
        [{"site": "checkpoint.before_latest", "action": "kill",
          "at_step": 1}],
        steps=2,
    )
    assert proc.returncode == 43, proc.stdout + proc.stderr
    with open(os.path.join(ckdir, "latest")) as f:
        assert f.read().strip() == "ckpt_0"  # step-1 save never committed
    assert verify_checkpoint(os.path.join(ckdir, "ckpt_1"))[0] == 1
    with fluid.scope_guard(fluid.Scope()):
        assert load_checkpoint(ckdir) == 1  # resumes AFTER ckpt_0
    # ... and the restarted worker replays to completion from there
    proc2 = _run_ckpt_worker(tmp_path, ckdir, None, steps=2)
    assert proc2.returncode == 0, proc2.stdout + proc2.stderr
    assert "WORKER_DONE 1" in proc2.stdout
    with open(os.path.join(ckdir, "latest")) as f:
        assert f.read().strip() == "ckpt_1"


def test_kill_mid_state_write_then_corrupted_latest(tmp_path):
    """Two stacked failures: a kill DURING the state write (torn tmp dir)
    followed by on-disk corruption of the `latest` target; resume must
    quarantine the corrupt entry and fall back to the older valid one."""
    from paddle_tpu.incubate.checkpoint import load_checkpoint
    from paddle_tpu.resilience import corrupt_file

    ckdir = str(tmp_path / "ck")
    proc = _run_ckpt_worker(
        tmp_path, ckdir,
        [{"site": "checkpoint.io", "action": "kill", "at_step": 2}],
        steps=3,
    )
    assert proc.returncode == 43, proc.stdout + proc.stderr
    assert os.path.isdir(os.path.join(ckdir, "ckpt_2.tmp"))  # torn debris
    # now the newest COMMITTED checkpoint rots on disk
    corrupt_file(os.path.join(ckdir, "ckpt_1", "state.npz"))
    with fluid.scope_guard(fluid.Scope()):
        assert load_checkpoint(ckdir) == 1  # walked back to ckpt_0
    assert any(".corrupt" in d for d in os.listdir(ckdir))
    proc2 = _run_ckpt_worker(tmp_path, ckdir, None, steps=3)
    assert proc2.returncode == 0, proc2.stdout + proc2.stderr
    assert "WORKER_DONE 1" in proc2.stdout
    assert not os.path.isdir(os.path.join(ckdir, "ckpt_2.tmp"))  # gc'd


def test_chaos_train_full_acceptance():
    """The chaos acceptance bar (tools/chaos_train.py, non-smoke scale):
    one injected worker kill + one corrupted newest checkpoint under the
    GangSupervisor -> auto-restart within budget, resume from the newest
    valid checkpoint, final parameters bit-identical to an uninterrupted
    run resumed from that same checkpoint."""
    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PADDLE_TPU_FAULTS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos_train.py"),
         "--steps", "20", "--interval", "4", "--kill-step", "11"],
        capture_output=True, text=True, timeout=560, env=env,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "CHAOS_OK" in proc.stdout


def test_kill_a_worker_job_survives():
    """PS job with 2 trainers; SIGKILL one mid-run: the server stays up,
    the survivor finishes its steps, and the heartbeat table shows the
    dead worker going stale."""
    from paddle_tpu.distributed.ps import PSClient, PSServer

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    PS_WORKER = os.path.join(REPO, "tests", "dist_worker_ps.py")

    srv = PSServer()
    try:
        env_base = {
            k: v for k, v in os.environ.items()
            if not k.startswith(("PADDLE_", "TRAINING_", "XLA_", "JAX_"))
        }
        env_base["PYTHONPATH"] = (
            REPO + os.pathsep + os.environ.get("PYTHONPATH", "")
        )
        env_base["JAX_PLATFORMS"] = "cpu"
        env_base["PADDLE_PSERVERS_IP_PORT_LIST"] = srv.endpoint
        trainers = []
        for rank, steps in ((0, 25), (1, 25)):
            env = dict(
                env_base,
                TRAINING_ROLE="TRAINER",
                PADDLE_TRAINER_ID=str(rank),
                PADDLE_TRAINERS_NUM="1",  # no barrier: workers independent
                DIST_STEPS=str(steps),
                DIST_PS_MODE="async",
                DIST_HEARTBEAT="1",
            )
            trainers.append(
                subprocess.Popen(
                    [sys.executable, PS_WORKER],
                    env=env,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE,
                    text=True,
                )
            )
            if rank == 0:
                time.sleep(3)  # rank 0 creates the tables first
        time.sleep(6)  # let both come up and start stepping
        trainers[1].send_signal(signal.SIGKILL)
        out0, err0 = trainers[0].communicate(timeout=300)
        assert trainers[0].returncode == 0, err0[-2000:]
        assert "DIST_RESULT" in out0
        # server is still healthy after the kill
        probe = PSClient([srv.endpoint])
        stats = probe.table_stats()
        assert isinstance(stats, dict)
        probe.close()
        trainers[1].wait(timeout=10)
    finally:
        srv.stop()
