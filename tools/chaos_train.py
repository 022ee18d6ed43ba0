#!/usr/bin/env python
"""Chaos harness: a supervised training job run under an injected fault
schedule, asserting it auto-recovers.

The scenario (the acceptance bar for the resilience subsystem): an
nproc-rank gang trains a deterministic model with crash-consistent
AutoCheckpoints while the fault schedule (a) SIGKILL-equivalent kills
one rank at a fixed step and (b) corrupts the survivor's newest
checkpoint before the supervised relaunch. The GangSupervisor must
terminate + relaunch the gang within its restart budget, the relaunched
workers must quarantine the corrupt entry and resume from the newest
VALID checkpoint, and rank 0's final parameters must be BIT-IDENTICAL
to an uninterrupted reference run resumed from that same (post-
corruption) checkpoint state.

`--smoke` runs the seconds-scale configuration and asserts all of it —
wired into the fast test tier by tests/test_resilience.py, the same
pattern as tools/chaos_serve.py.

Usage:
  python tools/chaos_train.py [--nproc 2] [--steps 30] [--interval 5]
      [--kill-step 12] [--kill-rank 1] [--max-restarts 2] [--smoke]
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


# ---------------------------------------------------------------------------
# worker: one deterministic training rank (also the reference runner)
# ---------------------------------------------------------------------------


def run_worker(args):
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.core.ir import Program, program_guard
    from paddle_tpu.incubate.checkpoint import AutoCheckpoint
    from paddle_tpu.resilience import faults
    from paddle_tpu.resilience.supervisor import heartbeat_tick

    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    ckpt_dir = os.path.join(args.ckpt_base, f"rank{rank}")

    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", shape=[-1, args.feat])
        y = fluid.data("y", shape=[-1, 1])
        pred = fluid.layers.fc(x, size=1, num_flatten_dims=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.Adam(learning_rate=0.05).minimize(loss)

    rng = np.random.RandomState(1234 + rank)
    feed = {
        "x": rng.randn(16, args.feat).astype("float32"),
        "y": rng.randn(16, 1).astype("float32"),
    }
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        ck = AutoCheckpoint(exe, main, ckpt_dir,
                            save_interval_steps=args.interval,
                            max_to_keep=8)
        start = ck.resume()
        print(f"CHAOS_WORKER rank={rank} start_step={start}", flush=True)
        last = None
        for step in range(start, args.steps):
            heartbeat_tick()
            # the schedule's kill-at-step fires here (fault-state marker
            # keeps the RESTARTED incarnation from re-firing it)
            faults.fire("train.step", step=step)
            last = float(exe.run(main, feed=feed, fetch_list=[loss])[0][0])
            # blocking saves: the chaos timeline must be exact, not racing
            # an async writer
            ck.maybe_save(step, blocking=True)
            if args.step_sleep:
                time.sleep(args.step_sleep)
        ck.close()
        final = {
            v.name: np.asarray(scope.find_var(v.name))
            for v in main.global_block().vars.values()
            if v.persistable and scope.find_var(v.name) is not None
        }
    os.makedirs(args.out, exist_ok=True)
    np.savez(os.path.join(args.out, f"final_rank{rank}.npz"), **final)
    print(f"CHAOS_RESULT rank={rank} steps={args.steps} loss={last}",
          flush=True)
    return 0


# ---------------------------------------------------------------------------
# supervisor: the chaos scenario driver
# ---------------------------------------------------------------------------


def run_supervisor(args):
    import numpy as np

    from paddle_tpu.incubate.checkpoint import newest_valid_checkpoint
    from paddle_tpu.resilience import corrupt_file
    from paddle_tpu.resilience.supervisor import GangSupervisor

    work = args.workdir or tempfile.mkdtemp(prefix="chaos_train_")
    ckpt_base = os.path.join(work, "ckpt")
    out_dir = os.path.join(work, "out")
    ref_ckpt = os.path.join(work, "ref_ckpt")
    ref_out = os.path.join(work, "ref_out")
    fault_state = os.path.join(work, "fault_state")
    os.makedirs(ckpt_base, exist_ok=True)

    schedule = [{
        "site": "train.step", "action": "kill", "at_step": args.kill_step,
        "rank": args.kill_rank, "exit_code": 43, "id": "chaos-kill",
    }]
    worker_args = [
        os.path.abspath(__file__), "--worker",
        "--steps", str(args.steps), "--interval", str(args.interval),
        "--feat", str(args.feat), "--step-sleep", str(args.step_sleep),
        "--ckpt-base", ckpt_base, "--out", out_dir,
    ]

    corrupted = {}

    def sabotage(attempt, events):
        """Before the first relaunch: corrupt rank 0's newest checkpoint
        (fault (b)), then snapshot the dir — the reference run resumes
        from this exact state."""
        if attempt != 1:
            return
        r0 = os.path.join(ckpt_base, "rank0")
        name = newest_valid_checkpoint(r0, quarantine=False)
        if name is None:
            return
        corrupt_file(os.path.join(r0, name, "state.npz"))
        corrupted["name"] = name
        shutil.copytree(r0, ref_ckpt)

    sup = GangSupervisor(
        worker_args, nproc=args.nproc, max_restarts=args.max_restarts,
        restart_backoff_s=0.2,
        hang_timeout_s=args.hang_timeout,
        checkpoint_dirs=[os.path.join(ckpt_base, f"rank{r}")
                         for r in range(args.nproc)],
        on_restart=sabotage,
        extra_env={
            "PADDLE_TPU_FAULTS": json.dumps(schedule),
            "PADDLE_TPU_FAULT_STATE": fault_state,
        },
    )
    t0 = time.perf_counter()
    codes = sup.run()
    wall = time.perf_counter() - t0

    kills = [e for e in sup.events
             if e["kind"] == "rank_exit" and e["code"] == 43]
    quarantined = [n for n in os.listdir(os.path.join(ckpt_base, "rank0"))
                   if ".corrupt" in n]

    # -- reference: uninterrupted run resumed from the same checkpoint ----
    env = {k: v for k, v in os.environ.items()
           if k not in ("PADDLE_TPU_FAULTS", "PADDLE_TPU_FAULT_STATE")}
    env["PADDLE_TRAINER_ID"] = "0"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # reference resumes from the snapshot taken right after corruption
    ref_ckpt_base = os.path.join(work, "ref_ckpt_base")
    os.makedirs(ref_ckpt_base, exist_ok=True)
    shutil.copytree(ref_ckpt, os.path.join(ref_ckpt_base, "rank0"))
    ref = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--worker",
         "--steps", str(args.steps), "--interval", str(args.interval),
         "--feat", str(args.feat), "--step-sleep", "0",
         "--ckpt-base", ref_ckpt_base, "--out", ref_out],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert ref.returncode == 0, ref.stdout[-2000:] + ref.stderr[-2000:]

    got = np.load(os.path.join(out_dir, "final_rank0.npz"))
    want = np.load(os.path.join(ref_out, "final_rank0.npz"))
    assert sorted(got.files) == sorted(want.files), (got.files, want.files)
    bit_identical = all(
        got[n].dtype == want[n].dtype and np.array_equal(got[n], want[n])
        for n in got.files
    )

    report = {
        "metric": "chaos_train_recovery",
        "value": sup.restarts,
        "unit": "restarts",
        "extra": {
            "codes": codes,
            "wall_s": round(wall, 2),
            "injected_kills": len(kills),
            "corrupted_checkpoint": corrupted.get("name"),
            "quarantined": quarantined,
            "restarts": sup.restarts,
            "bit_identical_to_reference": bit_identical,
            "events": [
                {k: v for k, v in e.items() if k != "time"}
                for e in sup.events
            ],
        },
    }
    print(json.dumps(report))
    assert all(c == 0 for c in codes), codes
    assert kills, "the kill fault never fired"
    assert sup.restarts >= 1, "gang never restarted"
    assert corrupted.get("name"), "no checkpoint was corrupted"
    assert quarantined, "corrupt checkpoint was not quarantined on resume"
    assert bit_identical, (
        "recovered run diverged from the uninterrupted reference"
    )
    print(f"CHAOS_OK restarts={sup.restarts} wall={wall:.1f}s")
    if not args.workdir:
        shutil.rmtree(work, ignore_errors=True)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--worker", action="store_true",
                    help="internal: run as one training rank")
    ap.add_argument("--nproc", type=int, default=2)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--interval", type=int, default=5)
    ap.add_argument("--feat", type=int, default=8)
    ap.add_argument("--kill-step", type=int, default=12)
    ap.add_argument("--kill-rank", type=int, default=1)
    ap.add_argument("--max-restarts", type=int, default=2)
    ap.add_argument("--hang-timeout", type=float, default=None)
    ap.add_argument("--step-sleep", type=float, default=0.05,
                    help="per-step sleep so kills land mid-gang")
    ap.add_argument("--ckpt-base", type=str, default=None)
    ap.add_argument("--out", type=str, default=None)
    ap.add_argument("--workdir", type=str, default=None,
                    help="keep artifacts here instead of a tmpdir")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale run + invariant asserts (CI)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.nproc, args.steps, args.interval = 2, 8, 2
        args.kill_step, args.kill_rank, args.max_restarts = 5, 1, 2
    if args.worker:
        return run_worker(args)
    return run_supervisor(args)


if __name__ == "__main__":
    sys.exit(main())
