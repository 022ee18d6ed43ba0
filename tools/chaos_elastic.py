#!/usr/bin/env python
"""Chaos harness for elastic gang training: kill a rank mid-step, shrink
the gang, grow it back — and prove the run is REPLAY-DETERMINISTIC.

The scenario (the acceptance bar for the elasticity subsystem): a
4-rank gang trains a sharded (SpecLayout over local virtual devices)
model over an elastic DataEngine stream with per-step blocking
AutoCheckpoints, under an ElasticGangSupervisor. The fault schedule
(a) hard-kills one rank mid-step (``train.step`` kill — capacity lost,
the supervisor shrinks 4 -> 2) and later (b) preempts a rank of the
shrunk gang (``worker.preempt`` term — the capacity-returns signal, the
supervisor grows 2 -> 4). Each incarnation resumes from the supervisor-
pinned SYNC checkpoint: params + optimizer slots shard-wise via
``resume(shardings=..., step=...)`` (format-2, restored onto a
DIFFERENT local mesh — ranks get 8/world virtual devices), the data
stream via the elastic global-cursor translation (grown ranks pull the
chief's data blob). Every manifest carries the gang generation.

The property gate — replay determinism:

* The elastic run's COMMITTED stream (what each surviving generation
  built on) is reconstructed from per-generation logs, and a fresh
  REFERENCE run is driven phase-by-phase with the SAME (world-size,
  step-range) schedule the elastic run realized — no kills, no
  supervisor. Rank 0's committed loss sequence and every committed
  batch (positions + bytes) must be BIT-IDENTICAL between the two.
* Exactly-once: per epoch, the committed global sample positions tile
  ``[0, consumed)`` with zero gaps and zero duplicates — no sample
  lost or double-consumed across either resize.
* Gang generations are monotone in every rank's checkpoint chain, and
  shard-wise (NamedSharding) restores actually happened at both
  resizes.

``--smoke`` runs the seconds-scale configuration and asserts all of it
— wired into the fast tier (tests/test_elastic.py reads the ``--json``
report of the same run and asserts its invariants).

Usage:
  python tools/chaos_elastic.py [--nproc 4] [--min-nproc 2]
      [--steps 16] [--interval 2] [--kill-step 5] [--kill-rank 3]
      [--preempt-step 12] [--smoke] [--json]
"""

import argparse
import hashlib
import json
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


# ---------------------------------------------------------------------------
# worker: one elastic training rank (also the reference-phase runner)
# ---------------------------------------------------------------------------


def _announce(run_dir, gen, rank, step):
    path = os.path.join(run_dir, f"step_g{gen}_r{rank}")
    with open(path, "w") as f:
        f.write(str(step))


def _barrier(run_dir, gen, rank, world, step, timeout=60.0):
    """Wait until every rank of this generation has announced `step`.
    The data-parallel lockstep collectives would impose: without it,
    free-running ranks drift apart and the realized sync step (the
    newest checkpoint COMMON to all ranks) stops being deterministic.
    A dead rank never advances its counter — survivors block here until
    the supervisor terminates them, which is exactly the wedged-gang
    behavior a dead collective produces."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        ready = True
        for r in range(world):
            if r == rank:
                continue
            try:
                with open(os.path.join(run_dir, f"step_g{gen}_r{r}")) as f:
                    other = int(f.read().strip() or "-1")
            except (OSError, ValueError):
                other = -1
            if other < step:
                ready = False
                break
        if ready:
            return True
        time.sleep(0.005)
    return False


def run_worker(args):
    import numpy as np

    import jax
    from jax.sharding import NamedSharding

    import paddle_tpu as fluid
    from paddle_tpu.dataio import DataEngine, ListSource
    from paddle_tpu.incubate.checkpoint import (
        AutoCheckpoint,
        load_data_state,
    )
    from paddle_tpu.parallel.env import make_mesh
    from paddle_tpu.parallel.spec_layout import SpecLayout
    from paddle_tpu.resilience import faults
    from paddle_tpu.resilience.elastic import (
        elastic_resume_step,
        gang_generation,
    )
    from paddle_tpu.resilience.supervisor import heartbeat_tick

    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    gen = gang_generation() or 0
    sync = elastic_resume_step()
    ckpt_dir = os.path.join(args.ckpt_base, f"rank{rank}")
    chief_dir = os.path.join(args.ckpt_base, "rank0")
    os.makedirs(args.log_dir, exist_ok=True)

    # -- data: elastic stream over the rank's shard of the global order --
    def transform(i, rng):
        x = (np.full(args.feat, float(i), dtype=np.float32) * 0.01
             + np.float32(rng.random() * 1e-3))
        return (x, np.array([x.sum()], dtype=np.float32))

    source = ListSource(list(range(args.n_samples)), seed=args.seed,
                        rank=rank, world=world)
    engine = DataEngine(source, transform=transform,
                        batch_size=args.batch, drop_last=True,
                        num_workers=args.num_workers, elastic=True)

    # -- model: fc stack sharded over THIS incarnation's local mesh ------
    devices = jax.devices()
    mesh = make_mesh(shape=(1, len(devices)), axis_names=("data", "fsdp"),
                     devices=devices)
    layout = SpecLayout()

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[-1, args.feat])
        y = fluid.data("y", shape=[-1, 1])
        h = fluid.layers.fc(x, size=args.hidden, act="relu")
        pred = fluid.layers.fc(h, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.Adam(learning_rate=0.05).minimize(loss)
        feeder = fluid.DataFeeder([x, y])

    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        prog = fluid.CompiledProgram(main).with_parallel(
            mesh=mesh, loss_name=loss.name, spec_layout=layout)
        ck = AutoCheckpoint(exe, main, ckpt_dir,
                            save_interval_steps=args.interval,
                            max_to_keep=32, scope=scope,
                            data_state=engine)
        persistables = [v.name for v in main.global_block().vars.values()
                        if v.persistable]
        target = layout.derive_shardings(
            main, persistables,
            [tuple(np.shape(scope.find_var(n))) for n in persistables],
            mesh)

        data_from_chief = False
        if sync is not None and os.path.isdir(
                os.path.join(ckpt_dir, f"ckpt_{sync}")):
            # surviving rank: params + optimizer slots shard-wise onto
            # THIS mesh (N->M reshape), data position from the same
            # verified manifest (elastic geometry translation inside
            # the engine). A corrupt pinned entry raises: the worker
            # exits nonzero and the supervisor re-validates.
            start = ck.resume(shardings=target, step=sync)
        elif sync is not None:
            # grown rank: no own checkpoint at the sync step — fresh
            # params, data position from the CHIEF's blob translated
            # onto this (world, rank)
            blob = load_data_state(chief_dir, step=sync)
            if blob is not None:
                engine.load_state_dict(blob)
                data_from_chief = True
            start = sync + 1
        else:
            start = ck.resume(shardings=target)

        # format-2 entries come back as mesh-placed jax.Arrays
        # (NamedSharding); plain entries as numpy — counting the former
        # counts exactly the arrays restored shard-wise (r07 pattern)
        sharded_restored = 0
        if start > 0 and not data_from_chief:
            sharded_restored = sum(
                1 for n in persistables
                if isinstance(scope.find_var(n), jax.Array)
                and isinstance(getattr(scope.find_var(n), "sharding",
                                       None), NamedSharding))
        with open(os.path.join(args.log_dir,
                               f"restore_g{gen}_r{rank}.json"), "w") as f:
            json.dump({"start": start, "gen": gen, "rank": rank,
                       "world": world, "ndev": len(devices),
                       "sharded_restored": sharded_restored,
                       "data_from_chief": data_from_chief}, f)
        print(f"ELASTIC_WORKER gen={gen} rank={rank}/{world} "
              f"start={start} ndev={len(devices)} "
              f"sharded_restored={sharded_restored} "
              f"chief_data={data_from_chief}", flush=True)

        log_path = os.path.join(args.log_dir, f"log_g{gen}_r{rank}.jsonl")
        it = iter(engine)
        with open(log_path, "a") as logf:
            for step in range(start, args.steps):
                _announce(args.run_dir, gen, rank, step)
                _barrier(args.run_dir, gen, rank, world, step)
                heartbeat_tick()
                faults.fire("train.step", step=step)
                faults.fire("worker.preempt", step=step)
                try:
                    batch = next(it)
                except StopIteration:
                    it = iter(engine)
                    batch = next(it)
                feed = feeder.feed(batch)
                val = float(np.asarray(
                    exe.run(prog, feed=feed, fetch_list=[loss])[0]
                ).reshape(-1)[0])
                # the batch covers shard positions [cursor-B, cursor) of
                # the suffix cut at `base`: global epoch positions
                # base + j*world + rank
                c0 = engine.cursor - args.batch
                positions = [engine.base + j * world + rank
                             for j in range(c0, engine.cursor)]
                h = hashlib.sha256()
                h.update(np.ascontiguousarray(feed["x"]).tobytes())
                h.update(np.ascontiguousarray(feed["y"]).tobytes())
                logf.write(json.dumps({
                    "gen": gen, "rank": rank, "world": world,
                    "step": step, "epoch": engine.epoch,
                    "positions": positions, "digest": h.hexdigest(),
                    "loss": val.hex(),
                }) + "\n")
                logf.flush()
                ck.maybe_save(step, blocking=True)
        ck.close()
    print(f"ELASTIC_WORKER_DONE gen={gen} rank={rank}", flush=True)
    return 0


# ---------------------------------------------------------------------------
# committed-stream reconstruction
# ---------------------------------------------------------------------------


def _read_logs(log_dir):
    rows = []
    for name in sorted(os.listdir(log_dir)):
        if not (name.startswith("log_g") and name.endswith(".jsonl")):
            continue
        with open(os.path.join(log_dir, name)) as f:
            for line in f:
                rows.append(json.loads(line))
    return rows


def committed_stream(rows):
    """The entries the FINAL run actually built on: generation g's
    entries survive only below the step generation g+1 resumed at (a
    later incarnation re-consumes everything from its sync point, under
    its own geometry)."""
    by_gen = {}
    for r in rows:
        by_gen.setdefault(r["gen"], []).append(r)
    gens = sorted(by_gen)
    starts = {g: min(r["step"] for r in by_gen[g]) for g in gens}
    committed = []
    for i, g in enumerate(gens):
        stop = starts[gens[i + 1]] if i + 1 < len(gens) else None
        for r in by_gen[g]:
            if stop is None or r["step"] < stop:
                committed.append(r)
    return committed


def stream_key(r):
    return (r["step"], r["world"], r["rank"], r["epoch"],
            tuple(r["positions"]), r["digest"], r["loss"])


def stream_digest(committed):
    """sha256 over the committed per-epoch position/sample stream —
    geometry-free, so elastic and reference runs must agree byte for
    byte."""
    entries = sorted(
        (r["epoch"], p, r["digest"])
        for r in committed for p in r["positions"]
    )
    return hashlib.sha256(json.dumps(entries).encode()).hexdigest()


def check_exactly_once(committed):
    """Per epoch, committed positions must tile [0, consumed) exactly:
    zero gaps (lost samples), zero duplicates (double-consumed)."""
    per_epoch = {}
    for r in committed:
        per_epoch.setdefault(r["epoch"], []).extend(r["positions"])
    problems = []
    for ep, poss in sorted(per_epoch.items()):
        s = sorted(poss)
        if len(set(s)) != len(s):
            dupes = sorted({p for p in s if s.count(p) > 1})
            problems.append(f"epoch {ep}: duplicated positions "
                            f"{dupes[:5]}")
        if s != list(range(len(s))):
            missing = sorted(set(range(s[-1] + 1)) - set(s))[:5]
            problems.append(f"epoch {ep}: gaps at positions {missing}")
    return problems, {ep: len(p) for ep, p in sorted(per_epoch.items())}


# ---------------------------------------------------------------------------
# supervisor: the chaos scenario driver
# ---------------------------------------------------------------------------


def worker_args(args, ckpt_base, log_dir, run_dir):
    return [
        os.path.abspath(__file__), "--worker",
        "--steps", str(args.steps), "--interval", str(args.interval),
        "--n-samples", str(args.n_samples), "--batch", str(args.batch),
        "--seed", str(args.seed), "--feat", str(args.feat),
        "--hidden", str(args.hidden),
        "--num-workers", str(args.num_workers),
        "--ckpt-base", ckpt_base, "--log-dir", log_dir,
        "--run-dir", run_dir,
    ]


def run_elastic_leg(args, work):
    """The chaotic leg: ElasticGangSupervisor + fault schedule."""
    from paddle_tpu.resilience.elastic import ElasticGangSupervisor

    ckpt_base = os.path.join(work, "ckpt")
    log_dir = os.path.join(work, "logs")
    run_dir = os.path.join(work, "run")
    for d in (ckpt_base, log_dir, run_dir):
        os.makedirs(d, exist_ok=True)

    schedule = [
        {"site": "train.step", "action": "kill", "at_step": args.kill_step,
         "rank": args.kill_rank, "exit_code": 43, "id": "elastic-kill"},
        {"site": "worker.preempt", "action": "term",
         "at_step": args.preempt_step, "rank": 0, "id": "elastic-preempt"},
    ]

    sup_box = {}

    def capacity():
        """The simulated cluster scheduler: full capacity until the hard
        kill (a host is gone: only min_nproc available), full again once
        the preemption fires (capacity returned)."""
        sup = sup_box["sup"]
        exits = [e for e in sup.events if e["kind"] == "rank_exit"]
        if any(e["code"] not in (0, 43) for e in exits):
            return args.nproc          # preemption seen: capacity back
        if any(e["code"] == 43 for e in exits):
            return args.min_nproc      # host lost
        return args.nproc

    def on_resize(old_world, new_world, sup):
        # surviving hosts pick up the lost ranks' local devices: the
        # per-rank mesh geometry CHANGES across the resize, which is
        # what makes the shard-wise N->M restore a real reshape
        sup.devices_per_proc = max(1, args.devices_total // new_world)

    sup = ElasticGangSupervisor(
        worker_args(args, ckpt_base, log_dir, run_dir),
        nproc=args.nproc, min_nproc=args.min_nproc,
        max_restarts=args.max_restarts, restart_backoff_s=0.2,
        capacity_fn=capacity, capacity_poll_s=0.05,
        on_resize=on_resize,
        devices_per_proc=max(1, args.devices_total // args.nproc),
        checkpoint_dirs=[os.path.join(ckpt_base, f"rank{r}")
                         for r in range(args.nproc)],
        extra_env={
            "PADDLE_TPU_FAULTS": json.dumps(schedule),
            "PADDLE_TPU_FAULT_STATE": os.path.join(work, "fault_state"),
        },
    )
    sup_box["sup"] = sup
    t0 = time.perf_counter()
    codes = sup.run()
    wall = time.perf_counter() - t0
    return {
        "codes": codes, "wall_s": wall, "sup": sup,
        "log_dir": log_dir, "ckpt_base": ckpt_base,
        "events": [{k: v for k, v in e.items() if k != "time"}
                   for e in sup.events],
    }


def realized_schedule(sup, args):
    """[(world, start_step, stop_step, sync)] phases the elastic run
    actually committed — extracted from the supervisor's structured
    events; the reference leg replays exactly this."""
    phases = []
    world = args.nproc
    start = 0
    gen = 0
    for e in sup.events:
        if e["kind"] == "restart":
            sync = e.get("resume_step")
            stop = (sync + 1) if sync is not None else 0
            phases.append({"world": world, "start": start, "stop": stop,
                           "gen": gen, "sync": sync})
            world = e.get("world", world)
            start = stop
            gen = e.get("generation", gen + 1)
    phases.append({"world": world, "start": start, "stop": args.steps,
                   "gen": gen, "sync": phases[-1]["sync"] if phases
                   else None})
    return phases


def run_reference_leg(args, work, phases):
    """The clean leg: replay the realized (world, step-range) schedule
    with NO kills and NO supervisor — fresh dirs, phase by phase, each
    phase resuming from the previous phase's sync checkpoint exactly
    like the elastic incarnations did."""
    from paddle_tpu.distributed.launch import spawn_gang, wait_gang
    from paddle_tpu.resilience.elastic import (
        GANG_GENERATION_ENV,
        RESUME_STEP_ENV,
    )

    ckpt_base = os.path.join(work, "ref_ckpt")
    log_dir = os.path.join(work, "ref_logs")
    run_dir = os.path.join(work, "ref_run")
    for d in (ckpt_base, log_dir, run_dir):
        os.makedirs(d, exist_ok=True)

    base_args = worker_args(args, ckpt_base, log_dir, run_dir)
    for i, ph in enumerate(phases):
        if ph["stop"] <= ph["start"]:
            continue
        extra_env = {
            GANG_GENERATION_ENV: str(ph["gen"]),
            # a phase stops right AFTER its sync step so the next one
            # resumes from the same checkpoint the elastic gang did
            "PADDLE_TPU_FAULTS": "", "PADDLE_TPU_FAULT_STATE": "",
        }
        prev_sync = phases[i - 1]["sync"] if i > 0 else None
        if prev_sync is not None:
            extra_env[RESUME_STEP_ENV] = str(prev_sync)
        phase_args = list(base_args)
        phase_args[phase_args.index("--steps") + 1] = str(ph["stop"])
        procs = spawn_gang(
            phase_args, nproc=ph["world"],
            devices_per_proc=max(1, args.devices_total // ph["world"]),
            extra_env=extra_env)
        codes = wait_gang(procs)
        assert all(c == 0 for c in codes), (
            f"reference phase {i} ({ph}) failed: {codes}")
    return {"log_dir": log_dir, "ckpt_base": ckpt_base}


def run_scenario(args, work):
    from paddle_tpu.incubate.checkpoint import gang_generations

    elastic = run_elastic_leg(args, work)
    sup = elastic["sup"]
    phases = realized_schedule(sup, args)
    ref = run_reference_leg(args, work, phases)

    failures = []

    def check(ok, msg):
        if not ok:
            failures.append(msg)
        return ok

    # -- the run resolved --------------------------------------------------
    check(all(c == 0 for c in elastic["codes"]),
          f"final gang exited nonzero: {elastic['codes']}")
    resize_dirs = [(e["old_world"], e["new_world"], e["direction"])
                   for e in sup.events if e["kind"] == "gang_resize"]
    check((args.nproc, args.min_nproc, "shrink") in resize_dirs,
          f"no shrink {args.nproc}->{args.min_nproc} happened: "
          f"{resize_dirs}")
    check((args.min_nproc, args.nproc, "grow") in resize_dirs,
          f"no grow {args.min_nproc}->{args.nproc} happened: "
          f"{resize_dirs}")
    kill_exits = [e for e in sup.events
                  if e["kind"] == "rank_exit" and e["code"] == 43]
    check(len(kill_exits) == 1,
          f"expected exactly one injected hard kill, saw {kill_exits}")

    # -- replay determinism ------------------------------------------------
    el_rows = _read_logs(elastic["log_dir"])
    el_committed = committed_stream(el_rows)
    ref_rows = _read_logs(ref["log_dir"])
    ref_committed = committed_stream(ref_rows)
    check(len(ref_committed) == len(ref_rows),
          "reference phases overlapped (harness bug)")

    el_keys = sorted(stream_key(r) for r in el_committed)
    ref_keys = sorted(stream_key(r) for r in ref_committed)
    bit_identical = el_keys == ref_keys
    if not bit_identical:
        diff = [(a, b) for a, b in zip(el_keys, ref_keys) if a != b][:3]
        check(False, f"REPLAY DETERMINISM VIOLATED: committed streams "
                     f"differ (sizes {len(el_keys)}/{len(ref_keys)}, "
                     f"first diffs {diff})")

    el_digest = stream_digest(el_committed)
    ref_digest = stream_digest(ref_committed)
    check(el_digest == ref_digest, "stream digests differ")

    # rank-0 committed loss sequence, bit-exact (float hex)
    el_losses = {r["step"]: r["loss"] for r in el_committed
                 if r["rank"] == 0}
    ref_losses = {r["step"]: r["loss"] for r in ref_committed
                  if r["rank"] == 0}
    check(el_losses == ref_losses,
          f"rank-0 loss sequence diverged at steps "
          f"{sorted(s for s in el_losses if el_losses.get(s) != ref_losses.get(s))[:5]}")

    # -- exactly-once ------------------------------------------------------
    problems, per_epoch = check_exactly_once(el_committed)
    for p in problems:
        check(False, f"EXACTLY-ONCE VIOLATED: {p}")

    # -- gang generations monotone in every manifest -----------------------
    gens_seen = set()
    for r in range(args.nproc):
        d = os.path.join(elastic["ckpt_base"], f"rank{r}")
        if not os.path.isdir(d):
            continue
        chain = gang_generations(d)
        gens = [g for _, g in chain if g is not None]
        gens_seen.update(gens)
        check(all(g is not None for _, g in chain),
              f"rank{r}: unstamped manifests in an elastic run: {chain}")
        check(gens == sorted(gens),
              f"rank{r}: gang generation not monotone by step: {chain}")
    check(len(gens_seen) >= 3,
          f"expected >= 3 gang generations in the chains, saw "
          f"{sorted(gens_seen)}")

    # -- shard-wise restores actually happened at both resizes -------------
    restores = {}
    for name in os.listdir(elastic["log_dir"]):
        if name.startswith("restore_"):
            with open(os.path.join(elastic["log_dir"], name)) as f:
                r = json.load(f)
            restores[(r["gen"], r["rank"])] = r
    shrink_r0 = restores.get((1, 0), {})
    grow_r0 = restores.get((2, 0), {})
    check(shrink_r0.get("sharded_restored", 0) > 0,
          f"shrink resume was not shard-wise: {shrink_r0}")
    check(grow_r0.get("sharded_restored", 0) > 0,
          f"grow resume was not shard-wise: {grow_r0}")
    check(shrink_r0.get("ndev") != grow_r0.get("ndev"),
          f"mesh geometry never changed across resizes: "
          f"{shrink_r0.get('ndev')} vs {grow_r0.get('ndev')}")
    grown = [r for (g, _), r in restores.items()
             if g == 2 and r.get("data_from_chief")]
    check(len(grown) >= 1,
          "no grown rank translated the chief's data blob")

    report = {
        "scenario": {
            "nproc": args.nproc, "min_nproc": args.min_nproc,
            "steps": args.steps, "interval": args.interval,
            "kill_step": args.kill_step, "kill_rank": args.kill_rank,
            "preempt_step": args.preempt_step,
            "n_samples": args.n_samples, "batch": args.batch,
            "seed": args.seed, "feat": args.feat, "hidden": args.hidden,
            "num_workers": args.num_workers,
            "devices_total": args.devices_total,
        },
        "invariants": {
            "schedule": [{k: ph[k] for k in
                          ("world", "start", "stop", "gen", "sync")}
                         for ph in phases],
            "resizes": resize_dirs,
            "generations": sorted(gens_seen),
            "committed_batches": len(el_committed),
            "samples_per_epoch": per_epoch,
            "lost_or_duplicated": len(problems),
            "bit_identical": bit_identical,
            "stream_digest": el_digest,
            "shrink_sharded_restored": shrink_r0.get("sharded_restored"),
            "grow_sharded_restored": grow_r0.get("sharded_restored"),
            "grown_ranks_from_chief": len(grown),
        },
        "measured": {
            "wall_s": round(elastic["wall_s"], 1),
            "restarts": sup.restarts,
            "events": [e["kind"] for e in sup.events],
            "ndev_by_gen_rank0": {g: r.get("ndev") for (g, rk), r in
                                  sorted(restores.items()) if rk == 0},
        },
        "failures": failures,
    }
    return report


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--worker", action="store_true",
                    help="internal: run as one elastic training rank")
    ap.add_argument("--nproc", type=int, default=4)
    ap.add_argument("--min-nproc", type=int, default=2)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--interval", type=int, default=2)
    ap.add_argument("--kill-step", type=int, default=5)
    ap.add_argument("--kill-rank", type=int, default=3)
    ap.add_argument("--preempt-step", type=int, default=12)
    ap.add_argument("--max-restarts", type=int, default=4)
    ap.add_argument("--n-samples", type=int, default=96)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--feat", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=16)
    ap.add_argument("--num-workers", type=int, default=2,
                    help="dataio worker threads inside each rank")
    ap.add_argument("--devices-total", type=int, default=8,
                    help="virtual device budget split across ranks")
    ap.add_argument("--ckpt-base", type=str, default=None)
    ap.add_argument("--log-dir", type=str, default=None)
    ap.add_argument("--run-dir", type=str, default=None)
    ap.add_argument("--workdir", type=str, default=None,
                    help="keep artifacts here instead of a tmpdir")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale run + invariant asserts (CI)")
    ap.add_argument("--json", action="store_true", dest="as_json")
    args = ap.parse_args(argv)

    if args.worker:
        return run_worker(args)
    if args.smoke:
        args.nproc, args.min_nproc = 4, 2
        args.steps, args.interval = 16, 2
        args.kill_step, args.kill_rank, args.preempt_step = 5, 3, 12

    work = args.workdir or tempfile.mkdtemp(prefix="chaos_elastic_")
    t0 = time.perf_counter()
    try:
        report = run_scenario(args, work)
    finally:
        if not args.workdir:
            shutil.rmtree(work, ignore_errors=True)
    wall = time.perf_counter() - t0
    if args.as_json:
        print(json.dumps({"pass": not report["failures"], **report,
                          "wall_s": round(wall, 1)}))
    else:
        print(json.dumps(report, indent=1))
    if report["failures"]:
        for f in report["failures"]:
            print(f"CHAOS FAIL: {f}", file=sys.stderr)
        return 1
    inv = report["invariants"]
    print(f"CHAOS_ELASTIC_OK schedule="
          f"{[(p['world'], p['start'], p['stop']) for p in inv['schedule']]} "
          f"committed={inv['committed_batches']} lost=0 dup=0 "
          f"generations={inv['generations']} wall={wall:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
