"""Elastic gang training (r14): global-cursor data re-sharding, pinned
sync-step resume, gang-generation stamping, the ElasticGangSupervisor
shrink/grow loop, the new fault sites, and the chaos_elastic property
gate (the smoke CLI's own invariants, read from its --json report).
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
from paddle_tpu.dataio import DataEngine, ListSource, elastic_resume
from paddle_tpu.dataio.state import IteratorState
from paddle_tpu.incubate.checkpoint import (
    AutoCheckpoint,
    CheckpointCorruptError,
    gang_generations,
    load_data_state,
)
from paddle_tpu.resilience import faults
from paddle_tpu.resilience.elastic import (
    GANG_GENERATION_ENV,
    RESUME_STEP_ENV,
    ElasticGangSupervisor,
    elastic_resume_step,
    gang_generation,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset_faults():
    yield
    faults.reset()


# ---------------------------------------------------------------------------
# state translation: the global sample cursor
# ---------------------------------------------------------------------------


def test_global_cursor_projection():
    st = IteratorState(epoch=2, cursor=5, base=8, world=4, rank=3)
    assert st.global_cursor() == 8 + 5 * 4
    # base survives the dict round trip (state version 2)
    st2 = IteratorState.from_dict(st.to_dict())
    assert st2.base == 8 and st2.global_cursor() == st.global_cursor()
    # version-1 blobs (no base) decode with base=0
    d = st.to_dict()
    d.pop("base")
    d["version"] = 1
    assert IteratorState.from_dict(d).base == 0


def test_elastic_resume_translation_and_validation():
    d = IteratorState(epoch=1, cursor=6, base=4, seed=7, world=4, rank=2,
                      emitted_batches=19).to_dict()
    t = IteratorState.from_dict(elastic_resume(d, 2, 1))
    assert t.base == 4 + 6 * 4 and t.cursor == 0
    assert (t.world, t.rank) == (2, 1)
    assert (t.epoch, t.seed, t.emitted_batches) == (1, 7, 19)
    with pytest.raises(ValueError):
        elastic_resume(d, 0, 0)
    with pytest.raises(ValueError):
        elastic_resume(d, 2, 2)


def test_env_constants_agree_with_checkpoint_module():
    # the literal is duplicated (import-cycle avoidance); pin equality
    from paddle_tpu.incubate import checkpoint as ck

    assert GANG_GENERATION_ENV == ck.GANG_GENERATION_ENV
    assert elastic_resume_step({RESUME_STEP_ENV: "9"}) == 9
    assert elastic_resume_step({}) is None
    assert gang_generation({GANG_GENERATION_ENV: "3"}) == 3
    assert gang_generation({}) is None


# ---------------------------------------------------------------------------
# suffix re-sharding: exactly-once tiling across arbitrary resizes
# ---------------------------------------------------------------------------


def test_epoch_shard_base_zero_is_byte_compatible():
    for world in (1, 2, 3, 5):
        for rank in range(world):
            s = ListSource(list(range(23)), seed=4, rank=rank, world=world)
            assert s.epoch_shard(1) == s.epoch_shard(1, base=0)


def test_suffix_resharding_tiles_stream_exactly():
    """Property: any schedule of (world, consumed-prefix) cuts yields
    globally contiguous positions with zero gaps/duplicates, and the
    consumed values cover the epoch order exactly once (before
    wrap-padding)."""
    import random as pyrandom

    rng = pyrandom.Random(7)
    for _ in range(100):
        n = rng.randrange(5, 50)
        seed = rng.randrange(999)
        order = ListSource(list(range(n)), seed=seed, rank=0,
                           world=1).epoch_order(0)
        consumed = []
        base = 0
        for phase in range(rng.randrange(1, 4)):
            w = rng.choice([1, 2, 3, 4])
            shards = [
                ListSource(list(range(n)), seed=seed, rank=r,
                           world=w).epoch_shard(0, base=base)
                for r in range(w)
            ]
            per = len(shards[0])
            assert all(len(s) == per for s in shards)
            if per == 0:
                break
            c = rng.randrange(0, per + 1)
            for j in range(c):
                for r in range(w):
                    consumed.append((base + j * w + r, shards[r][j]))
            base += c * w
        poss = [p for p, _ in sorted(consumed)]
        assert poss == list(range(len(poss)))
        real = [v for p, v in sorted(consumed) if p < n]
        assert real == order[:len(real)]


def test_engine_elastic_resume_translates_and_strict_mode_still_rejects():
    src4 = ListSource(list(range(32)), seed=5, rank=0, world=4)
    e4 = DataEngine(src4, batch_size=2, drop_last=True)
    it = iter(e4)
    next(it), next(it)
    st = e4.state_dict()

    # strict engine (default): world mismatch still raises
    strict = DataEngine(ListSource(list(range(32)), seed=5, rank=0,
                                   world=2), batch_size=2, drop_last=True)
    with pytest.raises(Exception):
        strict.load_state_dict(st)

    # elastic engine: translates to the global cursor
    el = DataEngine(ListSource(list(range(32)), seed=5, rank=1, world=2),
                    batch_size=2, drop_last=True, elastic=True)
    el.load_state_dict(st)
    assert el.base == st["base"] + st["cursor"] * st["world"]
    assert el.cursor == 0 and el.epoch == st["epoch"]
    # same-geometry load through an elastic engine stays a plain resume
    el2 = DataEngine(ListSource(list(range(32)), seed=5, rank=0, world=4),
                     batch_size=2, drop_last=True, elastic=True)
    el2.load_state_dict(st)
    assert el2.cursor == st["cursor"] and el2.base == st["base"]


def test_engine_schedule_stream_is_replay_deterministic():
    """The engine-level half of the chaos property: driving fresh
    engines through the same (world, steps) schedule twice yields the
    identical stream, and positions tile each epoch exactly."""

    def run(schedule, n=24, seed=3, bs=2):
        state, stream = None, []
        for w, steps in schedule:
            engines, iters = [], []
            for r in range(w):
                e = DataEngine(ListSource(list(range(n)), seed=seed,
                                          rank=r, world=w),
                               batch_size=bs, drop_last=True, elastic=True)
                if state is not None:
                    e.load_state_dict(state)
                engines.append(e)
                iters.append(iter(e))
            for _ in range(steps):
                for r in range(w):
                    e = engines[r]
                    try:
                        b = next(iters[r])
                    except StopIteration:
                        iters[r] = iter(e)
                        b = next(iters[r])
                    p0 = e.base + (e.cursor - bs) * w + r
                    for k, v in enumerate(b):
                        stream.append((e.epoch, p0 + k * w, v))
            state = engines[0].state_dict()
        return stream

    sched = [(2, 3), (3, 1), (4, 2), (1, 4)]
    s1, s2 = run(sched), run(sched)
    assert s1 == s2
    by_epoch = {}
    for ep, p, v in s1:
        by_epoch.setdefault(ep, []).append(p)
    for ep, poss in by_epoch.items():
        assert sorted(poss) == list(range(len(poss))), ep


def test_prefetcher_global_cursor_is_consumer_exact():
    from paddle_tpu.dataio import DevicePrefetcher

    src = ListSource(list(range(16)), seed=2, rank=0, world=2)
    eng = DataEngine(src, batch_size=2, drop_last=True)
    pre = DevicePrefetcher(eng, depth=2)
    it = iter(pre)
    next(it)
    time.sleep(0.2)  # let the producer read ahead
    # consumer has seen ONE batch of 2 samples at world 2
    assert pre.global_cursor() == 2 * 2
    assert eng.global_cursor >= pre.global_cursor()


# ---------------------------------------------------------------------------
# checkpoint: pinned sync-step resume + gang-generation stamps
# ---------------------------------------------------------------------------


def _train_ckpt(tmp_path, steps, interval=2, gen_env=None, dirname="ck"):
    from paddle_tpu.core.ir import Program, program_guard

    if gen_env is not None:
        os.environ[GANG_GENERATION_ENV] = str(gen_env)
    try:
        main, startup = Program(), Program()
        with program_guard(main, startup):
            x = fluid.data("x", shape=[-1, 4])
            pred = fluid.layers.fc(x, size=1)
            loss = fluid.layers.mean(pred)
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        feed = {"x": np.ones((4, 4), dtype=np.float32)}
        with fluid.scope_guard(scope):
            exe.run(startup)
            ck = AutoCheckpoint(exe, main, str(tmp_path / dirname),
                                save_interval_steps=interval, scope=scope,
                                max_to_keep=16)
            start = ck.resume()
            for step in range(start, steps):
                exe.run(main, feed=feed, fetch_list=[loss])
                ck.maybe_save(step, blocking=True)
            ck.close()
        return str(tmp_path / dirname)
    finally:
        if gen_env is not None:
            del os.environ[GANG_GENERATION_ENV]


def test_pinned_step_resume_and_strictness(tmp_path):
    d = _train_ckpt(tmp_path, steps=8, interval=2)  # saves at 1,3,5,7
    from paddle_tpu.incubate.checkpoint import load_checkpoint

    scope = fluid.Scope()
    assert load_checkpoint(d, scope=scope, step=3) == 4
    # pinned step that never existed: loud, no silent walk-back
    with pytest.raises(CheckpointCorruptError):
        load_checkpoint(d, scope=fluid.Scope(), step=4)
    # pinned step corrupted: quarantined + loud
    from paddle_tpu.resilience import corrupt_file

    corrupt_file(os.path.join(d, "ckpt_5", "state.npz"))
    with pytest.raises(CheckpointCorruptError):
        load_checkpoint(d, scope=fluid.Scope(), step=5)
    assert any(".corrupt" in n for n in os.listdir(d))
    # un-pinned resume still walks back past the quarantined entry
    assert load_checkpoint(d, scope=fluid.Scope()) == 8


def test_gang_generation_stamped_and_monotone(tmp_path):
    d = _train_ckpt(tmp_path, steps=4, interval=2, gen_env=0)
    _train_ckpt(tmp_path, steps=8, interval=2, gen_env=1)
    chain = gang_generations(d)
    steps = [s for s, _ in chain]
    gens = [g for _, g in chain]
    assert steps == sorted(steps) and gens == [0, 0, 1, 1]
    # meta.json carries it too
    with open(os.path.join(d, "ckpt_7", "meta.json")) as f:
        assert json.load(f)["gang_generation"] == 1
    # unstamped checkpoints read back as None
    d2 = _train_ckpt(tmp_path, steps=2, interval=2, dirname="ck2")
    assert gang_generations(d2) == [(1, None)]


def test_load_data_state_reads_blob_without_scope(tmp_path):
    from paddle_tpu.core.ir import Program, program_guard

    src = ListSource(list(range(16)), seed=1, rank=0, world=4)
    eng = DataEngine(src, batch_size=2, drop_last=True)
    it = iter(eng)
    next(it)
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", shape=[-1, 2])
        fluid.layers.fc(x, size=1)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        ck = AutoCheckpoint(exe, main, str(tmp_path / "ck"),
                            save_interval_steps=1, scope=scope,
                            data_state=eng)
        ck.save(0, blocking=True)
    blob = load_data_state(str(tmp_path / "ck"), step=0)
    assert blob["world"] == 4 and blob["cursor"] == 2
    assert load_data_state(str(tmp_path / "ck")) == blob
    # a corrupt pinned entry is quarantined AND loud (same contract as
    # load_checkpoint's pinned branch)
    from paddle_tpu.resilience import corrupt_file

    corrupt_file(os.path.join(str(tmp_path / "ck"), "ckpt_0",
                              "state.npz"))
    with pytest.raises(CheckpointCorruptError):
        load_data_state(str(tmp_path / "ck"), step=0)
    assert any(".corrupt" in n for n in os.listdir(tmp_path / "ck"))


# ---------------------------------------------------------------------------
# fault sites: worker.preempt (term) + elastic.resize
# ---------------------------------------------------------------------------


def test_term_action_parses_and_sigterms_subprocess(tmp_path):
    # schedule validation accepts the new action (and still rejects junk)
    faults.configure([{"site": "worker.preempt", "action": "term"}])
    faults.reset()
    with pytest.raises(ValueError):
        faults.configure([{"site": "x", "action": "vaporize"}])
    # a subprocess firing the site dies with -SIGTERM (not the hard-kill
    # exit code): the preemption shape, catchable in principle
    code = textwrap.dedent("""
        import os, sys
        sys.path.insert(0, %r)
        from paddle_tpu.resilience import faults
        faults.configure([{"site": "worker.preempt", "action": "term",
                           "at_step": 2}])
        for step in range(5):
            faults.fire("worker.preempt", step=step)
        print("SURVIVED")
    """ % REPO)
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == -15, (proc.returncode, proc.stdout)
    assert "SURVIVED" not in proc.stdout


def _trivial_worker(tmp_path, body):
    path = tmp_path / "w.py"
    path.write_text(textwrap.dedent(body))
    return str(path)


def test_elastic_resize_fault_degrades_to_same_size_restart(tmp_path):
    """An injected failure at the elastic.resize site falls back to the
    classic same-size restart instead of resizing — the resize path is
    itself a hardened path."""
    script = _trivial_worker(tmp_path, """
        import os, sys
        if (os.environ["PADDLE_ELASTIC_GANG_GENERATION"] == "0"
                and os.environ["PADDLE_TRAINER_ID"] == "1"):
            sys.exit(9)
        sys.exit(0)
    """)
    faults.configure([{"site": "elastic.resize", "action": "raise"}])
    try:
        sup = ElasticGangSupervisor([script], nproc=2, min_nproc=1,
                                    capacity_fn=lambda: 1,
                                    max_restarts=2, restart_backoff_s=0.05)
        codes = sup.run()
    finally:
        faults.reset()
    assert codes == [0, 0]
    kinds = [e["kind"] for e in sup.events]
    assert "resize_fault" in kinds
    assert "gang_resize" not in kinds         # the resize was degraded
    assert sup.world == 2                     # same-size restart
    assert sup.generation == 1                # but a new generation


# ---------------------------------------------------------------------------
# ElasticGangSupervisor policy
# ---------------------------------------------------------------------------


def test_supervisor_shrinks_on_loss_and_grows_on_capacity(tmp_path):
    script = _trivial_worker(tmp_path, """
        import os, sys, time
        gen = int(os.environ["PADDLE_ELASTIC_GANG_GENERATION"])
        rank = int(os.environ["PADDLE_TRAINER_ID"])
        world = int(os.environ["PADDLE_TRAINERS_NUM"])
        if gen == 0:
            assert world == 4, world
            if rank == 3:
                sys.exit(7)
        time.sleep(1.0)
        sys.exit(0)
    """)
    state = {"phase": 0}

    def capacity():
        return 2 if state["phase"] == 0 else 4

    sup = ElasticGangSupervisor([script], nproc=4, min_nproc=2,
                                capacity_fn=capacity, capacity_poll_s=0.2,
                                max_restarts=3, restart_backoff_s=0.05)
    orig = sup._decide_world

    def decide(failure):
        w = orig(failure)
        if failure["kind"] == "rank_exit":
            state["phase"] = 1   # capacity returns once the gang shrank
        return w

    sup._decide_world = decide
    codes = sup.run()
    assert codes == [0, 0, 0, 0]
    assert (4, 2, 1) in sup.resizes and (2, 4, 2) in sup.resizes
    assert sup.restarts == 1          # the grow never charged the budget
    gauge = None
    from paddle_tpu.observability import registry

    gauge = registry().gauge("elastic_world_size",
                             "current world size of the elastic "
                             "training gang")
    assert gauge.value == 4
    hist = registry().histogram(
        "elastic_resize_seconds",
        "failure/capacity detection to resized-gang spawn")
    assert hist.count >= 2


def test_supervisor_never_goes_below_min_nproc(tmp_path):
    script = _trivial_worker(tmp_path, """
        import os, sys
        if os.environ["PADDLE_ELASTIC_GANG_GENERATION"] in ("0", "1"):
            sys.exit(5)
        sys.exit(0)
    """)
    sup = ElasticGangSupervisor([script], nproc=3, min_nproc=2,
                                capacity_fn=lambda: 1,   # wants 1: clamped
                                max_restarts=3, restart_backoff_s=0.05)
    codes = sup.run()
    assert codes == [0, 0]
    worlds = [e["new_world"] for e in sup.events
              if e["kind"] == "gang_resize"]
    assert worlds and all(w >= 2 for w in worlds)
    assert sup.world == 2


def test_sync_step_is_newest_common_valid_entry(tmp_path):
    """Fabricated per-rank chains: the sync step must be the newest step
    EVERY active rank holds, skipping corrupt candidates (quarantined)."""
    import io as _io
    import zlib

    def fake_ckpt(d, step, corrupt=False):
        os.makedirs(os.path.join(d, f"ckpt_{step}"), exist_ok=True)
        p = os.path.join(d, f"ckpt_{step}")
        arr = np.arange(4, dtype=np.float32)
        buf = _io.BytesIO()
        np.savez(buf, w=arr)
        raw = buf.getvalue()
        with open(os.path.join(p, "state.npz"), "wb") as f:
            f.write(raw)
        manifest = {"format": 1, "step": step, "arrays": {},
                    "files": {"state.npz": {
                        "size": len(raw) + (7 if corrupt else 0),
                        "crc32": zlib.crc32(raw) & 0xFFFFFFFF}}}
        with open(os.path.join(p, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        with open(os.path.join(p, "meta.json"), "w") as f:
            json.dump({"step": step}, f)

    dirs = [str(tmp_path / f"rank{r}") for r in range(3)]
    for r, d in enumerate(dirs):
        for s in (1, 3, 5):
            fake_ckpt(d, s)
    fake_ckpt(dirs[1], 7)               # rank1 ran ahead: not common
    fake_ckpt(dirs[2], 5, corrupt=True)  # rank2's newest common is torn

    sup = ElasticGangSupervisor(["x.py"], nproc=3, min_nproc=1,
                                checkpoint_dirs=dirs)
    assert sup._sync_step() == 3
    # the torn candidate was quarantined on the walk
    assert any(".corrupt" in n for n in os.listdir(dirs[2]))
    # no checkpoints at all -> fresh start
    sup2 = ElasticGangSupervisor(["x.py"], nproc=2, min_nproc=1,
                                 checkpoint_dirs=[str(tmp_path / "empty0"),
                                                  str(tmp_path / "empty1")])
    assert sup2._sync_step() is None


def test_launch_cli_elastic_flags(tmp_path):
    """--min_nproc/--elastic route through ElasticGangSupervisor; the
    classic path stays untouched without them."""
    script = _trivial_worker(tmp_path, """
        import os, sys
        assert "PADDLE_ELASTIC_GANG_GENERATION" in os.environ
        sys.exit(0)
    """)
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc", "2", "--min_nproc", "1", script],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": REPO + os.pathsep + os.environ.get(
                 "PYTHONPATH", "")},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    classic = _trivial_worker(tmp_path, """
        import os, sys
        assert "PADDLE_ELASTIC_GANG_GENERATION" not in os.environ
        sys.exit(0)
    """)
    os.replace(str(tmp_path / "w.py"), str(tmp_path / "w2.py"))
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc", "2", str(tmp_path / "w2.py")],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": REPO + os.pathsep + os.environ.get(
                 "PYTHONPATH", "")},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]


# ---------------------------------------------------------------------------
# the property gate: the chaos smoke CLI, its report read back
# ---------------------------------------------------------------------------


def test_elastic_shrink_grow_replays_bit_identically():
    """`tools/chaos_elastic.py --smoke --json` LIVE: a rank is killed
    mid-step, the gang shrinks 4->2 and grows 2->4; the committed stream
    (batches and rank 0's losses) equals a reference run driven over
    the same schedule with no kill, every sample is consumed exactly
    once, generations rise 0, 1, 2 and both resumes restored shards."""
    env = dict(os.environ)
    env.pop("PADDLE_TPU_FAULTS", None)
    env.pop("PADDLE_TPU_FAULT_STATE", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos_elastic.py"),
         "--smoke", "--json"],
        capture_output=True, text=True, timeout=540, env=env,
    )
    assert proc.returncode == 0, \
        proc.stdout[-4000:] + proc.stderr[-4000:]
    assert "CHAOS_ELASTIC_OK" in proc.stdout
    report = json.loads(next(
        ln for ln in proc.stdout.splitlines() if ln.startswith("{")))
    assert report["pass"] and report["failures"] == []
    inv = report["invariants"]
    assert inv["bit_identical"] and inv["lost_or_duplicated"] == 0
    assert [4, 2, "shrink"] in inv["resizes"]
    assert [2, 4, "grow"] in inv["resizes"]
    assert [ph["world"] for ph in inv["schedule"]] == [4, 2, 4]
    assert inv["generations"] == [0, 1, 2]
    assert inv["committed_batches"] > 0
    assert inv["shrink_sharded_restored"] > 0
    assert inv["grow_sharded_restored"] > 0
    assert inv["grown_ranks_from_chief"] >= 1
