"""Sharded embedding engine (paddle_tpu/embedding/): hash partition,
dedup gather evidence, two-tier cache bit-exactness, fault/retry wiring,
format-2 checkpoint roundtrips, and the SpecLayout ep role."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.embedding import EmbeddingEngine, TableConfig
from paddle_tpu.embedding.gather import (
    dedup_evidence,
    dedup_ids,
    next_bucket,
    stablehlo_table_gathers,
)
from paddle_tpu.embedding.table import hash_shard, init_rows
from paddle_tpu.resilience import faults
from paddle_tpu.utils import hlo as uhlo
from paddle_tpu.utils.enforce import EnforceError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

B, S, D = 4, 3, 8


# ---------------------------------------------------------------------------
# table.py: hashing + deterministic init
# ---------------------------------------------------------------------------


def test_hash_shard_spreads_clustered_ids():
    """CTR ids arrive clustered (consecutive per slot); the mixed hash
    must still spread them evenly — unlike the reference's id % n."""
    ids = np.arange(10_000, dtype=np.uint64)  # worst case for % n
    shards = hash_shard(ids, 4, seed=1)
    counts = np.bincount(shards, minlength=4)
    assert counts.min() > 0.8 * counts.max(), counts
    # deterministic across calls, sensitive to seed
    assert np.array_equal(shards, hash_shard(ids, 4, seed=1))
    assert not np.array_equal(shards, hash_shard(ids, 4, seed=2))


def test_init_rows_pure_and_zero_range():
    ids = np.array([3, 2**40 + 7, 3], dtype=np.uint64)
    a = init_rows(ids, 6, 0.05, seed=9)
    b = init_rows(ids, 6, 0.05, seed=9)
    assert np.array_equal(a, b)
    assert np.array_equal(a[0], a[2])            # per-id, not per-position
    assert not np.array_equal(a[0], a[1])
    assert np.abs(a).max() <= 0.05
    assert not np.array_equal(init_rows(ids, 6, 0.05, seed=10), a)
    assert np.array_equal(init_rows(ids, 6, 0.0), np.zeros((3, 6), "f"))


def test_dedup_ids_and_buckets():
    ids = np.array([[5, 5, 9], [9, 2, 5]], dtype=np.int64)
    uniq, u_pad, inv = dedup_ids(ids, min_bucket=8)
    assert list(uniq) == [2, 5, 9]
    assert u_pad == 8
    assert inv.shape == ids.shape and inv.dtype == np.int32
    assert np.array_equal(uniq[inv], ids.astype(np.uint64))
    # the bench control: no dedup, inv is the identity
    uniq0, u_pad0, inv0 = dedup_ids(ids, min_bucket=8, dedup=False)
    assert len(uniq0) == 6 and u_pad0 == 8
    assert np.array_equal(inv0.reshape(-1), np.arange(6))
    assert next_bucket(9, 8) == 16 and next_bucket(1, 8) == 8


# ---------------------------------------------------------------------------
# training correctness: dense parity + cache-size invariance
# ---------------------------------------------------------------------------


def _build_sharded(capacity, ep, lr=0.5, seed=3, opt="sgd", clip=None):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.data("ids", shape=[-1, S], dtype="int64")
        y = fluid.data("y", shape=[-1, S, D], dtype="float32")
        emb = fluid.layers.sharded_embedding(
            ids, D, capacity=capacity, ep=ep, name="t0",
            init_range=0.05, lr=lr, seed=seed,
        )
        loss = fluid.layers.mean(fluid.layers.square(
            fluid.layers.elementwise_sub(emb, y)
        ))
        optimizer = (
            fluid.optimizer.Adam(learning_rate=1e-3) if opt == "adam"
            else fluid.optimizer.SGD(learning_rate=lr, grad_clip=clip)
        )
        optimizer.minimize(loss)
    return main, startup, loss


def _counter_snapshot(table):
    from paddle_tpu.observability import metrics as obs_metrics

    reg = obs_metrics.registry()
    out = {}
    for key, fam in (("hits", "embedding_cache_hits_total"),
                     ("misses", "embedding_cache_misses_total"),
                     ("evictions", "embedding_cache_evictions_total"),
                     ("writebacks", "embedding_writebacks_total")):
        m = reg.get(fam, {"table": table})
        out[key] = m.value if m is not None else 0
    return out


def _train_sharded(capacity, ep, steps=6, vocab=40, opt="sgd"):
    main, startup, loss = _build_sharded(capacity, ep, opt=opt)
    exe = fluid.Executor(fluid.CPUPlace())
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe.run(startup)
        eng = EmbeddingEngine(scope=sc)
        # the metrics registry is process-global and the table label
        # repeats across runs — measure this run as deltas
        before = _counter_snapshot("t0")
        rng = np.random.RandomState(0)
        losses = []
        for _ in range(steps):
            idv = rng.randint(0, vocab, (B, S)).astype("int64")
            idv[0, :2] = 7  # guaranteed duplicates -> grads must merge
            feed = {"ids": idv, "y": rng.randn(B, S, D).astype("float32")}
            eng.prepare_feed(main, feed)
            out = exe.run(main, feed=feed, fetch_list=[loss])
            losses.append(np.asarray(out[0]).copy())
        eng.flush()
        rt = eng.tables["t0"]
        values = {
            i: r.copy() for shard in rt.store._shards
            for i, r in shard.items()
        }
        after = _counter_snapshot("t0")
        stats = {k: after[k] - before[k] for k in after}
        stats["hit_rate"] = stats["hits"] / max(
            1, stats["hits"] + stats["misses"])
        eng.close()
    return np.array(losses).reshape(-1), values, stats


def test_sharded_training_matches_dense_embedding(rng):
    """Same stream through sharded_embedding and a dense
    embedding+SGD: losses and every touched row agree (the dense path's
    scatter-summed grads ARE the engine's dedup-merged row updates)."""
    vocab, lr = 40, 0.5
    losses, values, _ = _train_sharded(64, 2, vocab=vocab)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.data("ids", shape=[-1, S], dtype="int64")
        y = fluid.data("y", shape=[-1, S, D], dtype="float32")
        emb = fluid.layers.embedding(
            ids, (vocab, D), param_attr=fluid.ParamAttr(name="w"))
        loss = fluid.layers.mean(fluid.layers.square(
            fluid.layers.elementwise_sub(emb, y)))
        fluid.optimizer.SGD(learning_rate=lr).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe.run(startup)
        sc.set("w", init_rows(np.arange(vocab), D, 0.05, seed=3))
        r = np.random.RandomState(0)
        dense = []
        for _ in range(6):
            idv = r.randint(0, vocab, (B, S)).astype("int64")
            idv[0, :2] = 7
            feed = {"ids": idv, "y": r.randn(B, S, D).astype("float32")}
            out = exe.run(main, feed=feed, fetch_list=[loss])
            dense.append(float(np.asarray(out[0]).reshape(-1)[0]))
        w = np.asarray(sc.find_var("w"))
    np.testing.assert_allclose(losses, dense, rtol=1e-6)
    for i, row in values.items():
        np.testing.assert_allclose(w[int(i)], row, rtol=1e-6, atol=1e-7)


def test_cache_size_invariance_bit_exact():
    """The write-back contract: a tiny cache (heavy eviction traffic,
    different ep count) trains BIT-identically to a cache holding
    everything — losses and the final value map are array_equal."""
    l_small, v_small, st_small = _train_sharded(24, 2)
    l_big, v_big, st_big = _train_sharded(128, 4)
    assert st_small["evictions"] > 0, st_small
    assert st_big["evictions"] == 0, st_big
    assert np.array_equal(l_small, l_big), (l_small, l_big)
    assert set(v_small) == set(v_big)
    for i in v_small:
        assert np.array_equal(v_small[i], v_big[i]), i
    # and an Adam model config trains identically too (the dense Adam
    # never touches the slab: the deferred rewrite strips it)
    l_adam_small, _v, st = _train_sharded(24, 2, opt="adam")
    l_adam_big, _v2, _st = _train_sharded(128, 4, opt="adam")
    assert st["evictions"] > 0
    assert np.array_equal(l_adam_small, l_adam_big)


def test_capacity_overflow_is_clear_error():
    main, startup, loss = _build_sharded(8, 2)  # 4 slots/shard < uniques
    exe = fluid.Executor(fluid.CPUPlace())
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe.run(startup)
        eng = EmbeddingEngine(scope=sc)
        idv = np.arange(B * S, dtype=np.int64).reshape(B, S)
        with pytest.raises(EnforceError, match="cache slots for ONE batch"):
            eng.prepare_feed(main, {"ids": idv})
        eng.close()


def test_config_validation():
    with pytest.raises(EnforceError, match="multiple of ep"):
        TableConfig("t", 4, capacity=10, ep=4)


# ---------------------------------------------------------------------------
# the deferred update rewrite
# ---------------------------------------------------------------------------


def test_rewrite_strips_dense_optimizer_and_slots():
    main, startup, loss = _build_sharded(16, 2, opt="adam")
    exe = fluid.Executor(fluid.CPUPlace())
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe.run(startup)
        eng = EmbeddingEngine(scope=sc)
        feed = {"ids": np.zeros((B, S), "int64"),
                "y": np.zeros((B, S, D), "float32")}
        eng.prepare_feed(main, feed)
        exe.run(main, feed=feed, fetch_list=[loss])
        eng.close()
    types = [op.type for op in main.global_block().ops]
    assert "sharded_embedding_sgd" in types
    assert "sharded_embedding_lookup_grad" not in types
    # no optimizer op updates the slab; its moments left the block
    for op in main.global_block().ops:
        if op.type == "adam":
            assert op.inputs["Param"][0] != "t0__slab"
    assert not any("t0__slab_moment" in n for n in main.global_block().vars)


def test_grad_clip_on_sharded_table_is_build_error():
    clip = fluid.clip.GradientClipByGlobalNorm(1.0)
    main, startup, loss = _build_sharded(16, 2, clip=clip)
    exe = fluid.Executor(fluid.CPUPlace())
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe.run(startup)
        eng = EmbeddingEngine(scope=sc)
        feed = {"ids": np.zeros((B, S), "int64"),
                "y": np.zeros((B, S, D), "float32")}
        eng.prepare_feed(main, feed)
        with pytest.raises(EnforceError, match="sharded table slab"):
            exe.run(main, feed=feed, fetch_list=[loss])
        eng.close()


# ---------------------------------------------------------------------------
# HLO evidence: the dedup gather claim, read off the emitted computation
# ---------------------------------------------------------------------------


def test_hlo_dedup_gather_moves_unique_rows_only():
    """Exactly ONE gather reads the slab and it moves U_pad < n_ids
    rows; the dedup-off control moves every occurrence (and is flagged).
    capacity=64 keeps slab/rows shapes collision-free (24 ids pad to 32)."""
    cap = 64
    main, startup, loss = _build_sharded(cap, 2)
    exe = fluid.Executor(fluid.CPUPlace())
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe.run(startup)
        eng = EmbeddingEngine(scope=sc)
        rng = np.random.RandomState(0)
        idv = rng.randint(0, 8, (B, S)).astype("int64")  # <=8 uniques
        y = rng.randn(B, S, D).astype("float32")
        n_ids = B * S
        feed = {"ids": idv, "y": y}
        eng.prepare_feed(main, feed)
        txt = uhlo.lower_program_step(main, feed, [loss], scope=sc).as_text()
        ev = dedup_evidence(txt, (cap, D), n_ids)
        assert ev["gathers"] == 1, ev
        assert ev["rows_moved"] < n_ids and ev["dedup_saves"], ev
        # positive control: dedup off gathers one row per occurrence
        feed2 = {"ids": idv, "y": y}
        eng.prepare_feed(main, feed2, dedup=False)
        txt2 = uhlo.lower_program_step(main, feed2, [loss],
                                       scope=sc).as_text()
        ev2 = dedup_evidence(txt2, (cap, D), n_ids)
        assert ev2["rows_moved"] >= n_ids and not ev2["dedup_saves"], ev2
        eng.close()


def test_gather_scan_detector_fires():
    fake = ('%5 = "stablehlo.gather"(%2, %4) <{slice_sizes = array<i64: '
            "1, 8>}> : (tensor<64x8xf32>, tensor<16x1xi32>) -> "
            "tensor<16x8xf32>")
    assert stablehlo_table_gathers(fake, (64, 8)) == [(16, 8)]
    assert stablehlo_table_gathers(fake, (32, 8)) == []


# ---------------------------------------------------------------------------
# two-tier behavior: write-back, metrics, staleness, prefetch, faults
# ---------------------------------------------------------------------------


def test_writeback_updates_store_and_staleness_gauge():
    main, startup, loss = _build_sharded(16, 2)
    exe = fluid.Executor(fluid.CPUPlace())
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe.run(startup)
        eng = EmbeddingEngine(scope=sc)
        rng = np.random.RandomState(1)
        feed = {"ids": rng.randint(0, 8, (B, S)).astype("int64"),
                "y": rng.randn(B, S, D).astype("float32")}
        eng.prepare_feed(main, feed)
        exe.run(main, feed=feed, fetch_list=[loss])
        rt = eng.tables["t0"]
        assert rt._dirty, "trained rows must be marked dirty"
        # staleness gauge is live while dirty...
        rt._refresh_gauges()
        assert rt.g_staleness.value >= 0.0 and rt._oldest_dirty is not None
        # flush reconciles: store rows == device slab rows, gauge drops
        eng.flush()
        assert not rt._dirty and rt.g_staleness.value == 0.0
        slab = rt.slab_host()
        for i, slot in rt._slot.items():
            srow = rt.store.pull([i])[0][0]
            np.testing.assert_array_equal(srow, slab[slot])
        assert rt.g_occupancy.value == len(rt._slot)
        eng.close()


def test_prefetch_materializes_ahead():
    main, startup, loss = _build_sharded(32, 2)
    exe = fluid.Executor(fluid.CPUPlace())
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe.run(startup)
        eng = EmbeddingEngine(scope=sc)
        nxt = {"ids": np.arange(B * S, dtype=np.int64).reshape(B, S)}
        futs = eng.prefetch(main, nxt)
        for f in futs:
            f.result()
        rt = eng.tables["t0"]
        assert rt.m_prefetch.value == B * S
        assert len(rt.store) == B * S
        eng.close()


def test_transient_push_fault_retries_and_fatal_surfaces():
    """The engine's pull/push ride distributed/lookup.py's fault sites:
    a transient injected fault on lookup.push is retried away by the
    shared policy; a non-transient one surfaces from flush()."""
    main, startup, loss = _build_sharded(16, 2)
    exe = fluid.Executor(fluid.CPUPlace())
    sc = fluid.Scope()
    try:
        with fluid.scope_guard(sc):
            exe.run(startup)
            eng = EmbeddingEngine(scope=sc)
            feed = {"ids": np.arange(B * S, dtype=np.int64).reshape(B, S),
                    "y": np.ones((B, S, D), "float32")}
            eng.prepare_feed(main, feed)
            exe.run(main, feed=feed, fetch_list=[loss])
            faults.configure([{"site": "lookup.push", "times": 1,
                               "exc": "transient"}])
            eng.flush()  # retried under the shared policy
            stats = faults.get_injector().rule_stats()
            assert sum(r["fired"] for r in stats.values()) == 1
            faults.configure([{"site": "lookup.push", "times": 1,
                               "exc": "fatal"}])
            eng.prepare_feed(main, feed)
            exe.run(main, feed=feed, fetch_list=[loss])
            with pytest.raises(faults.InjectedFault):
                eng.flush()
            eng.close()
    finally:
        faults.reset()


# ---------------------------------------------------------------------------
# checkpoints: format-2 per-shard store, N -> M, kill-and-resume
# ---------------------------------------------------------------------------


def test_checkpoint_format2_roundtrip_and_n_to_m(tmp_path):
    from paddle_tpu.incubate.checkpoint import AutoCheckpoint

    main, startup, loss = _build_sharded(24, 2)
    exe = fluid.Executor(fluid.CPUPlace())
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe.run(startup)
        eng = EmbeddingEngine(scope=sc)
        ck = AutoCheckpoint(exe, main, str(tmp_path), save_interval_steps=1,
                            scope=sc, extra_state=eng)
        rng = np.random.RandomState(0)
        for step in range(3):
            idv = rng.randint(0, 40, (B, S)).astype("int64")
            feed = {"ids": idv, "y": rng.randn(B, S, D).astype("float32")}
            eng.prepare_feed(main, feed)
            exe.run(main, feed=feed, fetch_list=[loss])
        ck.save(2, blocking=True)
        ref = {i: r.copy() for sh in eng.tables["t0"].store._shards
               for i, r in sh.items()}
        eng.close()
    # manifest: format 2, the store arrays ride the per-shard path
    man = json.load(open(tmp_path / "ckpt_2" / "manifest.json"))
    assert man["format"] == 2
    names = set(man["sharded"])
    assert "__embedding_store__::t0::ids" in names
    assert "__embedding_store__::t0::rows" in names
    rows_entry = man["sharded"]["__embedding_store__::t0::rows"]
    assert len(rows_entry["shards"]) == 2  # one block per ep shard
    for sh in rows_entry["shards"]:
        assert {"crc32", "start", "stop", "file"} <= set(sh)

    # restore onto a DIFFERENT factorization: ep=4, other capacity
    main2, startup2, loss2 = _build_sharded(64, 4)
    sc2 = fluid.Scope()
    with fluid.scope_guard(sc2):
        exe.run(startup2)
        eng2 = EmbeddingEngine(scope=sc2)
        eng2._runtime_for(main2._sharded_tables["t0"])
        ck2 = AutoCheckpoint(exe, main2, str(tmp_path), scope=sc2,
                             extra_state=eng2)
        assert ck2.resume() == 3
        rt2 = eng2.tables["t0"]
        got = {i: r.copy() for sh in rt2.store._shards for i, r in sh.items()}
        assert set(got) == set(ref)
        for i in ref:
            assert np.array_equal(ref[i], got[i]), i
        assert not rt2._slot  # device cache restores cold
        eng2.close()


def test_kill_and_resume_bit_identical(tmp_path):
    """Chaos acceptance: SIGKILL mid-training, resume from the format-2
    checkpoint, and the full loss sequence matches an uninterrupted
    reference bit-for-bit (tables restored through the shard path)."""
    worker = os.path.join(REPO, "tests", "embedding_resume_worker.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def run(tag, ckdir, extra):
        log = tmp_path / f"{tag}.log"
        proc = subprocess.run(
            [sys.executable, worker, "--ckdir", str(ckdir),
             "--log", str(log), "--tag", tag] + extra,
            env=env, capture_output=True, text=True, timeout=420,
        )
        return proc, log

    proc, ref_log = run("ref", tmp_path / "ck_ref", [])
    assert proc.returncode == 0, proc.stderr[-2000:]

    proc, _ = run("killed", tmp_path / "ck", ["--kill-at-step", "5"])
    assert proc.returncode != 0  # SIGKILLed
    proc, res_log = run("resumed", tmp_path / "ck", [])
    assert proc.returncode == 0, proc.stderr[-2000:]

    ref = ref_log.read_text().strip().splitlines()
    res = res_log.read_text().strip().splitlines()
    # resumed run starts at the checkpointed step; every line it emits
    # must equal the reference's line for the same step
    ref_map = {l.split()[1]: l.split(" ", 2)[2] for l in ref}
    assert res, "resumed run logged nothing"
    assert int(res[0].split()[1]) > 0, "resume started from step 0"
    for l in res:
        step, payload = l.split()[1], l.split(" ", 2)[2]
        assert ref_map[step] == payload, f"step {step} diverged"


# ---------------------------------------------------------------------------
# SpecLayout: the slab's canonical ep placement
# ---------------------------------------------------------------------------


def test_spec_layout_embedding_shard_role():
    import jax
    from paddle_tpu.parallel.env import make_mesh
    from paddle_tpu.parallel.spec_layout import Role, SpecLayout

    main, startup, loss = _build_sharded(32, 4)
    layout = SpecLayout()
    assert layout.roles_for(main)["t0__slab"] == Role.EMBEDDING_SHARD
    assert jax.device_count() >= 8
    mesh = make_mesh(shape=(2, 4), axis_names=("data", "ep"))
    sh = layout.derive_shardings(main, ["t0__slab"], [(32, D)], mesh)
    assert tuple(sh["t0__slab"].spec) == ("ep",)
    # no ep axis on the mesh -> graceful degradation to replicated
    mesh_dp = make_mesh(shape=(8,), axis_names=("data",))
    sh2 = layout.derive_shardings(main, ["t0__slab"], [(32, D)], mesh_dp)
    assert tuple(sh2["t0__slab"].spec) == ()


def test_ep_mesh_no_slab_shaped_collectives():
    """The multichip property on the 8-device CPU mesh: with the slab
    row-sharded over ep, no collective in the optimized step moves a
    slab-shaped operand (collectives ride on unique rows/activations)."""
    import jax
    from paddle_tpu.parallel.env import make_mesh
    from paddle_tpu.parallel.spec_layout import SpecLayout

    assert jax.device_count() >= 8
    cap = 128
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.data("ids", shape=[-1, S], dtype="int64")
        y = fluid.data("y", shape=[-1, S, D], dtype="float32")
        emb = fluid.layers.sharded_embedding(
            ids, D, capacity=cap, ep=4, name="t0", lr=0.5)
        h = fluid.layers.fc(fluid.layers.reduce_sum(emb, dim=1),
                            size=16, act="relu")
        loss = fluid.layers.mean(fluid.layers.square(
            fluid.layers.elementwise_sub(
                fluid.layers.fc(h, size=D), fluid.layers.reduce_sum(y, dim=1)
            )))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    mesh = make_mesh(shape=(2, 4), axis_names=("data", "ep"))
    exe = fluid.Executor(fluid.CPUPlace())
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe.run(startup)
        eng = EmbeddingEngine(scope=sc)
        prog = fluid.CompiledProgram(main).with_parallel(
            mesh=mesh, loss_name=loss.name, spec_layout=SpecLayout())
        rng = np.random.RandomState(0)
        feed = {"ids": rng.randint(0, 300, (8, S)).astype("int64"),
                "y": rng.randn(8, S, D).astype("float32")}
        eng.prepare_feed(main, feed)
        out = exe.run(prog, feed=feed, fetch_list=[loss])
        assert np.isfinite(float(np.asarray(out[0]).reshape(-1)[0]))
        # the slab stays sharded on device between steps
        spec = getattr(sc.find_var("t0__slab").sharding, "spec", None)
        assert tuple(spec) == ("ep",)
        lowered, _ = uhlo.lower_parallel_step(exe, prog, feed, [loss], sc)
        txt = lowered.compile().as_text()
        offenders = uhlo.weight_shaped_collectives(txt, {(cap, D)})
        assert offenders == [], offenders
        eng.close()


# ---------------------------------------------------------------------------
# bench smoke: one live run, its report held two ways
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def bench_smoke_report():
    """tools/bench_embedding.py --smoke, run once; its JSON report."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_embedding.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=560,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    return json.loads(proc.stdout[proc.stdout.index("{"):])


def test_bench_embedding_smoke_cli(bench_smoke_report):
    """The smoke run's own asserts passed: bit-identical lookups across
    cache configs (eviction traffic included), device admission with no
    host round trip, the legacy path bit-identical."""
    smoke = bench_smoke_report["smoke"]
    assert smoke["asserts"] == "passed"
    assert smoke["bit_identical_across_configs"] is True
    assert smoke["device_admission_roundtrips"] == 0
    assert smoke["legacy_path_bit_identical"] is True


def test_dedup_gather_moves_fewer_rows_than_ids_and_control_fires(
        bench_smoke_report):
    """Read off the lowered computation of the live run: ONE slab gather
    moving fewer rows than ids, a dedup-off control that moves at least
    one row per id (or the claim proves nothing), a non-trivial measured
    hit rate and a hit counter that moved."""
    rep = bench_smoke_report
    ev = rep["dedup_evidence"]
    assert ev["gathers"] == 1
    assert ev["rows_moved"] < ev["n_ids"]
    assert rep["dedup_off_control"]["rows_moved"] >= ev["n_ids"]
    assert rep["smoke"]["hit_rate"] > 0.3
    assert rep["cache_hit_gauges"]["embedding_cache_hits_total"] > 0
