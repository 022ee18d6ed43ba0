"""Data engine tests: deterministic sharded sources, the order-deterministic
multi-worker pipeline, device prefetch, checkpointable iterator state, and
the DataLoader/Dataset/checkpoint integrations (ISSUE 5 acceptance: same
seed + world => identical batch sequence for num_workers in {1, 4};
crash-resume restores the exact stream; bench_input --smoke >= 2x)."""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core.ir import Program, program_guard
from paddle_tpu.dataio import (
    DataEngine,
    DevicePrefetcher,
    FileSource,
    ListSource,
    parallel_map_ordered,
)
from paddle_tpu.dataio.state import STATE_KEY, decode_state, encode_state
from paddle_tpu.incubate.checkpoint import AutoCheckpoint
from paddle_tpu.observability import registry
from paddle_tpu.reader import decorator as dec
from paddle_tpu.resilience import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# sources
# ---------------------------------------------------------------------------


def test_shard_assignment_disjoint_complete_equal():
    """Epoch shards across ranks are disjoint (up to wrap padding), cover
    every sample, and have EQUAL length (collectives stay in lockstep)."""
    world = 4
    sources = [
        ListSource(list(range(21)), seed=3, rank=r, world=world)
        for r in range(world)
    ]
    shards = [s.epoch_shard(epoch=2) for s in sources]
    lens = {len(sh) for sh in shards}
    assert lens == {6}  # ceil(21/4) with wrap padding
    flat = [i for sh in shards for i in sh]
    assert set(flat) == set(range(21))  # complete
    # only the wrap-padded tail duplicates
    assert len(flat) - len(set(flat)) == 3


def test_shard_tiling_when_dataset_smaller_than_world():
    """A dataset smaller than the world still gives every rank a
    non-empty, equal-length shard (cyclic tiling) — no rank sits out a
    collective step."""
    world = 3
    shards = [
        ListSource([10], seed=0, rank=r, world=world).epoch_shard(0)
        for r in range(world)
    ]
    assert all(sh == [0] for sh in shards)
    shards = [
        ListSource([5, 6], seed=0, rank=r, world=4, shuffle=False)
        .epoch_shard(0) for r in range(4)
    ]
    assert {len(sh) for sh in shards} == {1}
    assert sorted(x for sh in shards for x in sh) == [0, 0, 1, 1]


def test_epoch_order_deterministic_and_epoch_varying():
    s1 = ListSource(list(range(50)), seed=9)
    s2 = ListSource(list(range(50)), seed=9)
    assert s1.epoch_order(0) == s2.epoch_order(0)
    assert s1.epoch_order(1) == s2.epoch_order(1)
    assert s1.epoch_order(0) != s1.epoch_order(1)
    assert ListSource(list(range(50)), seed=10).epoch_order(0) != \
        s1.epoch_order(0)
    # module-global RNG is untouched: order is a pure function of
    # (seed, epoch), not of call history
    import random as _random

    before = _random.getstate()
    s1.epoch_order(3)
    assert _random.getstate() == before


def test_file_source_reads_lines(tmp_path):
    (tmp_path / "a.txt").write_text("l0\nl1\n\nl2\n")
    (tmp_path / "b.txt").write_text("l3\n")
    src = FileSource([str(tmp_path / "a.txt"), str(tmp_path / "b.txt")],
                     parse=lambda l: l.upper(), shuffle=False)
    assert len(src) == 4
    assert [src.item(i) for i in range(4)] == ["L0", "L1", "L2", "L3"]


# ---------------------------------------------------------------------------
# engine: order determinism (acceptance b)
# ---------------------------------------------------------------------------


def _stream(num_workers, seed=7, epochs=2, transform=None, n=37, bs=5):
    src = ListSource(list(range(n)), seed=seed)
    eng = DataEngine(src, transform=transform, batch_size=bs,
                     num_workers=num_workers)
    out = []
    for _ in range(epochs):
        out.append([list(b) for b in eng])
    return out


def test_same_seed_same_stream_across_workers_and_runs():
    """Same seed + same world => identical batch sequence across two
    fresh runs, for num_workers in {1, 4} (and the inline path)."""
    ref = _stream(0)
    for workers in (1, 4):
        assert _stream(workers) == ref
    assert _stream(4) == ref  # second fresh run


def test_order_independent_of_worker_timing():
    import random as _random

    def jitter(x):
        time.sleep(_random.random() * 0.003)
        return x * 2

    src = ListSource(list(range(48)), seed=1)
    expect = [i * 2 for i in src.epoch_shard(0)]
    got = list(DataEngine(ListSource(list(range(48)), seed=1),
                          transform=jitter, num_workers=6))
    assert got == expect


def test_per_sample_rng_invariant_to_worker_count():
    def aug(x, rng):
        return (x, rng.randint(0, 10 ** 9))

    runs = [
        list(DataEngine(ListSource(list(range(30)), seed=5), transform=aug,
                        num_workers=w))
        for w in (0, 1, 4)
    ]
    assert runs[0] == runs[1] == runs[2]


def test_sharded_engines_cover_dataset():
    world = 2
    seen = []
    for r in range(world):
        src = ListSource(list(range(40)), seed=2, rank=r, world=world)
        seen.extend(x for b in DataEngine(src, batch_size=4) for x in b)
    assert sorted(seen) == list(range(40))


# ---------------------------------------------------------------------------
# engine: robustness
# ---------------------------------------------------------------------------


def test_skip_errors_bounded_and_counted():
    def bad(x):
        if x % 4 == 0:
            raise ValueError("poison")
        return x

    eng = DataEngine(ListSource(list(range(16)), seed=0, shuffle=False),
                     transform=bad, num_workers=2, skip_errors=True,
                     name="skip-test")
    before = registry().counter("dataio_skipped_records_total",
                                labels={"pipeline": "skip-test"}).value
    got = list(eng)
    after = registry().counter("dataio_skipped_records_total",
                               labels={"pipeline": "skip-test"}).value
    assert got == [i for i in range(16) if i % 4]
    assert after - before == 4


def test_skip_errors_off_raises_and_max_skips_enforced():
    def bad(x):
        raise RuntimeError("always")

    with pytest.raises(RuntimeError):
        list(DataEngine(ListSource([1, 2], seed=0), transform=bad))
    eng = DataEngine(ListSource(list(range(10)), seed=0), transform=bad,
                     skip_errors=True, max_skips=3, name="skip-cap")
    with pytest.raises(RuntimeError):
        list(eng)


def test_dataio_read_fault_site_skips(tmp_path, monkeypatch):
    """The resilience harness can target source reads; skip_errors turns
    an injected transient read failure into a counted skip."""
    monkeypatch.setenv("PADDLE_TPU_FAULTS", json.dumps(
        [{"site": "dataio.read", "action": "raise", "at_step": 2}]
    ))
    monkeypatch.setenv("PADDLE_TPU_FAULT_STATE", str(tmp_path / "fs"))
    faults.reset()
    try:
        src = ListSource(list(range(8)), seed=0, shuffle=False)
        got = list(DataEngine(src, num_workers=2, skip_errors=True,
                              name="fault-test"))
        # shard position 2 was injected away; everything else flows
        assert got == [0, 1, 3, 4, 5, 6, 7]
    finally:
        monkeypatch.delenv("PADDLE_TPU_FAULTS")
        faults.reset()


# ---------------------------------------------------------------------------
# engine: checkpointable state
# ---------------------------------------------------------------------------


def test_state_roundtrip_resumes_mid_epoch():
    eng = DataEngine(ListSource(list(range(26)), seed=3), batch_size=4,
                     num_workers=2, drop_last=True)
    it = iter(eng)
    head = [next(it) for _ in range(3)]
    st = eng.state_dict()
    rest_live = list(it)
    rest_live += [list(b) for b in eng]  # next epoch too

    eng2 = DataEngine(ListSource(list(range(26)), seed=3), batch_size=4,
                      num_workers=4, drop_last=True)
    eng2.load_state_dict(st)
    assert eng2.epoch == 0 and eng2.cursor == 12 and \
        eng2.emitted_batches == 3
    rest_resumed = list(eng2) + [list(b) for b in eng2]
    assert rest_resumed == rest_live
    assert head  # head consumed before the snapshot, never repeated


def test_state_codec_and_world_mismatch():
    eng = DataEngine(ListSource(list(range(8)), seed=1, rank=0, world=2),
                     batch_size=2)
    blob = encode_state(eng.state_dict())
    assert blob.dtype == np.uint8
    d = decode_state(blob)
    assert d["world"] == 2
    other = DataEngine(ListSource(list(range(8)), seed=1, rank=0, world=4),
                       batch_size=2)
    with pytest.raises(Exception, match="world size"):
        other.load_state_dict(d)


def test_autocheckpoint_carries_data_state(tmp_path, rng):
    """Params and iterator position come back from the same manifest;
    the state blob never leaks into the scope as a variable."""
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", shape=[-1, 4])
        y = fluid.data("y", shape=[-1, 1])
        pred = fluid.layers.fc(x, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
        feeder = fluid.DataFeeder([x, y])

    def tf(i):
        xv = np.full(4, float(i), np.float32) * 0.1
        return (xv, np.array([xv.sum()], np.float32))

    def make_engine():
        return DataEngine(ListSource(list(range(32)), seed=4),
                          transform=tf, batch_size=4, num_workers=2)

    exe = fluid.Executor(fluid.CPUPlace())
    ckdir = str(tmp_path / "ck")
    s1 = fluid.Scope()
    with fluid.scope_guard(s1):
        exe.run(startup)
        eng = make_engine()
        ck = AutoCheckpoint(exe, main, ckdir, save_interval_steps=2,
                            data_state=eng)
        assert ck.resume() == 0
        it = iter(eng)
        for step in range(4):
            exe.run(main, feed=feeder.feed(next(it)), fetch_list=[loss])
            ck.maybe_save(step, blocking=True)
        it.close()
        ck.close()
        # batches 4.. of epoch 0, from live state
        expect_rest = [feeder.feed(b) for b in eng]

    s2 = fluid.Scope()
    with fluid.scope_guard(s2):
        exe.run(startup)
        eng2 = make_engine()
        ck2 = AutoCheckpoint(exe, main, ckdir, save_interval_steps=2)
        ck2.attach_data_state(eng2)
        start = ck2.resume()
        assert start == 4
        assert eng2.emitted_batches == 4 and eng2.cursor == 16
        assert s2.find_var(STATE_KEY) is None
        got_rest = [feeder.feed(b) for b in eng2]
        assert len(got_rest) == len(expect_rest) == 4
        for a, b in zip(expect_rest, got_rest):
            np.testing.assert_array_equal(a["x"], b["x"])
            np.testing.assert_array_equal(a["y"], b["y"])


# ---------------------------------------------------------------------------
# device prefetch
# ---------------------------------------------------------------------------


def test_prefetcher_values_order_and_types():
    feeds = [{"x": np.full((2, 3), i, np.float32),
              "y": np.array([i], np.int64)} for i in range(6)]
    out = list(DevicePrefetcher(iter(feeds), depth=2, name="pf-test"))
    assert len(out) == 6
    import jax

    for i, item in enumerate(out):
        assert isinstance(item["x"], jax.Array)
        np.testing.assert_array_equal(np.asarray(item["x"]), feeds[i]["x"])
        np.testing.assert_array_equal(np.asarray(item["y"]), feeds[i]["y"])


def test_prefetcher_propagates_producer_error():
    def gen():
        yield {"x": np.zeros(2, np.float32)}
        raise ValueError("upstream died")

    pf = DevicePrefetcher(gen(), depth=2)
    it = iter(pf)
    next(it)
    with pytest.raises(ValueError, match="upstream died"):
        next(it)


def test_prefetcher_state_proxy_is_consumer_exact():
    """The prefetcher reads ahead of the consumer, so it proxies
    checkpoint state: state_dict() reflects the last YIELDED batch, not
    the producer's read-ahead cursor — attaching the prefetcher to
    AutoCheckpoint can never skip queued-but-untrained batches."""
    def make():
        return DataEngine(ListSource(list(range(24)), seed=6),
                          batch_size=4, num_workers=2)

    eng = make()
    pre = DevicePrefetcher(eng, depth=3, name="pf-state")
    it = iter(pre)
    got = [np.asarray(next(it)) for _ in range(2)]
    time.sleep(0.3)  # let the producer run ahead into the queue
    st = pre.state_dict()
    assert st["emitted_batches"] == 2 and st["cursor"] == 8, st
    assert eng.emitted_batches > 2  # the engine itself HAS read ahead
    rest = [np.asarray(b) for b in it]

    eng2 = make()
    pre2 = DevicePrefetcher(eng2, depth=3, name="pf-state")
    pre2.load_state_dict(st)
    resumed = [np.asarray(b) for b in pre2]
    assert len(resumed) == len(rest)
    for a, b in zip(rest, resumed):
        np.testing.assert_array_equal(a, b)


def test_skip_errors_never_swallows_base_exceptions():
    """SystemExit-class failures abort the epoch for EVERY num_workers,
    even under skip_errors (only Exception subclasses are skippable)."""
    def fatal(x):
        if x == 3:
            raise SystemExit(7)
        return x

    for workers in (0, 2):
        eng = DataEngine(ListSource(list(range(8)), seed=0, shuffle=False),
                         transform=fatal, num_workers=workers,
                         skip_errors=True, name="fatal-test")
        with pytest.raises(SystemExit):
            list(eng)


def test_dataset_abandoned_pass_does_not_corrupt_next(tmp_path):
    """Abandoning a multi-worker pass mid-iteration and immediately
    starting a new one must not race the stateful feed backend: the new
    pass sees a full, ordered epoch."""
    from paddle_tpu.dataset import DatasetFactory

    p = tmp_path / "d.txt"
    p.write_text("\n".join(f"1 {i}" for i in range(64)) + "\n")
    ds = DatasetFactory().create_dataset("InMemoryDataset")
    main = Program()
    with program_guard(main, Program()):
        v = fluid.data("v", shape=[-1, 1], dtype="int64")
    ds.set_use_var([v])
    ds.set_batch_size(4)
    ds.set_num_workers(3)
    ds.set_filelist([str(p)])
    ds.load_into_memory()

    it = ds._iter_batches()
    next(it)  # consume one batch, then abandon with workers in flight
    full = list(ds._iter_batches())
    vals = [int(x) for b in full for x in b["v"].reshape(-1)]
    assert vals == list(range(64))


def test_prefetcher_sharded_placement():
    """Data-parallel mesh: batch-divisible arrays shard over the axis,
    others replicate (each host would stage only its slice on a pod)."""
    import jax
    from paddle_tpu.parallel.env import make_mesh

    mesh = make_mesh((8,), ("dp",))
    feeds = [{"x": np.arange(16, dtype=np.float32).reshape(16, 1),
              "scalar": np.float32(3.0)}]
    out = list(DevicePrefetcher(iter(feeds), mesh=mesh, batch_axis="dp"))
    x = out[0]["x"]
    assert len(x.sharding.device_set) == 8
    np.testing.assert_array_equal(
        np.asarray(x), feeds[0]["x"])  # reassembles bit-identically


# ---------------------------------------------------------------------------
# ordered parallel map (the reusable pool)
# ---------------------------------------------------------------------------


def test_parallel_map_ordered_matches_serial_and_raises_in_place():
    items = list(range(40))
    assert list(parallel_map_ordered(iter(items), lambda x: x * 3, 4)) == \
        [x * 3 for x in items]

    def boom(x):
        if x == 5:
            raise KeyError("five")
        return x

    got = []
    with pytest.raises(KeyError):
        for v in parallel_map_ordered(iter(items), boom, 3):
            got.append(v)
    assert got == [0, 1, 2, 3, 4]  # error surfaced AT its position


# ---------------------------------------------------------------------------
# DataLoader integration
# ---------------------------------------------------------------------------


def _loader_stream(num_workers, rng_seed=0, transform=None):
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", shape=[-1, 4])
        y = fluid.data("y", shape=[-1, 1])
    loader = fluid.DataLoader.from_generator(
        feed_list=[x, y], capacity=4, num_workers=num_workers)

    def sample_gen():
        r = np.random.RandomState(rng_seed)
        for _ in range(40):
            xv = r.rand(4).astype("float32")
            yield xv, np.array([xv.sum()], dtype="float32")

    loader.set_sample_generator(sample_gen, batch_size=8,
                                sample_transform=transform)
    return [
        {k: np.asarray(v) for k, v in feed.items()} for feed in loader
    ]


def test_dataloader_num_workers_parity():
    """num_workers > 0 must emit the IDENTICAL batch stream (round-robin
    reassembly), just faster."""
    ref = _loader_stream(0)
    par = _loader_stream(4)
    assert len(ref) == len(par) == 5
    for a, b in zip(ref, par):
        np.testing.assert_array_equal(a["x"], b["x"])
        np.testing.assert_array_equal(a["y"], b["y"])


def test_dataloader_sample_transform_applied():
    double = lambda s: (s[0] * 2, s[1])  # noqa: E731
    ref = _loader_stream(0)
    tr = _loader_stream(2, transform=double)
    for a, b in zip(ref, tr):
        np.testing.assert_allclose(b["x"], a["x"] * 2, rtol=1e-6)


def test_dataloader_trains_with_workers(rng):
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", shape=[-1, 4])
        y = fluid.data("y", shape=[-1, 1])
        pred = fluid.layers.fc(x, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
        loader = fluid.DataLoader.from_generator(
            feed_list=[x, y], capacity=4, num_workers=2)

    def sample_gen():
        for i in range(64):
            xv = rng.rand(4).astype("float32")
            yield xv, np.array([xv.sum()], dtype="float32")

    loader.set_sample_generator(sample_gen, batch_size=16)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    losses = []
    for _ in range(8):
        for feed in loader:
            losses.append(
                float(exe.run(main, feed=feed, fetch_list=[loss])[0][0])
            )
    assert losses[-1] < losses[0]


# ---------------------------------------------------------------------------
# feed validation (satellite: clear mismatch errors)
# ---------------------------------------------------------------------------


def test_feeder_shape_mismatch_names_variable():
    main = Program()
    with program_guard(main, Program()):
        img = fluid.data("img", shape=[-1, 2, 3])
        feeder = fluid.DataFeeder([img])
    with pytest.raises(ValueError) as ei:
        feeder.feed([(np.ones(5, np.float32),)])
    msg = str(ei.value)
    assert "img" in msg and "6" in msg and "5" in msg


def test_feeder_dtype_unconvertible_names_variable():
    main = Program()
    with program_guard(main, Program()):
        v = fluid.data("vec", shape=[-1, 2])
        feeder = fluid.DataFeeder([v])
    with pytest.raises(ValueError, match="vec"):
        feeder.feed([(np.array(["a", "b"]),)])


def test_feeder_ragged_samples_name_variable():
    main = Program()
    with program_guard(main, Program()):
        seq = fluid.data("seq", shape=[-1, -1], dtype="int64")
        feeder = fluid.DataFeeder([seq])
    with pytest.raises(ValueError, match="seq"):
        feeder.feed([([1, 2, 3],), ([1],)])


def test_batch_generator_mismatch_raises_by_name():
    main = Program()
    with program_guard(main, Program()):
        x = fluid.data("x", shape=[-1, 4])
        loader = fluid.DataLoader.from_generator(feed_list=[x], capacity=2)

    def bad_shape():
        yield {"x": np.zeros((2, 5), np.float32)}

    loader.set_batch_generator(bad_shape)
    with pytest.raises(ValueError, match="'x'.*shape mismatch"):
        list(loader)

    def bad_dtype():
        yield {"x": np.zeros((2, 4), np.int64)}

    loader.set_batch_generator(bad_dtype)
    with pytest.raises(ValueError, match="'x'.*dtype mismatch"):
        list(loader)

    def missing():
        yield {"not_x": np.zeros((2, 4), np.float32)}

    loader.set_batch_generator(missing)
    with pytest.raises(Exception, match="missing feed variable"):
        list(loader)


def test_feeder_float_to_int_truncation_raises():
    main = Program()
    with program_guard(main, Program()):
        c = fluid.data("cnt", shape=[-1, 2], dtype="int64")
        feeder = fluid.DataFeeder([c])
    with pytest.raises(ValueError, match="'cnt'.*truncate"):
        feeder.feed([(np.array([1.7, 2.9]),)])
    # int -> float per-sample feeds stay lenient (python scalars/lists)
    with program_guard(main, Program()):
        f = fluid.data("feat", shape=[-1, 2])
        feeder2 = fluid.DataFeeder([f])
    assert feeder2.feed([([1, 2],)])["feat"].dtype == np.float32


def test_batch_generator_preserves_extra_keys():
    """Auxiliary feeds beyond the declared feed_list pass through the
    validator untouched (regression: they used to be dropped)."""
    main = Program()
    with program_guard(main, Program()):
        x = fluid.data("x", shape=[-1, 4])
        loader = fluid.DataLoader.from_generator(feed_list=[x], capacity=2)
    loader.set_batch_generator(
        lambda: iter([{"x": np.zeros((2, 4), np.float32),
                       "aux": np.ones(2, np.float32)}]))
    (batch,) = list(loader)
    assert "aux" in batch and "x" in batch


def test_mix_seed_injective_across_epoch_idx():
    from paddle_tpu.dataio.source import mix_seed

    # a huge sample index must never alias the next epoch's stream
    assert mix_seed(7, 0, 1_000_003) != mix_seed(7, 1, 0)
    assert mix_seed(7, 0, 2 ** 40) != mix_seed(7, 1, 0)
    assert mix_seed(7, 1, 5) == mix_seed(7, 1, 5)


def test_batch_generator_safe_cast_still_silent():
    main = Program()
    with program_guard(main, Program()):
        x = fluid.data("x", shape=[-1, 4])
        loader = fluid.DataLoader.from_generator(feed_list=[x], capacity=2)

    def f64():
        yield {"x": np.zeros((2, 4), np.float64)}

    loader.set_batch_generator(f64)
    (batch,) = list(loader)
    assert np.asarray(batch["x"]).dtype == np.float32


# ---------------------------------------------------------------------------
# decorator.shuffle determinism (satellite)
# ---------------------------------------------------------------------------


def test_shuffle_seeded_is_deterministic_and_local():
    import random as _random

    r = dec.shuffle(lambda: iter(range(30)), buf_size=50, seed=42)
    first, second = list(r()), list(r())
    assert first == second  # replayable epoch after epoch
    assert sorted(first) == list(range(30))
    assert first != list(range(30))
    before = _random.getstate()
    list(r())
    assert _random.getstate() == before  # module-global RNG untouched
    # unseeded keeps legacy behavior (still a full permutation)
    assert sorted(dec.shuffle(lambda: iter(range(30)), 50)()) == \
        list(range(30))


# ---------------------------------------------------------------------------
# dataset integration
# ---------------------------------------------------------------------------


def test_dataset_num_workers_parity(tmp_path, rng):
    lines = []
    for i in range(40):
        n = rng.randint(1, 6)
        vals = " ".join(str(rng.randint(0, 50)) for _ in range(n))
        lines.append(f"1 {rng.rand():.4f} {n} {vals}")
    p = tmp_path / "d.txt"
    p.write_text("\n".join(lines) + "\n")

    def batches(workers):
        from paddle_tpu.dataset import DatasetFactory

        ds = DatasetFactory().create_dataset("InMemoryDataset")
        main = Program()
        with program_guard(main, Program()):
            w = fluid.data("w", shape=[-1, 1])
            s = fluid.data("s", shape=[-1, -1], dtype="int64")
        ds.set_use_var([w, s])
        ds.set_batch_size(8)
        ds.set_num_workers(workers)
        ds.set_filelist([str(p)])
        ds.load_into_memory()
        return list(ds._iter_batches())

    ref, par = batches(0), batches(3)
    assert len(ref) == len(par)
    for a, b in zip(ref, par):
        assert sorted(a) == sorted(b)
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])


# ---------------------------------------------------------------------------
# crash-resume determinism (acceptance a): subprocess kill + resume
# ---------------------------------------------------------------------------


def _run_worker(tmp_path, tag, kill_at=-1, timeout=180):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PADDLE_TPU_FAULTS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests",
                                      "dataio_resume_worker.py"),
         "--ckdir", str(tmp_path / "ck"), "--log", str(tmp_path / "log"),
         "--tag", tag, "--kill-at-step", str(kill_at)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    return proc


def _parse_log(path):
    rows = []
    with open(path) as f:
        for line in f:
            tag, idx, digest, loss = line.split()
            rows.append((tag, int(idx), digest, float(loss)))
    return rows


def test_crash_resume_stream_bit_identical(tmp_path):
    """Kill training mid-epoch (SIGKILL after step 4, last durable
    checkpoint at step 2), resume via incubate.checkpoint.resume():
    the combined stream is bit-identical to an uninterrupted run —
    no dropped batches, no duplicates beyond the expected replay of the
    two post-checkpoint steps, and the loss curve continues exactly."""
    ref_dir = tmp_path / "ref"
    ref_dir.mkdir()
    ref = _run_worker(ref_dir, "ref")
    assert ref.returncode == 0, ref.stdout[-2000:] + ref.stderr[-2000:]
    ref_rows = _parse_log(ref_dir / "log")
    n_batches = len(ref_rows)
    assert n_batches == 16  # 2 epochs x 8 batches

    crash_dir = tmp_path / "crash"
    crash_dir.mkdir()
    crashed = _run_worker(crash_dir, "runA", kill_at=4)
    assert crashed.returncode == -signal.SIGKILL
    resumed = _run_worker(crash_dir, "runB")
    assert resumed.returncode == 0, \
        resumed.stdout[-2000:] + resumed.stderr[-2000:]

    rows = _parse_log(crash_dir / "log")
    run_a = [r for r in rows if r[0] == "runA"]
    run_b = [r for r in rows if r[0] == "runB"]
    # runA logged steps 0..4 then died; checkpoint interval 3 => last
    # durable save at step 2; runB resumes at batch 3 (replays 3, 4)
    assert [r[1] for r in run_a] == [0, 1, 2, 3, 4]
    assert [r[1] for r in run_b] == list(range(3, n_batches))

    # combined stream (last occurrence per index) == reference, bit-equal
    combined = {}
    for tag, idx, digest, loss in rows:
        combined[idx] = (digest, loss)
    assert sorted(combined) == list(range(n_batches))
    for _, idx, digest, loss in ref_rows:
        got_digest, got_loss = combined[idx]
        assert got_digest == digest, f"batch {idx} differs after resume"
        np.testing.assert_allclose(got_loss, loss, rtol=1e-6, atol=1e-9)
    # the replayed overlap is ALSO bit-identical (same data, same params)
    overlap_a = {r[1]: r[2] for r in run_a if r[1] in (3, 4)}
    overlap_b = {r[1]: r[2] for r in run_b if r[1] in (3, 4)}
    assert overlap_a == overlap_b


# ---------------------------------------------------------------------------
# elastic crash-resume: kill mid-epoch, resume at HALF the world size
# ---------------------------------------------------------------------------


def _spawn_elastic_worker(tmp_path, tag, rank, world, num_workers,
                          kill_at=-1, max_steps=-1, resume_step=-1):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PADDLE_TPU_FAULTS", None)
    return subprocess.Popen(
        [sys.executable,
         os.path.join(REPO, "tests", "dataio_elastic_worker.py"),
         "--ckdir", str(tmp_path / "ck"), "--log",
         str(tmp_path / f"log_{tag}_r{rank}"), "--tag", tag,
         "--rank", str(rank), "--world", str(world),
         "--num-workers", str(num_workers),
         "--kill-at-step", str(kill_at), "--max-steps", str(max_steps),
         "--resume-step", str(resume_step)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True,
    )


def _elastic_rows(tmp_path):
    rows = []
    for name in os.listdir(tmp_path):
        if name.startswith("log_"):
            with open(tmp_path / name) as f:
                rows.extend(json.loads(l) for l in f)
    return rows


@pytest.fixture(scope="module")
def elastic_reference(tmp_path_factory):
    """(epoch, position) -> sample digest from a clean WORLD-1 run of
    the same worker: with no wrap-padding in this geometry, position p
    always maps to epoch_order[p], so any elastic schedule must
    conserve exactly this stream."""
    d = tmp_path_factory.mktemp("elastic_ref")
    proc = _spawn_elastic_worker(d, "ref", rank=0, world=1, num_workers=0)
    out, err = proc.communicate(timeout=300)
    assert proc.returncode == 0, out[-2000:] + err[-2000:]
    ref = {}
    for r in _elastic_rows(d):
        for p, dig in zip(r["positions"], r["digests"]):
            ref[(r["epoch"], p)] = dig
    return ref


@pytest.mark.parametrize("num_workers", [0, 2])
def test_elastic_resume_4_to_2_exactly_once(tmp_path, num_workers,
                                            elastic_reference):
    """Kill one of four ranks mid-epoch, resume the stream at world
    size 2 from the pinned sync checkpoint: the committed global stream
    conserves the world-1 digest per position and consumes every sample
    exactly once — for the synchronous pipeline AND the threaded pool
    (the stream is a pure function of position, never of workers)."""
    # phase A: world 4; rank 3 dies at step 4 (last durable save: 3),
    # survivors run on to step 5 before the "supervisor" stops them
    procs = [
        _spawn_elastic_worker(tmp_path, "runA", rank=r, world=4,
                              num_workers=num_workers,
                              kill_at=(4 if r == 3 else -1), max_steps=6)
        for r in range(4)
    ]
    outs = [p.communicate(timeout=300) for p in procs]
    for r, (p, (out, err)) in enumerate(zip(procs, outs)):
        if r == 3:
            assert p.returncode == -signal.SIGKILL, (r, out, err)
        else:
            assert p.returncode == 0, (r, out[-2000:], err[-2000:])

    # phase B: world 2 resumes pinned at the sync step every rank holds
    sync = 3
    procs = [
        _spawn_elastic_worker(tmp_path, "runB", rank=r, world=2,
                              num_workers=num_workers, resume_step=sync)
        for r in range(2)
    ]
    outs = [p.communicate(timeout=300) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, out[-2000:] + err[-2000:]

    rows = _elastic_rows(tmp_path)
    committed = [r for r in rows
                 if (r["tag"] == "runA" and r["step"] <= sync)
                 or r["tag"] == "runB"]
    # phase A DID log uncommitted work past the sync step (the crash
    # and the early stop) — reconstruction must drop it
    assert any(r["tag"] == "runA" and r["step"] > sync for r in rows)

    per_epoch = {}
    for r in committed:
        for p, dig in zip(r["positions"], r["digests"]):
            per_epoch.setdefault(r["epoch"], []).append((p, dig))
    assert sorted(per_epoch) == [0, 1]
    for ep, pairs in per_epoch.items():
        poss = sorted(p for p, _ in pairs)
        # exactly-once: zero gaps, zero duplicates, full epoch covered
        assert poss == list(range(96)), (
            f"epoch {ep}: lost/duplicated positions across the resize")
        # digest conservation: every position's bytes == world-1 stream
        for p, dig in pairs:
            assert elastic_reference[(ep, p)] == dig, (ep, p)


# ---------------------------------------------------------------------------
# bench CLI smoke (tier-1 wiring, like trace_view)
# ---------------------------------------------------------------------------


def test_bench_input_smoke_cli(tmp_path):
    """tools/bench_input.py --smoke: >= 2x samples/s at num_workers=4
    over the single-thread DataLoader on CPU-bound preprocessing,
    identical batch streams, and dataio:: spans + queue gauges in the
    captured Chrome trace / registry."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = str(tmp_path / "input.trace.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_input.py"),
         "--smoke", "--trace-out", out],
        capture_output=True, text=True, timeout=560, env=env,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "BENCH_INPUT_SMOKE_OK" in proc.stdout
    with open(out) as f:
        doc = json.load(f)
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert "dataio::transform" in names
    assert "dataio::device_put" in names


# ---------------------------------------------------------------------------
# sparse CTR batch assembly (PR 8: dataio/sparse.py)
# ---------------------------------------------------------------------------


def test_sparse_batch_transform_padding_and_weights():
    from paddle_tpu.dataio import make_sparse_batch_transform, pad_slot

    ids, w = pad_slot([7, 9], 4)
    assert ids.tolist() == [7, 9, 7, 7]      # pad repeats the first id
    assert w.tolist() == [1.0, 1.0, 0.0, 0.0]
    ids, w = pad_slot([], 3)
    assert ids.tolist() == [0, 0, 0] and w.tolist() == [0.0, 0.0, 0.0]
    ids, w = pad_slot([1, 2, 3, 4, 5], 3)    # truncation
    assert ids.tolist() == [1, 2, 3] and w.tolist() == [1.0, 1.0, 1.0]

    tf = make_sparse_batch_transform(["a", "b"], 3, dense=["dx"],
                                     label="click")
    out = tf({"slots": {"a": [5], "b": [1, 2, 3, 4]},
              "dx": [0.5, 0.25], "click": 1.0})
    a_ids, a_w, b_ids, b_w, dx, click = out
    assert a_ids.tolist() == [5, 5, 5] and a_w.tolist() == [1.0, 0.0, 0.0]
    assert b_ids.tolist() == [1, 2, 3] and b_w.tolist() == [1.0] * 3
    assert dx.dtype == np.float32 and click.tolist() == [1.0]
    # a sample missing a slot gets the empty encoding
    out2 = tf({"slots": {"a": [5]}, "dx": [0, 0], "click": 0.0})
    assert out2[3].tolist() == [0.0, 0.0, 0.0]


def test_sparse_batch_transform_on_worker_pool_deterministic():
    """The transform composed with the ordered pool: same batch stream
    for 0 and 3 workers (the dataio ordering contract), padding applied
    per sample on the pool."""
    from paddle_tpu.dataio import make_sparse_batch_transform, parallel_map_ordered

    tf = make_sparse_batch_transform(["s0"], 4)

    def records():
        rng = np.random.RandomState(3)
        for i in range(40):
            n = rng.randint(1, 5)
            yield {"slots": {"s0": rng.randint(0, 100, n).tolist()},
                   "click": float(i % 2)}

    def stream(workers):
        out = []
        for val in parallel_map_ordered(
            records(), tf, workers, name=f"sparse-{workers}"
        ):
            out.append(np.concatenate([v.reshape(-1).astype("f")
                                       for v in val]))
        return np.stack(out)

    np.testing.assert_array_equal(stream(0), stream(3))
