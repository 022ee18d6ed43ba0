"""Online serving subsystem (paddle_tpu/serving): admission queue,
bucketed dynamic batcher, SLO scheduling over the AOT predictor.

The acceptance test drives 64+ concurrent mixed-shape/mixed-priority
requests through ServingEngine on CPU and checks the subsystem's four
contracts at once: zero retraces after warmup, real batching (occupancy
above one row per batch), parity with single-request Predictor.run
(rtol/atol 1e-6: two differently shaped executables), and structured deadline/backpressure rejections with
accurate counters.
"""

import json
import os
import subprocess
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core.ir import Program, program_guard

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# fixtures: tiny per-position models (padding-invariant heads, so padded
# batches must match unpadded single runs)
# ---------------------------------------------------------------------------


def _save_fixed_model(tmpdir, rng, feat=8):
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", [-1, feat])
        h = fluid.layers.fc(x, 16, act="relu")
        pred = fluid.layers.fc(h, 4)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        model_dir = os.path.join(str(tmpdir), "fixed")
        fluid.io.save_inference_model(model_dir, ["x"], [pred], exe,
                                      main_program=main)
    return model_dir


def _save_seq_model(tmpdir, rng, feat=4):
    """Variable-length axis: x is [-1, -1, feat], per-token fc head."""
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", [-1, -1, feat])
        h = fluid.layers.fc(x, 8, act="relu", num_flatten_dims=2)
        pred = fluid.layers.fc(h, 3, num_flatten_dims=2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        model_dir = os.path.join(str(tmpdir), "seq")
        fluid.io.save_inference_model(model_dir, ["x"], [pred], exe,
                                      main_program=main)
    return model_dir


def _cpu_config(model_dir):
    from paddle_tpu import inference

    config = inference.Config(model_dir)
    config.disable_tpu()
    return config


# ---------------------------------------------------------------------------
# bucket lattice: deterministic + total bucket selection
# ---------------------------------------------------------------------------


def test_lattice_bucket_selection_total_and_deterministic():
    from paddle_tpu.serving import BucketLattice, RejectedError

    lat = BucketLattice(batch_sizes=(1, 2, 4, 8), seq_lens=(4, 8, 16))
    # every admissible row count maps to the smallest bucket >= rows
    for rows in range(1, 9):
        b = lat.bucket_rows(rows)
        assert b >= rows
        assert b == min(x for x in lat.batch_sizes if x >= rows)
        assert lat.bucket_rows(rows) == b  # deterministic
    for ln in range(1, 17):
        s = lat.bucket_len(ln)
        assert s == min(x for x in lat.seq_lens if x >= ln)
    # beyond the lattice: structured rejection, not a new compile bucket
    with pytest.raises(RejectedError):
        lat.bucket_rows(9)
    with pytest.raises(RejectedError):
        lat.bucket_len(17)


def test_lattice_classify_group_keys():
    from paddle_tpu.serving import BucketLattice, RejectedError

    lat = BucketLattice(batch_sizes=(1, 2, 4), seq_lens=(4, 8))
    a = {"x": np.zeros((2, 3, 5), "float32")}
    b = {"x": np.zeros((1, 7, 5), "float32")}
    ra, la, ka = lat.classify(a)
    rb, lb, kb = lat.classify(b)
    assert (ra, la) == (2, 3) and (rb, lb) == (1, 7)
    assert ka == kb  # different lengths batch together (padded axis masked)
    # dtype is part of the key: no silent cross-dtype batches
    _, _, kc = lat.classify({"x": np.zeros((1, 3, 5), "int64")})
    assert kc != ka
    # trailing non-padded dims are part of the key
    _, _, kd = lat.classify({"x": np.zeros((1, 3, 6), "float32")})
    assert kd != ka
    # inconsistent row counts across inputs: rejected
    with pytest.raises(RejectedError):
        lat.classify({"x": np.zeros((2, 3), "float32"),
                      "y": np.zeros((3, 1), "float32")})


def test_batcher_padding_masked_out_of_outputs():
    """assemble() zero-fills dummy rows and the padded axis; scatter()
    slices both back out, so callers never see padding."""
    from paddle_tpu.serving import BucketLattice, DynamicBatcher
    from paddle_tpu.serving.batcher import BatchPlan
    from paddle_tpu.serving.request import Request

    lat = BucketLattice(batch_sizes=(1, 2, 4), seq_lens=(4, 8))
    batcher = DynamicBatcher(lat)
    mk = lambda rid, rows, ln: Request(
        rid, {"x": np.full((rows, ln, 2), rid, "float32")}, rows, 1, None,
        ("key",), ln,
    )
    r1, r2 = mk(1.0, 2, 3), mk(2.0, 1, 4)
    plan = BatchPlan([r1, r2], bucket_rows=4, bucket_len=4)
    feeds = batcher.assemble(plan)
    assert feeds["x"].shape == (4, 4, 2)
    assert (feeds["x"][0:2, 0:3] == 1.0).all()
    assert (feeds["x"][0:2, 3:] == 0.0).all()  # r1's padded positions
    assert (feeds["x"][2:3] == 2.0).all()
    assert (feeds["x"][3:] == 0.0).all()  # dummy row

    # identity "model": outputs echo the padded batch
    outs = batcher.scatter(plan, {"out": feeds["x"] * 10.0})
    assert outs[0]["out"].shape == (2, 3, 2)  # r1: rows AND length sliced
    assert (outs[0]["out"] == 10.0).all()
    assert outs[1]["out"].shape == (1, 4, 2)
    assert (outs[1]["out"] == 20.0).all()


def test_lattice_classify_respects_declared_fixed_dims():
    """A feed whose pad_axis dim is declared fixed must keep its trailing
    dims in the group key and never contribute to var_len — padding it
    to a length bucket would produce a never-warmed shape the program
    rejects."""
    from paddle_tpu.serving import BucketLattice

    lat = BucketLattice(batch_sizes=(1, 2, 4), seq_lens=(4, 8))
    inputs = {"ids": np.zeros((2, 6), "int64"),
              "dense": np.zeros((2, 6), "float32")}
    # without specs both rank-2 inputs look variable
    _, vl_all, key_all = lat.classify(inputs)
    assert vl_all == 6
    assert all(t == (None,) for _, _, t in key_all)
    # with var_feeds only ids is variable; dense keeps its fixed 6
    _, vl, key = lat.classify(inputs, var_feeds={"ids"})
    assert vl == 6
    key_by_name = {n: t for n, _, t in key}
    assert key_by_name["ids"] == (None,)
    assert key_by_name["dense"] == (6,)


def test_engine_mixed_fixed_and_variable_feeds(tmp_path, rng):
    """Mixed-feed model (variable-length ids + fixed-width dense): the
    batcher pads ONLY the declared-variable axis, every served shape
    stays on the warmed lattice (zero retrace), outputs match the
    single-request path."""
    from paddle_tpu import inference
    from paddle_tpu.serving import ServingEngine

    main, startup = Program(), Program()
    with program_guard(main, startup):
        ids = fluid.data("ids", [-1, -1], dtype="int64")
        dense = fluid.data("dense", [-1, 6])
        emb = fluid.layers.embedding(ids, size=(30, 8))
        d = fluid.layers.unsqueeze(fluid.layers.fc(dense, 8), [1])
        h = fluid.layers.elementwise_add(emb, d)  # [B,S,8] + [B,1,8]
        pred = fluid.layers.fc(h, 3, num_flatten_dims=2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        model_dir = os.path.join(str(tmp_path), "mixed")
        fluid.io.save_inference_model(model_dir, ["ids", "dense"], [pred],
                                      exe, main_program=main)
    config = _cpu_config(model_dir)
    config.set_serving_buckets([1, 2, 4], seq_lens=[4, 8])
    eng = ServingEngine(config, queue_depth=64, max_wait_ms=3.0)
    assert eng._batcher.var_feeds == {"ids"}
    eng.start()
    try:
        ref = inference.create_predictor(_cpu_config(model_dir))
        out_name = eng.predictor.get_output_names()[0]
        resps, refs = [], []
        for i in range(12):
            rows, ln = 1 + i % 2, 2 + i % 7
            req = {"ids": rng.randint(0, 30, (rows, ln)).astype("int64"),
                   "dense": rng.randn(rows, 6).astype("float32")}
            refs.append(ref.run([req["ids"], req["dense"]])[0])
            resps.append(eng.submit(req))
        # batched+padded vs single-request: two executables, a tolerance
        for r, expect in zip(resps, refs):
            np.testing.assert_allclose(r.result(timeout=30)[out_name],
                                       expect, rtol=1e-6, atol=1e-6)
    finally:
        eng.shutdown()
    st = eng.stats()
    assert st["cache_misses"] == 0, st  # dense was never padded off-lattice
    assert st["completed"] == 12


# ---------------------------------------------------------------------------
# queue: admission control
# ---------------------------------------------------------------------------


def test_queue_backpressure_and_priority_lanes():
    from paddle_tpu.serving import Priority, RejectedError, RequestQueue
    from paddle_tpu.serving.request import Request

    q = RequestQueue(max_depth=4)
    mk = lambda rid, prio, rows=1: Request(
        rid, {}, rows, prio, None, ("k",), 0
    )
    q.put(mk(1, Priority.LOW))
    q.put(mk(2, Priority.NORMAL))
    q.put(mk(3, Priority.HIGH))
    assert q.head().id == 3  # high lane drains first
    with pytest.raises(RejectedError) as ei:
        q.put(mk(4, Priority.NORMAL, rows=2))  # 3 + 2 > 4
    assert ei.value.code == "rejected"
    assert ei.value.retry_after_s >= 0.0
    assert ei.value.to_dict()["code"] == "rejected"
    # drain mode: closed queue rejects with retry_after 0 (don't retry)
    q.close()
    with pytest.raises(RejectedError):
        q.put(mk(5, Priority.HIGH))
    assert [r.id for r in q.iter_requests()] == [3, 2, 1]


def test_queue_deadline_expiry_before_dispatch():
    from paddle_tpu.serving import RequestQueue
    from paddle_tpu.serving.request import Request

    q = RequestQueue(max_depth=8)
    now = time.perf_counter()
    fresh = Request(1, {}, 1, 1, now + 60.0, ("k",), 0)
    stale = Request(2, {}, 1, 1, now - 0.001, ("k",), 0)
    q.put(fresh)
    q.put(stale)
    dead = q.expire()
    assert [r.id for r in dead] == [2]
    assert [r.id for r in q.iter_requests()] == [1]
    assert q.depth() == 1


# ---------------------------------------------------------------------------
# engine: warmup, admission validation, isolation, drain
# ---------------------------------------------------------------------------


def test_predictor_warmup_precompiles_all_buckets(tmp_path, rng):
    from paddle_tpu import inference

    model_dir = _save_seq_model(tmp_path, rng)
    config = _cpu_config(model_dir)
    config.set_serving_buckets([1, 2], seq_lens=[4, 8])
    pred = inference.create_predictor(config)
    compiled = pred.warmup()
    assert len(compiled) == 4  # full lattice: 2 batches x 2 lens
    assert len(pred._cache) == 4
    assert all(seconds > 0 for _, seconds in compiled)
    assert pred.cache_stats()["misses"] == 4
    # idempotent: a second warmup compiles nothing
    assert pred.warmup() == []
    # served shapes on the lattice never miss
    pred.run_batch({"x": rng.randn(2, 8, 4).astype("float32")})
    cs = pred.cache_stats()
    assert cs["misses"] == 4 and cs["hits"] == 1


def test_engine_admission_validation(tmp_path, rng):
    from paddle_tpu.serving import RejectedError, ServingEngine

    config = _cpu_config(_save_fixed_model(tmp_path, rng))
    config.set_serving_buckets([1, 2, 4])
    eng = ServingEngine(config, queue_depth=8)
    # never started: validation happens at the door
    cases = [
        ({"wrong": np.zeros((1, 8), "float32")}, "names"),
        ({"x": np.zeros((1, 8), "float64")}, "dtype"),
        ({"x": np.zeros((1, 9), "float32")}, "trailing dim"),
        ({"x": np.zeros((1, 2, 8), "float32")}, "rank"),
        ({"x": np.zeros((5, 8), "float32")}, "rows beyond lattice"),
    ]
    for inputs, why in cases:
        with pytest.raises(RejectedError):
            eng.submit(inputs)
    assert eng.metrics.count("rejected_invalid") == len(cases)
    assert eng.metrics.count("rejected") == len(cases)
    assert eng.metrics.count("admitted") == 0


def test_engine_queue_full_backpressure(tmp_path, rng):
    from paddle_tpu.serving import RejectedError, ServingEngine

    config = _cpu_config(_save_fixed_model(tmp_path, rng))
    config.set_serving_buckets([1, 2])
    eng = ServingEngine(config, queue_depth=3)
    # workers not started: the queue fills and admission must push back
    for _ in range(3):
        eng.submit({"x": np.zeros((1, 8), "float32")})
    with pytest.raises(RejectedError) as ei:
        eng.submit({"x": np.zeros((1, 8), "float32")})
    assert ei.value.code == "rejected"
    assert ei.value.retry_after_s > 0.0
    assert eng.metrics.count("rejected_queue_full") == 1
    assert eng.metrics.count("admitted") == 3


def test_engine_poison_request_isolated(tmp_path, rng):
    """A request that faults its batch is re-run alone and fails alone;
    batchmates are served from the isolation re-run."""
    from paddle_tpu.serving import RequestError, ServingEngine

    config = _cpu_config(_save_fixed_model(tmp_path, rng))
    config.set_serving_buckets([1, 2, 4])
    eng = ServingEngine(config, num_replicas=1, queue_depth=32,
                        max_wait_ms=20.0)
    POISON = 6.66e6

    real_run_batch = type(eng.predictor).run_batch

    def poisoned_run_batch(self, feeds):
        # any batch containing the poison rows faults — the stand-in for
        # a runtime fault (bad buffer, device error); it faults the
        # isolation re-run too, so only the poison request may fail
        if (feeds["x"] == POISON).any():
            raise RuntimeError("device fault in batch")
        return real_run_batch(self, feeds)

    eng.predictor.run_batch = poisoned_run_batch.__get__(eng.predictor)
    eng.start()
    try:
        good_in = [rng.randn(1, 8).astype("float32") for _ in range(3)]
        bad_in = np.full((1, 8), POISON, "float32")
        # reference BEFORE submitting (single-request path, same weights)
        from paddle_tpu import inference

        ref_pred = inference.create_predictor(_cpu_config(
            os.path.join(str(tmp_path), "fixed")))
        refs = [ref_pred.run([g])[0] for g in good_in]

        resps = [eng.submit({"x": g}) for g in good_in]
        bad = eng.submit({"x": bad_in})
        out_name = eng.predictor.get_output_names()[0]
        for r, ref in zip(resps, refs):
            np.testing.assert_array_equal(r.result(timeout=30)[out_name], ref)
        with pytest.raises(RequestError) as ei:
            bad.result(timeout=30)
        assert ei.value.code == "request_failed"
        assert eng.metrics.count("failed") == 1
        assert eng.metrics.count("completed") == 3
    finally:
        eng.shutdown()


def test_engine_deadline_missed_rejected_before_dispatch(tmp_path, rng):
    from paddle_tpu.serving import DeadlineExceededError, ServingEngine

    config = _cpu_config(_save_fixed_model(tmp_path, rng))
    config.set_serving_buckets([1, 2])
    eng = ServingEngine(config, queue_depth=8, max_wait_ms=30.0)
    # submit EXPIRED requests before starting workers: the engine must
    # reject them at expiry scan, not burn device time
    dead = [eng.submit({"x": np.zeros((1, 8), "float32")}, deadline_ms=0)
            for _ in range(2)]
    live = eng.submit({"x": np.zeros((1, 8), "float32")})
    time.sleep(0.002)
    eng.start()
    try:
        assert live.result(timeout=30) is not None
        for d in dead:
            with pytest.raises(DeadlineExceededError) as ei:
                d.result(timeout=30)
            assert ei.value.code == "deadline"
        assert eng.metrics.count("deadline_missed") == 2
        assert eng.metrics.count("completed") == 1
    finally:
        eng.shutdown()


def test_engine_graceful_drain(tmp_path, rng):
    from paddle_tpu.serving import RejectedError, ServingEngine

    config = _cpu_config(_save_fixed_model(tmp_path, rng))
    config.set_serving_buckets([1, 2, 4])
    eng = ServingEngine(config, queue_depth=64, max_wait_ms=2.0)
    eng.start()
    resps = [eng.submit({"x": np.zeros((1, 8), "float32")})
             for _ in range(12)]
    eng.shutdown()  # drain: every admitted request still gets an answer
    assert all(r.done() for r in resps)
    assert all(r.error() is None for r in resps)
    with pytest.raises(RejectedError):
        eng.submit({"x": np.zeros((1, 8), "float32")})
    assert eng.metrics.count("rejected_shutdown") == 1


# ---------------------------------------------------------------------------
# acceptance: 64+ concurrent mixed requests, zero retrace, parity
# ---------------------------------------------------------------------------


def test_serving_engine_acceptance_64_concurrent(tmp_path, rng):
    from paddle_tpu import inference, profiler
    from paddle_tpu.serving import (
        BucketLattice,
        DeadlineExceededError,
        RejectedError,
        ServingEngine,
    )

    model_dir = _save_seq_model(tmp_path, rng)
    config = _cpu_config(model_dir)
    lattice = BucketLattice(batch_sizes=(1, 2, 4, 8), seq_lens=(4, 8))
    config.set_serving_buckets(lattice.batch_sizes, lattice.seq_lens)
    eng = ServingEngine(config, lattice=lattice, num_replicas=2,
                        queue_depth=256, max_wait_ms=4.0)
    profiler.reset_profiler()
    profiler.start_profiler()
    eng.start()

    # single-request references through a SEPARATE predictor on the same
    # saved model + weights (shared scope would be fine too; separate
    # proves the serving path reproduces the plain inference path)
    ref_pred = inference.create_predictor(_cpu_config(model_dir))
    out_name = eng.predictor.get_output_names()[0]

    n_requests = 72
    payloads = []
    for i in range(n_requests):
        rows = int(rng.randint(1, 4))  # 1..3 rows
        ln = int(rng.randint(2, 9))  # 2..8 tokens
        payloads.append(rng.randn(rows, ln, 4).astype("float32"))
    refs = [ref_pred.run([p])[0] for p in payloads]

    resps = [None] * n_requests
    submit_errors = []
    lock = threading.Lock()

    def submitter(start, step):
        for i in range(start, n_requests, step):
            try:
                r = eng.submit({"x": payloads[i]}, priority=i % 3)
            except Exception as e:  # pragma: no cover - must not happen
                with lock:
                    submit_errors.append((i, e))
                continue
            resps[i] = r

    threads = [threading.Thread(target=submitter, args=(t, 8))
               for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not submit_errors, submit_errors

    # parity: padded+batched serving == single-request run, within the
    # tolerance two differently shaped executables are owed
    for i, (r, ref) in enumerate(zip(resps, refs)):
        got = r.result(timeout=60)[out_name]
        np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6,
                                   err_msg=f"request {i}")

    # SLO/backpressure rejections are structured and counted accurately:
    # deadline-expired (submitted pre-dispatch with an already-dead SLO)
    dead = eng.submit({"x": payloads[0]}, deadline_ms=0)
    with pytest.raises(DeadlineExceededError):
        dead.result(timeout=30)
    # backpressure after drain starts
    eng.shutdown()
    with pytest.raises(RejectedError) as ei:
        eng.submit({"x": payloads[0]})
    assert ei.value.retry_after_s == 0.0  # draining: don't retry

    stats = eng.stats()
    profiler.stop_profiler()
    # zero retraces after warmup: every served batch hit the AOT cache
    assert stats["cache_misses"] == 0, stats
    assert stats["cache_hit_rate"] == 1.0, stats
    # real batching happened (mean rows per dispatched batch > 1)
    assert stats["avg_batch_rows"] > 1.0, stats
    assert 0.0 < stats["avg_batch_occupancy"] <= 1.0
    # accurate counters
    assert stats["completed"] == n_requests
    assert stats["admitted"] == n_requests + 1  # + the deadline one
    assert stats["deadline_missed"] == 1
    assert stats["rejected"] == 1 and stats["rejected_shutdown"] == 1
    assert stats["submitted"] == n_requests + 2
    assert stats["batches"] < n_requests  # coalescing, not 1:1 dispatch
    assert stats["latency_p99_s"] >= stats["latency_p50_s"] >= 0.0
    # serving events + counters surfaced through the profiler machinery
    counters = profiler.get_counters()
    assert counters.get("serving.batches") == stats["batches"]
    assert counters.get("serving.admitted") == stats["admitted"]


# ---------------------------------------------------------------------------
# C ABI bridge
# ---------------------------------------------------------------------------


def test_serving_capi_bridge_submit_poll(tmp_path, rng):
    """The flat bridge surface the C library drives: engine handle,
    memoryview submits, poll-until-done, stats JSON, shutdown."""
    from paddle_tpu.inference import capi_bridge as bridge

    model_dir = _save_fixed_model(tmp_path, rng)
    handle = bridge.new_serving_engine(
        model_dir, "", "", use_tpu=0, device_id=0, max_batch=4, max_seq=0,
        queue_depth=32, max_wait_ms=3, num_replicas=1,
    )
    try:
        x = rng.randn(2, 8).astype("float32")
        ticket = bridge.serving_submit(
            handle, ["x"], [0], [(2, 8)], [memoryview(x.tobytes())],
            priority=1, deadline_ms=0,
        )
        assert ticket >= 1
        out_name = handle.engine.predictor.get_output_names()[0]
        deadline = time.time() + 30
        while True:
            polled = bridge.serving_poll(handle, ticket, out_name)
            if polled is not None:
                break
            assert time.time() < deadline
            time.sleep(0.001)
        dtype_idx, shape, raw = polled
        assert dtype_idx == 0 and shape == (2, 4)
        got = np.frombuffer(raw, "float32").reshape(shape)
        from paddle_tpu import inference

        ref = inference.create_predictor(_cpu_config(model_dir)).run([x])[0]
        np.testing.assert_array_equal(got, ref)
        bridge.serving_release(handle, ticket)
        with pytest.raises(KeyError):
            bridge.serving_poll(handle, ticket, out_name)
        stats = json.loads(bridge.serving_stats_json(handle))
        assert stats["completed"] == 1 and stats["cache_misses"] == 0
    finally:
        bridge.serving_shutdown(handle)


@pytest.fixture(scope="module")
def capi_lib():
    from paddle_tpu.inference.capi import build_capi

    try:
        return build_capi()
    except Exception as e:  # no toolchain/libpython — skip, don't fail
        pytest.skip(f"cannot build libcapi: {e}")


def test_serving_capi_from_c_host(tmp_path, rng, capi_lib):
    """Out-of-process C host drives PD_NewServingEngine / PD_ServingSubmit
    / PD_ServingPoll / PD_ServingStats / PD_DeleteServingEngine and
    compares every served answer against PD_PredictorRun (rtol/atol 1e-6)."""
    model_dir = _save_fixed_model(tmp_path, rng)
    capi_dir = os.path.dirname(capi_lib)
    exe_path = os.path.join(str(tmp_path), "capi_serving_smoke")
    build = subprocess.run(
        ["g++", os.path.join(REPO, "tests", "capi_serving_smoke.c"),
         f"-I{capi_dir}", f"-L{capi_dir}", "-lcapi",
         f"-Wl,-rpath,{capi_dir}", "-o", exe_path],
        capture_output=True, text=True, timeout=120,
    )
    assert build.returncode == 0, build.stderr
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [exe_path, model_dir, "12", "8"],
        capture_output=True, text=True, timeout=560, env=env,
    )
    assert proc.returncode == 0, f"stdout={proc.stdout}\nstderr={proc.stderr}"
    assert "matched=12/12" in proc.stdout
    assert "SERVING_CAPI_OK" in proc.stdout
    stats_line = [l for l in proc.stdout.splitlines()
                  if l.startswith("stats=")][0]
    stats = json.loads(stats_line[len("stats="):])
    assert stats["completed"] == 12
    assert stats["cache_misses"] == 0  # warmed lattice, zero retrace
