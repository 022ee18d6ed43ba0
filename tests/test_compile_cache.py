"""Unified lowering + content-addressed persistent compile cache.

The tentpole contract (ROADMAP open item 5 / ISSUE 6): one lowering
entrypoint for Executor / CompiledProgram / Predictor, a process-wide
memory tier shared by all of them, and an on-disk tier keyed by a
content-addressed program fingerprint so a SECOND PROCESS running the
same program compiles zero times — and a corrupt/truncated entry falls
back to a retrace silently with bit-identical results, never a crash or
a wrong answer.
"""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import compile_cache
from paddle_tpu.core.ir import Program, program_guard

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "compile_cache_worker.py")


def _run_worker(cache_dir=None, hidden=16):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["JAX_PLATFORMS"] = "cpu"
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    proc = subprocess.run(
        [sys.executable, WORKER, "--hidden", str(hidden)],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _entries(cache_dir):
    return sorted(
        f for f in os.listdir(cache_dir / "ptcc") if f.endswith(".ptcc")
    )


# ---------------------------------------------------------------------------
# cross-process reuse (the acceptance gate)
# ---------------------------------------------------------------------------


def test_cross_process_warm_start(tmp_path):
    """Second fresh process on the same program: ZERO traces, zero
    compile-histogram observations, and bit-identical losses — with and
    without the cache enabled."""
    cache = tmp_path / "cache"
    baseline = _run_worker(cache_dir=None)
    assert baseline["traces"] > 0  # startup + train step

    cold = _run_worker(cache_dir=cache)
    assert cold["traces"] == baseline["traces"]
    assert _entries(cache), "populate run wrote no cache entries"
    # placement: jax read JAX_COMPILATION_CACHE_DIR natively and nothing
    # re-pointed it — XLA's own tier sits in the directory itself, the
    # jax.export tier in ptcc/ under it, and an unplaced CPU process
    # (the baseline) persisted nowhere
    assert cold["jax_cache_dir"] == str(cache)
    assert baseline["jax_cache_dir"] is None
    assert any(f.endswith("-cache") for f in os.listdir(cache))
    assert sorted(os.listdir(tmp_path)) == ["cache"]
    # cache enabled vs disabled must not change a single bit
    assert cold["losses"] == baseline["losses"]

    warm = _run_worker(cache_dir=cache)
    assert warm["traces"] == 0, f"warm process retraced: {warm}"
    assert warm["compile_observations"] == 0
    assert warm["persistent_hits"] > 0
    assert warm["losses"] == baseline["losses"]


def test_poisoned_cache_entries_fall_back_to_retrace(tmp_path):
    """Flip bytes in one entry, truncate another: the run must silently
    retrace (correct, bit-identical losses), count the corruption, and
    quarantine the bad entries as *.corrupt."""
    cache = tmp_path / "cache"
    baseline = _run_worker(cache_dir=cache)
    entries = _entries(cache)
    assert len(entries) >= 2  # startup + main step

    # bit-rot in the payload of the first entry
    p0 = cache / "ptcc" / entries[0]
    raw = bytearray(p0.read_bytes())
    raw[-8] ^= 0xFF
    p0.write_bytes(bytes(raw))
    # torn write on the second
    p1 = cache / "ptcc" / entries[1]
    p1.write_bytes(p1.read_bytes()[: max(8, len(p1.read_bytes()) // 3)])

    poisoned = _run_worker(cache_dir=cache)
    assert poisoned["losses"] == baseline["losses"]
    assert poisoned["traces"] == baseline["traces"]  # full retrace
    assert poisoned["persistent_errors"] >= 2
    corrupt = [f for f in os.listdir(cache / "ptcc")
               if f.endswith(".corrupt")]
    assert len(corrupt) >= 2, "bad entries were not quarantined"

    # the retrace re-populated the cache: a fourth process is warm again
    warm = _run_worker(cache_dir=cache)
    assert warm["traces"] == 0
    assert warm["losses"] == baseline["losses"]


def test_garbage_file_in_cache_dir_is_ignored(tmp_path):
    cache = tmp_path / "cache"
    _run_worker(cache_dir=cache)
    for name in _entries(cache):
        (cache / "ptcc" / name).write_bytes(b"not a cache entry at all")
    out = _run_worker(cache_dir=cache)
    assert out["traces"] > 0  # fell back
    assert out["persistent_errors"] >= 1


# ---------------------------------------------------------------------------
# fingerprint semantics
# ---------------------------------------------------------------------------


def _tiny_program(hidden=4):
    # reset auto-naming so two builds of the same code are textually
    # identical — the position a fresh process is always in
    from paddle_tpu.utils import unique_name

    with unique_name.guard():
        main, startup = Program(), Program()
        with program_guard(main, startup):
            x = fluid.data("x", shape=[-1, 4])
            loss = fluid.layers.mean(fluid.layers.fc(x, size=hidden))
    return main


def test_fingerprint_stability_and_sensitivity():
    feed_sig = (("x", (2, 4), "float32"),)
    p1, p2 = _tiny_program(), _tiny_program()
    fp = compile_cache.program_fingerprint(p1, feed_sig, ["loss"])
    # identical CONTENT -> identical fingerprint, even for distinct objects
    assert fp == compile_cache.program_fingerprint(p2, feed_sig, ["loss"])
    # any input that can change the compiled artifact must change it
    assert fp != compile_cache.program_fingerprint(
        _tiny_program(hidden=8), feed_sig, ["loss"])
    assert fp != compile_cache.program_fingerprint(
        p1, (("x", (4, 4), "float32"),), ["loss"])
    assert fp != compile_cache.program_fingerprint(
        p1, feed_sig, ["loss", "other"])
    assert fp != compile_cache.program_fingerprint(
        p1, feed_sig, ["loss"], donate=False)
    assert fp != compile_cache.program_fingerprint(
        p1, feed_sig, ["loss"], extra=("mb", 4))
    assert fp != compile_cache.program_fingerprint(
        p1, feed_sig, ["loss"], scope_sig=(("w", (4, 4), "float32"),))


def test_fingerprint_covers_jax_version_and_backend(monkeypatch):
    """A jax upgrade or backend switch must invalidate persisted entries
    (stale modules fall back to retrace, never a wrong answer)."""
    import jax

    feed_sig = (("x", (2, 4), "float32"),)
    p = _tiny_program()
    fp = compile_cache.program_fingerprint(p, feed_sig, ["loss"])
    monkeypatch.setattr(jax, "__version__", "999.0.0")
    assert fp != compile_cache.program_fingerprint(p, feed_sig, ["loss"])


@pytest.mark.parametrize("bump, same", [(0, True), (1, False)],
                         ids=["unchanged", "bumped"])
def test_fingerprint_covers_lowering_version(monkeypatch, bump, same):
    """The Program's bytes do not say what an op lowering emits for them:
    a bumped ``lowering.LOWERING_VERSION`` misses every persisted entry,
    an unchanged one still hits."""
    from paddle_tpu.core import lowering

    feed_sig = (("x", (2, 4), "float32"),)
    p = _tiny_program()
    fp = compile_cache.program_fingerprint(p, feed_sig, ["loss"])
    monkeypatch.setattr(lowering, "LOWERING_VERSION",
                        lowering.LOWERING_VERSION + bump)
    again = compile_cache.program_fingerprint(p, feed_sig, ["loss"])
    assert (fp == again) is same


def test_flag_changes_miss_cleanly():
    from paddle_tpu.utils.flags import flags

    feed_sig = (("x", (2, 4), "float32"),)
    p = _tiny_program()
    fp = compile_cache.program_fingerprint(p, feed_sig, ["loss"])
    old = flags.rng_impl
    try:
        flags.rng_impl = "rbg"
        assert fp != compile_cache.program_fingerprint(p, feed_sig, ["loss"])
    finally:
        flags.rng_impl = old


# ---------------------------------------------------------------------------
# in-process sharing + single-flight
# ---------------------------------------------------------------------------


def test_memory_tier_shared_across_executors(rng):
    """Two Executor objects (fresh per-executor cheap caches) lowering the
    same program content share ONE trace through the process-wide tier."""
    from paddle_tpu.core.executor import _CACHE_MISSES

    compile_cache.clear_memory_cache()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", shape=[-1, 6])
        loss = fluid.layers.mean(fluid.layers.fc(x, size=3))
    feed = {"x": rng.rand(2, 6).astype("float32")}

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe1 = fluid.Executor(fluid.CPUPlace())
        exe1.run(startup)
        m0 = _CACHE_MISSES.value
        r1 = exe1.run(main, feed=feed, fetch_list=[loss])
        assert _CACHE_MISSES.value == m0 + 1
        exe2 = fluid.Executor(fluid.CPUPlace())
        r2 = exe2.run(main, feed=feed, fetch_list=[loss])
        # exe2 never traced: served from the shared memory tier
        assert _CACHE_MISSES.value == m0 + 1
        np.testing.assert_array_equal(np.asarray(r1[0]), np.asarray(r2[0]))


def test_single_flight_dedupes_concurrent_predictor_compiles(tmp_path, rng):
    """The documented lock-free race (N clones x same signature -> N
    duplicate compiles under replica warmup) is gone: concurrent requests
    for one signature share a single in-flight compile."""
    from paddle_tpu import inference
    from paddle_tpu.observability import metrics as obs_metrics

    compile_cache.clear_memory_cache()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", shape=[-1, 9])
        h = fluid.layers.fc(x, size=7, act="relu")
        pred = fluid.layers.fc(h, size=2)
    exe = fluid.Executor(fluid.CPUPlace())
    model_dir = str(tmp_path / "model")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ["x"], [pred], exe,
                                      main_program=main)

    config = inference.Config(model_dir)
    config.disable_tpu()
    predictor = inference.create_predictor(config)
    clones = [predictor.clone() for _ in range(7)]

    def compile_count():
        h = obs_metrics.registry().get("predictor_compile_seconds")
        return h.count if h is not None else 0

    before = compile_count()
    barrier = threading.Barrier(len(clones) + 1)
    errors = []
    outs = []

    def worker(p):
        try:
            barrier.wait(timeout=30)
            outs.append(p.run_batch({"x": np.ones((3, 9), "float32")}))
        except Exception as e:  # pragma: no cover - surfaced by assert
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(c,)) for c in clones]
    for t in threads:
        t.start()
    barrier.wait(timeout=30)
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    after = compile_count()
    assert after - before == 1, \
        f"expected exactly 1 compile for 7 concurrent requests, got " \
        f"{after - before}"
    # threads that reach the local-cache check after the leader stores
    # the bucket legitimately record hits, so misses is a range, not 7
    stats = predictor.cache_stats()
    assert 1 <= stats["misses"] <= 7
    ref = outs[0]
    for o in outs[1:]:
        for k in ref:
            np.testing.assert_array_equal(ref[k], o[k])


def test_predictor_and_executor_share_one_lowering(tmp_path, rng):
    """Train and serve share one cache: a Predictor bucket lowered first
    is reused when an identical program/feed signature arrives (both
    route through core/lowering.py — the grep gate in the acceptance
    criteria is behavioral here)."""
    from paddle_tpu.core import lowering

    compile_cache.clear_memory_cache()
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", shape=[-1, 5])
        out = fluid.layers.fc(x, size=2)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed_sig = (("x", (2, 5), "float32"),)
        e1, s1 = lowering.lower_step(main, scope, feed_sig, [out.name],
                                     donate=False, label="predictor")
        e2, s2 = lowering.lower_step(main, scope, feed_sig, [out.name],
                                     donate=False, label="predictor")
        assert s1 == "trace" and s2 == "memory"
        assert e1 is e2


# ---------------------------------------------------------------------------
# mandatory pre-lowering verification
# ---------------------------------------------------------------------------


def test_verifier_gates_lowering(rng):
    """A malformed program (use-before-def) must fail verification BEFORE
    tracing — naming the diagnostic, not crashing inside a lowering
    rule."""
    from paddle_tpu.utils.enforce import EnforceError

    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", shape=[-1, 4])
        loss = fluid.layers.mean(fluid.layers.fc(x, size=2))
    block = main.global_block()
    block.create_var(name="never_written", shape=[4], dtype="float32")
    block.append_op(
        "elementwise_add",
        inputs={"X": ["never_written"], "Y": ["never_written"]},
        outputs={"Out": ["never_written_out"]},
    )
    block.create_var(name="never_written_out", shape=[4], dtype="float32")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        with pytest.raises(EnforceError, match="verification"):
            exe.run(main, feed={"x": rng.rand(2, 4).astype("float32")},
                    fetch_list=[loss])


# ---------------------------------------------------------------------------
# cold-start bench CLI (tier-1 wiring, like bench_input/trace_view)
# ---------------------------------------------------------------------------


def test_bench_cold_start_smoke_cli():
    """tools/bench_cold_start.py --smoke: warm processes report zero
    traces/compiles and bit-identical first losses."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_cold_start.py"),
         "--smoke", "--hidden", "24"],
        capture_output=True, text=True, timeout=560,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SMOKE OK" in proc.stdout
