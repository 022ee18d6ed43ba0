#!/usr/bin/env python
"""Cold- vs warm-process startup bench for the persistent compile cache.

Two scenarios, each run in FRESH subprocesses (the cache under test is
cross-process by definition):

- **train**: process start -> first optimized step of a small MLP train
  program. Cold pays trace + XLA compile; warm loads the serialized step
  from ``JAX_COMPILATION_CACHE_DIR`` (zero traces).
- **predictor**: Predictor.warmup() over a (batch x seq-like) bucket
  lattice — the serving cold-replica story (ROADMAP item 2's compile
  storm). Cold compiles every lattice point; warm loads each bucket from
  disk in milliseconds.

Each scenario reports cold (cache disabled), populate (cache enabled,
empty — the write-through run), and warm (cache enabled, populated), with
trace/persistent-hit counters from the observability registry so the
"zero compiles" claim is checked, not implied from timing.

``--smoke`` is the tier-1 CI hook (wired by tests/test_compile_cache.py):
asserts warm runs report ZERO traces, nonzero persistent hits, and
bit-identical first-step output vs the cold run.

Usage:
  python tools/bench_cold_start.py [--smoke] [--buckets 1,2,4]
      [--hidden 64] [--cache-dir DIR]
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("OMP_NUM_THREADS", "1")
os.environ.setdefault("OPENBLAS_NUM_THREADS", "1")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


# ---------------------------------------------------------------------------
# child workloads (run in fresh subprocesses)
# ---------------------------------------------------------------------------


def _counters():
    from paddle_tpu.observability import metrics as obs_metrics

    reg = obs_metrics.registry()

    def val(name):
        m = reg.get(name)
        return int(m.value) if m is not None else 0

    return {
        "traces": val("executor_cache_misses_total"),
        "persistent_hits": val("compile_cache_persistent_hits_total"),
    }


def _worker_train(hidden, layers):
    t_start = time.perf_counter()
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.core.ir import program_guard

    main, startup = fluid.Program(), fluid.Program()
    with program_guard(main, startup):
        x = fluid.data("x", shape=[-1, 32])
        y = fluid.data("y", shape=[-1, 1])
        h = x
        for _ in range(layers):
            h = fluid.layers.fc(h, size=hidden, act="relu")
        pred = fluid.layers.fc(h, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        rng = np.random.RandomState(0)
        feed = {"x": rng.randn(8, 32).astype("float32"),
                "y": rng.randn(8, 1).astype("float32")}
        out = exe.run(main, feed=feed, fetch_list=[loss])
    first_step_s = time.perf_counter() - t_start
    rec = {"startup_to_first_step_s": round(first_step_s, 4),
           "first_loss": repr(float(np.asarray(out[0]).reshape(-1)[0]))}
    rec.update(_counters())
    print(json.dumps(rec))


def _worker_predictor(model_dir, buckets):
    t_start = time.perf_counter()
    from paddle_tpu import inference
    from paddle_tpu.observability import metrics as obs_metrics

    config = inference.Config(model_dir)
    config.disable_tpu()
    config.set_serving_buckets([int(b) for b in buckets.split(",")])
    pred = inference.create_predictor(config)
    t_warm = time.perf_counter()
    compiled = pred.warmup()
    warmup_s = time.perf_counter() - t_warm
    hist = obs_metrics.registry().get("predictor_compile_seconds")
    rec = {
        "startup_to_warm_s": round(time.perf_counter() - t_start, 4),
        "warmup_s": round(warmup_s, 4),
        "buckets_warmed": len(compiled),
        "aot_compiles": hist.count if hist is not None else 0,
        "cache_stats": pred.cache_stats(),
    }
    rec.update(_counters())
    print(json.dumps(rec))


# ---------------------------------------------------------------------------
# parent orchestration
# ---------------------------------------------------------------------------


def _run_child(mode, cache_dir, extra_args):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--worker", mode]
        + extra_args,
        env=env, capture_output=True, text=True, timeout=600,
    )
    wall = time.perf_counter() - t0
    if proc.returncode != 0:
        raise RuntimeError(
            f"child {mode} failed:\n{proc.stderr.strip()[-2000:]}"
        )
    line = [l for l in proc.stdout.strip().splitlines()
            if l.startswith("{")][-1]
    rec = json.loads(line)
    rec["process_wall_s"] = round(wall, 4)
    return rec


def _make_model(dirname, hidden, layers):
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.core.ir import program_guard

    main, startup = fluid.Program(), fluid.Program()
    with program_guard(main, startup):
        x = fluid.data("x", shape=[-1, 32])
        h = x
        for _ in range(layers):
            h = fluid.layers.fc(h, size=hidden, act="relu")
        pred = fluid.layers.fc(h, size=1)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(dirname, ["x"], [pred], exe,
                                      main_program=main)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", choices=["train", "predictor"])
    ap.add_argument("--model-dir")
    ap.add_argument("--buckets", default="1,2,4")
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--cache-dir", default=None)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()

    if args.worker == "train":
        return _worker_train(args.hidden, args.layers)
    if args.worker == "predictor":
        return _worker_predictor(args.model_dir, args.buckets)

    cache_dir = args.cache_dir or tempfile.mkdtemp(prefix="ptcc_bench_")
    model_dir = os.path.join(tempfile.mkdtemp(prefix="ptcc_model_"), "model")
    _make_model(model_dir, args.hidden, args.layers)

    report = {"cache_dir": cache_dir}
    train_args = ["--hidden", str(args.hidden),
                  "--layers", str(args.layers)]
    report["train_cold"] = _run_child("train", None, train_args)
    report["train_populate"] = _run_child("train", cache_dir, train_args)
    report["train_warm"] = _run_child("train", cache_dir, train_args)

    pred_args = ["--model-dir", model_dir, "--buckets", args.buckets,
                 "--hidden", str(args.hidden), "--layers", str(args.layers)]
    report["predictor_cold"] = _run_child("predictor", None, pred_args)
    report["predictor_populate"] = _run_child("predictor", cache_dir,
                                              pred_args)
    report["predictor_warm"] = _run_child("predictor", cache_dir, pred_args)

    cold, warm = report["train_cold"], report["train_warm"]
    report["summary"] = {
        "train_first_step_cold_s": cold["startup_to_first_step_s"],
        "train_first_step_warm_s": warm["startup_to_first_step_s"],
        "train_warm_traces": warm["traces"],
        "predictor_warmup_cold_s": report["predictor_cold"]["warmup_s"],
        "predictor_warmup_warm_s": report["predictor_warm"]["warmup_s"],
        "predictor_warm_aot_compiles":
            report["predictor_warm"]["aot_compiles"],
    }
    print(json.dumps(report, indent=1))

    if args.smoke:
        _smoke_asserts(report)
        print("SMOKE OK")


def _smoke_asserts(report):
    warm = report["train_warm"]
    assert warm["traces"] == 0, \
        f"warm train process retraced: {warm['traces']} traces"
    assert warm["persistent_hits"] > 0, "warm train saw no persistent hits"
    # correctness, not just speed: the warm (deserialized) step must
    # produce the bit-identical first loss
    assert warm["first_loss"] == report["train_cold"]["first_loss"], (
        f"warm loss {warm['first_loss']} != cold "
        f"{report['train_cold']['first_loss']}"
    )
    pw = report["predictor_warm"]
    assert pw["aot_compiles"] == 0, \
        f"warm predictor compiled {pw['aot_compiles']} buckets"
    assert pw["cache_stats"]["persistent_hits"] == pw["buckets_warmed"] \
        or pw["persistent_hits"] > 0, "warm predictor saw no persistent hits"


if __name__ == "__main__":
    sys.exit(main())
