"""Subprocess worker for the cross-process compile-cache tests.

Builds a deterministic small train program, runs a few steps, and prints
one JSON line with the fetched losses (exact reprs, for bit-identity
comparison across processes) and the compile counters — the parent test
asserts a second process with a populated ``JAX_COMPILATION_CACHE_DIR``
reports ZERO traces (``executor_cache_misses_total`` and the
``executor_compile_seconds`` observation count both 0), and that
poisoned/truncated cache entries silently fall back to a retrace with
identical results.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import numpy as np

import paddle_tpu as fluid
from paddle_tpu.core.ir import program_guard


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--hidden", type=int, default=16)
    args = ap.parse_args()

    main_p, startup = fluid.Program(), fluid.Program()
    with program_guard(main_p, startup):
        x = fluid.data("x", shape=[-1, 8])
        y = fluid.data("y", shape=[-1, 1])
        h = fluid.layers.fc(x, size=args.hidden, act="relu")
        pred = fluid.layers.fc(h, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)

    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    losses = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        rng = np.random.RandomState(7)
        for _ in range(args.steps):
            feed = {"x": rng.randn(4, 8).astype("float32"),
                    "y": rng.randn(4, 1).astype("float32")}
            out = exe.run(main_p, feed=feed, fetch_list=[loss])
            losses.append(repr(float(np.asarray(out[0]).reshape(-1)[0])))

    from paddle_tpu.observability import metrics as obs_metrics

    reg = obs_metrics.registry()

    def val(name):
        m = reg.get(name)
        return int(m.value) if m is not None else 0

    compile_hist = reg.get("executor_compile_seconds")
    print(json.dumps({
        "losses": losses,
        "traces": val("executor_cache_misses_total"),
        "cache_hits": val("executor_cache_hits_total"),
        "persistent_hits": val("compile_cache_persistent_hits_total"),
        "persistent_errors": val("compile_cache_persistent_errors_total"),
        "compile_observations":
            compile_hist.count if compile_hist is not None else 0,
        "jax_cache_dir": jax.config.jax_compilation_cache_dir,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
