"""Runnable distributed-worker model script (the analog of the reference's
dist_mnist.py driven by TestDistBase, reference: python/paddle/fluid/tests/
unittests/test_dist_base.py:506 + dist_mnist.py).

Spawned by distributed/launch.py with the fleet env contract; brings up the
JAX multi-process runtime through fleet.init (fleet/base.py
_maybe_init_jax_distributed), trains a deterministic MLP with collective
data parallelism, and prints one JSON line of per-step losses.

Run single-process mode with DIST_SINGLE=1 (the `_run_local` reference arm).
"""

import json
import os
import sys

import numpy as np

# one virtual CPU device per process (set before jax import)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=1"
    ).strip()

import jax

jax.config.update("jax_default_matmul_precision", "float32")

import paddle_tpu as fluid
from paddle_tpu.core.ir import Program, program_guard


def build(seed=7):
    main, startup = Program(), Program()
    main.random_seed = seed
    with program_guard(main, startup):
        x = fluid.data("x", shape=[-1, 32])
        y = fluid.data("y", shape=[-1, 1], dtype="int64")
        h = fluid.layers.fc(
            x, size=64, act="relu", num_flatten_dims=1,
            param_attr=fluid.ParamAttr(
                name="w1", initializer=fluid.initializer.TruncatedNormal(0, 0.05)
            ),
            bias_attr=fluid.ParamAttr(name="b1"),
        )
        logits = fluid.layers.fc(
            h, size=10, num_flatten_dims=1,
            param_attr=fluid.ParamAttr(
                name="w2", initializer=fluid.initializer.TruncatedNormal(0, 0.05)
            ),
            bias_attr=fluid.ParamAttr(name="b2"),
        )
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y)
        )
    return main, startup, loss


def batches(steps, batch=32):
    rng = np.random.RandomState(42)
    out = []
    for _ in range(steps):
        out.append(
            {
                "x": rng.randn(batch, 32).astype("float32"),
                "y": rng.randint(0, 10, (batch, 1)).astype("int64"),
            }
        )
    return out


def main():
    steps = int(os.environ.get("DIST_STEPS", "5"))
    single = os.environ.get("DIST_SINGLE") == "1"
    main_prog, startup, loss = build()
    exe = fluid.Executor(fluid.CPUPlace())

    if single:
        with program_guard(main_prog, startup):
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe.run(startup)
        prog = main_prog
    else:
        from paddle_tpu.fleet import collective as coll

        fleet = coll.fleet
        from paddle_tpu.fleet.role_maker import PaddleCloudRoleMaker
        fleet.init(PaddleCloudRoleMaker())
        strategy = coll.DistributedStrategy()
        with program_guard(main_prog, startup):
            opt = fleet.distributed_optimizer(
                fluid.optimizer.SGD(learning_rate=0.1), strategy
            )
            opt.minimize(loss)
        exe.run(startup)
        prog = fleet.main_program
        assert jax.process_count() == fleet.worker_num(), (
            jax.process_count(), fleet.worker_num(),
        )

    losses = []
    for feed in batches(steps):
        # every process feeds the SAME global batch; the compiled program
        # shards dim 0 over the mesh, so each process computes its half
        out = exe.run(prog, feed=feed, fetch_list=[loss])
        losses.append(float(np.asarray(out[0]).reshape(-1)[0]))
    print("DIST_RESULT " + json.dumps(losses))
    sys.stdout.flush()


if __name__ == "__main__":
    main()
