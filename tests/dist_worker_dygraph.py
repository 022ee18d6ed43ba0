"""Runnable dygraph DataParallel worker (reference: python/paddle/fluid/
tests/unittests/test_parallel_dygraph_mnist.py pattern — here spawned as a
real process by test_dist_multiprocess-style machinery).

Each process trains the same tiny dygraph model on ITS shard of a fixed
global batch; gradients cross processes through
DataParallel.apply_collective_grads (a coalesced psum over the global
device mesh). Prints per-step losses; DIST_SINGLE=1 runs the
full-batch single-process reference arm.
"""

import json
import os
import sys

import numpy as np

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=1"
    ).strip()

import jax

jax.config.update("jax_default_matmul_precision", "float32")

import paddle_tpu as fluid
from paddle_tpu.dygraph import Linear, to_variable


def batches(steps, batch=16):
    rng = np.random.RandomState(7)
    w = rng.randn(6, 1).astype("float32")
    out = []
    for _ in range(steps):
        x = rng.randn(batch, 6).astype("float32")
        out.append((x, (x @ w).astype("float32")))
    return out


def main():
    steps = int(os.environ.get("DIST_STEPS", "5"))
    single = os.environ.get("DIST_SINGLE") == "1"
    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))

    if not single:
        coord = os.environ["PADDLE_DIST_COORDINATOR"]
        jax.distributed.initialize(
            coordinator_address=coord, num_processes=world, process_id=rank
        )

    with fluid.dygraph.guard():
        model = Linear(6, 1)
        if not single:
            model = fluid.dygraph.DataParallel(model)
        opt = fluid.optimizer.SGD(learning_rate=0.1)
        # identical init on every process: deterministic constant weights
        for p, val in zip(model.parameters(), (0.05, 0.0)):
            p.set_value(np.full(p.shape, val, dtype="float32"))
        losses = []
        for x, y in batches(steps):
            if not single:
                shard = x.shape[0] // world
                x = x[rank * shard:(rank + 1) * shard]
                y = y[rank * shard:(rank + 1) * shard]
            pred = model(to_variable(x))
            diff = pred - to_variable(y)
            sq = diff * diff
            loss = fluid.dygraph.trace_op("mean", {"X": [sq]}, {})["Out"][0]
            if not single:
                loss = model.scale_loss(loss)
            loss.backward()
            if not single:
                model.apply_collective_grads()
            opt.minimize(loss, parameter_list=model.parameters())
            model.clear_gradients()
            # report the GLOBAL mean loss (single arm already is)
            val = float(np.asarray(loss.numpy()).reshape(-1)[0])
            losses.append(val * (world if not single else 1))
    print("DIST_RESULT " + json.dumps(losses), flush=True)


if __name__ == "__main__":
    main()
