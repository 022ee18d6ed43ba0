"""Runnable PS-fleet worker/server script (the analog of the reference's
dist_ctr.py + TestDistBase pserver spawning, reference: python/paddle/fluid/
tests/unittests/test_dist_base.py:586 start_pserver).

TRAINING_ROLE=PSERVER runs the TCP parameter server until killed;
TRAINING_ROLE=TRAINER pulls/pushes sparse tables while training the CTR
model, then prints one JSON line of losses.
"""

import json
import os
import sys

import numpy as np

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

jax.config.update("jax_default_matmul_precision", "float32")

import paddle_tpu as fluid
from paddle_tpu.fleet import parameter_server as psfleet
from paddle_tpu.fleet.role_maker import PaddleCloudRoleMaker
from paddle_tpu.models import ctr


def main():
    fleet = psfleet.fleet
    fleet.init(PaddleCloudRoleMaker(is_collective=False))

    if fleet.is_server():
        port = int(
            os.environ["PADDLE_CURRENT_ENDPOINT"].rsplit(":", 1)[1]
        )
        srv = fleet.init_server(port=port)
        print("PS_SERVER_READY", flush=True)
        fleet.run_server()
        return

    steps = int(os.environ.get("DIST_STEPS", "10"))
    mode = os.environ.get("DIST_PS_MODE", "async")
    main_prog, startup, feeds, fetches = ctr.build_ctr_train(
        num_slots=4, ids_per_slot=2, deep_dim=8, hidden=(16,), sparse_lr=0.2
    )
    fleet._strategy = psfleet.PSDistributedStrategy(mode=mode, merge_steps=3)
    fleet.init_worker(main_prog)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    worker = fleet.worker(exe, main_prog)
    if os.environ.get("DIST_HEARTBEAT"):
        import threading

        def _beat():
            while True:
                try:
                    fleet._client.heartbeat(fleet.worker_index())
                except Exception:
                    return
                import time as _t

                _t.sleep(0.5)

        threading.Thread(target=_beat, daemon=True).start()
    rng = np.random.RandomState(123 + fleet.worker_index())
    # fixed batch per worker: convergence = memorization, the same
    # signal the reference's dist tests assert on short runs
    feed = ctr.synthetic_batch(rng, 64, num_slots=4, ids_per_slot=2)
    losses = []
    for _ in range(steps):
        out = worker.run(main_prog, feed, fetch_list=[fetches[0]])
        losses.append(float(np.asarray(out[0]).reshape(-1)[0]))
    worker.flush()
    if fleet.worker_num() > 1:
        fleet._client.barrier(fleet.worker_num())
    print("DIST_RESULT " + json.dumps(losses), flush=True)
    fleet.stop_worker()


if __name__ == "__main__":
    main()
