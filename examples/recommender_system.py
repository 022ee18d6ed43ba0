"""Wide&Deep CTR over the parameter-server fleet — the reference's
recommender_system book example modernized to its production shape
(reference: python/paddle/fluid/tests/book/test_recommender_system.py +
dist_ctr.py): sparse features live ONLY on the PS; the in-graph remote
lookup pulls/pushes inside the compiled step with prefetch.

Run: python examples/recommender_system.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def build_programs():
    """Pure graph construction (no PS server, no training): the Wide&Deep
    CTR train program in local mode. Returns (main, startup, feed_names,
    fetch_vars) — also the entry point tools/lint_program.py-style program
    linting uses in CI. (main() builds the remote-PS variant instead, which
    needs an initialized fleet.)"""
    from paddle_tpu.models import ctr

    main_prog, startup, feeds, fetches = ctr.build_ctr_train(
        num_slots=4, ids_per_slot=2, deep_dim=8, hidden=(16,),
        sparse_lr=0.2, ps_mode=False, vocab_size=200,
    )
    feed_names = [f if isinstance(f, str) else f.name for f in feeds]
    return main_prog, startup, feed_names, fetches


def main():
    import jax

    print(f"backend: {jax.devices()[0].platform}")

    import paddle_tpu as fluid
    from paddle_tpu.distributed import lookup as rl
    from paddle_tpu.fleet import parameter_server as psfleet
    from paddle_tpu.fleet.role_maker import UserDefinedRoleMaker
    from paddle_tpu.models import ctr

    fleet = psfleet.fleet
    fleet.init(UserDefinedRoleMaker(current_id=0, worker_num=1))
    main_prog, startup, feeds, fetches = ctr.build_ctr_train(
        num_slots=4, ids_per_slot=2, deep_dim=8, hidden=(16,),
        sparse_lr=0.2, ps_mode="remote",
    )
    srv = fleet.init_server(port=0)
    rng = np.random.RandomState(3)
    try:
        fleet.init_worker(main_prog)
        exe = fluid.Executor(fluid.TPUPlace(0))
        losses = []
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            # a SMALL id space so ids repeat across batches and the click
            # signal (a hash of slot 0's ids) is actually learnable
            batches = [
                ctr.synthetic_batch(rng, 64, num_slots=4, ids_per_slot=2,
                                    id_space=200)
                for _ in range(10)
            ] * 6
            for i, feed in enumerate(batches):
                if i + 1 < len(batches):
                    rl.prefetch_for_program(main_prog, batches[i + 1])
                (loss,) = exe.run(main_prog, feed=feed,
                                  fetch_list=[fetches[0]])
                losses.append(float(loss[0]))
                if i % 20 == 0:
                    print(f"step {i}: loss {losses[-1]:.4f}")
        stats = fleet._client.table_stats()
        ctx = rl.active_context()
        print(f"server-side rows: {sum(stats.values())}; "
              f"prefetch hits: {ctx.stats['prefetch_hits']}")
        assert sum(stats.values()) > 0
        first = np.mean(losses[:10])
        last = np.mean(losses[-10:])
        print(f"mean loss first 10 steps {first:.4f} -> last 10 {last:.4f}")
        assert last < first - 0.01, "CTR model did not learn"
    finally:
        fleet.stop_worker()
        srv.stop()


if __name__ == "__main__":
    main()
