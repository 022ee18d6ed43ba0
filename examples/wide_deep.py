"""Wide&Deep CTR over the sharded embedding engine — BASELINE.md
workload 5 (the reference's flagship parameter-server job,
reference: python/paddle/fluid/tests/unittests/dist_ctr.py), TPU-native:

* sparse features ride ``layers.sharded_embedding`` — hash-partitioned
  device hot caches over a host-RAM overflow tier (paddle_tpu/embedding/),
  ids spanning a 2^40 space with no dense table anywhere;
* click-log records (variable-length id lists per slot) are assembled
  into fixed (ids, weights) batches by the ``sparse_batch`` transform on
  the DataLoader's ordered worker pool (paddle_tpu/dataio/sparse.py);
* the engine's per-step dedup gather + hot cache stats print at the end,
  and AutoCheckpoint(extra_state=engine) demonstrates a bit-identical
  save -> restore -> continue through the format-2 shard path.

Run: python examples/wide_deep.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

NUM_SLOTS = 4
IDS_PER_SLOT = 5
DEEP_DIM = 16
ID_SPACE = 2 ** 40
BATCH = 32
STEPS = 100
EP = 2


def build_programs(main_prog=None, startup_prog=None):
    """Wide (linear, zero-init) + deep (embedding -> MLP) -> sigmoid CTR,
    all sparse features on sharded_embedding tables. Returns
    (main, startup, feed_names, [loss, auc_pred])."""
    import paddle_tpu as fluid

    main_prog = main_prog if main_prog is not None else fluid.Program()
    startup_prog = (startup_prog if startup_prog is not None
                    else fluid.Program())
    with fluid.program_guard(main_prog, startup_prog):
        feeds = []
        wide_parts, deep_parts = [], []
        for i in range(NUM_SLOTS):
            ids = fluid.data(f"slot_{i}", shape=[-1, IDS_PER_SLOT],
                             dtype="int64")
            w = fluid.data(f"slot_{i}_w", shape=[-1, IDS_PER_SLOT],
                           dtype="float32")
            feeds += [ids.name, w.name]
            wide_e = fluid.layers.sharded_embedding(
                ids, 1, capacity=4096, ep=EP, name=f"wide_{i}",
                init_range=0.0, lr=0.1, seed=100 + i,
            )
            deep_e = fluid.layers.sharded_embedding(
                ids, DEEP_DIM, capacity=4096, ep=EP, name=f"deep_{i}",
                init_range=0.01, lr=0.1, seed=200 + i,
            )
            # weighted sum-pool over the slot (padding weight 0 -> its
            # repeated-id rows contribute exactly nothing)
            wexp = fluid.layers.reshape(w, [-1, IDS_PER_SLOT, 1])
            wide_parts.append(fluid.layers.reduce_sum(
                fluid.layers.elementwise_mul(wide_e, wexp), dim=1))
            deep_parts.append(fluid.layers.reduce_sum(
                fluid.layers.elementwise_mul(deep_e, wexp), dim=1))
        label = fluid.data("click", shape=[-1, 1], dtype="float32")
        feeds.append("click")

        wide = fluid.layers.sums(wide_parts)                  # [B, 1]
        deep = fluid.layers.concat(deep_parts, axis=1)
        for h in (64, 32):
            deep = fluid.layers.fc(deep, size=h, act="relu")
        logit = wide + fluid.layers.fc(deep, size=1)
        loss = fluid.layers.mean(
            fluid.layers.sigmoid_cross_entropy_with_logits(logit, label)
        )
        pred = fluid.layers.sigmoid(logit)
        # Adam drives the DENSE half; every sharded table trains with its
        # own row-sparse SGD (the deferred rewrite strips Adam off the
        # slabs — an Adam step on untouched cached rows would break the
        # engine's cache-size invariance)
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return main_prog, startup_prog, feeds, [loss, pred]


def click_log(n, seed=0):
    """Synthetic click-log records: zipfian variable-length id lists per
    slot over a 2^40 space; click probability driven by a hash of slot
    0's ids so the model has signal to learn."""
    from paddle_tpu.embedding.table import splitmix64

    rng = np.random.RandomState(seed)
    for _ in range(n):
        rec_slots = {}
        for i in range(NUM_SLOTS):
            n_ids = rng.randint(1, IDS_PER_SLOT + 1)
            ranks = rng.zipf(1.5, size=n_ids).astype(np.uint64)
            ids = (splitmix64(ranks + np.uint64(i * 1000))
                   % np.uint64(ID_SPACE)).astype(np.int64)
            rec_slots[f"slot_{i}"] = ids.tolist()
        # click rate is a pure function of slot 0's FIRST id: hot head
        # ids recur constantly (zipf 1.5), so their wide rows can
        # memorize the rate — exactly the memorization half of Wide&Deep
        sig = rec_slots["slot_0"][0] % 97
        p = (sig / 97.0) * 0.8 + 0.1
        yield {"slots": rec_slots, "click": float(rng.rand() < p)}


def main():
    import jax

    print(f"backend: {jax.devices()[0].platform}")

    import paddle_tpu as fluid
    from paddle_tpu.dataio import make_sparse_batch_transform
    from paddle_tpu.embedding import EmbeddingEngine
    from paddle_tpu.incubate.checkpoint import AutoCheckpoint

    main_p, startup, feed_names, (loss, pred) = build_programs(
        fluid.default_main_program(), fluid.default_startup_program()
    )
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup)

    engine = EmbeddingEngine()
    ckdir = tempfile.mkdtemp(prefix="wide_deep_ck_")
    ck = AutoCheckpoint(exe, main_p, ckdir, save_interval_steps=20,
                        extra_state=engine)

    # click-log -> (ids, weights, label) batches on the ordered pool
    slot_names = [f"slot_{i}" for i in range(NUM_SLOTS)]
    transform = make_sparse_batch_transform(slot_names, IDS_PER_SLOT)
    loader = fluid.reader.DataLoader.from_generator(
        feed_list=feed_names, capacity=8, num_workers=2,
    ).set_sample_generator(
        lambda: click_log(BATCH * STEPS, seed=0), BATCH,
        sample_transform=transform,
    )

    losses = []
    step = 0
    for feed in loader:
        feed = dict(feed)
        engine.prepare_feed(main_p, feed)
        out = exe.run(main_p, feed=feed, fetch_list=[loss])
        losses.append(float(np.asarray(out[0]).reshape(-1)[0]))
        ck.maybe_save(step)
        step += 1
    head = float(np.mean(losses[:10]))
    tail = float(np.mean(losses[-10:]))
    print(f"trained {step} steps: loss {head:.4f} -> {tail:.4f}")
    assert tail < head, "CTR loss did not improve"

    for t, st in sorted(engine.stats().items()):
        print(f"  table {t}: hit_rate={st['hit_rate']:.2f} "
              f"store_rows={st['store_rows']} evictions={st['evictions']}")

    # save -> fresh engine -> resume -> the next step is bit-identical
    ck.save(step - 1, blocking=True)
    probe = dict(next(iter(
        fluid.reader.DataLoader.from_generator(
            feed_list=feed_names, capacity=2,
        ).set_sample_generator(
            lambda: click_log(BATCH, seed=9), BATCH,
            sample_transform=transform,
        )
    )))
    f1 = dict(probe)
    engine.prepare_feed(main_p, f1, train=False)
    before = np.asarray(exe.run(main_p, feed=f1, fetch_list=[pred])[0])

    engine2 = EmbeddingEngine(scope=fluid.global_scope())
    ck2 = AutoCheckpoint(exe, main_p, ckdir, extra_state=engine2)
    resumed_at = ck2.resume()
    f2 = dict(probe)
    engine2.prepare_feed(main_p, f2, train=False)
    after = np.asarray(exe.run(main_p, feed=f2, fetch_list=[pred])[0])
    assert np.array_equal(before, after), "restore was not bit-identical"
    print(f"resumed at step {resumed_at}: restored predictions "
          "bit-identical through the format-2 shard path")
    engine.close()
    engine2.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
