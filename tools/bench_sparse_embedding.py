"""Dense vs row-sparse embedding update benchmark (VERDICT r3 item 6).

Times one embedding-regression train step at vocab >= 100k in both forms:
  dense : lookup_table_grad materializes the [V, D] gradient, sgd applies
          p - lr*g over every row (the pre-r4 behavior)
  sparse: sparse_weight_update pass -> sgd_sparse row scatter (SelectedRows
          analog)

Usage: python tools/bench_sparse_embedding.py [vocab] [dim] [tokens]
Prints one JSON line with both times and the speedup.
"""

import json
import sys
import time

import numpy as np


def bench(vocab=100_000, dim=512, tokens=8192, steps=20):
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.utils.flags import flags

    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (tokens,)).astype("int64")
    y = rng.randn(tokens, dim).astype("float32")
    results = {}
    for sparse in (False, True):
        old = flags.sparse_embedding_update
        flags.sparse_embedding_update = sparse
        try:
            main, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main, startup):
                iv = fluid.data("ids", [tokens], dtype="int64")
                yv = fluid.data("y", [tokens, dim])
                emb = fluid.layers.embedding(
                    iv, size=[vocab, dim],
                    param_attr=fluid.ParamAttr(
                        name=f"w_{sparse}",
                        initializer=fluid.initializer.NormalInitializer(
                            0, 0.1
                        ),
                    ),
                )
                loss = fluid.layers.mean(fluid.layers.square(
                    fluid.layers.elementwise_sub(emb, yv)
                ))
                fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        finally:
            flags.sparse_embedding_update = old
        types = [op.type for op in main.global_block().ops]
        assert ("sgd_sparse" in types) == sparse, types
        exe = fluid.Executor(fluid.TPUPlace(0))
        sc = fluid.Scope()
        with fluid.scope_guard(sc):
            exe.run(startup)
            feed = {"ids": ids, "y": y}
            for _ in range(3):  # compile + warm
                out = exe.run(main, feed=feed, fetch_list=[loss],
                              return_numpy=False)
            jax.block_until_ready(out[0])
            t0 = time.perf_counter()
            for _ in range(steps):
                out = exe.run(main, feed=feed, fetch_list=[loss],
                              return_numpy=False)
            jax.block_until_ready(out[0])
            dt = (time.perf_counter() - t0) / steps
        results["sparse" if sparse else "dense"] = dt * 1000.0
    return {
        "metric": "embedding_update_ms",
        "vocab": vocab,
        "dim": dim,
        "tokens": tokens,
        "device": jax.devices()[0].platform,
        "dense_ms": round(results["dense"], 3),
        "sparse_ms": round(results["sparse"], 3),
        "speedup": round(results["dense"] / results["sparse"], 2),
    }


if __name__ == "__main__":
    args = [int(a) for a in sys.argv[1:4]]
    print(json.dumps(bench(*args)))
