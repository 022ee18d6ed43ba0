"""Online serving of a transformer encoder — engine start -> concurrent
submits -> graceful drain.

A single-block masked-attention encoder (embedding -> scaled dot-product
attention -> residual -> FFN head, all per-token) is saved as an
inference model, then served through `paddle_tpu.serving.ServingEngine`:
requests of mixed batch size, sequence length, and priority arrive from
concurrent client threads; the dynamic batcher coalesces them onto a
fixed (batch, seq-len) bucket lattice that was fully AOT-compiled at
startup, so no request ever pays a trace.

The attention mask rides as an explicit input: the batcher zero-fills
padding, a zero mask position contributes exactly 0 to the softmax —
which is why the padded batched outputs below match the single-request
predictor bit-for-bit.

Run: JAX_PLATFORMS=cpu python examples/serve_transformer.py
"""

import os
import sys
import tempfile
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

VOCAB, D_MODEL, N_CLASSES = 100, 16, 5


def build_programs(main_prog=None, startup_prog=None):
    """Pure graph construction (no training, no execution): one masked
    self-attention block with a per-token classifier head. Returns
    (main, startup, feed_names, fetch_vars) — also the entry point the
    tools/lint_program.py CI linting uses (tests/test_analysis.py)."""
    import paddle_tpu as fluid

    main_prog = main_prog if main_prog is not None else fluid.Program()
    startup_prog = startup_prog if startup_prog is not None else fluid.Program()
    with fluid.program_guard(main_prog, startup_prog):
        ids = fluid.data("ids", shape=[-1, -1], dtype="int64")
        mask = fluid.data("mask", shape=[-1, -1], dtype="float32")
        emb = fluid.layers.embedding(ids, size=(VOCAB, D_MODEL))
        q = fluid.layers.fc(emb, D_MODEL, num_flatten_dims=2)
        k = fluid.layers.fc(emb, D_MODEL, num_flatten_dims=2)
        v = fluid.layers.fc(emb, D_MODEL, num_flatten_dims=2)
        scores = fluid.layers.matmul(
            q, k, transpose_y=True, alpha=1.0 / float(np.sqrt(D_MODEL))
        )
        # [B, S] key mask -> additive bias: 0 where real, -1e9 where
        # padded (exp underflows to exactly 0, so padding cannot leak)
        bias = fluid.layers.unsqueeze(
            fluid.layers.scale(mask, scale=1e9, bias=-1e9), [1]
        )
        att = fluid.layers.softmax(
            fluid.layers.elementwise_add(scores, bias), axis=-1
        )
        ctx = fluid.layers.matmul(att, v)
        h = fluid.layers.elementwise_add(ctx, emb)
        ffn = fluid.layers.fc(h, 4 * D_MODEL, act="relu", num_flatten_dims=2)
        logits = fluid.layers.fc(ffn, N_CLASSES, num_flatten_dims=2)
    return main_prog, startup_prog, ["ids", "mask"], [logits]


def _make_request(rng, max_len):
    rows = int(rng.randint(1, 3))
    ln = int(rng.randint(2, max_len + 1))
    ids = rng.randint(1, VOCAB, (rows, ln)).astype("int64")
    return {"ids": ids, "mask": np.ones((rows, ln), "float32")}


def main():
    import jax

    print(f"backend: {jax.devices()[0].platform}")

    import paddle_tpu as fluid
    from paddle_tpu import inference
    from paddle_tpu.serving import (
        BucketLattice,
        Priority,
        RejectedError,
        ServingEngine,
        ServingError,
    )

    main_prog, startup, feed_names, (logits,) = build_programs()
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    with tempfile.TemporaryDirectory() as tmp:
        model_dir = os.path.join(tmp, "encoder")
        with fluid.scope_guard(scope):
            exe.run(startup)
            fluid.io.save_inference_model(
                model_dir, feed_names, [logits], exe, main_program=main_prog
            )

        # -- engine start: warm the whole lattice up front ----------------
        config = inference.Config(model_dir)
        lattice = BucketLattice(batch_sizes=(1, 2, 4, 8), seq_lens=(4, 8, 16))
        config.set_serving_buckets(lattice.batch_sizes, lattice.seq_lens)
        engine = ServingEngine(config, lattice=lattice, num_replicas=2,
                               queue_depth=128, max_wait_ms=4.0)
        engine.start()
        print(f"warmed {len(engine.predictor._cache)} buckets "
              f"({engine.predictor.cache_stats()['compile_s']:.2f}s compile)")

        # single-request reference path for parity checking
        ref = inference.create_predictor(config)
        out_name = ref.get_output_names()[0]

        # -- concurrent submits: mixed shapes, lengths, priorities --------
        n_clients, per_client = 6, 10
        results, failures = {}, []
        lock = threading.Lock()

        def client(cid):
            rng = np.random.RandomState(cid)
            for i in range(per_client):
                req = _make_request(rng, max_len=16)
                prio = (Priority.HIGH, Priority.NORMAL, Priority.LOW)[i % 3]
                try:
                    out = engine.submit(
                        req, priority=prio, deadline_ms=30_000
                    ).result(timeout=120)
                except ServingError as e:  # structured: code + message
                    with lock:
                        failures.append(e.to_dict())
                    continue
                expect = ref.run([req["ids"], req["mask"]])[0]
                assert np.array_equal(out[out_name], expect), \
                    f"client {cid} request {i}: served != single-request"
                with lock:
                    results[(cid, i)] = out[out_name].shape

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        # -- graceful drain ----------------------------------------------
        engine.shutdown()
        try:
            engine.submit(_make_request(np.random.RandomState(0), 8))
            raise AssertionError("post-drain submit must be rejected")
        except RejectedError as e:
            print(f"post-drain submit rejected: {e.to_dict()}")

        stats = engine.stats()
        assert not failures, failures
        assert len(results) == n_clients * per_client
        assert stats["cache_misses"] == 0, "a served shape missed the lattice"
        print(f"served {stats['completed']} requests in {stats['batches']} "
              f"batches (avg {stats['avg_batch_rows']:.2f} rows/batch, "
              f"occupancy {stats['avg_batch_occupancy']:.0%}), "
              f"p99 latency {stats['latency_p99_s'] * 1e3:.1f} ms, "
              f"compile-cache hit rate {stats['cache_hit_rate']:.0%}")
        print("serve_transformer: OK")


if __name__ == "__main__":
    main()
