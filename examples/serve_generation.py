"""Continuous-batching generation — a cached-attention decoder served
end-to-end through the iteration-level engine.

A small residual transformer decoder (token+position embedding, per-layer
cached attention + FFN, logits head) is hosted by
`paddle_tpu.serving.decode.GenerationEngine`: mixed-length prompts from
two weighted tenants arrive concurrently, prefill into free KV-arena
slots mid-flight, and step through ONE compiled ``[S, 1]`` decode
executable — finished sequences retire between iterations instead of
holding their slot until the slowest batchmate drains.

Every generation is asserted bit-identical to the offline whole-sequence
reference (full causal re-forward per token), which is the engine's
correctness contract: active-slot masking and the additive ``-1e9``
attention bias make retired slots and stale cache positions contribute
exactly 0.0, so batchmates can never perturb each other.

Run: JAX_PLATFORMS=cpu python examples/serve_generation.py
"""

import os
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

VOCAB, HIDDEN, LAYERS, SLOTS, MAX_LEN = 50, 16, 2, 4, 32


def _build_model():
    from paddle_tpu.serving.decode import build_decoder_model

    return build_decoder_model(
        vocab_size=VOCAB, hidden=HIDDEN, num_layers=LAYERS, slots=SLOTS,
        max_len=MAX_LEN, name="storyteller", version="1",
    )


def build_programs():
    """Pure graph construction for the static-analysis CI gates: the
    decoder's prefill program (whole-prompt causal forward at [1, L]) —
    the same weights the decode step reads through the KV arena."""
    from paddle_tpu.serving.decode import DecodeModel

    m = _build_model()
    feed_names = [DecodeModel.PRE_TOKENS, DecodeModel.PRE_POSITIONS,
                  DecodeModel.PRE_BIAS]
    return (m.prefill_program, m.startup_program, feed_names,
            [m.prefill_logits_fetch])


def main():
    import jax

    print(f"backend: {jax.devices()[0].platform}")

    from paddle_tpu.serving import Priority
    from paddle_tpu.serving.decode import GenerationEngine
    from paddle_tpu.serving.request import RejectedError

    engine = GenerationEngine(queue_depth=128, hbm_budget_mb=256)
    engine.set_tenant("gold", weight=2.0)
    engine.set_tenant("silver", weight=1.0, max_queued=64)
    entry = engine.register_model(_build_model)
    print(f"hosted {entry.model.label}: {SLOTS} slots x {MAX_LEN} tokens "
          f"({entry.stats()['arena_mib'] * 1024:.0f} KiB KV arena), "
          f"executables from {entry.compile_sources}")
    engine.start()

    # -- concurrent clients: mixed lengths, tenants, priorities ----------
    n_clients, per_client = 4, 6
    results, failures = {}, []
    lock = threading.Lock()

    def client(cid):
        rng = np.random.RandomState(cid)
        tenant = "gold" if cid % 2 == 0 else "silver"
        for i in range(per_client):
            prompt = [int(t) for t in
                      rng.randint(0, VOCAB, size=rng.randint(1, 9))]
            max_new = int(rng.randint(2, 17))
            try:
                out = engine.submit(
                    prompt, max_new_tokens=max_new, tenant=tenant,
                    priority=(Priority.HIGH, Priority.NORMAL,
                              Priority.LOW)[i % 3],
                ).result(timeout=120)
            except Exception as e:
                with lock:
                    failures.append((cid, i, repr(e)))
                continue
            with lock:
                results[(cid, i)] = (prompt, max_new,
                                     [int(t) for t in out["tokens"]])

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not failures, failures
    assert len(results) == n_clients * per_client

    # -- the contract: continuous == offline, request by request ---------
    for (cid, i), (prompt, max_new, got) in sorted(results.items()):
        ref = entry.offline_decode(prompt, max_new)
        assert got == ref, f"client {cid} request {i}: {got} != {ref}"
    print(f"verified {len(results)} generations bit-identical to the "
          "offline whole-sequence reference")

    # -- shared-prefix dedup: same prompt pays one prefill ---------------
    hits0 = entry.prefix_cache.hits
    story = [7, 3, 7, 1]
    a = engine.submit(story, max_new_tokens=8).result(timeout=120)
    b = engine.submit(story, max_new_tokens=8).result(timeout=120)
    assert [int(t) for t in a["tokens"]] == [int(t) for t in b["tokens"]]
    assert entry.prefix_cache.hits > hits0
    print("shared-prefix dedup: duplicate prompt served from the prefix "
          "cache, bit-identical")

    # -- graceful drain --------------------------------------------------
    engine.shutdown()
    try:
        engine.submit([1, 2], max_new_tokens=2)
        raise AssertionError("post-drain submit must be rejected")
    except RejectedError as e:
        print(f"post-drain submit rejected: {e}")

    st = entry.stats()
    assert st["completed"] == len(results) + 2
    assert st["failed"] == 0
    print(f"served {st['completed']} requests / "
          f"{st['generated_tokens'] + st['prefill_tokens']} tokens in "
          f"{st['decode_steps']} "
          f"decode steps (occupancy {st['occupancy']:.0%}, "
          f"{st['tokens_per_step']:.2f} tok/step), "
          f"p99 latency {st['latency_p99_s'] * 1e3:.1f} ms, "
          f"tenant tokens {st['tenant_tokens']}")
    print("serve_generation: OK")


if __name__ == "__main__":
    main()
