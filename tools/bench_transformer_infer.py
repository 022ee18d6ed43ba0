"""Transformer-big beam-search inference throughput (BASELINE workload 4).

Bucketed AOT serving at the real 37k vocab: warm every length bucket, then
stream mixed-length batches and report generated tokens/s. On the chip this
runs the big config; the CPU fallback shrinks depth (same code path).

Usage: python tools/bench_transformer_infer.py [batch] [beam]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"

    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as tfm

    batch = int(sys.argv[1]) if len(sys.argv) > 1 else (32 if on_tpu else 4)
    beam = int(sys.argv[2]) if len(sys.argv) > 2 else 4
    if on_tpu:
        cfg = tfm.TransformerConfig.big()
        cfg.max_len = 64
        buckets = (16, 32, 64)
        rounds = 8
    else:
        cfg = tfm.TransformerConfig(
            vocab_size=37000, d_model=128, n_heads=4, d_ffn=256,
            n_enc_layers=2, n_dec_layers=2, max_len=32,
        )
        buckets = (8, 16)
        rounds = 3

    main_prog, startup, feeds, fetches = tfm.build_wmt_train(
        cfg, src_len=16, tgt_len=16
    )
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        params = tfm.params_from_scope(cfg, scope)

    tr = tfm.BucketedBeamTranslator(
        cfg, params, beam_size=beam, src_buckets=buckets, batch_size=batch
    )
    t0 = time.perf_counter()
    tr.warmup(batch)
    warm_s = time.perf_counter() - t0

    rng = np.random.RandomState(0)
    for _ in range(rounds):
        for b in buckets:
            L = max(2, b - rng.randint(0, b // 2))
            src = rng.randint(3, cfg.vocab_size, (batch, L)).astype("int64")
            tr.translate(src)
    print(json.dumps({
        "metric": "transformer_beam_infer_tokens_per_sec",
        "value": round(tr.tokens_per_sec(), 1),
        "unit": "tokens/s",
        "extra": {
            "device": platform,
            "vocab": cfg.vocab_size,
            "beam": beam,
            "batch": batch,
            "buckets": list(buckets),
            "warmup_seconds": round(warm_s, 1),
            "bucket_hits": tr.stats["bucket_hits"],
            "sentences": tr.stats["sentences"],
        },
    }))


if __name__ == "__main__":
    main()
