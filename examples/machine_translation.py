"""Seq2seq translation with beam search — the reference's
machine_translation book example (reference: python/paddle/fluid/tests/
book/test_machine_translation.py), on a copy task: train the Transformer
encoder-decoder, then serve bucketed beam search through the AOT
translator.

Run: python examples/machine_translation.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def build_programs(src_len=12, tgt_len=12):
    """Pure graph construction (no training, no execution): the tiny
    transformer train program. Returns (main, startup, feed_names,
    fetch_vars, cfg) — also the entry point tools/lint_program.py-style
    program linting uses in CI."""
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig.tiny()
    main_prog, startup, feeds, fetches = tfm.build_wmt_train(
        cfg, src_len=src_len, tgt_len=tgt_len,
        optimizer=fluid.optimizer.Adam(2e-3),
    )
    feed_names = [f if isinstance(f, str) else f.name for f in feeds]
    return main_prog, startup, feed_names, fetches, cfg


def main():
    import jax

    print(f"backend: {jax.devices()[0].platform}")

    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as tfm

    src_len = tgt_len = 12
    main_prog, startup, _, fetches, cfg = build_programs(src_len, tgt_len)
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe.run(startup)
        for step in range(300):
            feed = tfm.synthetic_batch(rng, 32, src_len, tgt_len, cfg)
            (loss,) = exe.run(main_prog, feed=feed, fetch_list=[fetches[0]])
            if step % 100 == 0:
                print(f"step {step}: loss {float(loss[0]):.3f}")
        params = tfm.params_from_scope(cfg)

    translator = tfm.BucketedBeamTranslator(
        cfg, params, beam_size=4, src_buckets=(12, 16)
    ).warmup(8)
    body = rng.randint(3, cfg.vocab_size, (8, 11)).astype("int64")
    toks, scores = translator.translate(body)
    exact = 0
    for i in range(8):
        got = [t for t in toks[i].tolist()
               if t not in (cfg.pad_id, cfg.eos_id)]
        exact += got == body[i].tolist()
    print(f"beam-decode copy accuracy: {exact}/8; "
          f"{translator.tokens_per_sec():.0f} tokens/s")
    assert exact >= 6, "trained model should copy most sequences"


if __name__ == "__main__":
    main()
