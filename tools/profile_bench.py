"""Ablation profiler for the headline BERT bench (feeds PROFILE.md).

Runs the same Program/Executor step bench.py times, under a matrix of knobs,
and reports tokens/s + MFU per variant so the step-time budget can be
attributed (the reference attributes per-op time via its profiler,
reference: paddle/fluid/platform/profiler.h:199; on TPU the step is one XLA
computation, so attribution is by ablation + jax.profiler trace instead).

Usage:
  python tools/profile_bench.py [batch] [seq_len]        # ablation table
  PROFILE_TRACE_DIR=/tmp/trace python tools/profile_bench.py  # + xplane trace
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build(cfg_kwargs, seq_len, use_amp, max_pred=None):
    import paddle_tpu as fluid
    from paddle_tpu.models import bert

    cfg = bert.BertConfig.base()
    for k, v in cfg_kwargs.items():
        setattr(cfg, k, v)
    main, startup, feeds, fetches = bert.build_bert_pretrain(
        cfg, seq_len=seq_len, lr=1e-4, use_amp=use_amp,
        max_predictions_per_seq=max_pred,
    )
    return cfg, main, startup, fetches


def run_variant(name, batch, seq_len, steps=10, use_amp=True,
                trace_dir=None, max_pred=None, rng_impl="threefry",
                **cfg_kwargs):
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.models import bert
    from paddle_tpu.utils.flags import flags

    flags.rng_impl = rng_impl
    cfg, main, startup, fetches = _build(cfg_kwargs, seq_len, use_amp,
                                         max_pred)
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup)
    rng = np.random.RandomState(0)
    data = bert.synthetic_batch(
        rng, batch, seq_len, cfg, max_predictions_per_seq=max_pred
    )

    for _ in range(2):  # compile + settle
        out = exe.run(main, feed=data, fetch_list=[fetches[0]],
                      return_numpy=False)
    jax.block_until_ready(out[0])

    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = exe.run(main, feed=data, fetch_list=[fetches[0]],
                      return_numpy=False)
    jax.block_until_ready(out[0])  # steps chain through donated params
    dt = time.perf_counter() - t0
    if trace_dir:
        jax.profiler.stop_trace()

    tokens_per_sec = steps * batch * seq_len / dt
    rec = {
        "variant": name,
        "tokens_per_sec": round(tokens_per_sec, 1),
        "ms_per_step": round(1000 * dt / steps, 2),
    }
    print(json.dumps(rec), flush=True)
    scope = fluid.core.scope.global_scope()
    scope.erase(list(scope.var_names()))
    exe.close()
    return rec


def main():
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    seq = int(sys.argv[2]) if len(sys.argv) > 2 else 128
    trace_dir = os.environ.get("PROFILE_TRACE_DIR")
    only = os.environ.get("PROFILE_ONLY")

    P = max(1, seq // 7) + 1
    variants = [
        # the shipped bench config: flash + gathered head + rbg dropout
        ("bench_config", dict(_max_pred=P, _rng="rbg",
                              use_flash_attention=True,
                              attention_probs_dropout_prob=0.0)),
        # one knob off at a time
        ("no_flash", dict(_max_pred=P, _rng="rbg")),
        ("threefry", dict(_max_pred=P, _rng="threefry",
                          use_flash_attention=True,
                          attention_probs_dropout_prob=0.0)),
        ("full_vocab_head", dict(_rng="rbg", use_flash_attention=True,
                                 attention_probs_dropout_prob=0.0)),
        # the round-2 configuration for the before/after line
        ("r2_baseline", dict(_rng="threefry")),
    ]
    if os.environ.get("PROFILE_EXTRA"):
        variants += [
            ("fp32", dict(_use_amp=False, _max_pred=P, _rng="rbg")),
            ("no_dropout", dict(_max_pred=P, _rng="rbg",
                                hidden_dropout_prob=0.0,
                                attention_probs_dropout_prob=0.0)),
        ]
    for name, kw in variants:
        if only and only != name:
            continue
        use_amp = kw.pop("_use_amp", True)
        max_pred = kw.pop("_max_pred", None)
        rng_impl = kw.pop("_rng", "threefry")
        try:
            run_variant(name, batch, seq, use_amp=use_amp,
                        max_pred=max_pred, rng_impl=rng_impl,
                        trace_dir=trace_dir if name == "bench_config"
                        else None, **kw)
        except Exception as e:  # keep the table going past one bad variant
            print(json.dumps({"variant": name, "error": str(e)[:300]}),
                  flush=True)


if __name__ == "__main__":
    main()
