"""Headline benchmark: BERT-base pretraining + ResNet-50 throughput, one chip.

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}.
The primary metric is BERT-base pretrain tokens/s; the second BASELINE.md
headline — ResNet-50 imgs/sec/chip — rides in extra.resnet50 (one line keeps
the driver contract). The reference publishes no in-repo numbers (see
BASELINE.md), so vs_baseline is reported against the BASELINE.json
north-star MFU target (value/target).

One process, and it measures on a TPU or not at all: with no TPU among
``jax.devices()`` it exits non-zero before building anything (a CPU run
is never written under this metric's name — ``python chip_smoke.py`` is
the quickest proof the chip path starts). A failing leg raises; nothing
here turns an exception into a JSON field.
"""

import json
import os
import sys
import time

import numpy as np


def _emit(value, vs_baseline, extra):
    print(
        json.dumps(
            {
                "metric": "bert_base_pretrain_tokens_per_sec_per_chip",
                "value": value,
                "unit": "tokens/s",
                "vs_baseline": vs_baseline,
                "extra": extra,
            }
        )
    )


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(
            f"bench.py measures on a TPU; jax.devices()[0] is {dev!r} "
            f"(platform {dev.platform!r}). Run it through the chip tool."
        )

    import paddle_tpu as fluid
    from paddle_tpu.models import bert

    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    seq_len = int(sys.argv[2]) if len(sys.argv) > 2 else 128
    steps = 30
    cfg = bert.BertConfig.base()
    from paddle_tpu import kernels as _kernels_probe

    if os.environ.get("PADDLE_TPU_BENCH_FLASH", "1") != "0" and \
            _kernels_probe.probe("flash_attention"):
        # flash path: Pallas fused attention fwd+bwd, taken whenever the
        # kernel registry would actually serve it (PADDLE_TPU_KERNELS
        # auto, the default, on TPU). The kernel applies no
        # attention-prob dropout (enforced, models/bert.py), so that knob
        # is 0 here - recorded in extra so the config change is visible.
        cfg.use_flash_attention = True
        cfg.attention_probs_dropout_prob = 0.0
    from paddle_tpu.utils.flags import flags as _flags

    # hardware-RNG dropout bits by default on the chip (same distribution,
    # cheaper stream than threefry); PADDLE_TPU_RNG_IMPL overrides
    _flags.rng_impl = os.environ.get("PADDLE_TPU_RNG_IMPL", "rbg")

    # bf16 AMP is the TPU-native default posture (SURVEY §7: bf16-first
    # policy). PADDLE_TPU_BENCH_FP32=1 reverts to f32 for comparison runs.
    use_amp = not os.environ.get("PADDLE_TPU_BENCH_FP32")
    max_pred = max(1, seq_len * 15 // 100) + 1  # the standard ~15% recipe
    main_prog, startup, feeds, fetches = bert.build_bert_pretrain(
        cfg, seq_len=seq_len, lr=1e-4, use_amp=use_amp,
        max_predictions_per_seq=max_pred,
    )
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup)
    rng = np.random.RandomState(0)
    data = bert.synthetic_batch(
        rng, batch, seq_len, cfg, max_predictions_per_seq=max_pred
    )

    # warmup (compile), then sync on the fetched loss before the timed
    # region. Steps are chained through the donated parameters, so the
    # last loss is ready only when every step before it has run.
    for _ in range(3):
        out = exe.run(main_prog, feed=data, fetch_list=[fetches[0]],
                      return_numpy=False)
    jax.block_until_ready(out[0])
    t0 = time.perf_counter()
    for _ in range(steps):
        # return_numpy=False keeps the loop async: fetches stay on device so
        # step N+1's host-side dispatch overlaps step N's device execution;
        # the final-loss sync below is the only sync point
        out = exe.run(main_prog, feed=data, fetch_list=[fetches[0]],
                      return_numpy=False)
    jax.block_until_ready(out[0])
    dt = time.perf_counter() - t0
    final_loss = float(np.asarray(out[0]).reshape(-1)[0])
    tokens_per_sec = steps * batch * seq_len / dt

    # MFU estimate: ~6 * params * tokens FLOPs for fwd+bwd
    n_params = sum(
        int(np.prod(p.shape)) for p in main_prog.all_parameters()
    )
    flops_per_token = 6 * n_params
    achieved = tokens_per_sec * flops_per_token
    peak = _chip_peak_flops()
    mfu = achieved / peak
    # measured roofline: a pure-matmul chain timed with the same sync gives
    # the chip's ACHIEVABLE TF/s; mfu_est is vs book peak, frac_of_roofline
    # vs this measurement
    roofline = _measure_roofline()
    frac_roofline = achieved / roofline

    # flash_attention is a LIVE registry probe (paddle_tpu/kernels/,
    # imported above): would the Pallas flash kernel serve the sdpa op
    # on this backend under the current PADDLE_TPU_KERNELS mode
    # (auto/off/interpret — set PADDLE_TPU_KERNELS=off to opt out of
    # every registry kernel)?
    extra = {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "batch": batch,
        "seq_len": seq_len,
        "params": n_params,
        "mfu_est": round(mfu, 4),
        "roofline_tfps": round(roofline / 1e12, 1),
        "frac_of_roofline": round(frac_roofline, 4),
        "final_loss": final_loss,
        "flash_attention": _kernels_probe.probe("flash_attention"),
        "kernels": {
            "mode": _kernels_probe.mode(),
            "resolved": _kernels_probe.resolved_mode(),
            "registry": [s.name for s in _kernels_probe.all_specs()],
        },
        "max_predictions_per_seq": max_pred,
        "attention_dropout": cfg.attention_probs_dropout_prob,
        "rng_impl": _flags.rng_impl,
        # compile/cache evidence: trace count, hit counts, and whether
        # steps came from the persistent tier
        "compile": _compile_evidence(),
    }
    if not os.environ.get("PADDLE_TPU_BENCH_NO_RESNET"):
        extra["resnet50"] = _bench_resnet(peak)
    if not os.environ.get("PADDLE_TPU_BENCH_NO_DECODE"):
        extra["decode"] = _bench_decode()
    if not os.environ.get("PADDLE_TPU_BENCH_NO_COST"):
        extra["cost"] = _bench_cost(main_prog, data, fetches)
    if not os.environ.get("PADDLE_TPU_BENCH_NO_PIPELINE"):
        extra["pipeline"] = _bench_pipeline()
    _emit(
        round(tokens_per_sec, 1),
        round(mfu / 0.5, 4),  # vs the >=50% MFU north star
        extra,
    )


def _bench_cost(main_prog, data, fetches):
    """Static roofline prediction for the bench program (analysis/cost.py,
    r16): the PRE-COMPILE counterpart of mfu_est — predicted step time,
    MFU, and bound-class counts on the default machine model, so the
    bench records how far the measured number sits from the static
    roofline it will one day be gated against."""
    import numpy as np

    from paddle_tpu.analysis.cost import analyze_cost

    feed_shapes = {k: tuple(np.asarray(v).shape) for k, v in data.items()}
    fetch_names = [f if isinstance(f, str) else f.name for f in fetches]
    rep = analyze_cost(main_prog, feed_shapes=feed_shapes,
                       fetch_names=fetch_names)
    return {
        "machine": rep.cost_model.machine.name,
        "step_seconds": round(rep.step_seconds, 9),
        "mfu_pred": round(rep.mfu, 6),
        "total_flops": rep.total_flops,
        "total_hbm_bytes": rep.total_hbm_bytes,
        "bound_counts": rep.bound_counts(),
        "unknown_ops": sorted(rep.unknown_ops),
    }


def _bench_pipeline():
    """Pipeline-schedule evidence for `extra` (r20): the compiled slot
    tables at the COST_EVIDENCE operating point (4 stages x 4
    microbatches) — predicted vs table-walk realized bubble per schedule.
    Pure schedule-compiler arithmetic, no devices; the realized numbers
    must match PIPELINE_EVIDENCE_r20.json's step accounting."""
    from paddle_tpu.parallel.pipeline_runtime.schedule import (
        compile_schedule,
    )

    out = {"stages": 4, "num_microbatches": 4, "schedules": {}}
    for kind in ("gpipe", "1f1b"):
        sched = compile_schedule(kind, 4, 4)
        out["schedules"][kind] = {
            "interleave": sched.interleave,
            "ticks": sched.num_ticks,
            "predicted_bubble": round(sched.predicted(), 6),
            "realized_bubble": round(sched.realized_bubble(), 6),
            "peak_stash_slots": sched.peak_stash_slots(),
        }
    return out


def _bench_decode():
    """Decode-serving evidence for `extra` (r13): paged block-pool
    occupancy + radix dedup on a share-heavy admission, and speculative
    acceptance/steps-per-token through a byte-identical draft entry.
    Deterministic hand-stepped engines — counters, not wall-clock."""
    from paddle_tpu.serving.decode import GenerationEngine, build_decoder_model

    geom = dict(vocab_size=32, hidden=8, num_layers=2, slots=4, max_len=24)
    engine = GenerationEngine(queue_depth=32, breaker_threshold=0)
    tgt = engine.register_model(lambda: build_decoder_model(
        block_size=4, name="bench_dec", version="1", **geom))
    engine.register_model(lambda: build_decoder_model(
        block_size=4, name="bench_dec_draft", version="1", **geom))
    prefix = [3, 1, 4, 1, 5, 9, 2, 6]
    resps = [engine.submit(prefix + [i], model="bench_dec",
                           max_new_tokens=6) for i in range(3)]
    tgt._admit_free_slots()
    mid = tgt.block_pool.stats()
    for _ in range(geom["max_len"]):
        if all(r.done() for r in resps):
            break
        tgt._step()
    engine.start()
    engine.submit(prefix, model="bench_dec", max_new_tokens=10,
                  draft_model="bench_dec_draft",
                  spec_k=3).result(timeout=300)
    st = tgt.stats()
    engine.shutdown()
    return {
        "block_size": tgt.model.block_size,
        "block_pool_occupancy": round(mid["occupancy"], 3),
        "block_dedup_ratio": round(mid["dedup_ratio"], 3),
        "radix_hits": mid["radix_hits"],
        "arena_mib": round(st["arena_mib"], 4),
        "slotted_equivalent_mib": round(st["slotted_equivalent_mib"], 4),
        "spec_acceptance_rate": round(st["spec_acceptance_rate"], 3),
        "spec_steps_per_token": round(st["spec_steps_per_token"], 3),
    }


def _compile_evidence():
    """Compile-cache counters for `extra`: how many traces the run paid,
    how many steps hit the in-memory cache, and whether executables came
    from the persistent tier (core/compile_cache.py ``cache_dir()``)."""
    from paddle_tpu.core.compile_cache import cache_dir
    from paddle_tpu.observability import metrics as obs_metrics

    reg = obs_metrics.registry()

    def val(name):
        m = reg.get(name)
        return int(m.value) if m is not None else 0

    return {
        "traces": val("executor_cache_misses_total"),
        "cache_hits": val("executor_cache_hits_total"),
        "persistent_hits": val("compile_cache_persistent_hits_total"),
        "memory_tier_hits": val("compile_cache_memory_hits_total"),
        "persistent_cache_dir": cache_dir(),
    }


def _bench_resnet(peak):
    """ResNet-50 ImageNet train throughput (BASELINE.md headline 2)."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.models import resnet

    batch = 128
    steps = 20
    main, startup, feeds, fetches = resnet.build_resnet_train(
        depth=50, class_dim=1000, lr=0.1,
        use_amp=not os.environ.get("PADDLE_TPU_BENCH_FP32"),
    )
    exe = fluid.Executor(fluid.TPUPlace(0))
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        rng = np.random.RandomState(0)
        feed = {
            "img": rng.randn(batch, 3, 224, 224).astype("float32"),
            "label": rng.randint(0, 1000, (batch, 1)).astype("int64"),
        }
        for _ in range(3):
            out = exe.run(main, feed=feed, fetch_list=[fetches[0]],
                          return_numpy=False)
        jax.block_until_ready(out[0])
        t0 = time.perf_counter()
        for _ in range(steps):
            out = exe.run(main, feed=feed, fetch_list=[fetches[0]],
                          return_numpy=False)
        jax.block_until_ready(out[0])
        dt = time.perf_counter() - t0
    imgs_per_sec = steps * batch / dt
    # ~7.7 GFLOP fwd per 224x224 image at bs>=1; x3 for fwd+bwd
    flops_per_img = 3 * 7.7e9
    mfu = imgs_per_sec * flops_per_img / peak
    return {
        "metric": "resnet50_imgs_per_sec_per_chip",
        "value": round(imgs_per_sec, 2),
        "unit": "imgs/s",
        "batch": batch,
        "mfu_est": round(mfu, 4),
    }


def _measure_roofline(n=4096, inner=50):
    """Achievable bf16 matmul FLOP/s on THIS chip, timed with the same
    sync discipline the bench uses."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (n, n), jnp.bfloat16)
    w = jax.random.normal(key, (n, n), jnp.bfloat16)

    @jax.jit
    def pure(z, wz):
        def body(_, y):
            return y @ wz
        return jnp.sum(
            jax.lax.fori_loop(0, inner, body, z).astype(jnp.float32)
        )

    jax.block_until_ready(pure(x, w))  # compile + settle
    t0 = time.perf_counter()
    jax.block_until_ready(pure(x, w))
    dt = time.perf_counter() - t0
    return 2 * n * n * n * inner / dt


# published bf16 peaks, FLOP/s per chip (Google Cloud TPU documentation)
_PEAK_BF16 = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5": 459e12,
    "TPU v6 lite": 918e12,
}


def _chip_peak_flops():
    """Published peak bf16 FLOP/s of the local chip; an unknown device is
    an error, not a default."""
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in _PEAK_BF16:
        raise KeyError(
            f"no published bf16 peak recorded for device_kind {kind!r} "
            f"(known: {sorted(_PEAK_BF16)})"
        )
    return _PEAK_BF16[kind]


if __name__ == "__main__":
    main()
