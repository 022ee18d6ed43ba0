"""The quickest proof that the system still starts on the chip.

One process, one ``import jax``, the system's two normal paths through
their public entry points at full width, then — when the host has four
chips — the same training program over a mesh:

  kernels    each registered Pallas kernel against its reference at the
             shape the legs use (flash fwd + three grads; cached decode;
             paged decode at ragged lengths)
  train      models.bert.build_bert_pretrain(BertConfig.base(), s128, bf16
             AMP, gathered MLM head) -> fluid.Executor(TPUPlace(0)) at
             batch 256: flash attention through the registry's default
             mode, rbg dropout bits. Same batch every step: loss must fall.
  serve      serving.GenerationEngine() -> register_model(build_decoder_model
             at hidden 1024 / vocab 32000 / 4 layers / 8 slots / 512 ctx,
             paged, chunked prefill) -> start -> submit x N -> result ->
             shutdown; every generation compared with entry.offline_decode
             (REPORTED, not asserted: bit-identity had only been seen on CPU)
  four_chip  CompiledProgram(main).with_parallel(mesh=(4,) 'data') on the
             same BERT-base program, global batch 256

It fails — non-zero exit, nothing on stdout — unless ``jax.devices()[0]`` is
a TPU. No leg is wrapped: an exception anywhere is the exit status. Stdout
is two lines of JSON, written only once every leg has passed: the REPORT
(what each leg saw), then, last, the VERDICT in the shape the driver's chip
check reads — exactly ``{"ok": true, "device": {"platform", "kind",
"count"}}`` as jax reports the device. Every number of seconds in the
report is labelled set-up or smoke; none is a rate, and ``"claim"`` is
null — measurements belong to the benchmark.

``--rehearse-cpu`` (the on-chip-measurement guide's "make the command run
here first") runs the same code at toy sizes with the Pallas kernels
interpreted, REQUIRES the CPU platform and prints ``"rehearsal": true``.
Sizes change by that flag only, never by what hardware is found.
"""

import argparse
import collections
import contextlib
import json
import math
import sys
import time
from importlib.metadata import version

import jax
import jax.numpy as jnp
import jaxlib
import numpy as np

FULL = {
    "cached": dict(slots=8, length=128, hidden=1024),
    # BertConfig() is BERT-base: 12 layers, hidden 768, 12 heads, vocab 30522
    "train": dict(config={}, batch=256, seq_len=128, max_pred=20, steps=12),
    "serve": dict(vocab_size=32000, hidden=1024, num_layers=4, slots=8,
                  max_len=512, block_size=16, chunk_tokens=64,
                  prompt_lens=(5, 17, 33, 64, 200, 9, 48, 120, 3, 70),
                  max_new_tokens=12),
    "four_chip": dict(steps=6),
}
REHEARSAL = {
    "cached": dict(slots=2, length=16, hidden=8),
    "train": dict(config=dict(vocab_size=128, hidden_size=32,
                              num_hidden_layers=1, num_attention_heads=2,
                              intermediate_size=64,
                              max_position_embeddings=32),
                  batch=8, seq_len=32, max_pred=5, steps=16),
    "serve": dict(vocab_size=64, hidden=16, num_layers=2, slots=4,
                  max_len=32, block_size=4, chunk_tokens=4,
                  prompt_lens=(5, 3, 9, 2, 12, 4, 7, 10),
                  max_new_tokens=6),
    "four_chip": dict(steps=12),
}
SEED = 0
# build_bert_pretrain warms the rate up linearly from 0 over 10,000 steps;
# a peak of 0.2 makes step k run at k * 2e-5: a dozen steps on one batch
# move the loss well clear of the dropout noise, and the rate stays under
# the 5e-4 at which BERT-base was seen to spike on the chip (PR 21)
TRAIN_LR = 0.2


class Compiles:
    """Counts what was compiled or loaded, from jax's own monitoring
    events and the repo's counters, so a leg can state how many
    compilations fell inside its steady window."""

    def __init__(self):
        self.events = collections.Counter()
        jax.monitoring.register_event_listener(
            lambda name, **kw: self.events.update([name]))
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, secs, **kw: self.events.update([name]))

    def snapshot(self):
        from paddle_tpu.observability import metrics

        reg = metrics.registry()

        def val(name):
            m = reg.get(name)
            return int(m.value) if m is not None else 0

        return {
            "lowering_jit_total": val("lowering_jit_total"),
            "executor_cache_misses_total": val("executor_cache_misses_total"),
            "ptcc_persistent_hits": val("compile_cache_persistent_hits_total"),
            "ptcc_persistent_stores":
                val("compile_cache_persistent_stores_total"),
            "xla_backend_compiles_or_loads":
                self.events["/jax/core/compile/backend_compile_duration"],
            "xla_persistent_hits":
                self.events["/jax/compilation_cache/cache_hits"],
            "xla_persistent_misses":
                self.events["/jax/compilation_cache/cache_misses"],
        }

    @contextlib.contextmanager
    def steady_window(self, what):
        """Yields a dict that holds, once the block ends, how far every
        counter moved inside it; the repo's own two must not have."""
        moved = {}
        before = self.snapshot()
        yield moved
        after = self.snapshot()
        moved.update((k, after[k] - before[k]) for k in after)
        for name in ("lowering_jit_total", "executor_cache_misses_total"):
            if moved[name]:
                raise AssertionError(
                    f"{what}: {name} moved by {moved[name]} after warm-up")


def _kernel_fallbacks():
    from paddle_tpu import kernels

    return int(kernels.fallback_counter().value)


def _memory(devices):
    return [
        {k: (d.memory_stats() or {}).get(k)
         for k in ("bytes_in_use", "peak_bytes_in_use")}
        for d in devices
    ]


def _check_flash(attend, shape):
    """``attend(q, k, v, bias)`` in bf16 against the repo's own composite
    (ops/nn.py _sdpa_reference) in f32 at precision "highest": the output
    and all four gradients. Stated tolerance: bf16 operands and outputs
    carry 8 mantissa bits, so each tensor must sit within 2 % of the
    reference's largest magnitude (the measured deviations are printed)."""
    from paddle_tpu.core.registry import OpRegistry

    B, H, S, D = shape
    rng = np.random.RandomState(SEED)
    q, k, v, g = (jnp.asarray(rng.randn(B, H, S, D), jnp.bfloat16)
                  for _ in range(4))
    # padding-style key bias: the last eighth of every other row is masked
    bias_np = np.zeros((B, S), np.float32)
    bias_np[::2, S - S // 8:] = -10000.0
    bias = jnp.asarray(bias_np)
    reference = OpRegistry.get("scaled_dot_product_attention").lowering(
        use_pallas=False)

    def ref_attend(q, k, v, bias):
        with jax.default_matmul_precision("highest"):
            return reference(
                {"Q": [q.astype(jnp.float32)], "K": [k.astype(jnp.float32)],
                 "V": [v.astype(jnp.float32)], "Bias": [bias]},
                {"sm_scale": 1.0 / float(np.sqrt(D))})["Out"][0]

    def grads(fn):
        def loss(q, k, v, bias):
            o = fn(q, k, v, bias)
            return jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32)), o
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3), has_aux=True))

    t0 = time.perf_counter()
    got = jax.block_until_ready(grads(attend)(q, k, v, bias))
    ref = jax.block_until_ready(grads(ref_attend)(q, k, v, bias))
    tol = 0.02
    errs = {}
    for name, a, b in zip(("dq", "dk", "dv", "dbias", "out"),
                          got[0] + (got[1],), ref[0] + (ref[1],)):
        a = a.astype(jnp.float32)
        if not bool(jnp.isfinite(a).all()):
            raise AssertionError(f"flash_attention {name}: non-finite")
        errs[name] = float(jnp.abs(a - b).max() / jnp.abs(b).max())
        if errs[name] > tol:
            raise AssertionError(
                f"flash_attention {name}: max |err| / max |ref| = "
                f"{errs[name]:.4f} > {tol}")
    return {
        "shape": [B, H, S, D], "dtype": "bfloat16", "bias": True,
        "reference": "ops/nn.py _sdpa_reference, f32, precision highest",
        "tolerance_rel_to_max": tol, "max_err_rel_to_max": errs,
        "smoke_seconds_incl_compile": round(time.perf_counter() - t0, 2),
    }


def _flash_shape(sizes):
    """[B, H, S, D] of the attention inside the train leg's model."""
    from paddle_tpu.models import bert

    t = sizes["train"]
    cfg = bert.BertConfig(**t["config"])
    return (t["batch"], cfg.num_attention_heads, t["seq_len"],
            cfg.hidden_size // cfg.num_attention_heads)


def leg_kernels(sizes, interpret):
    """Each registered kernel against its reference, on this device."""
    from paddle_tpu.kernels import attention
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    out = {"flash_attention": _check_flash(
        lambda q, k, v, bias: flash_attention(q, k, v, bias=bias,
                                              interpret=interpret),
        _flash_shape(sizes))}
    rng = np.random.RandomState(SEED)

    c = sizes["cached"]
    Sl, L, Hd = c["slots"], c["length"], c["hidden"]
    qd = jnp.asarray(rng.randn(Sl, Hd), jnp.float32)
    kc = jnp.asarray(rng.randn(Sl, L, Hd), jnp.float32)
    vc = jnp.asarray(rng.randn(Sl, L, Hd), jnp.float32)
    cur = rng.randint(1, L, Sl)
    cb = jnp.asarray(np.where(np.arange(L)[None, :] < cur[:, None], 0.0,
                              -1e9).astype("float32").reshape(Sl, 1, L))
    sm = 1.0 / float(np.sqrt(Hd))
    before = _kernel_fallbacks()
    got = np.asarray(jax.jit(lambda *a: attention.decode_attention(
        *a, sm, interpret=interpret))(qd, kc, vc, cb))
    if _kernel_fallbacks() != before:
        raise AssertionError("cached_attention gave way to its composite")
    ref = np.asarray(jax.jit(lambda *a: attention.cached_attention_composite(
        *a, sm))(qd, kc, vc, cb))
    err = float(np.abs(got - ref).max() / np.abs(ref).max())
    if not np.isfinite(got).all() or err > 1e-2:
        raise AssertionError(f"cached_attention: rel err {err}")
    out["cached_attention"] = {
        "shape": {"slots": Sl, "length": L, "hidden": Hd},
        "max_err_rel_to_max": err,
        "bit_identical_to_composite": got.tobytes() == ref.tobytes(),
    }

    # the blocked paged kernel at the serve leg's geometry, ragged lengths
    # (a free slot, a full one), against its composite at full precision
    from paddle_tpu import kernels

    p = sizes["serve"]
    Sp, Lp, bs, Hp = p["slots"], p["max_len"], p["block_size"], p["hidden"]
    lengths = [0, 1, bs - 1, bs, bs + 1, Lp // 3, Lp - 1, Lp][:Sp]
    lengths += [Lp // 2] * (Sp - len(lengths))
    args = kernels._paged_case(rng, Sp, Lp, bs, Hp, lengths)
    sm = 1.0 / float(np.sqrt(Hp))
    before = _kernel_fallbacks()
    got = np.asarray(jax.jit(lambda *a: attention.paged_attention(
        *a, Sp, Lp, bs, sm, interpret=interpret))(*args))
    if _kernel_fallbacks() != before:
        raise AssertionError("paged_attention gave way to its composite")
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(
            lambda *a: attention.paged_attention_composite(
                *a, Sp, Lp, sm))(*args))
    live = np.asarray(lengths) > 0
    err = float(np.abs(got[live] - ref[live]).max() / np.abs(ref[live]).max())
    if not np.isfinite(got).all() or got[~live].any() or err > 1e-4:
        raise AssertionError(f"paged_attention: rel err {err}")
    out["paged_attention"] = {
        "shape": {"slots": Sp, "length": Lp, "block": bs, "hidden": Hp},
        "lengths": lengths, "max_err_rel_to_max": err,
    }
    return out


def _bert(sizes, flash):
    from paddle_tpu.models import bert
    from paddle_tpu.utils.flags import flags

    t = sizes["train"]
    cfg = bert.BertConfig(**t["config"])
    if flash:
        # the fused kernel applies no attention-prob dropout
        # (models/bert.py enforces it)
        cfg.use_flash_attention = True
        cfg.attention_probs_dropout_prob = 0.0
    flags.rng_impl = "rbg"
    main, startup, _feeds, fetches = bert.build_bert_pretrain(
        cfg, seq_len=t["seq_len"], lr=TRAIN_LR, use_amp=True,
        max_predictions_per_seq=t["max_pred"])
    data = bert.synthetic_batch(
        np.random.RandomState(SEED), t["batch"], t["seq_len"], cfg,
        max_predictions_per_seq=t["max_pred"])
    return cfg, main, startup, fetches[0], data


def _check_losses(losses, what):
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"{what}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(
            f"{what}: loss did not fall on a repeated batch: {losses}")


def _compiled(entry, feed_sig, scope, mesh=None):
    """(optimized HLO text, XLA's memory analysis) of the step that ran.
    Lowering the entry's own jitted function again with the same abstract
    arguments resolves to the same executable (XLA's persistent cache
    serves the second compile)."""
    from paddle_tpu.core import lowering
    from paddle_tpu.parallel.env import mesh_context

    ctx = mesh_context(mesh) if mesh is not None else contextlib.nullcontext()
    with ctx:
        compiled = entry.lower(
            *lowering.abstract_signature(entry, feed_sig, scope)).compile()
    analysis = compiled.memory_analysis()
    return compiled.as_text(), {
        k: getattr(analysis, k + "_size_in_bytes")
        for k in ("temp", "argument", "output", "alias")}


def _feed_sig(data):
    return tuple((n, tuple(data[n].shape), str(data[n].dtype))
                 for n in sorted(data))


def leg_train(sizes, compiles, on_chip):
    import paddle_tpu as fluid
    from paddle_tpu import kernels
    from paddle_tpu.utils import hlo

    flash = kernels.probe("flash_attention")
    if on_chip and not flash:
        raise AssertionError(
            "the registry would not serve flash_attention on this TPU "
            f"(mode {kernels.mode()!r} -> {kernels.resolved_mode()!r})")
    cfg, main, startup, loss, data = _bert(sizes, flash)
    steps = sizes["train"]["steps"]
    device = fluid.TPUPlace(0).jax_device()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace(0))
        t0 = time.perf_counter()
        exe.run(startup)
        first = exe.run(main, feed=data, fetch_list=[loss],
                        return_numpy=False)[0]
        jax.block_until_ready(first)
        compile_s = time.perf_counter() - t0
        warm = exe.run(main, feed=data, fetch_list=[loss],
                       return_numpy=False)[0]
        jax.block_until_ready(warm)

        with compiles.steady_window("train") as in_window:
            t0 = time.perf_counter()
            window = [exe.run(main, feed=data, fetch_list=[loss],
                              return_numpy=False)[0] for _ in range(steps)]
            jax.block_until_ready(window[-1])
            run_s = time.perf_counter() - t0
            # a sync that under-waits would show here: the value fetch
            # after block_until_ready would take as long as the window did
            t0 = time.perf_counter()
            last_value = float(np.asarray(window[-1]).reshape(-1)[0])
            fetch_after_s = time.perf_counter() - t0

        losses = [float(np.asarray(x).reshape(-1)[0])
                  for x in [first, warm] + window]
        _check_losses(losses, "train")
        (entry,) = (e for e in exe._cache.values()
                    if loss.name in e.fetch_names)
        text, compiled_memory = _compiled(entry, _feed_sig(data), scope)
        census = hlo.pallas_custom_calls(text)
        exe.close()
    if on_chip:
        layers = cfg.num_hidden_layers
        for name in ("flash_attention_fwd", "flash_attention_bwd_dkdv",
                     "flash_attention_bwd_dq"):
            # >=: each grad op re-runs its layer's forward kernel for the
            # residuals, so the forward shows up more than once per layer
            if census.get(name, {}).get("count", 0) < layers:
                raise AssertionError(
                    f"train: expected >= {layers} {name} custom calls in "
                    f"the compiled step, found {census}")
    return {
        "model": {"layers": cfg.num_hidden_layers, "hidden": cfg.hidden_size,
                  "heads": cfg.num_attention_heads, "vocab": cfg.vocab_size},
        "amp": "bf16",
        "batch": sizes["train"]["batch"],
        "seq_len": sizes["train"]["seq_len"],
        "max_predictions_per_seq": sizes["train"]["max_pred"],
        "flash_attention": flash, "rng_impl": "rbg",
        "lr_at_step_k": f"k*{TRAIN_LR / 10000:g}",
        "steps_done": len(losses), "steps_in_window": steps,
        "first_loss": losses[0], "last_loss": losses[-1],
        "losses": [round(x, 4) for x in losses],
        "setup_seconds_startup_and_first_step_compile": round(compile_s, 2),
        "smoke_seconds_window_block_until_ready": round(run_s, 3),
        "smoke_seconds_value_fetch_after_sync": round(fetch_after_s, 4),
        "last_value_after_sync": last_value,
        "compilations_in_window": in_window,
        "step_source": entry.source,
        "pallas_custom_calls": census,
        "compiled_step_bytes": compiled_memory,
        "memory": _memory([device]),
    }


def leg_serve(sizes, compiles):
    from paddle_tpu.serving import GenerationEngine, build_decoder_model
    from paddle_tpu.utils import hlo

    s = dict(sizes["serve"])
    prompt_lens = s.pop("prompt_lens")
    max_new = s.pop("max_new_tokens")
    rng = np.random.RandomState(SEED)
    prompts = [[int(t) for t in rng.randint(1, s["vocab_size"], n)]
               for n in prompt_lens]
    chunked = sum(n > s["chunk_tokens"] for n in prompt_lens)
    if len(prompts) < 8 or not chunked:
        raise AssertionError("serve leg wants >= 8 prompts, one chunked")

    t0 = time.perf_counter()
    engine = GenerationEngine()
    entry = engine.register_model(lambda: build_decoder_model(
        name="smoke_decoder", version="1", **s))
    compile_s = time.perf_counter() - t0
    sources = dict(entry.compile_sources)
    census = {kind: hlo.pallas_custom_calls(executable.as_text())
              for kind, (_e, executable) in entry._entries.items()}

    with compiles.steady_window("serve") as in_window:
        t0 = time.perf_counter()
        engine.start()
        responses = [engine.submit(p, max_new_tokens=max_new)
                     for p in prompts]
        served = [[int(t) for t in r.result(timeout=600)["tokens"]]
                  for r in responses]
        run_s = time.perf_counter() - t0
        stats = engine.entry().stats()
        engine.shutdown()
    if dict(entry.compile_sources) != sources:
        raise AssertionError(
            f"serve: compile_sources moved {sources} -> "
            f"{entry.compile_sources}")

    for p, toks in zip(prompts, served):
        if not 1 <= len(toks) <= max_new or \
                not all(0 <= t < s["vocab_size"] for t in toks):
            raise AssertionError(f"serve: bad generation {toks}")
    # reported, not asserted: see the module docstring
    equal = [toks == [int(t) for t in entry.offline_decode(p, max_new)]
             for p, toks in zip(prompts, served)]
    return {
        "geometry": s, "prompt_lens": list(prompt_lens),
        "chunked_prefill_prompts": chunked, "max_new_tokens": max_new,
        "place": repr(engine.place), "device": str(engine.device),
        "requests_completed": len(served),
        "tokens_generated": sum(len(t) for t in served),
        "equal_to_offline_decode": sum(equal),
        "all_equal_to_offline_decode": all(equal),
        "setup_seconds_register_compile": round(compile_s, 2),
        "smoke_seconds_serve_window": round(run_s, 3),
        "compile_sources": sources,
        "compilations_in_window": in_window,
        "pallas_custom_calls": census,
        "block_pool": stats["block_pool"],
        "failed": stats.get("failed", 0),
        "memory": _memory([engine.device]),
    }


def leg_four_chip(sizes, compiles):
    import paddle_tpu as fluid
    from paddle_tpu import compiler, kernels
    from paddle_tpu.core.registry import OpRegistry
    from paddle_tpu.parallel.env import make_mesh, mesh_context
    from paddle_tpu.utils import hlo

    n = 4
    mesh = make_mesh((n,), ("data",))
    devices = list(mesh.devices.flat)
    cfg, main, startup, loss, data = _bert(
        sizes, kernels.probe("flash_attention"))
    steps = sizes["four_chip"]["steps"]
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup)
        prog = fluid.CompiledProgram(main).with_parallel(
            mesh=mesh, loss_name=loss.name)
        t0 = time.perf_counter()
        first = exe.run(prog, feed=data, fetch_list=[loss],
                        return_numpy=False)[0]
        jax.block_until_ready(first)
        compile_s = time.perf_counter() - t0
        (entry,) = prog._cache.values()
        with compiles.steady_window("four_chip") as in_window:
            t0 = time.perf_counter()
            window = [exe.run(prog, feed=data, fetch_list=[loss],
                              return_numpy=False)[0] for _ in range(steps)]
            jax.block_until_ready(window[-1])
            run_s = time.perf_counter() - t0
        losses = [float(np.asarray(x).reshape(-1)[0])
                  for x in [first] + window]
        _check_losses(losses, "four_chip")

        # the feed as CompiledProgram commits it on every step
        names = sorted(data)
        ids = compiler._to_global(
            data["input_ids"],
            entry.meta["feed_shardings"][names.index("input_ids")])
        param = scope.find_var("layer_0.ffn1.w")
        text, compiled_memory = _compiled(entry, _feed_sig(data), scope,
                                          mesh=mesh)
        memory = _memory(devices)
    # the kernel under a data x tensor-parallel mesh, through the op's own
    # lowering: GSPMD cannot partition a Mosaic call, so ops/nn.py runs it
    # per shard, and the bias cotangent sums over the head shards
    shape = _flash_shape(sizes)
    sdpa = OpRegistry.get("scaled_dot_product_attention").lowering()
    with mesh_context(make_mesh((2, 2), ("data", "model"))):
        flash_on_mesh = _check_flash(
            lambda q, k, v, bias: sdpa(
                {"Q": [q], "K": [k], "V": [v], "Bias": [bias]},
                {"sm_scale": 1.0 / math.sqrt(shape[-1])})["Out"][0],
            shape)
    spread = {
        "feed_devices": len(ids.sharding.device_set),
        "feed_shard_shape": list(ids.addressable_shards[0].data.shape),
        "param_devices": len(param.sharding.device_set),
        "all_reduce_in_hlo": text.count("all-reduce("),
    }
    if spread["feed_devices"] != n or spread["param_devices"] != n \
            or not spread["all_reduce_in_hlo"]:
        raise AssertionError(f"four_chip: work is not spread: {spread}")
    in_use = [m["bytes_in_use"] for m in memory]
    if None not in in_use and min(in_use) < 2**20:
        raise AssertionError(f"four_chip: an idle device: {in_use}")
    return {
        "ran": True, "mesh": {"data": n},
        "devices": [str(d) for d in devices],
        "global_batch": sizes["train"]["batch"],
        "steps_done": len(losses),
        "first_loss": losses[0], "last_loss": losses[-1],
        "losses": [round(x, 4) for x in losses],
        "setup_seconds_first_step_compile": round(compile_s, 2),
        "smoke_seconds_window_block_until_ready": round(run_s, 3),
        "compilations_in_window": in_window,
        "spread": spread,
        "flash_attention_mesh_2x2_data_model": flash_on_mesh,
        "pallas_custom_calls": hlo.pallas_custom_calls(text),
        "compiled_step_bytes_per_device": compiled_memory,
        "memory": memory,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy sizes on the CPU platform, kernels interpreted")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    want = "cpu" if args.rehearse_cpu else "tpu"
    if dev.platform != want:
        sys.exit(
            f"chip_smoke.py{' --rehearse-cpu' if args.rehearse_cpu else ''} "
            f"needs platform {want!r}; jax.devices()[0] is {dev!r} "
            f"(platform {dev.platform!r})")

    from paddle_tpu import kernels
    from paddle_tpu.core import compile_cache

    sizes = REHEARSAL if args.rehearse_cpu else FULL
    on_chip = not args.rehearse_cpu
    compiles = Compiles()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    report = {
        "device": device,
        "rehearsal": args.rehearse_cpu,
        "local_device_count": jax.local_device_count(),
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": version("libtpu"),
                     "python": sys.version.split()[0]},
        "compile_cache_dir": compile_cache.cache_dir(),
        "compile_cache_enabled": compile_cache.enabled(),
    }
    # the rehearsal interprets the Pallas kernels (the guide's recipe); on
    # the chip the registry's DEFAULT mode must pick the compiled ones
    mode = (kernels.scoped_mode("interpret") if args.rehearse_cpu
            else contextlib.nullcontext())
    t0 = time.perf_counter()
    with mode:
        report["kernels_resolved_mode"] = kernels.resolved_mode()
        report["kernels"] = leg_kernels(sizes, args.rehearse_cpu)
        report["train"] = leg_train(sizes, compiles, on_chip)
        report["serve"] = leg_serve(sizes, compiles)
        if jax.local_device_count() >= 4:
            report["four_chip"] = leg_four_chip(sizes, compiles)
        else:
            report["four_chip"] = {"ran": False,
                                   "devices": jax.local_device_count()}
    report["kernel_fallbacks_total"] = _kernel_fallbacks()
    report["flash_grid"] = kernels.flash_grid_snapshot()
    report["compiles_total"] = compiles.snapshot()
    report["smoke_seconds_total"] = round(time.perf_counter() - t0, 1)
    report["claim"] = None
    print(json.dumps(report))
    # the verdict: these keys and no others, last on stdout
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
