#!/usr/bin/env python
"""Serving load generator: closed/open loop over ServingEngine, and
open-loop continuous-batching decode over GenerationEngine.

Closed loop (`--mode closed`): N concurrent clients, each submitting its
next request the moment the previous one returns — measures saturated
throughput and the batcher's coalescing gain. Open loop (`--mode open`):
Poisson arrivals at `--rate` req/s regardless of completions — measures
SLO behavior under offered load, including explicit backpressure
(rejections counted, not retried). Both report one JSON line:
throughput, p50/p99 queue+total latency, mean batch occupancy,
rejection/deadline counters, and the post-warmup compile-cache hit rate
(anything < 1.0 means the bucket lattice is mis-sized for the traffic).

Decode (`--decode`): open-loop autoregressive generation through the
continuous-batching engine (serving/decode) — Poisson arrivals of
mixed-length prompts from weighted tenants, optionally swept over
`--rates`. Reports slot occupancy, tokens/step, tokens/s, per-tenant
token counts and completion ranks, and the occupancy gain over a
request-at-a-time baseline (the PR-2 bucketing discipline: the same
completed requests grouped into admission-order batches of S, each
holding every slot for max(tokens) iterations — what the engine would
have done without iteration-level retirement).

Generation modes (r17): `--sample` replays a committed-threefry sampled
workload through TWO shuffled admission orders and bit-compares both
against the offline reference; `--beam` runs width-3 COW beam search and
bit-compares every ranked hypothesis against the offline beam reference
while asserting block-pool conservation across fork/prune.

`--smoke` runs a seconds-scale configuration and asserts the invariants
(all served, zero retrace after warmup; for --decode also continuous-
vs-offline bit-identity, occupancy gain > 1.5x, and the KERNEL parity
leg: the same paged+chunked+speculative workload under
PADDLE_TPU_KERNELS=off vs =interpret must produce the same
tokens; for --sample/--beam also replay bit-identity, zero retraces
after warmup, and beam block-conservation) — wired into tier-1 CI by
tests/test_serving.py and tests/test_decode.py.

Usage:
  python tools/bench_serving.py [--mode closed|open] [--requests 512]
      [--clients 8] [--rate 200] [--replicas 2] [--max-batch 8]
      [--seq 0] [--deadline-ms 0] [--smoke]
  python tools/bench_serving.py --decode [--requests 128] [--slots 8]
      [--max-len 64] [--rates 50,200,800] [--paged] [--spec]
      [--sample] [--beam] [--smoke]
"""

import argparse
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _save_model(tmpdir, feat=8, seq=0):
    """Tiny fc stack; with --seq a per-token head over a [-1, -1, feat]
    input (the padded-axis path)."""
    import paddle_tpu as fluid
    from paddle_tpu.core.ir import Program, program_guard

    main, startup = Program(), Program()
    with program_guard(main, startup):
        if seq:
            x = fluid.data("x", [-1, -1, feat])
            h = fluid.layers.fc(x, 16, act="relu", num_flatten_dims=2)
            pred = fluid.layers.fc(h, 4, num_flatten_dims=2)
        else:
            x = fluid.data("x", [-1, feat])
            h = fluid.layers.fc(x, 16, act="relu")
            pred = fluid.layers.fc(h, 4)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        model_dir = os.path.join(tmpdir, "model")
        fluid.io.save_inference_model(model_dir, ["x"], [pred], exe,
                                      main_program=main)
    return model_dir


def _make_request(rng, args):
    rows = int(rng.randint(1, 3))
    if args.seq:
        ln = int(rng.randint(2, args.seq + 1))
        return {"x": rng.randn(rows, ln, args.feat).astype("float32")}
    return {"x": rng.randn(rows, args.feat).astype("float32")}


def run_closed(engine, args, rng):
    from paddle_tpu.serving import ServingError

    lock = threading.Lock()
    served, errors = [], []
    per_client = args.requests // args.clients

    def client(cid):
        crng = np.random.RandomState(1000 + cid)
        for i in range(per_client):
            try:
                resp = engine.submit(
                    _make_request(crng, args), priority=i % 3,
                    deadline_ms=args.deadline_ms or None,
                )
                out = resp.result(timeout=120)
                with lock:
                    served.append(out)
            except ServingError as e:
                with lock:
                    errors.append(e.code)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(args.clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return len(served), errors, time.perf_counter() - t0


def run_open(engine, args, rng):
    from paddle_tpu.serving import ServingError

    responses, errors = [], []
    t0 = time.perf_counter()
    for i in range(args.requests):
        time.sleep(float(rng.exponential(1.0 / args.rate)))
        try:
            responses.append(engine.submit(
                _make_request(rng, args), priority=i % 3,
                deadline_ms=args.deadline_ms or None,
            ))
        except ServingError as e:
            errors.append(e.code)
    served = 0
    for r in responses:
        try:
            r.result(timeout=120)
            served += 1
        except ServingError as e:
            errors.append(e.code)
    return served, errors, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# continuous-batching decode (--decode)
# ---------------------------------------------------------------------------

TENANT_WEIGHTS = {"gold": 2.0, "silver": 1.0}


def _decode_workload(rng, n, max_len, vocab):
    """Alternating short/long requests (the shape where request-at-a-time
    bucketing wastes the most slot-steps: every short request waits for
    the long batchmate to drain)."""
    reqs = []
    tenants = sorted(TENANT_WEIGHTS)
    for i in range(n):
        plen = int(rng.randint(1, 5))
        prompt = [int(t) for t in rng.randint(0, vocab, size=plen)]
        room = max_len - plen
        if i % 2:
            max_new = int(rng.randint(max(room - 4, 1), room + 1))
        else:
            max_new = int(rng.randint(1, 4))
        reqs.append((prompt, max_new,
                     tenants[int(rng.randint(len(tenants)))]))
    return reqs


def _baseline_occupancy(token_counts, slots):
    """Request-at-a-time occupancy on the SAME completed requests: batches
    of S in admission order, each running max(tokens) iterations with no
    mid-flight retirement or admission."""
    total = wasted_steps = 0
    for i in range(0, len(token_counts), slots):
        group = token_counts[i:i + slots]
        total += sum(group)
        wasted_steps += slots * max(group)
    return total / float(max(wasted_steps, 1))


def _jit_count():
    from paddle_tpu.observability import metrics as obs_metrics

    m = obs_metrics.registry().get("lowering_jit_total")
    return int(m.value) if m is not None else 0


def run_decode(args, rng):
    from paddle_tpu.serving.decode import GenerationEngine, build_decoder_model

    engine = GenerationEngine(queue_depth=args.queue_depth,
                              breaker_threshold=0)
    for tenant, weight in TENANT_WEIGHTS.items():
        engine.set_tenant(tenant, weight=weight)
    t0 = time.perf_counter()
    entry = engine.register_model(lambda: build_decoder_model(
        vocab_size=args.vocab, hidden=args.hidden, num_layers=args.layers,
        slots=args.slots, max_len=args.max_len, name="bench", version="1",
    ))
    engine.start()
    # warmup: one request per slot, drained — steady-state executables
    for r in [engine.submit([1, 2], max_new_tokens=2)
              for _ in range(args.slots)]:
        r.result(timeout=120)
    warm_s = time.perf_counter() - t0
    jits_warm = _jit_count()

    m = entry.metrics
    sweep = []
    mismatches = errors = served = verified = 0
    sample = None if args.smoke else args.verify  # None = every request
    for rate in args.rates:
        reqs = _decode_workload(rng, args.requests, args.max_len, args.vocab)
        steps0 = m.count("decode_steps")
        active0 = m.count("active_slot_steps")
        tokens0 = m.count("generated_tokens")
        t0 = time.perf_counter()
        resps = []
        for prompt, max_new, tenant in reqs:
            time.sleep(float(rng.exponential(1.0 / rate)))
            try:
                resps.append(engine.submit(prompt, max_new_tokens=max_new,
                                           tenant=tenant))
            except Exception:
                # open-loop overload IS the measured regime: a rejected
                # submit (queue full / quota) is an error datum, not a
                # bench crash
                resps.append(None)
        outs = []
        for r in resps:
            if r is None:
                outs.append(None)
                errors += 1
                continue
            try:
                outs.append([int(t) for t in r.result(timeout=300)["tokens"]])
                served += 1
            except Exception:
                outs.append(None)
                errors += 1
        wall = time.perf_counter() - t0
        counts = [len(o) for o in outs if o is not None]
        steps = m.count("decode_steps") - steps0
        occupancy = ((m.count("active_slot_steps") - active0)
                     / float(max(steps, 1) * args.slots))
        baseline = _baseline_occupancy(counts, args.slots)
        # bit-identity vs the offline whole-sequence reference (every
        # request under --smoke; a sample otherwise — offline replays the
        # full prefill per token, so it dominates the bench runtime)
        for (prompt, max_new, _t), out in list(zip(reqs, outs))[:sample]:
            if out is None:
                continue
            verified += 1
            if out != entry.offline_decode(prompt, max_new):
                mismatches += 1
        sweep.append({
            "rate_req_per_s": rate,
            "occupancy": round(occupancy, 3),
            "baseline_occupancy": round(baseline, 3),
            "occupancy_gain": round(occupancy / max(baseline, 1e-9), 2),
            "tokens_per_step": round(
                (m.count("generated_tokens") - tokens0) / max(steps, 1), 2),
            "tokens_per_sec": round(sum(counts) / max(wall, 1e-9), 1),
            "decode_steps": steps,
        })

    # fairness burst: equal offered load per tenant under full contention;
    # the weight-2 tenant's requests should finish earlier (smaller mean
    # completion rank), tokens split tracking the 2:1 stride shares
    burst = []
    for i in range(args.slots * 4):
        tenant = sorted(TENANT_WEIGHTS)[i % 2]
        try:
            burst.append((tenant, engine.submit(
                [int(x) for x in rng.randint(0, args.vocab, size=2)],
                max_new_tokens=6, tenant=tenant)))
        except Exception:
            errors += 1
    done = []
    for tenant, resp in burst:
        try:
            resp.result(timeout=300)
            done.append((tenant, resp))
        except Exception:
            errors += 1
    ranks = {}
    for rank, (tenant, _r) in enumerate(
            sorted(done, key=lambda x: x[1].finish_time)):
        ranks.setdefault(tenant, []).append(rank)
    mean_rank = {t: round(sum(r) / len(r), 2) for t, r in ranks.items()}

    # the main engine's retrace gate closes HERE: the paged/spec legs
    # below build their own (new) models, whose first-build traces are
    # inherent, not retraces
    jits_end = _jit_count()
    stats = entry.stats()

    paged = _paged_sweep(args, rng) if args.paged else None
    spec = _spec_leg(args, rng) if args.spec else None
    sampled = _sample_leg(args, rng) if args.sample_leg else None
    beam = _beam_leg(args, rng) if args.beam_leg else None
    overload = (_overload_leg(args, rng)
                if (args.overload_leg or args.smoke) else None)
    kernel_parity = _kernel_modes_leg(args) if args.smoke else None

    engine.shutdown()
    last = sweep[-1]
    report = {
        "metric": "serving_decode_tokens_per_sec",
        "value": last["tokens_per_sec"],
        "unit": "tok/s",
        "extra": {
            "mode": "decode",
            "slots": args.slots, "max_len": args.max_len,
            "arena_mib": round(stats["arena_mib"], 3),
            "served": served, "errors": errors,
            "offline_mismatches": mismatches,
            "verified_bit_identical": verified,
            "sweep": sweep,
            "warmup_seconds": round(warm_s, 2),
            "retraces_after_warmup": jits_end - jits_warm,
            "compile_sources": stats["compile_sources"],
            "prefix_hits": stats["prefix_hits"],
            "tenant_tokens": stats["tenant_tokens"],
            "tenant_weights": TENANT_WEIGHTS,
            "fairness_mean_completion_rank": mean_rank,
            "latency_p50_s": round(stats["latency_p50_s"], 5),
            "latency_p99_s": round(stats["latency_p99_s"], 5),
            "queue_wait_p99_s": round(stats["queue_wait_p99_s"], 5),
            "decode_step_p99_s": round(stats["decode_step_p99_s"], 5),
        },
    }
    if paged is not None:
        report["extra"]["paged"] = paged
    if spec is not None:
        report["extra"]["spec"] = spec
    if sampled is not None:
        report["extra"]["sample"] = sampled
    if beam is not None:
        report["extra"]["beam"] = beam
    if overload is not None:
        report["extra"]["overload"] = overload
    if kernel_parity is not None:
        report["extra"]["kernel_parity"] = kernel_parity
    print(json.dumps(report))
    if args.smoke:
        assert kernel_parity["bit_identical"], kernel_parity
        assert errors == 0 and served == args.requests * len(args.rates), \
            (served, errors)
        assert mismatches == 0, f"{mismatches} continuous!=offline"
        assert jits_end == jits_warm, \
            f"{jits_end - jits_warm} retraces after warmup"
        assert last["occupancy_gain"] > 1.5, sweep
        if paged is not None:
            for leg in paged["sweep"]:
                assert leg["offline_mismatches"] == 0, leg
            shared = [leg for leg in paged["sweep"]
                      if leg["block_size"] < args.max_len]
            assert any(leg["radix_hits"] > 0 for leg in shared), paged
            assert any(leg["peak_dedup_ratio"] > 1.0 for leg in shared), \
                paged
        if spec is not None:
            assert spec["offline_mismatches"] == 0, spec
            assert spec["steps_per_token"] < 1.0, spec
            assert spec["retraces"] == 0, spec
        if sampled is not None:
            assert sampled["bit_identical"], sampled
            assert sampled["retraces"] == 0, sampled
        if beam is not None:
            assert beam["tokens_bit_identical"], beam
            assert beam["conservation_ok"], beam
            assert beam["beam_forks"] > 0, beam
            assert beam["retraces"] == 0, beam
        if overload is not None:
            p = overload["park"]
            assert overload["bit_identical"], overload
            assert p["failed"] == 0, overload
            assert overload["goodput_admitted"] == 1.0, overload
            assert p["parked"] >= 1 and p["resumed"] >= 1, overload
            assert p["completed"] >= overload["shed_only"]["completed"], \
                overload
            assert p["retraces"] == 0, overload
        print("DECODE_SMOKE_OK")
    return 0


def _kernel_modes_leg(args):
    """Kernel on/off gate (PADDLE_TPU_KERNELS): the same paged + chunked
    + speculative workload decoded hand-stepped under the registry's
    "off" (composite fallbacks) and "interpret" (Pallas kernels through
    the interpreter) modes must produce the SAME tokens for every
    request — the blocked paged-attention kernel is within 1e-5 of its
    composite (an online softmax, not its bytes), and this is where that
    is held against the real engine, not a unit harness."""
    from paddle_tpu import kernels
    from paddle_tpu.serving.decode import GenerationEngine, build_decoder_model

    prompts = [[7, 3, 9, 2, 11, 5, 8, 1, 4], [7, 3, 9, 2, 11, 5, 8, 1],
               [1, 2], [9, 9, 4, 4, 1, 2, 3, 4, 5, 6, 7, 8]]

    def drive(mode):
        with kernels.scoped_mode(mode):
            engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
            geom = dict(vocab_size=args.vocab, hidden=args.hidden,
                        num_layers=args.layers, slots=args.slots,
                        max_len=args.max_len)
            entry = engine.register_model(lambda: build_decoder_model(
                block_size=4, chunk_tokens=4, name="bench_kmode",
                version="1", **geom))
            engine.register_model(lambda: build_decoder_model(
                block_size=4, name="bench_kmode_draft", version="1",
                **geom))
            resps = [engine.submit(p, max_new_tokens=5,
                                    model="bench_kmode") for p in prompts]
            resps.append(engine.submit(
                prompts[0], max_new_tokens=5, model="bench_kmode",
                draft_model="bench_kmode_draft", spec_k=2))
            for _ in range(args.max_len * 4):
                if all(r.done() for r in resps):
                    break
                entry._iterate()
            outs = [[int(t) for t in r.result(timeout=120)["tokens"]]
                    for r in resps]
            engine.shutdown()
            return outs

    off = drive("off")
    interp = drive("interpret")
    return {
        "modes": ["off", "interpret"],
        "requests": len(off),
        "bit_identical": off == interp,
    }


def _sample_leg(args, rng):
    """Committed-threefry sampled decode (r17): the SAME sampled workload
    admitted in TWO shuffled orders must byte-equal the offline
    whole-sequence reference both times — the stream is keyed per
    (request seed, emitted-token index), so batchmates, slots, and
    arrival timing never enter it. Zero retraces: the policy runs on the
    host over the one compiled logits fetch."""
    from paddle_tpu.serving.decode import (
        GenerationEngine,
        SamplingParams,
        build_decoder_model,
    )

    engine = GenerationEngine(queue_depth=args.queue_depth,
                              breaker_threshold=0)
    entry = engine.register_model(lambda: build_decoder_model(
        vocab_size=args.vocab, hidden=args.hidden, num_layers=args.layers,
        slots=args.slots, max_len=args.max_len, block_size=4,
        name="bench_sample", version="1"))
    n = max(args.slots * 2, 8)
    prompts = [[int(t) for t in rng.randint(0, args.vocab,
                                            size=int(rng.randint(1, 6)))]
               for _ in range(n)]
    sp = SamplingParams(temperature=0.9, top_k=8, top_p=0.95, seed=1234)
    refs = [entry.offline_decode(p, 6, sampling=sp) for p in prompts]
    jits0 = _jit_count()
    engine.start()
    identical = True
    for order_seed in (0, 1):
        order = np.random.RandomState(order_seed).permutation(n)
        resps = {}
        for i in order:
            resps[int(i)] = engine.submit(prompts[i], max_new_tokens=6,
                                          sampling=sp)
        outs = [[int(t) for t in resps[i].result(timeout=300)["tokens"]]
                for i in range(n)]
        identical = identical and outs == refs
    st = entry.stats()
    engine.shutdown()
    return {
        "requests": n,
        "admission_orders": 2,
        "params": sp.describe(),
        "bit_identical": identical,
        "sampled_tokens": st["sampled_tokens"],
        "retraces": _jit_count() - jits0,
    }


def _beam_leg(args, rng):
    """Width-3 COW beam search (r17): every ranked hypothesis byte-equals
    the offline beam reference; forks/prunes are counted and the block
    pool's free/cached/live partition is re-asserted after retirement
    (conservation across fork = refcount++ / prune = release)."""
    from paddle_tpu.serving.decode import (
        BeamParams,
        GenerationEngine,
        build_decoder_model,
    )

    engine = GenerationEngine(queue_depth=args.queue_depth,
                              breaker_threshold=0)
    entry = engine.register_model(lambda: build_decoder_model(
        vocab_size=args.vocab, hidden=args.hidden, num_layers=args.layers,
        slots=args.slots, max_len=args.max_len, block_size=4, eos_id=0,
        name="bench_beam", version="1"))
    prompts = [[int(t) for t in rng.randint(1, args.vocab,
                                            size=int(rng.randint(2, 6)))]
               for _ in range(4)]
    refs = [entry.offline_beam(p, 6, BeamParams(3)) for p in prompts]
    jits0 = _jit_count()
    engine.start()
    identical = True
    for p, ref in zip(prompts, refs):
        got = engine.submit(p, max_new_tokens=6,
                            beam_width=3).result(timeout=300)
        identical = identical and (
            [[int(t) for t in h["tokens"]] for h in got["beams"]]
            == [list(rt) for rt, _rs in ref])
    entry.block_pool.check_conservation()
    st = entry.stats()
    conserved = st["block_pool"]["blocks_live"] == 0
    engine.shutdown()
    return {
        "requests": len(prompts),
        "width": 3,
        "tokens_bit_identical": identical,
        "beam_forks": st["beam_forks"],
        "beam_prunes": st["beam_prunes"],
        "beam_finished": st["beam_finished"],
        "conservation_ok": conserved,
        "retraces": _jit_count() - jits0,
    }


def _paged_sweep(args, rng):
    """Block-size sweep over a SHARE-HEAVY workload (half the prompts
    extend one common prefix): small blocks let the radix tree dedup
    physical storage; block_size == max_len is the degenerate slotted
    design (one block per slot, zero sharing possible beyond whole-slot
    geometry). Mid-flight pool state is sampled hand-stepped (no
    scheduler thread) so the dedup numbers are deterministic."""
    from paddle_tpu.serving.decode import GenerationEngine, build_decoder_model

    out = []
    for bs in (4, args.max_len):
        engine = GenerationEngine(queue_depth=args.queue_depth,
                                  breaker_threshold=0)
        entry = engine.register_model(lambda bs=bs: build_decoder_model(
            vocab_size=args.vocab, hidden=args.hidden,
            num_layers=args.layers, slots=args.slots, max_len=args.max_len,
            block_size=bs, name=f"bench_paged{bs}", version="1",
        ))
        shared_prefix = [int(t) for t in rng.randint(0, args.vocab, size=8)]
        reqs = []
        for i in range(args.slots):
            if i % 2 == 0:
                prompt = shared_prefix + [int(rng.randint(0, args.vocab))]
            else:
                prompt = [int(t) for t in
                          rng.randint(0, args.vocab,
                                      size=int(rng.randint(2, 6)))]
            reqs.append((prompt, 6))
        refs = [entry.offline_decode(p, n) for p, n in reqs]
        resps = [engine.submit(p, max_new_tokens=n) for p, n in reqs]
        entry._admit_free_slots()
        mid = entry.block_pool.stats()          # sampled while live
        for _ in range(args.max_len):
            if all(r.done() for r in resps):
                break
            entry._step()
        mism = sum(
            1 for r, ref in zip(resps, refs)
            if [int(t) for t in r.result(timeout=120)["tokens"]] != ref)
        st = entry.stats()
        out.append({
            "block_size": bs,
            "num_blocks": entry.model.num_blocks,
            "arena_mib": round(st["arena_mib"], 3),
            "slotted_equivalent_mib":
                round(st["slotted_equivalent_mib"], 3),
            "peak_occupancy": round(mid["occupancy"], 3),
            "peak_dedup_ratio": round(mid["dedup_ratio"], 3),
            "radix_hits": mid["radix_hits"],
            "cow_copies": st["block_pool"]["cow_copies"],
            "offline_mismatches": mism,
        })
        engine.shutdown()
    return {"sweep": out}


def _spec_leg(args, rng):
    """Speculative decoding on a repeat-heavy workload: draft = a second
    registry entry with the TARGET's geometry (deterministic init makes
    the weights byte-identical — the acceptance upper bound, and the
    honest way to measure the machinery without a trained draft), plus a
    distinct-geometry draft leg whose acceptance is reported unasserted."""
    from paddle_tpu.serving.decode import GenerationEngine, build_decoder_model

    engine = GenerationEngine(queue_depth=args.queue_depth,
                              breaker_threshold=0)
    geom = dict(vocab_size=args.vocab, hidden=args.hidden,
                num_layers=args.layers, slots=args.slots,
                max_len=args.max_len)
    tgt = engine.register_model(lambda: build_decoder_model(
        name="bench_spec_t", version="1", **geom))
    engine.register_model(lambda: build_decoder_model(
        name="bench_spec_d", version="1", **geom))
    engine.register_model(lambda: build_decoder_model(
        name="bench_spec_d1", version="1", **{**geom, "num_layers": 1}))
    # repeat-heavy prompts: short cycles the greedy head locks onto
    base = [int(t) for t in rng.randint(0, args.vocab, size=2)]
    reqs = [(base * 2, 12), (base * 3, 10), (base * 2 + [1], 12),
            (base * 2, 12)]
    refs = [tgt.offline_decode(p, n) for p, n in reqs]
    jits0 = _jit_count()
    engine.start()
    resps = [engine.submit(p, model="bench_spec_t", max_new_tokens=n,
                           draft_model="bench_spec_d", spec_k=3)
             for p, n in reqs]
    mism = sum(
        1 for r, ref in zip(resps, refs)
        if [int(t) for t in r.result(timeout=300)["tokens"]] != ref)
    st = tgt.stats()
    identical = {
        "steps_per_token": round(st["spec_steps_per_token"], 3),
        "acceptance_rate": round(st["spec_acceptance_rate"], 3),
    }
    # distinct-draft leg: acceptance is a property of the models, so it
    # is REPORTED, never gated
    d_resps = [engine.submit(p, model="bench_spec_t", max_new_tokens=n,
                             draft_model="bench_spec_d1", spec_k=3)
               for p, n in reqs[:2]]
    mism += sum(
        1 for r, ref in zip(d_resps, refs[:2])
        if [int(t) for t in r.result(timeout=300)["tokens"]] != ref)
    st2 = tgt.stats()
    engine.shutdown()
    return {
        "spec_k": 3,
        "steps_per_token": identical["steps_per_token"],
        "acceptance_rate": identical["acceptance_rate"],
        "distinct_draft_acceptance_rate": round(
            (st2["spec_accepted_tokens"] - st["spec_accepted_tokens"])
            / max(st2["spec_proposed_tokens"]
                  - st["spec_proposed_tokens"], 1), 3),
        "target_steps": st2["spec_target_steps"],
        "emitted_tokens": st2["spec_emitted_tokens"],
        "offline_mismatches": mism,
        "retraces": _jit_count() - jits0,
    }


def _overload_leg(args, rng):
    """r18 graceful-degradation leg: the SAME 2x-overload open-loop
    burst through an undersized block pool (12 rows, 2 slots), once
    with the host KV tier enabled (exhaustion parks, sessions resume)
    and once with it zeroed (parking impossible — the shed-only
    baseline where mid-generation exhaustion fails the request). The
    park leg must lose NOTHING it admitted (goodput-of-admitted 1.0,
    every completion bit-identical to offline) and complete at least as
    many requests as the shed-only baseline, with zero retraces — the
    spill/re-inject path reuses the admission inject/prefill
    executables. The brownout ladder runs hot through the burst; its
    transition log is returned as the overload witness."""
    from paddle_tpu.serving.decode import GenerationEngine, build_decoder_model

    n = 8
    prompts = [[int(t) for t in rng.randint(0, args.vocab, size=4)]
               for _ in range(n)]

    def drive(host_tier_mb, name):
        engine = GenerationEngine(queue_depth=n * 2 + 8,
                                  breaker_threshold=0,
                                  host_tier_mb=host_tier_mb)
        entry = engine.register_model(lambda: build_decoder_model(
            vocab_size=args.vocab, hidden=args.hidden,
            num_layers=args.layers, slots=2, max_len=16, block_size=2,
            num_blocks=6, name=name, version="1"))
        refs = [entry.offline_decode(p, 6) for p in prompts]
        engine.start()
        # warm: one request per slot drained, then close the jit gate
        for r in [engine.submit([1, 2], max_new_tokens=2)
                  for _ in range(2)]:
            r.result(timeout=120)
        jits0 = _jit_count()
        resps = []
        shed = 0
        for p in prompts:
            time.sleep(0.001)
            try:
                resps.append(engine.submit(p, max_new_tokens=6))
            except Exception:
                resps.append(None)     # brownout shed at the door
                shed += 1
        completed = failed = mismatches = 0
        for r, ref in zip(resps, refs):
            if r is None:
                continue
            try:
                out = [int(t) for t in r.result(timeout=300)["tokens"]]
                completed += 1
                if out != ref:
                    mismatches += 1
            except Exception:
                failed += 1
        st = entry.stats()
        engine.shutdown()
        return {
            "admitted": n - shed, "shed": shed,
            "completed": completed, "failed": failed,
            "mismatches": mismatches,
            "parked": st["sessions_parked"],
            "resumed": st["sessions_resumed"],
            "resume_replays": st["resume_replays"],
            "host_tier": {k: st["host_tier"][k]
                          for k in ("spills", "writebacks", "hits",
                                    "rejected")},
            "brownout_transitions":
                len(st["brownout"]["transitions"]),
            "brownout_peak": max(
                [t["to"] for t in st["brownout"]["transitions"]],
                default=0),
            "retraces": _jit_count() - jits0,
        }

    park = drive(64, "bench_ov")
    shed_only = drive(0, "bench_ov_shed")
    return {
        "requests": n,
        "park": park,
        "shed_only": shed_only,
        "goodput_admitted": round(
            park["completed"] / max(park["admitted"], 1), 3),
        "bit_identical": park["mismatches"] == 0,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mode", choices=("closed", "open"), default="closed")
    ap.add_argument("--requests", type=int, default=512)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--rate", type=float, default=200.0,
                    help="open-loop offered load, req/s")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=0,
                    help="max padded-axis length (0 = fixed-shape model)")
    ap.add_argument("--feat", type=int, default=8)
    ap.add_argument("--queue-depth", type=int, default=512)
    ap.add_argument("--max-wait-ms", type=float, default=5.0)
    ap.add_argument("--deadline-ms", type=float, default=0.0)
    ap.add_argument("--decode", action="store_true",
                    help="continuous-batching decode over GenerationEngine")
    ap.add_argument("--slots", type=int, default=8,
                    help="decode: KV arena slots (the iteration batch)")
    ap.add_argument("--max-len", type=int, default=64,
                    help="decode: KV arena length per slot")
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=16)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--rates", type=str, default=None,
                    help="decode: comma-separated arrival-rate sweep, req/s")
    ap.add_argument("--paged", action="store_true",
                    help="decode: block-size sweep (pool occupancy, "
                         "radix dedup, COW) on a share-heavy workload")
    ap.add_argument("--spec", action="store_true",
                    help="decode: speculative-decoding leg "
                         "(steps-per-token, acceptance rate)")
    ap.add_argument("--sample", dest="sample_leg", action="store_true",
                    help="decode: committed-threefry sampled leg "
                         "(shuffled-admission replay bit-identity)")
    ap.add_argument("--beam", dest="beam_leg", action="store_true",
                    help="decode: COW beam-search leg (offline "
                         "reference bit-identity + block conservation)")
    ap.add_argument("--overload", dest="overload_leg",
                    action="store_true",
                    help="decode: r18 degradation leg (park/resume vs "
                         "shed-only goodput under a 2x open-loop burst)")
    ap.add_argument("--verify", type=int, default=8,
                    help="decode: requests/rate checked against offline "
                         "(--smoke checks every request)")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale run + invariant asserts (CI)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.requests, args.clients, args.replicas = 32, 4, 1
        args.max_batch = 4
    if args.decode:
        if args.smoke:
            args.requests, args.slots, args.max_len = 48, 4, 24
            args.vocab, args.hidden, args.layers = 32, 8, 2
            args.rates = args.rates or "500"
        args.rates = [float(r) for r in
                      (args.rates or str(args.rate)).split(",")]

    if args.decode:
        return run_decode(args, np.random.RandomState(0))

    import jax

    from paddle_tpu import inference
    from paddle_tpu.serving import BucketLattice, ServingEngine

    with tempfile.TemporaryDirectory() as tmp:
        model_dir = _save_model(tmp, feat=args.feat, seq=args.seq)
        config = inference.Config(model_dir)
        lattice = BucketLattice.pow2(args.max_batch, args.seq or None,
                                     min_seq=2)
        config.set_serving_buckets(lattice.batch_sizes, lattice.seq_lens)
        engine = ServingEngine(
            config, lattice=lattice, num_replicas=args.replicas,
            queue_depth=args.queue_depth, max_wait_ms=args.max_wait_ms,
        )
        t0 = time.perf_counter()
        engine.start()
        warm_s = time.perf_counter() - t0

        rng = np.random.RandomState(0)
        runner = run_closed if args.mode == "closed" else run_open
        served, errors, wall = runner(engine, args, rng)
        stats = engine.stats()
        engine.shutdown()

    report = {
        "metric": f"serving_{args.mode}_loop_requests_per_sec",
        "value": round(served / max(wall, 1e-9), 1),
        "unit": "req/s",
        "extra": {
            "device": jax.devices()[0].platform,
            "served": served,
            "rejected": stats["rejected"],
            "deadline_missed": stats["deadline_missed"],
            "error_codes": sorted(set(errors)),
            "warmup_seconds": round(warm_s, 2),
            "avg_batch_rows": round(stats["avg_batch_rows"], 2),
            "avg_batch_occupancy": round(stats["avg_batch_occupancy"], 3),
            "queue_wait_p50_s": round(stats["queue_wait_p50_s"], 5),
            "queue_wait_p99_s": round(stats["queue_wait_p99_s"], 5),
            "latency_p50_s": round(stats["latency_p50_s"], 5),
            "latency_p99_s": round(stats["latency_p99_s"], 5),
            "cache_hit_rate": stats["cache_hit_rate"],
            "replicas": args.replicas,
            "mode": args.mode,
        },
    }
    print(json.dumps(report))
    if args.smoke:
        assert served == args.requests, (served, args.requests, errors)
        assert stats["cache_hit_rate"] == 1.0, stats
        assert stats["cache_misses"] == 0, stats
        print("SERVING_SMOKE_OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
