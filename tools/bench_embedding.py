"""Sharded-embedding engine benchmark: lookups/s vs hot-cache ratio,
dedup on/off, with the correctness gates the engine's contracts promise.

Streams a zipfian CTR id workload (the realistic shape: a hot head that
should live on device, a cold tail that should overflow to host RAM)
through ``EmbeddingEngine.prepare_feed`` + a compiled
``sharded_embedding`` train step at several cache capacities, measuring
end-to-end lookups/s and the measured hit rate per config.

``--smoke`` (fast tier, tests/test_embedding.py) shrinks the workload
and ASSERTS the engine's promises instead of trusting them:

  * bit-identical per-step embedding outputs AND final table values
    across every cache configuration (eviction traffic included);
  * a non-trivial measured hit rate on the zipfian stream;
  * HLO dedup evidence: one slab gather moving U_pad < n_ids rows, and
    a firing dedup-off control.

Prints one JSON report; tests/test_embedding.py reads the ``--smoke``
run's report from stdout and holds its claims.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np


def zipf_batches(steps, batch, ids_per_slot, id_space, seed=0):
    """Zipfian id stream: ranks drawn s=1.2, mapped through a hash so
    hot ids are spread over the space (not 0..k)."""
    from paddle_tpu.embedding.table import splitmix64

    rng = np.random.RandomState(seed)
    out = []
    for _ in range(steps):
        ranks = rng.zipf(1.2, size=(batch, ids_per_slot)).astype(np.uint64)
        ids = splitmix64(ranks) % np.uint64(id_space)
        out.append(ids.astype(np.int64))
    return out


def build(capacity, ep, dim, s, name="bench", lr=0.5, seed=3):
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.data("ids", shape=[-1, s], dtype="int64")
        y = fluid.data("y", shape=[-1, s, dim], dtype="float32")
        emb = fluid.layers.sharded_embedding(
            ids, dim, capacity=capacity, ep=ep, name=name,
            init_range=0.05, lr=lr, seed=seed,
        )
        loss = fluid.layers.mean(fluid.layers.square(
            fluid.layers.elementwise_sub(emb, y)
        ))
        fluid.optimizer.SGD(learning_rate=lr).minimize(loss)
    return main, startup, emb, loss


def run_config(batches, capacity, ep, dim, dedup, fetch_emb=False):
    """Train the stream under one cache config; returns timing, stats,
    per-step fetched embeddings (optional), and the final value map."""
    import paddle_tpu as fluid
    from paddle_tpu.embedding import EmbeddingEngine

    s = batches[0].shape[1]
    main, startup, emb, loss = build(capacity, ep, dim, s)
    exe = fluid.Executor(fluid.CPUPlace())
    sc = fluid.Scope()
    outs, n_ids = [], 0
    with fluid.scope_guard(sc):
        exe.run(startup)
        eng = EmbeddingEngine(scope=sc)
        rngy = np.random.RandomState(7)
        ys = [rngy.randn(b.shape[0], s, dim).astype("float32")
              for b in batches]
        # warm the compile caches outside the timed loop
        feed0 = {"ids": batches[0], "y": ys[0]}
        eng.prepare_feed(main, dict(feed0), dedup=dedup, train=False)
        fetches = [emb, loss] if fetch_emb else [loss]
        t0 = time.perf_counter()
        for bi, (b, y) in enumerate(zip(batches, ys)):
            feed = {"ids": b, "y": y}
            eng.prepare_feed(main, feed, dedup=dedup)
            out = exe.run(main, feed=feed, fetch_list=fetches)
            if fetch_emb:
                outs.append(np.asarray(out[0]).copy())
            n_ids += b.size
        dt = time.perf_counter() - t0
        eng.flush()
        rt = eng.tables["bench"]
        stats = rt.stats()
        values = {i: r.copy() for sh in rt.store._shards
                  for i, r in sh.items()}
        eng.close()
    return {
        "capacity": capacity,
        "ep": ep,
        "dedup": dedup,
        "seconds": dt,
        "lookups_per_s": n_ids / dt if dt > 0 else 0.0,
        "hit_rate": stats["hit_rate"],
        "evictions": stats["evictions"],
        "store_rows": stats["store_rows"],
    }, outs, values


def dedup_hlo_evidence(dim=8, s=6, capacity=64, ep=2):
    """Lower one step both ways and scan the gathers (gather.py)."""
    import paddle_tpu as fluid
    from paddle_tpu.embedding import EmbeddingEngine
    from paddle_tpu.embedding.gather import dedup_evidence
    from paddle_tpu.utils import hlo as uhlo

    main, startup, emb, loss = build(capacity, ep, dim, s, name="ev")
    exe = fluid.Executor(fluid.CPUPlace())
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe.run(startup)
        eng = EmbeddingEngine(scope=sc)
        rng = np.random.RandomState(0)
        idv = rng.randint(0, 8, (4, s)).astype("int64")
        y = rng.randn(4, s, dim).astype("float32")
        n_ids = idv.size
        feed = {"ids": idv, "y": y}
        eng.prepare_feed(main, feed)
        on = dedup_evidence(
            uhlo.lower_program_step(
                main, feed, [loss], scope=sc).as_text(),
            (capacity, dim), n_ids,
        )
        feed2 = {"ids": idv, "y": y}
        eng.prepare_feed(main, feed2, dedup=False)
        off = dedup_evidence(
            uhlo.lower_program_step(
                main, feed2, [loss], scope=sc).as_text(),
            (capacity, dim), n_ids,
        )
        eng.close()
    return on, off


def main():
    ap = argparse.ArgumentParser("sharded embedding engine bench")
    ap.add_argument("--smoke", action="store_true",
                    help="small workload + hard asserts (fast tier)")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    args = ap.parse_args()

    from paddle_tpu.observability import metrics as obs_metrics

    if args.smoke:
        steps, batch, s, dim, id_space, ep = 12, 16, 6, 8, 4096, 2
        ratios = (0.125, 0.5, 1.0)
    else:
        steps, batch, s, dim, id_space, ep = 50, 256, 12, 32, 1 << 20, 4
        ratios = (0.1, 0.25, 0.5, 1.0)
    steps = args.steps or steps
    batch = args.batch or batch

    batches = zipf_batches(steps, batch, s, id_space)
    working_set = len(np.unique(np.concatenate(
        [b.reshape(-1) for b in batches])))
    max_batch_unique = max(len(np.unique(b)) for b in batches)

    def cap_for(ratio):
        # capacity must hold one batch's uniques per shard with slack;
        # round up to an ep multiple
        c = max(int(working_set * ratio), 2 * max_batch_unique)
        return ((c + ep - 1) // ep) * ep

    from paddle_tpu import kernels
    from paddle_tpu.kernels.embedding import admission_roundtrip_counter

    rt0 = admission_roundtrip_counter().value
    configs, outputs, valuemaps = [], [], []
    for ratio in ratios:
        rep, outs, values = run_config(
            batches, cap_for(ratio), ep, dim, dedup=True, fetch_emb=True)
        rep["hot_ratio"] = ratio
        configs.append(rep)
        outputs.append(outs)
        valuemaps.append(values)
    device_admission_roundtrips = admission_roundtrip_counter().value - rt0
    # legacy-path control: the smallest config re-run with
    # PADDLE_TPU_KERNELS=off must produce BIT-identical training through
    # the host capacity-slab round-trip (and the round-trip counter must
    # fire — the zero above proves something)
    with kernels.scoped_mode("off"):
        _rep_leg, outs_legacy, values_legacy = run_config(
            batches, cap_for(ratios[0]), ep, dim, dedup=True,
            fetch_emb=True)
    legacy_roundtrips = (admission_roundtrip_counter().value - rt0
                         - device_admission_roundtrips)
    legacy_bit_identical = all(
        np.array_equal(a, b) for a, b in zip(outputs[0], outs_legacy)
    ) and set(values_legacy) == set(valuemaps[0]) and all(
        np.array_equal(valuemaps[0][i], values_legacy[i])
        for i in values_legacy
    )
    # dedup-off control at the largest cache
    rep_off, outs_off, values_off = run_config(
        batches, cap_for(ratios[-1]), ep, dim, dedup=False, fetch_emb=True)
    rep_off["hot_ratio"] = ratios[-1]
    configs.append(rep_off)

    # bit-exactness across every CACHE configuration (the engine's
    # write-back contract); the dedup-off control is numerically
    # equivalent only to summation order (segment-sum vs per-occurrence
    # scatter), so it gets an allclose bound, not a bit gate
    ref = outputs[0]
    bit_identical = all(
        all(np.array_equal(a, b) for a, b in zip(ref, outs))
        for outs in outputs[1:]
    ) and all(
        set(vm) == set(valuemaps[0])
        and all(np.array_equal(valuemaps[0][i], vm[i]) for i in vm)
        for vm in valuemaps[1:]
    )
    dedup_off_max_diff = max(
        (float(np.max(np.abs(a - b))) for a, b in zip(ref, outs_off)),
        default=0.0,
    )

    ev_on, ev_off = dedup_hlo_evidence(dim=dim, s=s)
    reg = obs_metrics.registry()
    gauges = {}
    for fam in ("embedding_cache_hits_total", "embedding_cache_misses_total",
                "embedding_cache_evictions_total", "embedding_cache_occupancy",
                "embedding_staleness_seconds", "embedding_store_rows"):
        total = 0
        for m in reg.collect():
            if m.name == fam:
                total += m.value
        gauges[fam] = total

    smallest = configs[0]
    report = {
        "workload": {
            "steps": steps, "batch": batch, "ids_per_slot": s, "dim": dim,
            "id_space": id_space, "working_set": working_set, "ep": ep,
        },
        "configs": configs,
        "dedup_evidence": ev_on,
        "dedup_off_control": ev_off,
        "cache_hit_gauges": gauges,
        "smoke": {
            "bit_identical_across_configs": bool(bit_identical),
            "dedup_off_max_abs_diff": dedup_off_max_diff,
            "hit_rate": smallest["hit_rate"],
            "device_admission_roundtrips": int(device_admission_roundtrips),
            "legacy_admission_roundtrips": int(legacy_roundtrips),
            "legacy_path_bit_identical": bool(legacy_bit_identical),
        },
    }
    if args.smoke:
        assert bit_identical, (
            "lookup results diverged across cache configurations"
        )
        assert device_admission_roundtrips == 0, (
            "on-device admission still round-tripped the capacity slab "
            f"through host numpy {device_admission_roundtrips}x"
        )
        assert legacy_roundtrips > 0, (
            "legacy control never fired the round-trip counter — the "
            "zero above proves nothing"
        )
        assert legacy_bit_identical, (
            "device admission drifted from the legacy host path"
        )
        assert dedup_off_max_diff < 1e-6, (
            f"dedup on/off drifted past summation-order noise: "
            f"{dedup_off_max_diff}"
        )
        assert smallest["hit_rate"] > 0.3, configs
        assert smallest["evictions"] > 0, (
            "smallest cache saw no evictions — the invariance claim "
            "was not exercised"
        )
        assert ev_on["gathers"] == 1 and ev_on["dedup_saves"], ev_on
        assert ev_off["rows_moved"] >= ev_on["n_ids"], ev_off
        report["smoke"]["asserts"] = "passed"

    print(json.dumps(report, indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
