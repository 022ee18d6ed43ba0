#!/usr/bin/env python
"""Input-pipeline throughput benchmark: samples/s vs num_workers.

Measures the dataio-backed DataLoader on a CPU-bound preprocessing
workload (per-sample numpy matmul chain — BLAS releases the GIL, which
is exactly the decode/augment profile the thread pool is built for)
against the single-thread baseline (num_workers=0: same code path,
transform inline). Also verifies the determinism contract while it's at
it: every worker count must produce the identical batch stream.

`--smoke` is the tier-1 CI hook (wired by tests/test_dataio.py):
a seconds-scale run asserting the acceptance invariants — >= 2x
samples/s at num_workers=4 over the single-thread DataLoader, identical
batch streams across worker counts, and `dataio::` spans + queue-depth
gauges visible in a captured Chrome trace / the metrics registry.

Usage:
  python tools/bench_input.py [--samples 8192] [--batch-size 32]
      [--workers 0,1,2,4,8] [--work 64] [--smoke]
      [--trace-out /tmp/input.trace.json]
"""

import argparse
import hashlib
import json
import os
import sys
import tempfile
import time

# keep BLAS single-threaded so worker scaling is measured, not OpenMP's
os.environ.setdefault("OMP_NUM_THREADS", "1")
os.environ.setdefault("OPENBLAS_NUM_THREADS", "1")
os.environ.setdefault("MKL_NUM_THREADS", "1")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def make_reader(n_samples):
    def reader():
        for i in range(n_samples):
            yield (i,)

    return reader


def make_preprocess(work):
    """CPU-bound per-sample decode/augment stand-in: `work`x`work`
    float32 matmuls derived deterministically from the sample id. The
    cost must sit in GIL-RELEASING C (BLAS) — like real decode/resize —
    for a thread pool to scale it; pure-Python or tiny-array work is
    GIL-bound and parallelizes with processes, not threads (the
    README determinism-contract section documents this boundary)."""
    base = np.random.RandomState(0).rand(work, work).astype(np.float32)
    base /= np.linalg.norm(base, axis=1, keepdims=True)

    def preprocess(sample):
        (i,) = sample
        a = base + np.float32((int(i) % 97) * 1e-4)
        a = a @ base
        a = a @ base
        x = (a[0, :4] / (np.abs(a).max() + 1.0)).astype(np.float32)
        y = np.array([float(x.sum())], dtype=np.float32)
        return (x, y)

    return preprocess


def run_loader(n_samples, batch_size, num_workers, work, digest=False):
    """Consume one full pass; returns (samples_per_s, n_consumed, digest).
    digest=True hashes the batch stream (order-sensitive) so worker
    counts can be compared for bit-identical output."""
    import paddle_tpu as fluid
    from paddle_tpu.core.ir import Program, program_guard

    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", shape=[-1, 4])
        y = fluid.data("y", shape=[-1, 1])
    loader = fluid.DataLoader.from_generator(
        feed_list=[x, y], capacity=8, num_workers=num_workers
    )
    loader.set_sample_generator(
        make_reader(n_samples), batch_size, drop_last=False,
        sample_transform=make_preprocess(work),
    )
    h = hashlib.sha256() if digest else None
    t0 = time.perf_counter()
    count = 0
    for feed in loader:
        count += int(feed["x"].shape[0])
        if h is not None:
            h.update(np.asarray(feed["x"]).tobytes())
            h.update(np.asarray(feed["y"]).tobytes())
    dt = time.perf_counter() - t0
    return count / dt, count, (h.hexdigest() if h else None)


def capture_trace(out_path, n_samples, batch_size, work):
    """Short traced pass: returns the span-name aggregate from the
    exported Chrome trace."""
    from paddle_tpu import observability as obs

    obs.enable_tracing()
    try:
        run_loader(n_samples, batch_size, num_workers=4, work=work)
    finally:
        obs.disable_tracing()
    n_events = obs.export_chrome_trace(out_path)
    with open(out_path) as f:
        doc = json.load(f)
    names = {e["name"] for e in doc.get("traceEvents", [])
             if e.get("ph") == "X"}
    return n_events, names


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--samples", type=int, default=8192)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--workers", default="0,1,2,4,8",
                    help="comma-separated num_workers sweep (0 = baseline)")
    ap.add_argument("--work", type=int, default=384,
                    help="preprocess matmul size (CPU cost per sample)")
    ap.add_argument("--trace-out", default=os.path.join(
        tempfile.gettempdir(), "paddle_tpu.input.trace.json"))
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale run + invariant asserts (CI)")
    args = ap.parse_args(argv)
    workers = [int(w) for w in args.workers.split(",")]
    if args.smoke:
        args.samples = min(args.samples, 768)
        workers = [0, 4]

    print(f"samples={args.samples} batch_size={args.batch_size} "
          f"work={args.work}x{args.work} (single-threaded BLAS)")
    print(f"{'num_workers':>12}{'samples/s':>12}{'speedup':>9}  stream")
    base_rate = None
    rates = {}
    digests = {}
    for w in workers:
        rate, count, digest = run_loader(
            args.samples, args.batch_size, w, args.work, digest=True)
        rates[w] = rate
        digests[w] = digest
        if base_rate is None:
            base_rate = rate
        print(f"{w:>12}{rate:>12.0f}{rate / base_rate:>8.2f}x  "
              f"{digest[:12]}")

    n_events, span_names = capture_trace(
        args.trace_out, min(args.samples, 512), args.batch_size, args.work)
    dataio_spans = sorted(n for n in span_names if n.startswith("dataio::"))
    print(f"\ntrace: {args.trace_out} ({n_events} events); "
          f"dataio spans: {dataio_spans}")

    if args.smoke:
        _smoke_asserts(args, workers, rates, digests, dataio_spans)
        print("BENCH_INPUT_SMOKE_OK")
    return 0


def _smoke_asserts(args, workers, rates, digests, dataio_spans):
    from paddle_tpu.observability import registry

    # 1. determinism: every worker count produced the identical stream
    uniq = set(digests.values())
    assert len(uniq) == 1, f"batch streams differ across workers: {digests}"

    # 2. throughput: >= 2x over the single-thread DataLoader at 4 workers
    speedup = rates[4] / rates[0]
    print(f"speedup at num_workers=4: {speedup:.2f}x")
    assert speedup >= 2.0, (
        f"num_workers=4 speedup {speedup:.2f}x < 2x over single-thread "
        f"baseline ({rates[0]:.0f} -> {rates[4]:.0f} samples/s)"
    )

    # 3. observability: dataio spans in the Chrome trace, queue gauges +
    # wait histograms in the one registry
    for required in ("dataio::transform", "dataio::device_put"):
        assert required in dataio_spans, (required, dataio_spans)
    snap = registry().snapshot()
    for family in ("dataio_queue_depth", "dataio_producer_wait_seconds",
                   "dataio_consumer_wait_seconds"):
        assert family in snap, (family, sorted(snap))


if __name__ == "__main__":
    sys.exit(main())
