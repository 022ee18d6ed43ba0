"""Subprocess worker for the decode engine's AOT warm-start tests.

Builds the canonical cached-attention decoder, registers it with a
GenerationEngine (compile cache dir from ``JAX_COMPILATION_CACHE_DIR``),
serves a fixed prompt set, and prints one JSON line: where each of the
three executables came from (``compile_sources``), the process-wide
trace/compile counters, and the generated tokens (exact ints, for
bit-identity comparison across processes). The parent test asserts a
SECOND process reports ``trace == 0`` with all three entries
disk-sourced (``lowering_jit_total`` still moves: disk loads create a
cheap jit WRAPPER around the deserialized module, never a retrace) — a
relaunched replica reaches full decode/prefill/inject coverage with
zero compiles, which is what lets the circuit breaker swap replicas
without a warmup outage.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


PROMPTS = ([3, 1, 4], [1, 5], [9, 2, 6, 5], [3, 5, 8, 9, 7, 9])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=5)
    args = ap.parse_args()

    from paddle_tpu.serving.decode import (
        GenerationEngine,
        build_decoder_model,
    )

    engine = GenerationEngine(breaker_threshold=0)
    entry = engine.register_model(lambda: build_decoder_model(
        vocab_size=32, hidden=8, num_layers=2, slots=args.slots,
        max_len=args.max_len, name="worker", version="1",
    ))
    engine.start()
    resps = [engine.submit(p, max_new_tokens=args.max_new)
             for p in PROMPTS]
    tokens = [[int(t) for t in r.result(timeout=120)["tokens"]]
              for r in resps]
    engine.shutdown()

    from paddle_tpu.observability import metrics as obs_metrics

    reg = obs_metrics.registry()

    def val(name):
        m = reg.get(name)
        return int(m.value) if m is not None else 0

    print(json.dumps({
        "compile_sources": entry.compile_sources,
        "jits": val("lowering_jit_total"),
        "persistent_hits": val("compile_cache_persistent_hits_total"),
        "persistent_errors": val("compile_cache_persistent_errors_total"),
        "tokens": tokens,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
