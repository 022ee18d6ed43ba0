"""Continuous-batching decode subsystem (online serving v4).

The PR-2 engine (serving/engine.py) schedules at REQUEST granularity:
whole requests coalesce into fixed (batch, seq) buckets and a finished
sequence holds its rows until the slowest batchmate drains. This package
schedules at ITERATION granularity (Orca, OSDI'22) over a **paged KV
arena** (vLLM's PagedAttention block tables, SOSP'23): a decode batch of
S slots is stepped once per model iteration through ONE compiled
``[S, 1]`` executable, finished sequences retire between iterations,
admitted prompts prefill into free slots mid-flight, KV storage is
allocated block-by-block so memory scales with USED tokens, and prompts
sharing a prefix share PHYSICAL blocks through a radix tree over chained
block hashes (copy-on-write at divergence). Long prompts stream through
a budgeted chunk-prefill program interleaved with decode iterations, and
a draft-model **speculative decoding** path (Leviathan et al.) emits
multiple greedy-exact tokens per target forward.

r17 adds the **generation-modes layer** (``generate/``): committed
threefry **sampling** (temperature / top-k / top-p, replay bit-exact
under any admission order), **beam search** as copy-on-write forks over
the radix block arena (each hypothesis is a live slot; fork = refcount++
plus a private tail block), **draft-KV speculative slots** (proposals
decode O(1)/token from the draft entry's own paged arena instead of
replaying the prompt), and **grammar-constrained decode** (regex / JSON
schema compiled host-side to per-step fixed-shape logits masks fed as
data through the donated ``DEC_MASK`` input — zero retraces).

Modules:

* `model`  — `DecodeModel`: the fixed-shape paged-program contract
  (decode step / prefill / inject / optional chunk prefill) +
  `build_decoder_model`, the canonical cached-attention decoder builder.
* `hybrid` — `build_nemotron_h_model`: a Mamba-2 / grouped-query attention
  / routed-experts decoder with per-slot recurrent state beside the arena;
  `build_lfm2_model`: gated short convolutions beside grouped-query
  attention with QK-norm and rotary positions, gated routed experts;
  `build_ouro_model`: one stack of layers run several times a token with
  shared parameters, paged K/V rows per (pass, layer), an exit gate;
  `build_granite_hybrid_model`: Mamba-2 or position-free grouped-query
  mixers with a dense SwiGLU in every layer and four scalar multipliers;
  `build_latent_moe_model`: latent attention over ONE arena a layer (a
  token's compressed K/V and its shared rotary key), YaRN rotation, softmax
  routed experts beside a shared one.
* `pool`   — host-side slot allocator, block allocator + radix prefix
  index (storage dedup), and the content-hash prefill cache (compute
  dedup).
* `engine` — `GenerationEngine`: multi-tenant model registry, weighted-
  fair admission over the queue's priority lanes, the per-entry
  scheduler loop (decode steps, chunked prefill, speculative verify
  cycles), circuit-breaker relaunch, and AOT warm start through the
  compile cache.
* `metrics`— `DecodeMetrics`: the serving counter set + occupancy /
  tokens-per-step / block-pool / speculative-acceptance series.
* `generate` — the decode-policy layer: `SamplingParams`, `BeamParams`,
  `CompiledGrammar` / `GrammarConstraint`, the offline beam reference.
"""

from paddle_tpu.serving.decode.engine import (
    GenerationEngine,
    GenerationRequest,
)
from paddle_tpu.serving.decode.generate import (
    BeamParams,
    CompiledGrammar,
    GrammarConstraint,
    SamplingParams,
)
from paddle_tpu.serving.decode.metrics import DecodeMetrics
from paddle_tpu.serving.decode.hybrid import (
    build_afmoe_model, build_granite_hybrid_model, build_latent_moe_model, build_lfm2_model,
    build_keye_vl_model, build_nemotron_h_model, build_ouro_model,
    build_sdar_model)
from paddle_tpu.serving.decode.model import DecodeModel, build_decoder_model
from paddle_tpu.serving.decode.pool import (
    BlockPool,
    PrefixCache,
    SlotPool,
    block_hashes,
    prompt_key,
)

__all__ = [
    "BeamParams",
    "BlockPool",
    "CompiledGrammar",
    "DecodeMetrics",
    "DecodeModel",
    "GenerationEngine",
    "GenerationRequest",
    "GrammarConstraint",
    "PrefixCache",
    "SamplingParams",
    "SlotPool",
    "block_hashes",
    "build_afmoe_model",
    "build_decoder_model",
    "build_nemotron_h_model",
    "build_granite_hybrid_model",
    "build_latent_moe_model",
    "build_lfm2_model",
    "build_ouro_model",
    "build_keye_vl_model",
    "build_sdar_model",
    "prompt_key",
]
