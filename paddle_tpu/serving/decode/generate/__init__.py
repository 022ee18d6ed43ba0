"""Generation modes for the paged decode engine: the decode-POLICY layer
between the scheduler and the fixed-shape programs.

Everything the engine compiles stays exactly as PR 13 left it — one
``[S, 1]`` decode step, donated arenas, the content-addressed compile
cache — and every mode here is host-side policy over the fetched logits
and the block tables:

* ``sampling`` — temperature/top-k/top-p on a committed threefry
  stream keyed per-(request seed, absolute token index): replay is
  bit-exact for any admission order, batchmates, or slot assignment,
  and speculative acceptance graduates from greedy-match to the
  committed-coupling rejection rule (same realized stream as
  target-only sampled decode).
* ``beam`` — beam search as COW forks over the paged block arena:
  beams are slots in the shared decode batch, a fork is refcount++ plus
  one private tail block, pruning releases through the normal retire
  path (row conservation asserted).
* ``grammar`` — JSON-schema / regex compiled host-side to per-step
  fixed-shape ``[S, V]`` logits masks fed as DATA through the
  ``DEC_MASK`` feed: structured output with zero retraces.

Each mode (and each composition) is bit-identical to its offline
whole-sequence reference, and all of them back to back on one warm
engine compile nothing: tests/test_generate.py::
test_every_generation_mode_on_one_warm_engine_compiles_nothing.
"""

from paddle_tpu.serving.decode.generate.beam import (
    BeamParams,
    offline_beam_decode,
)
from paddle_tpu.serving.decode.generate.grammar import (
    CompiledGrammar,
    GrammarConstraint,
    compile_regex,
    json_schema_regex,
)
from paddle_tpu.serving.decode.generate.sampling import (
    SamplingParams,
    gumbel_vector,
    sample_token,
)

__all__ = [
    "BeamParams",
    "CompiledGrammar",
    "GrammarConstraint",
    "SamplingParams",
    "compile_regex",
    "gumbel_vector",
    "json_schema_regex",
    "offline_beam_decode",
    "sample_token",
]
