"""Shared by the decode test modules: weights under which the served
tokens depend on the prompt and on the K/V rows, the jit counter, and
the three-request speculative scenario."""

from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.serving.decode import GenerationEngine, build_decoder_model

SPEC_PROMPTS = ([3, 1, 4, 1, 5], [9, 2, 6], [3, 1, 4, 1, 5, 9])
SPEC_MAX_NEW = (12, 10, 12)


def sharpen(entry, factor=8.0):
    """Scale the entry's token embedding in place. Under the startup
    program's Xavier draws the position embedding drowns the token
    embedding: every answer is nearly the same whatever the prompt, and
    a wrong K/V row cannot change a served token (PERF.md, PR 23), so
    "tokens equal the reference's" could not fail. ``offline_decode``
    reads the same scope, so the reference follows. Returns the entry."""
    scope = entry._scope
    for name in scope.var_names():
        if name.endswith(".tok_emb"):
            scope.set(name, scope.find_var(name) * factor)
    return entry


def jits():
    """jax.jit computations created through the lowering chokepoint."""
    m = obs_metrics.registry().get("lowering_jit_total")
    return int(m.value) if m is not None else 0


def spec_leg(name, **submit_kw):
    """Three speculative requests (spec_k=3) against a draft of the
    target's geometry — deterministic init makes its weights
    byte-identical: the acceptance upper bound — on a fresh engine.
    Returns the target's stats, the jits past registration and whether
    every stream equals target-only decode."""
    geom = dict(vocab_size=32, hidden=8, num_layers=2, slots=4, max_len=32,
                block_size=4, version="1")
    engine = GenerationEngine(queue_depth=16, breaker_threshold=0)
    tgt = engine.register_model(
        lambda: build_decoder_model(name=f"{name}_t", **geom))
    engine.register_model(
        lambda: build_decoder_model(name=f"{name}_d", **geom))
    refs = [tgt.offline_decode(p, n)
            for p, n in zip(SPEC_PROMPTS, SPEC_MAX_NEW)]
    j0 = jits()
    engine.start()
    try:
        resps = [engine.submit(p, model=f"{name}_t", max_new_tokens=n,
                               draft_model=f"{name}_d", spec_k=3,
                               **submit_kw)
                 for p, n in zip(SPEC_PROMPTS, SPEC_MAX_NEW)]
        outs = [[int(t) for t in r.result(timeout=120)["tokens"]]
                for r in resps]
    finally:
        engine.shutdown()
    return tgt.stats(), jits() - j0, outs == refs
