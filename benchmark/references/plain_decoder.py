"""The plain reference of the repo's servable decoder
(``serving/decode/model.py build_decoder_model``): learned token and position
embeddings; per layer one attention head of the full width under a causal
mask, an output projection and a relu feed-forward, each added to the
residual; a linear head. No layer norm. The whole sequence in one pass, no
cache, no slots, no paging, float32 with every matmul at
``Precision.HIGHEST`` (the program serves at the TPU's default precision, so
this is the more exact of the two).

It shares nothing with the program but the weights, which it is handed as
plain arrays by name: ``tok_emb``, ``pos_emb``, ``l<i>.<q|k|v|out|ffn1|ffn2>.<w|b>``,
``head.<w|b>``; a weight is [in, out].
"""

import functools

import numpy as np


@functools.lru_cache(maxsize=None)
def _functions():
    import jax
    import jax.numpy as jnp

    dot = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)

    @jax.jit
    def embed(tok_emb, pos_emb, tokens):
        return tok_emb[tokens] + pos_emb[:tokens.shape[0]]

    @jax.jit
    def layer(h, qw, qb, kw, kb, vw, vb, ow, ob, f1w, f1b, f2w, f2b):
        n, width = h.shape
        q, k, v = dot(h, qw) + qb, dot(h, kw) + kb, dot(h, vw) + vb
        scores = dot(q, k.T) / np.sqrt(width).astype(np.float32)
        causal = jnp.tril(jnp.ones((n, n), bool))
        att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        h = h + dot(dot(att, v), ow) + ob
        return h + dot(jnp.maximum(dot(h, f1w) + f1b, 0.0), f2w) + f2b

    @jax.jit
    def head(h, w, b):
        return dot(h, w) + b

    return embed, layer, head


def logits(weights, num_layers, tokens, positions, pad_to):
    """The logits [len(positions), vocabulary] that follow ``tokens`` at each
    of ``positions`` (position p: the distribution of token p + 1). The
    sequence is padded to ``pad_to`` so that every call has one shape; under
    a causal mask what follows a position does not reach it."""
    embed, layer, head = _functions()
    padded = np.zeros((pad_to,), np.int32)
    padded[:len(tokens)] = tokens
    h = embed(weights["tok_emb"], weights["pos_emb"], padded)
    for i in range(num_layers):
        h = layer(h, *(weights[f"l{i}.{part}.{wb}"]
                       for part in ("q", "k", "v", "out", "ffn1", "ffn2")
                       for wb in ("w", "b")))
    # every position's row comes back (one shape, whatever is asked for)
    every = np.asarray(head(h, weights["head.w"], weights["head.b"]))
    return every[np.asarray(list(positions), np.int64)]
