"""The plain reference of the ``lfm2_moe`` family (Liquid LFM2 with routed
experts: ``serving/decode/hybrid.py build_lfm2_model`` is the served form):
the forward pass in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, the whole sequence in one pass,
no cache, no slots, no paging, no kernels. It shares nothing with the
program but the weights, which it is handed as the served (bfloat16) arrays
by name and upcasts LAYER BY LAYER, so that it fits beside the engine on
the chip (the largest layer, 8 experts, is 0.30 GB in float32).

The equations (``config`` holds the published keys as they are run; ``H``
the hidden size, a head ``H / num_attention_heads`` wide):

* ``x_0 = embed[token]``; layer ``i``: ``x <- x + op_i(RMSNorm(x;
  operator_norm))`` then ``x <- x + ffn_i(RMSNorm(x; ffn_norm))``;
  ``logits = embed . RMSNorm(x_last; embedding_norm)``; ``norm_eps``; no
  bias anywhere.
* ``op_i``, ``layer_types[i] == "conv"``: ``B | C | u = in_proj . h`` (``[H,
  3 H]``); ``v_t = sum_j w[j] * (B * u)_{t - (K - 1) + j}`` over ``K =
  conv_L_cache`` taps (``conv_w`` ``[K, H]``, tap ``K - 1`` on the current
  token, zeros before the prompt); ``out_proj . (C * v)``. No activation.
* ``op_i``, ``"full_attention"``: ``q`` to ``heads x D``, ``k``, ``v`` to
  ``kv_heads x D``; ``q <- RMSNorm(q; q_layernorm [D])`` per head, ``k``
  likewise; rotary positions on both (``rope_parameters.rope_theta``, the
  whole head, rotate-half: lane ``i < D / 2`` with lane ``i + D / 2``, angle
  ``position * theta^(-2 i / D)``); causal softmax at ``1 / sqrt(D)``,
  grouped-query; ``out_proj``.
* ``ffn_i``, ``i < num_dense_layers``: ``w2 . (silu(w1 . h) * (w3 . h))``.
* ``ffn_i``, the others: ``s = sigmoid(gate . h)`` over all
  ``router_experts``; the top ``num_experts_per_tok`` of ``s +
  expert_bias`` (the bias moves the choice, not the weight); weights the
  chosen ``s`` over their sum + 1e-6 (``norm_topk_prob``), times
  ``routed_scaling_factor``; expert ``e``: ``w2_e . (silu(w1_e . h) *
  (w3_e . h))`` (all three ``[F, H]``); no shared expert.

Departures from the published description, each also under ``assumed`` in
the configuration's file:

* the head is TIED to the embedding (the family's ``tie_embedding`` as
  remembered; the catalog's row does not give it); the epsilon of the
  router's normalisation (1e-6) is remembered likewise.
* of the ``router_experts`` experts only ``held`` (ids ``offset .. offset +
  held - 1``, the served share of an expert-parallel deployment) are summed:
  the router still scores all, chooses its top k over all and normalises
  over all k; what the absent experts would add is left out, here as in the
  program, and nothing stands in for them.
"""

import functools

import numpy as np


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


@functools.lru_cache(maxsize=None)
def _functions(sizes, round_to=None, round_operands=None):
    import jax
    import jax.numpy as jnp

    c = dict(sizes)
    f32 = jnp.float32
    eps = c["norm_eps"]

    def up(*ws):
        # ``round_to`` is the comparison's control and no part of the
        # reference: every weight through a narrower dtype first
        if round_to is not None:
            ws = [w.astype(round_to) for w in ws]
        return [w.astype(f32) for w in ws]

    def op(x):
        # ``round_operands`` is a diagnosis and no part of the reference
        # either: every product's left operand (and the K and V rows)
        # through the dtype the served program feeds its products in
        return x if round_operands is None else x.astype(
            round_operands).astype(f32)

    @jax.jit
    def embed(table, tokens):
        return table[tokens].astype(f32)

    @jax.jit
    def head(h, norm_w, table):
        norm_w, table = up(norm_w, table)
        return op(_rms(h, norm_w, eps)) @ table.T

    @jax.jit
    def conv(h, norm_w, in_w, conv_w, out_w):
        norm_w, in_w, conv_w, out_w = up(norm_w, in_w, conv_w, out_w)
        t, taps = h.shape[0], conv_w.shape[0]
        b, cc, u = jnp.split(op(_rms(h, norm_w, eps)) @ in_w, 3, axis=-1)
        ext = jnp.concatenate([jnp.zeros((taps - 1, h.shape[1]), f32), b * u])
        v = sum(conv_w[k] * ext[k:k + t] for k in range(taps))
        return h + op(cc * v) @ out_w

    def rotate(x, positions):
        # x [T, heads, D]: the whole head, rotate-half
        half = x.shape[-1] // 2
        freq = c["rope_theta"] ** (-jnp.arange(half, dtype=f32) / half)
        angle = positions.astype(f32)[:, None, None] * freq
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    @jax.jit
    def attention(h, norm_w, qw, kw, vw, qn, kn, ow):
        norm_w, qw, kw, vw, qn, kn, ow = up(norm_w, qw, kw, vw, qn, kn, ow)
        nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
        t, d = h.shape[0], h.shape[1] // nq
        at = jnp.arange(t)
        x = op(_rms(h, norm_w, eps))
        q = rotate(_rms((x @ qw).reshape(t, nq, d), qn, eps), at)
        k = rotate(_rms((x @ kw).reshape(t, nkv, d), kn, eps), at)
        q = op(q).reshape(t, nkv, nq // nkv, d)
        k, v = op(k), op(x @ vw).reshape(t, nkv, d)
        scores = jnp.einsum("tgqd,sgd->gqts", q, k) / np.sqrt(d).astype(f32)
        causal = jnp.tril(jnp.ones((t, t), bool))
        att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        ctx = jnp.einsum("gqts,sgd->tgqd", att, v).reshape(t, nq * d)
        return h + op(ctx) @ ow

    @jax.jit
    def dense(h, norm_w, w1, w3, w2):
        norm_w, w1, w3, w2 = up(norm_w, w1, w3, w2)
        x = op(_rms(h, norm_w, eps))
        return h + op(jax.nn.silu(x @ w1) * (x @ w3)) @ w2

    @functools.partial(jax.jit, static_argnames=("offset",))
    def experts(h, norm_w, gate, expert_bias, w1, w3, w2, offset):
        norm_w, w1, w3, w2 = up(norm_w, w1, w3, w2)
        k, held = c["num_experts_per_tok"], w1.shape[0]
        x = _rms(h, norm_w, eps)
        s = jax.nn.sigmoid(x @ gate.astype(f32).T)
        # one more than the router chooses: the first loser and its score
        # say how near a token's choice was to another (``routing``)
        near, ranked = jax.lax.top_k(s + expert_bias.astype(f32), k + 1)
        idx, x = ranked[:, :k], op(x)
        w = jnp.take_along_axis(s, idx, -1)
        if c["norm_topk_prob"]:
            w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
        w = w * c["routed_scaling_factor"]
        out = jnp.zeros_like(h)
        for e in range(held):
            mine = jnp.sum(jnp.where(idx == offset + e, w, 0.0), -1)
            part = op(jax.nn.silu(x @ w1[e].T) * (x @ w3[e].T)) @ w2[e]
            out = out + mine[:, None] * part
        return h + out, ranked, near

    return embed, head, conv, attention, dense, experts


_KEYS = ("norm_eps", "num_attention_heads", "num_key_value_heads",
         "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor")


def logits(weights, config, tokens, positions, pad_to, expert_offset=0,
           round_to=None, round_operands=None, routing=False):
    """The logits ``[len(positions), vocabulary]`` that follow ``tokens`` at
    each of ``positions`` (position p: the distribution of token p + 1).
    ``weights`` by the program's names less their prefix; ``config`` the
    published keys as run; ``expert_offset`` the id of the first held
    expert. The sequence is padded to ``pad_to``: what follows a position
    reaches it neither through the causal mask nor through the convolution.
    ``round_to`` (a dtype's name) is for the comparison's control alone:
    the same pass with every weight rounded through a narrower dtype, which
    a comparison worth its name has to tell from the served model.
    ``round_operands`` and ``routing`` are a diagnosis's
    (``tools/check_hybrid_logits.py``): the products' left operands through
    the dtype the served program feeds them in, and, returned beside the
    logits, every expert layer's ranked experts ``[layers, positions, k +
    1]`` (the k chosen, then the first loser) with their selection
    scores."""
    import jax

    sizes = tuple((k, config[k]) for k in _KEYS) + (
        ("rope_theta", float(config["rope_parameters"]["rope_theta"])),)
    embed, head, conv, attention, dense, experts = _functions(
        sizes, round_to, round_operands)
    at = np.asarray(list(positions), np.int64)
    ranked, scores = [], []
    padded = np.zeros((pad_to,), np.int32)
    padded[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        h = embed(weights["embed"], padded)
        for i, kind in enumerate(config["layer_types"]):
            w = lambda part: weights[f"l{i}.{part}"]  # noqa: E731
            if kind == "conv":
                h = conv(h, w("operator_norm"), w("in_proj.w"), w("conv_w"),
                         w("out_proj.w"))
            else:
                h = attention(h, w("operator_norm"), w("q.w"), w("k.w"),
                              w("v.w"), w("q_layernorm"), w("k_layernorm"),
                              w("out_proj.w"))
            if i < config["num_dense_layers"]:
                h = dense(h, w("ffn_norm"), w("w1.w"), w("w3.w"), w("w2.w"))
            else:
                h, ids, near = experts(
                    h, w("ffn_norm"), w("gate"), w("expert_bias"), w("w1"),
                    w("w3"), w("w2"), offset=int(expert_offset))
                if routing:
                    ranked.append(np.asarray(ids)[at])
                    scores.append(np.asarray(near)[at])
        every = np.asarray(head(h, weights["embedding_norm"],
                                weights["embed"]))
    if routing:
        return every[at], np.stack(ranked), np.stack(scores)
    return every[at]
