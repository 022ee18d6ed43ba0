"""The plain reference of the ``afmoe`` family (Arcee Trinity:
``serving/decode/hybrid.py build_afmoe_model`` is the served form): the
forward pass in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, the whole sequence in one pass,
no cache, no slots, no paging, no kernels. It shares nothing with the
program but the weights, which it is handed as the served (bfloat16) arrays
by name and upcasts a matrix at a time (an expert at a time: a layer's 32
held experts are 3.6 GB in float32), so that it fits beside the weights on
the chip.

The equations (``config`` holds the published keys as they are run; ``H``
the hidden size, ``D`` ``head_dim``):

* ``x_0 = embed[token] * sqrt(H)`` (``mup_enabled``); layer ``i``: ``a = x +
  N2(attn_i(N1(x)))``, ``x' = a + N4(ffn_i(N3(a)))``, four RMSNorms
  (``input_layernorm``, ``post_attention_layernorm``, ``pre_mlp_layernorm``,
  ``post_mlp_layernorm``; ``rms_norm_eps``); ``logits = head . RMSNorm(x_last;
  norm)``; no bias anywhere.
* ``attn_i``, ``u = N1(x)``: ``q = u Wq`` to ``heads x D``, ``k``, ``v`` to
  ``kv_heads x D``, ``g = u Wg`` to ``heads x D``; ``q <- RMSNorm(q; q_norm
  [D])`` per head, ``k`` likewise; where ``layer_types[i]`` is
  ``sliding_attention`` rotary positions on both (``rope_theta``, the whole
  head, rotate-half) and query ``t`` sees key ``s`` iff ``0 <= t - s <
  sliding_window``; where it is ``full_attention`` no rotation and ``t``
  sees every ``s <= t``; softmax of ``q k / sqrt(D)``, grouped-query; ``(ctx
  * sigmoid(g)) Wo``.
* ``ffn_i``, ``i < num_dense_layers``: ``down . (silu(gate . h) * (up . h))``.
* ``ffn_i``, the others: ``shared(h) + route_scale * sum_e w_e expert_e(h)``;
  ``s = sigmoid(router . h)`` over all ``router_experts``; the top
  ``num_experts_per_tok`` of ``s + expert_bias`` (the bias moves the choice,
  not the weight); ``w_e`` the chosen ``s`` over their sum + 1e-20
  (``route_norm``); expert ``e``: ``w2_e . (silu(w1_e . h) * (w3_e . h))``
  (all three ``[F, H]``); the shared expert the same form.

Speed-ups that change no number: attention runs a block of queries at a
time (33k positions' scores would not fit otherwise), and a sliding layer's
block reads only the ``sliding_window + block`` keys its queries can see
(every other key's weight is exactly 0 under the mask); a held expert is
applied to the tokens routed to it alone, gathered on the host (every other
token's weight for it is exactly 0); and the LAST layer is computed for the
asked positions alone (its keys and values for every position, its queries,
its feed-forward and the head for those: no other row of it is read).

Departures from the published description, each also under ``assumed`` in
the configuration's file: the gate's width and input, the full layers
without position encoding, the window's edge, muP as the embedding's scale
alone, the bias in the choice and not the weight, the 1e-20. Of the
``router_experts`` experts only ``held`` (ids ``offset .. offset + held -
1``, the served share of an expert-parallel deployment) are summed: the
router still scores all, chooses its top k over all and normalises over all
k; what the absent experts would add is left out, here as in the program,
and nothing stands in for them.
"""

import functools

import numpy as np

#: queries a block of the attention holds, by kind of layer
_QUERY_BLOCK = {"sliding_attention": 256, "full_attention": 128}
#: a held expert's tokens are padded to this times a power of two (few
#: shapes)
_EXPERT_PAD = 512


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


@functools.lru_cache(maxsize=None)
def _functions(sizes, round_to=None):
    import jax
    import jax.numpy as jnp

    c = dict(sizes)
    f32 = jnp.float32
    eps = c["rms_norm_eps"]
    nq, nkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])

    def up(*ws):
        # ``round_to`` is the comparison's control and no part of the
        # reference: every weight through a narrower dtype first
        if round_to is not None:
            # (the barrier keeps the round trip: plain_granite_hybrid.py)
            ws = [jax.lax.optimization_barrier(w.astype(round_to))
                  for w in ws]
        return [w.astype(f32) for w in ws]

    @jax.jit
    def embed(table, tokens):
        h = table[tokens].astype(f32)
        return h * np.sqrt(h.shape[-1]).astype(f32) if c["mup"] else h

    @jax.jit
    def head(h, norm_w, w):
        norm_w, w = up(norm_w, w)
        return _rms(h, norm_w, eps) @ w

    def rotate(x, positions):
        # x [T, heads, D]: the whole head, rotate-half
        half = x.shape[-1] // 2
        freq = c["rope_theta"] ** (-jnp.arange(half, dtype=f32) / half)
        angle = positions.astype(f32)[:, None, None] * freq
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def attend(qs, ks, vs, qp, kp, window):
        """Queries at positions ``qp`` over keys at ``kp`` under the mask:
        a key at or before the query and, with ``window``, inside it."""
        sees = kp[None, :] <= qp[:, None]
        if window:
            sees &= qp[:, None] - kp[None, :] < window
        scores = jnp.einsum("tgqd,sgd->gqts", qs, ks) / np.sqrt(d).astype(f32)
        att = jax.nn.softmax(jnp.where(sees, scores, -jnp.inf), axis=-1)
        return jnp.einsum("gqts,sgd->tgqd", att, vs)

    @functools.partial(jax.jit, static_argnames=("window", "rotated",
                                                 "gated"))
    def attention(h, norm_w, qw, kw, vw, gw, qn, kn, ow, post_w, window,
                  rotated, gated, rows=None):
        """``h + N2(attn(N1(h)))`` for every position, or with ``rows``
        (positions) for those alone: the LAST layer's, whose other rows
        nothing reads."""
        norm_w, qw, kw, vw, gw, qn, kn, ow, post_w = up(
            norm_w, qw, kw, vw, gw, qn, kn, ow, post_w)
        t = h.shape[0]
        at = jnp.arange(t)
        u = _rms(h, norm_w, eps)
        k = _rms((u @ kw).reshape(t, nkv, d), kn, eps)
        k = rotate(k, at) if rotated else k
        v = (u @ vw).reshape(t, nkv, d)
        if rows is not None:
            h, u, at, t = h[rows], u[rows], rows, rows.shape[0]
        q = _rms((u @ qw).reshape(t, nq, d), qn, eps)
        q = (rotate(q, at) if rotated else q).reshape(t, nkv, nq // nkv, d)
        if rows is not None:
            ctx = attend(q, k, v, at, jnp.arange(k.shape[0]), window)
            ctx = ctx.reshape(t, nq * d)
            if gated:
                ctx = ctx * jax.nn.sigmoid(u @ gw)
            return h + _rms(ctx @ ow, post_w, eps)
        qb = _QUERY_BLOCK["sliding_attention" if window else
                          "full_attention"]
        qb = t if t % qb else qb
        # the keys a block of queries can see: all of them, or the window's
        # and the block's own
        span = min(t, -(-(window + qb) // qb) * qb) if window else t

        def block(b):
            s = b * qb
            k0 = jnp.clip(s + qb - span, 0, t - span)
            return attend(jax.lax.dynamic_slice_in_dim(q, s, qb),
                          jax.lax.dynamic_slice_in_dim(k, k0, span),
                          jax.lax.dynamic_slice_in_dim(v, k0, span),
                          s + jnp.arange(qb), k0 + jnp.arange(span), window)

        ctx = jax.lax.map(block, jnp.arange(t // qb)).reshape(t, nq * d)
        if gated:
            ctx = ctx * jax.nn.sigmoid(u @ gw)
        return h + _rms(ctx @ ow, post_w, eps)

    @jax.jit
    def dense(h, pre_w, w_gate, w_up, w_down):
        pre_w, w_gate, w_up, w_down = up(pre_w, w_gate, w_up, w_down)
        x = _rms(h, pre_w, eps)
        return jax.nn.silu(x @ w_gate) * (x @ w_up) @ w_down

    @jax.jit
    def route(h, pre_w, router, expert_bias):
        """The normed input, each token's ranked experts ``[T, k + 1]`` (the
        k chosen, then the first loser: how near a token's choice was to
        another), the k weights, and the ranked selection scores."""
        k = c["num_experts_per_tok"]
        x = _rms(h, up(pre_w)[0], eps)
        s = jax.nn.sigmoid(x @ router.astype(f32).T)
        near, ranked = jax.lax.top_k(s + expert_bias.astype(f32), k + 1)
        w = jnp.take_along_axis(s, ranked[:, :k], -1)
        if c["route_norm"]:
            w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
        return x, ranked, w * c["route_scale"], near

    @jax.jit
    def expert(x, w1, w3, w2):
        w1, w3, w2 = up(w1, w3, w2)
        return jax.nn.silu(x @ w1.T) * (x @ w3.T) @ w2

    @jax.jit
    def close(h, out, post_w):
        return h + _rms(out, up(post_w)[0], eps)

    return embed, head, attention, dense, route, expert, close


def routed_part(x, ranked, weights, w1, w3, w2, offset, expert):
    """``sum_e w_e expert_e(x)`` over the held experts (ids from
    ``offset``), each applied to the tokens routed to it alone: their rows
    gathered, padded to ``_EXPERT_PAD`` times a power of two (a few compiled
    shapes; a padding row carries weight 0), and added back."""
    import jax.numpy as jnp

    k = weights.shape[1]
    idx, wts = np.asarray(ranked)[:, :k], np.asarray(weights)
    out = jnp.zeros_like(x)
    for e in range(w1.shape[0]):
        hit = idx == offset + e
        rows = np.nonzero(hit.any(-1))[0]
        if not len(rows):
            continue
        n = _EXPERT_PAD
        while n < len(rows):
            n *= 2
        padded, mine = np.zeros(n, np.int32), np.zeros((n, 1), np.float32)
        padded[:len(rows)] = rows
        mine[:len(rows), 0] = (wts * hit).sum(-1)[rows]
        at = jnp.asarray(padded)
        out = out.at[at].add(
            jnp.asarray(mine) * expert(x[at], w1[e], w3[e], w2[e]))
    return out


_KEYS = ("rms_norm_eps", "num_attention_heads", "num_key_value_heads",
         "head_dim", "num_experts_per_tok", "route_norm")


def logits(weights, config, tokens, positions, pad_to, expert_offset=0,
           round_to=None, routing=False, sliding_window=None, route_scale=None,
           rotate_full=False, gate=True):
    """The logits ``[len(positions), vocabulary]`` that follow ``tokens`` at
    each of ``positions`` (position p: the distribution of token p + 1).
    ``weights`` by the program's names less their prefix; ``config`` the
    published keys as run; ``expert_offset`` the id of the first held
    expert. The sequence is padded to ``pad_to``: what follows a position
    does not reach it through the causal mask. The rest are for the
    comparison's CONTROLS alone and no part of the reference: ``round_to``
    (a dtype's name: every weight through a narrower dtype),
    ``sliding_window`` (another window than the configuration's: left out,
    or off by a block), ``route_scale`` (another scale on the routed sum),
    ``rotate_full`` (the full layers rotated like the sliding ones),
    ``gate`` False (the attention's output gate left out); and ``routing``
    a diagnosis's: returned beside the logits, every expert layer's ranked
    experts ``[layers, positions, k + 1]`` and their selection scores."""
    import jax

    window = int(config["sliding_window"] if sliding_window is None
                 else sliding_window)
    sizes = tuple((k, config[k]) for k in _KEYS) + (
        ("rope_theta", float(config["rope_theta"])),
        ("mup", bool(config.get("mup_enabled", True))),
        ("route_scale", float(config["route_scale"] if route_scale is None
                              else route_scale)))
    embed, head, attention, dense, route, expert, close = _functions(
        sizes, round_to)
    at = np.asarray(list(positions), np.int64)
    ranked_all, scores = [], []
    padded = np.zeros((pad_to,), np.int32)
    padded[:len(tokens)] = tokens
    last = len(config["layer_types"]) - 1
    with jax.default_matmul_precision("highest"):
        h = embed(weights["embed"], padded)
        for i, kind in enumerate(config["layer_types"]):
            w = lambda part: weights[f"l{i}.{part}"]  # noqa: E731
            slides = kind == "sliding_attention"
            # the last layer's output is read at the asked positions alone
            h = attention(
                h, w("input_layernorm"), w("q.w"), w("k.w"), w("v.w"),
                w("gate_proj.w"), w("q_norm"), w("k_norm"), w("o.w"),
                w("post_attention_layernorm"),
                window=min(window, pad_to) if slides else 0,
                rotated=slides or bool(rotate_full), gated=bool(gate),
                rows=jax.numpy.asarray(at) if i == last else None)
            if i < config["num_dense_layers"]:
                out = dense(h, w("pre_mlp_layernorm"), w("gate.w"),
                            w("up.w"), w("down.w"))
            else:
                x, ranked, wts, near = route(h, w("pre_mlp_layernorm"),
                                             w("router"), w("expert_bias"))
                out = routed_part(x, ranked, wts, w("w1"), w("w3"), w("w2"),
                                  int(expert_offset), expert)
                out = out + dense(h, w("pre_mlp_layernorm"),
                                  w("shared_gate.w"), w("shared_up.w"),
                                  w("shared_down.w"))
                if routing:
                    every = slice(None) if i == last else at
                    ranked_all.append(np.asarray(ranked)[every])
                    scores.append(np.asarray(near)[every])
            h = close(h, out, w("post_mlp_layernorm"))
        rows = head(h, weights["norm"], weights["head.w"])
    if routing:
        return np.asarray(rows), np.stack(ranked_all), np.stack(scores)
    return np.asarray(rows)
