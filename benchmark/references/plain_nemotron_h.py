"""The plain reference of the ``nemotron_h`` family (NVIDIA Nemotron-H /
Nemotron 3: ``serving/decode/hybrid.py build_nemotron_h_model`` is the served
form): the forward pass in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, the whole sequence in one pass,
the state-space recurrence one token at a time (``lax.scan``), no cache, no
slots, no paging, no kernels. It shares nothing with the program but the
weights, which it is handed as the served (bfloat16) arrays by name and
upcasts LAYER BY LAYER, so that it fits beside the engine on the chip (the
largest layer, 16 experts, is 0.64 GB in float32).

The equations (``config`` holds the published keys as they are run):

* every block ``x <- x + mixer(RMSNorm(x))`` (weight, eps
  ``layer_norm_epsilon``), one mixer a block by ``hybrid_override_pattern``;
  a final RMSNorm; untied ``embed`` ``[V, H]`` and ``head.w`` ``[H, V]``; no
  bias but the convolution's.
* ``M``: ``in_proj`` to ``z | xBC | dt``; ``xBC <- silu(causal depthwise
  conv1d(xBC) + b)`` over ``conv_kernel`` taps (``conv_w`` ``[K, D]``, tap
  ``K - 1`` on the current token); ``x | B | C`` split, ``n_groups`` groups
  of B and C shared by ``heads / n_groups`` heads each; ``dt <- softplus(dt
  + dt_bias)``, ``A = -exp(a_log)``; per head ``h_t = exp(dt_t A) h_{t-1} +
  dt_t x_t (outer) B_t``, ``y_t = h_t . C_t + D x_t``; ``y <- RMSNorm(y *
  silu(z))`` over ``n_groups`` groups, times ``mixer_norm``; ``out_proj``.
* ``*``: ``q`` to ``heads x head_dim``, ``k``, ``v`` to ``kv_heads x
  head_dim``, causal softmax at ``1 / sqrt(head_dim)``, grouped-query, ``o``.
* ``E``: ``s = sigmoid(gate . x)`` over all ``router_experts``; the top
  ``num_experts_per_tok`` of ``s + select_bias``; weights the chosen ``s``
  over their sum + 1e-20 (``norm_topk_prob``), times
  ``routed_scaling_factor``; expert ``e``: ``w_down_e . relu(w_up_e . x)^2``
  (both ``[F, H]``); plus the shared expert, the same form, always.

Departures from the published description, each also under ``assumed`` in
the configuration's file:

* ``d_inner = mamba_num_heads x mamba_head_dim``; the published ``expand``
  is not read.
* NO rotary or other position encoding in the attention layers
  (``rope_theta`` and ``partial_rotary_factor`` are not read): as remembered
  of ``nemotron_h``'s modelling code, where the Mamba layers carry the order.
* of the ``router_experts`` experts only ``held`` (ids ``offset .. offset +
  held - 1``, the served share of an expert-parallel deployment) are summed:
  the router still scores all, chooses its top k over all and normalises
  over all k; what the absent experts would add is left out, here as in the
  program, and nothing stands in for them.
* the vocabulary is the served slice (``embed`` and ``head.w`` as given).
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
    eps = c["layer_norm_epsilon"]

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
    def head(h, norm_w, w):
        norm_w, w = up(norm_w, w)
        return op(_rms(h, norm_w, eps)) @ w

    @jax.jit
    def mamba(h, norm_w, in_w, conv_w, conv_b, dt_bias, a_log, d, mix_w,
              out_w):
        (norm_w, in_w, conv_w, conv_b, dt_bias, a_log, d, mix_w,
         out_w) = up(norm_w, in_w, conv_w, conv_b, dt_bias, a_log, d, mix_w,
                     out_w)
        heads, p = c["mamba_num_heads"], c["mamba_head_dim"]
        g, n = c["n_groups"], c["ssm_state_size"]
        d_inner, t, taps = heads * p, h.shape[0], conv_w.shape[0]
        zxbcdt = op(_rms(h, norm_w, eps)) @ in_w
        z = zxbcdt[:, :d_inner]
        xbc = zxbcdt[:, d_inner:2 * d_inner + 2 * g * n]
        dt = jax.nn.softplus(zxbcdt[:, 2 * d_inner + 2 * g * n:] + dt_bias)
        ext = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), f32), xbc])
        xbc = jax.nn.silu(
            sum(conv_w[k] * ext[k:k + t] for k in range(taps)) + conv_b)
        x = xbc[:, :d_inner].reshape(t, heads, p)
        b = jnp.repeat(xbc[:, d_inner:d_inner + g * n].reshape(t, g, n),
                       heads // g, axis=1)
        cc = jnp.repeat(xbc[:, d_inner + g * n:].reshape(t, g, n),
                        heads // g, axis=1)
        a = -jnp.exp(a_log)

        def step(state, inp):
            xt, dtt, bt, ct = inp
            state = (jnp.exp(dtt * a)[:, None, None] * state
                     + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
            return state, jnp.sum(state * ct[:, None, :], -1) + d[:, None] * xt

        _, y = jax.lax.scan(step, jnp.zeros((heads, p, n), f32),
                            (x, dt, b, cc))
        y = (y.reshape(t, d_inner) * jax.nn.silu(z)).reshape(t, g, -1)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
        return h + op(y.reshape(t, d_inner) * mix_w) @ out_w

    @jax.jit
    def attention(h, norm_w, qw, kw, vw, ow):
        norm_w, qw, kw, vw, ow = up(norm_w, qw, kw, vw, ow)
        nq, nkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                      c["head_dim"])
        t = h.shape[0]
        x = op(_rms(h, norm_w, eps))
        q = op(x @ qw).reshape(t, nkv, nq // nkv, d)
        k = op(x @ kw).reshape(t, nkv, d)
        v = op(x @ vw).reshape(t, nkv, d)
        scores = jnp.einsum("tgqd,sgd->gqts", q, k) / np.sqrt(d).astype(f32)
        causal = jnp.tril(jnp.ones((t, t), bool))
        att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        ctx = jnp.einsum("gqts,sgd->tgqd", att, v).reshape(t, nq * d)
        return h + op(ctx) @ ow

    @functools.partial(jax.jit, static_argnames=("offset",))
    def experts(h, norm_w, gate, select_bias, w_up, w_down, sh_up, sh_down,
                offset):
        norm_w, w_up, w_down, sh_up, sh_down = up(norm_w, w_up, w_down,
                                                  sh_up, sh_down)
        k, held = c["num_experts_per_tok"], w_up.shape[0]
        x = _rms(h, norm_w, eps)
        s = jax.nn.sigmoid(x @ gate.astype(f32).T)
        # one more than the router chooses: the first loser and its score
        # say how near a token's choice was to another (``routing``)
        near, ranked = jax.lax.top_k(s + select_bias.astype(f32), k + 1)
        idx, x = ranked[:, :k], op(x)
        w = jnp.take_along_axis(s, idx, -1)
        if c["norm_topk_prob"]:
            w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
        w = w * c["routed_scaling_factor"]
        out = op(jnp.square(jnp.maximum(x @ sh_up, 0.0))) @ sh_down
        for e in range(held):
            mine = jnp.sum(jnp.where(idx == offset + e, w, 0.0), -1)
            part = op(jnp.square(jnp.maximum(x @ w_up[e].T, 0.0))) @ w_down[e]
            out = out + mine[:, None] * part
        return h + out, ranked, near

    return embed, head, mamba, attention, experts


_KEYS = ("layer_norm_epsilon", "mamba_num_heads", "mamba_head_dim",
         "n_groups", "ssm_state_size", "num_attention_heads",
         "num_key_value_heads", "head_dim", "num_experts_per_tok",
         "norm_topk_prob", "routed_scaling_factor")


def logits(weights, config, tokens, positions, pad_to, expert_offset=0,
           round_to=None, round_operands=None, routing=False):
    """The logits ``[len(positions), vocabulary]`` that follow ``tokens`` at
    each of ``positions`` (position p: the distribution of token p + 1).
    ``weights`` by the program's names less their prefix; ``config`` the
    published keys as run; ``expert_offset`` the id of the first held
    expert. The sequence is padded to ``pad_to``: what follows a position
    reaches it neither through the causal mask nor through the recurrence.
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

    embed, head, mamba, attention, experts = _functions(
        tuple((k, config[k]) for k in _KEYS), round_to, round_operands)
    at = np.asarray(list(positions), np.int64)
    ranked, scores = [], []
    padded = np.zeros((pad_to,), np.int32)
    padded[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        h = embed(weights["embed"], padded)
        for i, kind in enumerate(config["hybrid_override_pattern"]):
            w = lambda part: weights[f"l{i}.{part}"]  # noqa: E731
            if kind == "M":
                h = mamba(h, w("norm"), w("in_proj.w"), w("conv_w"),
                          w("conv_b"), w("dt_bias"), w("a_log"), w("d"),
                          w("mixer_norm"), w("out_proj.w"))
            elif kind == "*":
                h = attention(h, w("norm"), w("q.w"), w("k.w"), w("v.w"),
                              w("o.w"))
            else:
                h, ids, near = experts(
                    h, w("norm"), w("gate"), w("select_bias"), w("w_up"),
                    w("w_down"), w("shared_up.w"), w("shared_down.w"),
                    offset=int(expert_offset))
                if routing:
                    ranked.append(np.asarray(ids)[at])
                    scores.append(np.asarray(near)[at])
        every = np.asarray(head(h, weights["final_norm"], weights["head.w"]))
    if routing:
        return every[at], np.stack(ranked), np.stack(scores)
    return every[at]
