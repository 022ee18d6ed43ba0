"""The plain reference of the ``mistral4`` family (Mistral Small 4, the
DeepSeek-V2/V3 decoder's keys: ``serving/decode/hybrid.py
build_latent_moe_model`` is the served form): the forward pass in
``jax.numpy`` and float32 under ``jax.default_matmul_precision("highest")``,
the whole sequence in one pass, EXPANDED attention (every token's compressed
K/V up-projected to every head's key and value), no cache, no slots, no
paging, no kernels, no batching. It shares nothing with the program but the
weights, which it is handed as the served (bfloat16) arrays by name and
upcasts layer by layer (the routed experts one expert at a time). Attention
runs by blocks of queries and the experts by blocks of rows (``lax.map``),
so that 32k positions fit beside the engine's weights on the chip; neither
changes a number.

The equations (``config`` holds the published keys as they are run; ``rp``
is its ``rope_parameters`` group), for token ``t`` at position ``p``:

1. ``h = RMSNorm(x)`` (``input_layernorm``, eps ``rms_norm_eps``). ``c_q =
   RMSNorm(h . q_a)`` (``q_lora_rank`` wide); ``q = c_q . q_b``, ``heads x
   (nope + rope)``: head i is ``[q_i^N | q_i^R]``.
2. ``[c | k^R] = h . kv_a`` (``kv_lora_rank`` + ``rope``); ``c <-
   RMSNorm(c)``. Head i: ``k_i^N = c . kv_b_k[i]^T`` (``nope``), ``v_i = c .
   kv_b_v[i]`` (``v_head_dim``): the published ``kv_b_proj``'s rows by head,
   its key part and its value part stored apart. ``k^R`` is ONE vector a
   token, shared by all heads.
3. Rotation of ``q_i^R`` and ``k^R`` over their ``rope`` lanes, INTERLEAVED
   pairs ``(2 j, 2 j + 1)`` (``rope_interleave``), angle ``p f_j``. YaRN:
   ``theta_j = rope_theta^(-2 j / rope)``; ``r(b) = rope ln(original / (2 pi
   b)) / (2 ln rope_theta)``, ``low = floor(r(beta_fast))``, ``high =
   ceil(r(beta_slow))``; ``g_j = clip((j - low) / (high - low), 0, 1)``;
   ``f_j = (1 - g_j) theta_j + g_j theta_j / factor``. Sines and cosines are
   not scaled (``mscale == mscale_all_dim``).
4. ``q_i <- q_i (1 + llama_4_scaling_beta ln(1 + floor(p / original)))``.
5. ``a_ij = softmax_j(s (q_i^N . k_ji^N + q_i^R . k_j^R))``, causal, every
   layer full attention; ``s = (nope + rope)^-1/2 m^2``, ``m = 0.1
   mscale_all_dim ln(factor) + 1``.
6. ``x <- x + concat_i(sum_j a_ij v_ji) . o``.
7. ``h = RMSNorm(x)`` (``post_attention_layernorm``). Router over ALL
   ``router_experts``: ``softmax(h . gate^T)``, top ``num_experts_per_tok``,
   the chosen weights over their sum (``norm_topk_prob``), times
   ``routed_scaling_factor``. ``x <- x + sum over the chosen experts HELD
   here of w_e E_e(h) + E_shared(h)``, ``E(h) = (silu(h . w1) * (h . w3)) .
   w2`` of width ``moe_intermediate_size``.
8. ``logits = RMSNorm(x) (norm) . head`` over the vocabulary rows held.

Departures from the published description, each also in the
configuration's ``assumed``:

* the router's SCORING is a softmax over all the experts: the published keys
  name none (``n_group = topk_group = 1``, ``norm_topk_prob``, no
  ``scoring_func``); softmax is the Mixtral lineage's, and with the
  renormalisation it equals a softmax over the chosen logits. No selection
  bias (the served ``select_bias`` is zeros and is not read here).
* ``s``'s ``m^2`` follows the DeepSeek-V3 convention these keys belong to
  (``softmax_scale * mscale * mscale`` with ``mscale = yarn_get_mscale(
  factor, mscale_all_dim)``).
* the vision encoder is not part of the language model's keys and is left
  out; traffic is text.
* ``intermediate_size`` (a dense layer's width) is not read:
  ``first_k_dense_replace`` is 0, every layer is an expert layer.
"""

import functools
import math

import numpy as np

#: queries one block of the attention covers, rows one block of the experts
_QUERY_BLOCK, _ROW_BLOCK = 64, 2048


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def frequencies(rope, rp):
    """Step 3's ``f_j``, ``rope / 2`` numbers."""
    theta, original = float(rp["rope_theta"]), float(
        rp["original_max_position_embeddings"])

    def r(b):
        return rope * math.log(original / (2 * math.pi * b)) / (
            2 * math.log(theta))

    low = max(math.floor(r(rp["beta_fast"])), 0)
    high = min(math.ceil(r(rp["beta_slow"])), rope - 1)
    out = []
    for j in range(rope // 2):
        t = theta ** (-2.0 * j / rope)
        g = min(max((j - low) / max(high - low, 0.001), 0.0), 1.0)
        out.append((1 - g) * t + g * t / float(rp["factor"]))
    return np.asarray(out, np.float64)


@functools.lru_cache(maxsize=None)
def _functions(sizes, round_to=None):
    import jax
    import jax.numpy as jnp

    c = dict(sizes)
    f32 = jnp.float32
    eps = c["rms_norm_eps"]
    heads, nope, rope = (c["num_attention_heads"], c["qk_nope_head_dim"],
                         c["qk_rope_head_dim"])
    rp = dict(c["rope_parameters"])
    freq = jnp.asarray(frequencies(rope, rp), f32)
    m = 0.1 * rp["mscale_all_dim"] * math.log(rp["factor"]) + 1.0
    scale = m * m / math.sqrt(nope + rope)
    beta, original = (rp["llama_4_scaling_beta"],
                      rp["original_max_position_embeddings"])

    def up(*ws):
        # ``round_to`` is the comparison's control and no part of the
        # reference: every weight through a narrower dtype first (the
        # barrier keeps the round trip: plain_granite_hybrid.py)
        if round_to is not None:
            ws = [jax.lax.optimization_barrier(w.astype(round_to))
                  for w in ws]
        return [w.astype(f32) for w in ws]

    def blocks(f, xs, size):
        t = jax.tree.leaves(xs)[0].shape[0]
        if t <= size or t % size:
            return f(xs)
        out = jax.lax.map(f, jax.tree.map(
            lambda x: x.reshape((t // size, size) + x.shape[1:]), xs))
        return out.reshape((t,) + out.shape[2:])

    def rotate(x, at):
        """``x`` ``[T, ..., rope]`` turned by ``at`` ``[T]``, pairs ``(2 j,
        2 j + 1)``; with ``rope_lanes`` false (a control's) left as it is."""
        if not c["rope_lanes"]:
            return x
        angle = at.astype(f32).reshape((-1,) + (1,) * (x.ndim - 1)) * freq
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        pairs = x.reshape(x.shape[:-1] + (rope // 2, 2))
        a, b = pairs[..., 0], pairs[..., 1]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         -1).reshape(x.shape)

    @jax.jit
    def embed(table, tokens):
        (rows,) = up(table[tokens])
        return rows

    @jax.jit
    def head(h, norm_w, head_w):
        norm_w, head_w = up(norm_w, head_w)
        return _rms(h, norm_w, eps) @ head_w

    @jax.jit
    def attention(h, at, norm_w, qa_w, qa_norm, qb_w, kva_w, kva_norm, wk,
                  wv, ow):
        (norm_w, qa_w, qa_norm, qb_w, kva_w, kva_norm, wk, wv,
         ow) = up(norm_w, qa_w, qa_norm, qb_w, kva_w, kva_norm, wk, wv, ow)
        t, latent = h.shape[0], wk.shape[-1]
        x = _rms(h, norm_w, eps)
        q = (_rms(x @ qa_w, qa_norm, eps) @ qb_w).reshape(
            t, heads, nope + rope)
        q = q * (1.0 + beta * jnp.log1p(jnp.floor(
            at.astype(f32) / original)))[:, None, None]
        qn, qr = q[..., :nope], rotate(q[..., nope:], at)
        ckv = x @ kva_w
        cl = _rms(ckv[:, :latent], kva_norm, eps)
        kr = rotate(ckv[:, latent:], at)                      # [T, rope]
        kn = jnp.einsum("tl,hnl->thn", cl, wk)                # [T, heads, n]
        v = jnp.einsum("tl,hlv->thv", cl, wv)

        def block(qa):
            qnb, qrb, ab = qa
            scores = (jnp.einsum("thn,shn->hts", qnb, kn)
                      + jnp.einsum("thr,sr->hts", qrb, kr)) * scale
            causal = at[None, :] <= ab[:, None]
            att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
            return jnp.einsum("hts,shv->thv", att, v).reshape(
                qnb.shape[0], -1)

        return h + blocks(block, (qn, qr, at), _QUERY_BLOCK) @ ow

    @jax.jit
    def experts(h, norm_w, gate, w1, w3, w2, s1, s3, s2, offset):
        norm_w, gate, s1, s3, s2 = up(norm_w, gate, s1, s3, s2)
        held, k = w1.shape[0], c["num_experts_per_tok"]

        def rows(hb):
            x = _rms(hb, norm_w, eps)
            score = jax.nn.softmax(x @ gate.T, axis=-1)
            w, idx = jax.lax.top_k(score, k)
            if c["norm_topk_prob"]:
                w = w / jnp.sum(w, -1, keepdims=True)
            w = w * c["routed_scaling_factor"]
            local = idx - offset
            # [T, held]: the weight of each held expert a token chose
            cw = jnp.sum(jnp.where(
                local[:, :, None] == jnp.arange(held)[None, None, :],
                w[:, :, None], 0.0), axis=1)

            def one(acc, e):
                g, u, d, ce = e
                g, u, d = up(g, u, d)                  # [F, H] each
                y = (jax.nn.silu(x @ g.T) * (x @ u.T)) @ d
                return acc + ce[:, None] * y, None

            routed, _ = jax.lax.scan(one, jnp.zeros_like(hb),
                                     (w1, w3, w2, cw.T))
            shared = (jax.nn.silu(x @ s1) * (x @ s3)) @ s2
            return hb + routed + shared

        return blocks(rows, h, _ROW_BLOCK)

    return embed, head, attention, experts


_KEYS = ("rms_norm_eps", "num_attention_heads", "qk_nope_head_dim",
         "qk_rope_head_dim", "num_experts_per_tok", "norm_topk_prob",
         "routed_scaling_factor")

#: the keys of ``rope_parameters`` the equations read
_ROPE_KEYS = ("rope_theta", "factor", "beta_fast", "beta_slow",
              "original_max_position_embeddings", "mscale_all_dim",
              "llama_4_scaling_beta")


def logits(weights, config, tokens, positions, pad_to, expert_offset=0,
           round_to=None, **read_as):
    """The logits ``[len(positions), vocabulary]`` that follow ``tokens`` at
    each of ``positions`` (position p: the distribution of token p + 1).
    ``weights`` by the program's names less their prefix (the held experts'
    share: ids ``expert_offset ..``); ``config`` the published keys as run.
    The sequence is padded to ``pad_to``: what follows a position reaches it
    through nothing. ``round_to`` (a dtype's name) and ``read_as`` are for
    the comparison's controls alone: every weight rounded through a narrower
    dtype, or the description misread (a key of ``_KEYS`` or of
    ``rope_parameters`` as another value: ``llama_4_scaling_beta=0.0``,
    ``mscale_all_dim=0.0``; ``rope_lanes=False``: the rotary lanes left
    unrotated)."""
    import jax

    unknown = set(read_as) - set(_KEYS) - set(_ROPE_KEYS) - {"rope_lanes"}
    if unknown:
        raise TypeError(f"no published key of the reference: {unknown}")
    rp = {k: float(config["rope_parameters"][k]) for k in _ROPE_KEYS}
    rp.update({k: float(v) for k, v in read_as.items() if k in _ROPE_KEYS})
    sizes = {k: config[k] for k in _KEYS}
    sizes.update({k: v for k, v in read_as.items() if k in _KEYS})
    sizes["rope_parameters"] = tuple(sorted(rp.items()))
    sizes["rope_lanes"] = bool(read_as.get("rope_lanes", True))
    embed, head, attention, experts = _functions(
        tuple(sorted(sizes.items())), round_to)
    at = np.asarray(list(positions), np.int64)
    padded = np.zeros((pad_to,), np.int32)
    padded[:len(tokens)] = tokens
    where = np.arange(pad_to, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        h = embed(weights["embed"], padded)
        for i in range(config["num_hidden_layers"]):
            w = lambda part: weights[f"l{i}.{part}"]  # noqa: E731
            h = attention(h, where, w("input_layernorm"), w("q_a.w"),
                          w("q_a_layernorm"), w("q_b.w"), w("kv_a.w"),
                          w("kv_a_layernorm"), w("kv_b_k"), w("kv_b_v"),
                          w("o.w"))
            h = experts(h, w("post_attention_layernorm"), w("gate"),
                        w("w1"), w("w3"), w("w2"), w("shared_gate.w"),
                        w("shared_up.w"), w("shared_down.w"),
                        np.int32(expert_offset))
        return np.asarray(head(h[at], weights["norm"], weights["head.w"]))
