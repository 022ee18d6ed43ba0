"""The plain reference of the dense ``granitemoehybrid`` family (IBM Granite
4.0-H: ``serving/decode/hybrid.py build_granite_hybrid_model`` is the served
form): the forward pass in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, the whole sequence in one pass,
the state-space recurrence one token at a time (``lax.scan``), no cache, no
slots, no paging, no kernels, no batching. It shares nothing with the
program but the weights, which it is handed as the served (bfloat16) arrays
by name and upcasts LAYER BY LAYER. Attention runs by blocks of queries and
the MLP by blocks of rows (``lax.map``), so that a 16k-token answer fits
beside the engine on the chip; neither changes a number.

The equations (``config`` holds the published keys as they are run):

* ``h = embed[tok] * embedding_multiplier``.
* every layer ``h <- h + residual_multiplier * mixer(RMSNorm(h))`` (weight
  ``input_layernorm``, eps ``rms_norm_eps``), the mixer by ``layer_types``,
  then ``h <- h + residual_multiplier * mlp(RMSNorm(h))`` (weight
  ``post_attention_layernorm``).
* ``mamba``: ``in_proj`` to ``z | xBC | dt``; ``xBC <- silu(causal depthwise
  conv1d(xBC) + b)`` over ``mamba_d_conv`` taps (``conv_w`` ``[K, D]``, tap
  ``K - 1`` on the current token); ``x | B | C`` split, ``mamba_n_groups``
  groups of B and C shared by ``heads / groups`` heads each (ONE group
  here: all 64 heads read the same B and C); ``dt <- softplus(dt +
  dt_bias)``, ``A = -exp(a_log)``; per head ``h_t = exp(dt_t A) h_{t-1} +
  dt_t x_t (outer) B_t``, ``y_t = h_t . C_t + D x_t``; ``y <- RMSNorm(y *
  silu(z))`` over each group's share of the inner width, times
  ``mixer_norm``; ``out_proj``.
* ``attention``: ``q`` to ``heads x head``, ``k``, ``v`` to ``kv_heads x
  head`` (a head ``hidden_size / num_attention_heads`` wide), NO position
  encoding, causal softmax of the scores times ``attention_multiplier``,
  grouped-query, ``o``.
* ``mlp``: ``mlp_down(silu(mlp_gate x) * mlp_up x)``, the published input
  projection's two halves stored apart, width ``shared_intermediate_size``.
* ``logits = (RMSNorm(h) * norm) embed^T / logits_scaling``: the head is the
  embedding.

Not read of the source: ``rope_theta`` (``position_embedding_type`` is
``nope``), ``mamba_expand`` (the inner width is ``mamba_n_heads x
mamba_d_head``), ``intermediate_size`` and the expert counts (0 experts: no
routed term).
"""

import functools

import numpy as np

#: queries one block of the attention covers, rows one block of the MLP
_QUERY_BLOCK, _ROW_BLOCK = 256, 2048


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
    eps, by = c["rms_norm_eps"], c["residual_multiplier"]

    def up(*ws):
        # ``round_to`` is the comparison's control and no part of the
        # reference: every weight through a narrower dtype first. The
        # barrier keeps the round trip: without it the TPU compiler drops
        # the pair of converts and the control reads as the sound
        # reference does (PR 51: bfloat16 -> float8_e4m3fn -> float32
        # came back unrounded, to the bit)
        if round_to is not None:
            ws = [jax.lax.optimization_barrier(w.astype(round_to))
                  for w in ws]
        return [w.astype(f32) for w in ws]

    def blocks(f, xs, size):
        """``f`` over the rows of ``xs`` (an array, or a tuple of arrays
        equally long) in blocks of ``size`` (a multiple of it long, or
        shorter than one)."""
        t = jax.tree.leaves(xs)[0].shape[0]
        if t <= size or t % size:
            return f(xs)
        out = jax.lax.map(f, jax.tree.map(
            lambda x: x.reshape((t // size, size) + x.shape[1:]), xs))
        return out.reshape((t,) + out.shape[2:])

    @jax.jit
    def embed(table, tokens):
        (rows,) = up(table[tokens])
        return rows * c["embedding_multiplier"]

    @jax.jit
    def head(h, norm_w, table):
        norm_w, table = up(norm_w, table)
        return _rms(h, norm_w, eps) @ table.T / c["logits_scaling"]

    @jax.jit
    def mamba(h, norm_w, in_w, conv_w, conv_b, dt_bias, a_log, d, mix_w,
              out_w):
        (norm_w, in_w, conv_w, conv_b, dt_bias, a_log, d, mix_w,
         out_w) = up(norm_w, in_w, conv_w, conv_b, dt_bias, a_log, d, mix_w,
                     out_w)
        heads, p = c["mamba_n_heads"], c["mamba_d_head"]
        g, n = c["mamba_n_groups"], c["mamba_d_state"]
        d_inner, t, taps = heads * p, h.shape[0], conv_w.shape[0]
        zxbcdt = _rms(h, norm_w, eps) @ in_w
        z = zxbcdt[:, :d_inner]
        xbc = zxbcdt[:, d_inner:2 * d_inner + 2 * g * n]
        dt = jax.nn.softplus(zxbcdt[:, 2 * d_inner + 2 * g * n:] + dt_bias)
        ext = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), f32), xbc])
        xbc = jax.nn.silu(
            sum(conv_w[k] * ext[k:k + t] for k in range(taps)) + conv_b)
        x = xbc[:, :d_inner].reshape(t, heads, p)
        b = xbc[:, d_inner:d_inner + g * n].reshape(t, g, n)
        cc = xbc[:, d_inner + g * n:].reshape(t, g, n)
        a = -jnp.exp(a_log)

        def step(state, inp):
            xt, dtt, bt, ct = inp
            bt = jnp.repeat(bt, heads // g, axis=0)          # [heads, n]
            ct = jnp.repeat(ct, heads // g, axis=0)
            state = (jnp.exp(dtt * a)[:, None, None] * state
                     + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
            return state, jnp.sum(state * ct[:, None, :], -1) + d[:, None] * xt

        _, y = jax.lax.scan(step, jnp.zeros((heads, p, n), f32),
                            (x, dt, b, cc))
        y = (y.reshape(t, d_inner) * jax.nn.silu(z)).reshape(t, g, -1)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
        return h + by * ((y.reshape(t, d_inner) * mix_w) @ out_w)

    @jax.jit
    def attention(h, norm_w, qw, kw, vw, ow):
        norm_w, qw, kw, vw, ow = up(norm_w, qw, kw, vw, ow)
        nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
        t, d = h.shape[0], qw.shape[1] // nq
        x = _rms(h, norm_w, eps)
        q = (x @ qw).reshape(t, nkv, nq // nkv, d)
        k = (x @ kw).reshape(t, nkv, d)
        v = (x @ vw).reshape(t, nkv, d)
        at = jnp.arange(t)

        def block(qa):
            qb, ab = qa
            scores = (jnp.einsum("tgqd,sgd->gqts", qb, k)
                      * c["attention_multiplier"])
            causal = at[None, :] <= ab[:, None]
            att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
            return jnp.einsum("gqts,sgd->tgqd", att, v).reshape(-1, nq * d)

        return h + by * (blocks(block, (q, at), _QUERY_BLOCK) @ ow)

    @jax.jit
    def mlp(h, norm_w, gate_w, up_w, down_w):
        norm_w, gate_w, up_w, down_w = up(norm_w, gate_w, up_w, down_w)

        def rows(hb):
            x = _rms(hb, norm_w, eps)
            return hb + by * ((jax.nn.silu(x @ gate_w) * (x @ up_w))
                              @ down_w)

        return blocks(rows, h, _ROW_BLOCK)

    return embed, head, mamba, attention, mlp


_KEYS = ("rms_norm_eps", "mamba_n_heads", "mamba_d_head", "mamba_n_groups",
         "mamba_d_state", "num_attention_heads", "num_key_value_heads",
         "embedding_multiplier", "attention_multiplier",
         "residual_multiplier", "logits_scaling")


def logits(weights, config, tokens, positions, pad_to, round_to=None,
           **read_as):
    """The logits ``[len(positions), vocabulary]`` that follow ``tokens`` at
    each of ``positions`` (position p: the distribution of token p + 1).
    ``weights`` by the program's names less their prefix; ``config`` the
    published keys as run. The sequence is padded to ``pad_to``: what
    follows a position reaches it neither through the causal mask nor
    through the recurrence. ``round_to`` (a dtype's name) and ``read_as``
    (a published key read as another value: ``attention_multiplier=0.125``)
    are for the comparison's controls alone: the same pass with every
    weight rounded through a narrower dtype, or with one multiplier
    misread, which a comparison worth its name has to tell from the served
    model."""
    import jax

    unknown = set(read_as) - set(_KEYS)
    if unknown:
        raise TypeError(f"no published key of the reference: {unknown}")
    sizes = dict({k: config[k] for k in _KEYS}, **read_as)
    embed, head, mamba, attention, mlp = _functions(
        tuple(sorted(sizes.items())), round_to)
    at = np.asarray(list(positions), np.int64)
    padded = np.zeros((pad_to,), np.int32)
    padded[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        h = embed(weights["embed"], padded)
        for i, kind in enumerate(config["layer_types"]):
            w = lambda part: weights[f"l{i}.{part}"]  # noqa: E731
            if kind == "mamba":
                h = mamba(h, w("input_layernorm"), w("in_proj.w"),
                          w("conv_w"), w("conv_b"), w("dt_bias"),
                          w("a_log"), w("d"), w("mixer_norm"),
                          w("out_proj.w"))
            else:
                h = attention(h, w("input_layernorm"), w("q.w"), w("k.w"),
                              w("v.w"), w("o.w"))
            h = mlp(h, w("post_attention_layernorm"), w("mlp_gate.w"),
                    w("mlp_up.w"), w("mlp_down.w"))
        # the head over the asked positions alone: 100k columns a row
        return np.asarray(head(h[at], weights["norm"], weights["embed"]))
