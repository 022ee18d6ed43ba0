"""The plain reference of the ``ouro`` family (ByteDance Ouro, a looped
language model: ``serving/decode/hybrid.py build_ouro_model`` is the served
form): the forward pass in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, the whole sequence in one
pass, no cache, no slots, no paging, no kernels. It shares nothing with the
program but the weights, which it is handed as the served (bfloat16) arrays
by name and upcasts a LAYER AT A TIME, so that it fits beside the engine on
the chip (a layer is 0.21 GB in float32).

The equations (``config`` holds the published keys as they are run; ``H``
the hidden size, ``D`` ``head_dim``, ``T`` ``total_ut_steps``):

* ``h = embed[token]`` (untied from the head).
* layer ``l`` at pass ``t`` (four RMSNorms a layer, ``rms_norm_eps``, no
  bias anywhere): ``h <- h + RMSNorm(attn_{t,l}(RMSNorm(h;
  input_layernorm)); post_attention_layernorm)``, then ``h <- h +
  RMSNorm(down . (silu(gate . x) * (up . x)); post_feedforward_layernorm)``
  with ``x = RMSNorm(h; pre_feedforward_layernorm)``.
* ``attn_{t,l}``: ``q, k, v = W x`` to ``num_attention_heads`` (q) and
  ``num_key_value_heads`` (k, v) heads of ``D``; rotary positions on q and
  k (``rope_theta``, the whole head, rotate-half: lane ``i < D / 2`` with
  lane ``i + D / 2``, angle ``position * theta^(-2 i / D)``); causal softmax
  at ``1 / sqrt(D)`` over the keys and values that THIS layer produced in
  THIS pass; ``o``. The weights of layer ``l`` are the same in every pass.
  (Every matrix is handed ``[in, out]`` but ``W_q`` and ``W_k``, which the
  served model stores ``[heads x D, H]``.)
* one pass: the ``num_hidden_layers`` layers in order, then ``h^t =
  RMSNorm(h; final_norm)``; the NORMED ``h^t`` enters pass ``t + 1``.
* exit gate, every pass: ``l_t = sigmoid(w_g . h^t + b_g)``; ``p_t = l_t
  prod_{j<t}(1 - l_j)`` for ``t < T`` and ``p_T = prod_{j<T}(1 - l_j)``.
* ``logits = head . h^T`` (every pass is taken: the published
  ``early_exit_threshold`` is 1).

What the catalog's row does not carry (the sandwich norms, the absent
biases, the norm between passes, the gate's form) is the family's paper
("Scaling Latent Reasoning via Looped Language Models") and its modelling
code as remembered, and stands under ``assumed`` in the configuration's
file.
"""

import functools

import numpy as np


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


#: (exponent bits, mantissa bits, smallest normal) of the narrow formats
#: (bfloat16 has float32's exponent: nothing met here is under its normals)
_NARROW = {"float8_e4m3fn": (4, 3, 2.0 ** -6), "float8_e5m2": (5, 2, 2.0 ** -14),
           "bfloat16": (8, 7, 0.0)}


def _through(w, dtype):
    """Float32 ``w`` rounded through ``dtype`` and back, to nearest even, by
    ``lax.reduce_precision`` and, under an 8-bit format's smallest normal,
    on its subnormals' fixed grid: a ``convert`` there and back is what the
    TPU compiler may leave out of a fused chain (it keeps excess precision:
    on the chip the float8 control read the float32 reference's own numbers
    to sixteen digits, PERF.md section 6, PR 43). Magnitudes past the
    format's largest are not met by a weight and not handled."""
    import jax
    import jax.numpy as jnp

    if dtype not in _NARROW:
        return w.astype(dtype).astype(jnp.float32)
    exponent, mantissa, normal = _NARROW[dtype]
    rounded = jax.lax.reduce_precision(w, exponent, mantissa)
    if not normal:
        return rounded
    step = normal / 2 ** mantissa
    return jnp.where(jnp.abs(w) < normal, jnp.round(w / step) * step, rounded)


@functools.lru_cache(maxsize=None)
def _functions(sizes, round_to=None, round_operands=None):
    import jax
    import jax.numpy as jnp

    c = dict(sizes)
    f32 = jnp.float32
    eps = c["rms_norm_eps"]

    def up(*ws):
        # ``round_to`` is the comparison's control and no part of the
        # reference: every weight through a narrower dtype first
        ws = [w.astype(f32) for w in ws]
        return ws if round_to is None else [_through(w, round_to)
                                            for w in ws]

    def cut(x):
        # ``round_operands`` is a diagnosis and no part of the reference:
        # an activation through the dtype the served program holds it in,
        # at that program's own cast points (a norm's output that feeds a
        # product, q and the stored k and v, the softmax's weights, the
        # attention's output, the gated product, the head's input); the
        # residual stream, the norms and every accumulation stay float32
        return x if round_operands is None else _through(x, round_operands)

    @jax.jit
    def embed(table, tokens):
        return table[tokens].astype(f32)

    def rotate(x, positions):
        # x [T, heads, D]: the whole head, rotate-half
        half = x.shape[-1] // 2
        freq = c["rope_theta"] ** (-jnp.arange(half, dtype=f32) / half)
        angle = positions.astype(f32)[:, None, None] * freq
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    @jax.jit
    def layer(h, n1, qw, kw, vw, ow, n2, n3, gw, uw, dw, n4):
        n1, qw, kw, vw, ow, n2, n3, gw, uw, dw, n4 = up(
            n1, qw, kw, vw, ow, n2, n3, gw, uw, dw, n4)
        nq, nkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                      c["head_dim"])
        t = h.shape[0]
        at = jnp.arange(t)
        x = cut(_rms(h, n1, eps))
        # the q and k matrices are handed as stored, [heads x D, H]
        q = cut(rotate((x @ qw.T).reshape(t, nq, d), at)).reshape(
            t, nkv, nq // nkv, d)
        k = cut(rotate((x @ kw.T).reshape(t, nkv, d), at))
        v = cut(x @ vw).reshape(t, nkv, d)
        scores = jnp.einsum("tgqd,sgd->gqts", q, k) / np.sqrt(d).astype(f32)
        causal = jnp.tril(jnp.ones((t, t), bool))
        att = cut(jax.nn.softmax(jnp.where(causal, scores, -jnp.inf),
                                 axis=-1))
        ctx = cut(jnp.einsum("gqts,sgd->tgqd", att, v).reshape(t, nq * d))
        h = h + _rms(ctx @ ow, n2, eps)
        x = cut(_rms(h, n3, eps))
        gated = cut(jax.nn.silu(x @ gw) * (x @ uw))
        return h + _rms(gated @ dw, n4, eps)

    @jax.jit
    def close(h, norm_w, gate_w, gate_b):
        (norm_w,) = up(norm_w)
        h = _rms(h, norm_w, eps)
        leave = jax.nn.sigmoid(h @ gate_w.astype(f32) + gate_b.astype(f32))
        return h, leave[:, 0]

    @jax.jit
    def head(h, w):
        (w,) = up(w)
        return cut(h) @ w

    return embed, layer, close, head


_KEYS = ("rms_norm_eps", "num_attention_heads", "num_key_value_heads",
         "head_dim")
_LAYER = ("input_layernorm", "q.w", "k.w", "v.w", "o.w",
          "post_attention_layernorm", "pre_feedforward_layernorm", "gate.w",
          "up.w", "down.w", "post_feedforward_layernorm")


def forward(weights, config, tokens, positions, pad_to, round_to=None,
            passes=None, round_operands=None):
    """``(logits [len(positions), vocabulary], exit [len(positions),
    passes])``: the logits that follow ``tokens`` at each of ``positions``
    (position p: the distribution of token p + 1) and the exit gate's
    distribution ``p_1 .. p_T`` there. ``weights`` by the program's names
    less their prefix; ``config`` the published keys as run. The sequence
    is padded to ``pad_to``: what follows a position does not reach it
    through the causal mask. ``round_to`` (a dtype's name) and ``passes``
    (fewer than ``total_ut_steps``) are for the comparison's controls
    alone: the same pass with every weight rounded through a narrower
    dtype, or the stack run fewer times, which a comparison worth its name
    has to tell from the served model. ``round_operands`` (a dtype's name)
    is a diagnosis: the activations rounded where the served program
    rounds them, which says how far rounding alone carries a row from this
    reference's."""
    import jax

    sizes = tuple((k, config[k]) for k in _KEYS) + (
        ("rope_theta", float(config["rope_theta"])),)
    embed, layer, close, head = _functions(sizes, round_to, round_operands)
    at = np.asarray(list(positions), np.int64)
    padded = np.zeros((pad_to,), np.int32)
    padded[:len(tokens)] = tokens
    steps = int(config["total_ut_steps"] if passes is None else passes)
    stays, exits = np.ones(len(at), np.float32), []
    with jax.default_matmul_precision("highest"):
        h = embed(weights["embed"], padded)
        for t in range(steps):
            for i in range(config["num_hidden_layers"]):
                h = layer(h, *(weights[f"l{i}.{part}"] for part in _LAYER))
            h, leave = close(h, weights["final_norm"],
                             weights["exit_gate.w"], weights["exit_gate.b"])
            leave = np.asarray(leave)[at]
            exits.append(stays * leave if t < steps - 1 else stays)
            stays = stays * (1.0 - leave)
        every = np.asarray(head(h, weights["head.w"]))
    return every[at], np.stack(exits, axis=1)


def logits(weights, config, tokens, positions, pad_to, round_to=None,
           passes=None, round_operands=None):
    """``forward``'s logits alone: what the cell's comparison reads."""
    return forward(weights, config, tokens, positions, pad_to,
                   round_to=round_to, passes=passes,
                   round_operands=round_operands)[0]
