"""The plain reference of the ``keye_vl`` language model (Kwai Keye-VL-2.0's
decoder: the Qwen3-MoE layer with a DeepSeek-Sparse-Attention indexer a
layer; ``serving/decode/hybrid.py build_keye_vl_model`` is the served form):
the forward pass in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, the whole sequence in one pass,
no cache, no slots, no paging, no kernels, no batch. It shares nothing with
the program but the weights, which it is handed as the served (bfloat16)
arrays by name and upcasts LAYER BY LAYER, the head a slice of the
vocabulary at a time, so that it fits beside the weights on the chip once the
arenas are released. A layer's attention runs a block of ``_QUERY_ROWS``
query rows at a time (a ``[heads, rows, n]`` tile of scores, 1 GB at 32k;
one compiled function a padded length, called for the blocks that hold a
real position), its experts the same rows at a time, and the LAST layer
runs the asked positions alone.

The equations (``config`` holds the published keys as they are run; ``H``
the hidden size, ``D = head_dim``, ``sa = sa_config``). For token t at
position p_t, ``x_t = RMSNorm(h_t; input_layernorm)``:

* ``q_t = rot(N_q(W_q x_t))`` (``num_attention_heads`` of D), ``k_t =
  rot(N_k(W_k x_t))``, ``v_t = W_v x_t`` (``num_key_value_heads`` of D);
  ``N_q``, ``N_k`` RMSNorms over a head; ``rot`` the whole head,
  rotate-half: lane ``i < D / 2`` with lane ``i + D / 2``, angle ``p *
  rope_theta^(-2 i / D)``.
* the indexer: ``qI_t = rot(W_qI x_t)`` (``sa.indexer_num_heads`` of
  ``sa.indexer_head_dim``); ``kI_t = rot(LN(W_kI x_t))``, ONE key head for
  all (LayerNorm with weight and bias, eps 1e-6; the whole head rotated);
  ``w_t = W_w x_t . heads^-1/2 . dim^-1/2``.
* ``I(t, s) = sum_j w_{t,j} . relu(qI_{t,j} . kI_s)`` for ``s <= t``.
* ``S_t`` = the ``min(sa.topk, t + 1)`` positions ``s <= t`` of largest
  ``I(t, s)``, a tie to the LOWER ``s`` (as ``lax.top_k``). One set a token,
  shared by all heads.
* ``o_t = W_o . concat_h(sum_{s in S_t} softmax_{s in S_t}(q_{t,h} .
  k_{s,g(h)} / sqrt(D)) v_{s,g(h)})``; ``h += o``; ``f = RMSNorm(h;
  post_attention_layernorm)``; ``p = softmax(gate . f)`` over ALL
  ``router_experts`` in float32; the top ``num_experts_per_tok`` kept, their
  weights over their sum (``norm_topk_prob``); ``h += sum_e w_e . w2_e
  (silu(w1_e f) * (w3_e f))``; ``logits = head . RMSNorm(h; norm)``, the
  head untied; ``rms_norm_eps``; no bias but the index key's LayerNorm's.

Departures from the published description, each also under ``assumed`` in
the configuration's file: the QK-norm (the family's), the indexer's input
(the layer's normed hidden state: the family has no query latent), its
whole-head rotation, its key's LayerNorm, no Hadamard rotation (an
orthogonal map of qI and kI leaves their products unchanged) and no float8;
``sa.q_chunk_size`` / ``sa.kv_chunk_size`` tile the source's score
computation and change no result; the three ``mrope_section``s carry the
same position for text, so the rotation is the plain rotary one. Of the
``router_experts`` experts only ``held`` (ids ``offset .. offset + held -
1``) are summed, as in ``plain_sdar``.
"""

import functools

import numpy as np

from benchmark.references.plain_ouro import _through

#: slices the vocabulary is taken in by the head
_HEAD_SLICES = 8

#: query rows a block of a layer's attention covers
_QUERY_ROWS = 256


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _kth_largest(x, k):
    """The ``k``-th largest value of each row of ``x`` (float32, ``-inf``
    where a position is not seen), exactly and without a sort: what
    ``lax.top_k(x, k)[0][:, -1:]`` gives, found by fixing the bits of the
    answer from the highest down (32 counts a row over the floats' ordered
    bit pattern). On the chip ``lax.top_k`` of 2,048 out of 32,768 sorts,
    and a pass of this reference over a 32k-token sequence spent more time
    in it than in its attention (PERF.md section 6, PR 63)."""
    import jax
    import jax.numpy as jnp

    i32 = jnp.int32
    bits = jax.lax.bitcast_convert_type(
        jnp.where(x == 0, jnp.float32(0.0), x), i32)
    key = bits ^ ((bits >> 31) & i32(0x7FFFFFFF))

    def reach(cand):
        return jnp.sum(key >= cand, axis=1, keepdims=True) >= k

    zero = jnp.zeros((x.shape[0], 1), i32)
    t = jnp.where(reach(zero), zero, zero + i32(-(1 << 31)))

    def fix(i, t):
        cand = t + jax.lax.shift_left(i32(1), i32(30) - i)
        return jnp.where(reach(cand), cand, t)

    t = jax.lax.fori_loop(0, 31, fix, t)
    return jax.lax.bitcast_convert_type(
        t ^ ((t >> 31) & i32(0x7FFFFFFF)), jnp.float32)


@functools.lru_cache(maxsize=None)
def _functions(sizes, round_to=None):
    import jax
    import jax.numpy as jnp

    c = dict(sizes)
    f32 = jnp.float32
    eps = c["rms_norm_eps"]
    nq, nkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    ih, idim = c["indexer_num_heads"], c["indexer_head_dim"]

    def up(*ws):
        # ``round_to`` is the comparison's control and no part of the
        # reference: every weight through a narrower dtype first
        ws = [w.astype(f32) for w in ws]
        return ws if round_to is None else [_through(w, round_to)
                                            for w in ws]

    @jax.jit
    def embed(table, tokens):
        return up(table[tokens])[0]

    @jax.jit
    def final_norm(h, norm_w):
        return _rms(h, up(norm_w)[0], eps)

    @jax.jit
    def head(x, w):
        return x @ up(w)[0]

    def rotate(x, positions):
        # x [T, heads, width]: the whole head, rotate-half
        half = x.shape[-1] // 2
        freq = c["rope_theta"] ** (-jnp.arange(half, dtype=f32) / half)
        angle = positions.astype(f32)[:, None, None] * freq
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    @jax.jit
    def prepare(h, norm_w, kw, vw, kn, ik, ikn, ikb, turn_keys):
        """A layer's normed input and what every query of it reads: ``x``,
        the rotated keys, the values and the index keys of ALL positions
        (``turn_keys`` 0.0 is a control's: the index keys unrotated)."""
        norm_w, kw, vw, kn, ik, ikn, ikb = up(norm_w, kw, vw, kn, ik, ikn,
                                              ikb)
        t = h.shape[0]
        pos = jnp.arange(t)
        x = _rms(h, norm_w, eps)
        k = rotate(_rms((x @ kw).reshape(t, nkv, d), kn, eps), pos)
        v = (x @ vw).reshape(t, nkv, d)
        raw = x @ ik                                          # [t, idim]
        mean = jnp.mean(raw, -1, keepdims=True)
        var = jnp.mean((raw - mean) ** 2, -1, keepdims=True)
        ki = ((raw - mean) * jax.lax.rsqrt(var + 1e-6) * ikn + ikb)[:, None]
        return x, k, v, rotate(ki, pos * turn_keys)[:, 0]

    @functools.partial(jax.jit, static_argnames=(
        "select", "topk", "relu", "index_lag", "tie_low", "kth"))
    def block(x, k, v, ki, rows, n, qw, qn, ow, iq, iw, select, topk, relu,
              index_lag, tie_low, kth):
        """What attention adds to the residual at the query rows ``rows``
        of a sequence of ``n`` real positions, and how many rows each
        kept."""
        qw, qn, ow, iq, iw = up(qw, qn, ow, iq, iw)
        t = x.shape[0]
        pos = jnp.arange(t)
        xr = x[rows]
        q = rotate(_rms((xr @ qw).reshape(-1, nq, d), qn, eps), rows)
        qi = rotate((xr @ iq).reshape(-1, ih, idim), rows)
        w = (xr @ iw) / np.sqrt(ih * idim).astype(f32)         # [r, ih]
        dots = jnp.einsum("rjd,sd->rjs", qi, ki)
        dots = jnp.maximum(dots, 0.0) if relu else dots
        score = jnp.einsum("rjs,rj->rs", dots, w)              # [r, t]
        sees = (pos[None, :] <= rows[:, None]) & (pos[None, :] < n)
        # ``index_lag`` is a control's: the key of the query's own position
        # not yet in the arena (zeros there: a score of 0)
        if index_lag:
            score = jnp.where(pos[None, :] == rows[:, None], 0.0, score)
        keep = sees
        if select:
            masked = jnp.where(sees, score, -jnp.inf)
            edge = (_kth_largest(masked, min(topk, t)) if kth == "bisect"
                    else jax.lax.top_k(masked, min(topk, t))[0][:, -1:])
            above = masked > edge
            ties = (masked == edge) & sees
            order = (jnp.cumsum(ties, axis=1) if tie_low else
                     jnp.cumsum(ties[:, ::-1], axis=1)[:, ::-1])
            quota = min(topk, t) - jnp.sum(above, 1, keepdims=True)
            keep = sees & (above | (ties & (order <= quota)))
        qg = q.reshape(-1, nkv, nq // nkv, d)
        att = jnp.einsum("rgqd,sgd->gqrs", qg, k) / np.sqrt(d).astype(f32)
        att = jax.nn.softmax(jnp.where(keep, att, -jnp.inf), axis=-1)
        ctx = jnp.einsum("gqrs,sgd->rgqd", att, v).reshape(-1, nq * d)
        return ctx @ ow, jnp.sum(keep, axis=1)

    @functools.partial(jax.jit, static_argnames=("offset",))
    def experts(h, norm_w, gate, w1, w3, w2, offset):
        norm_w, w1, w3, w2 = up(norm_w, w1, w3, w2)
        k, held = c["num_experts_per_tok"], w1.shape[0]
        x = _rms(h, norm_w, eps)
        p = jax.nn.softmax(x @ gate.astype(f32).T, axis=-1)
        w, idx = jax.lax.top_k(p, k)
        if c["norm_topk_prob"]:
            w = w / jnp.sum(w, -1, keepdims=True)
        out = jnp.zeros_like(h)
        for e in range(held):
            mine = jnp.sum(jnp.where(idx == offset + e, w, 0.0), -1)
            part = (jax.nn.silu(x @ w1[e].T) * (x @ w3[e].T)) @ w2[e]
            out = out + mine[:, None] * part
        return h + out

    return embed, final_norm, head, prepare, block, experts


_KEYS = ("rms_norm_eps", "num_attention_heads", "num_key_value_heads",
         "head_dim", "num_experts_per_tok", "norm_topk_prob")


def logits(weights, config, tokens, positions, pad_to, expert_offset=0,
           round_to=None, select=True, topk=None, relu=True,
           rotate_index_keys=True, index_lag=False, tie_low=True,
           kth="top_k", kept=None):
    """The logits ``[len(positions), vocabulary]`` of the token AFTER each
    of ``positions`` of ``tokens``. ``weights`` by the program's names less
    their prefix; ``config`` the published keys as run; ``expert_offset``
    the id of the first held expert. The sequence is padded to ``pad_to``:
    the padding is masked from every position. The other keywords are for
    the comparison's controls alone: ``round_to`` (every weight through a
    narrower dtype), ``select=False`` (every row attended), ``topk``
    (another count), ``relu=False``, ``rotate_index_keys=False``,
    ``index_lag=True`` (a query's own index key not yet written),
    ``tie_low=False`` (a tie to the HIGHER position). ``kth`` says how
    the k-th largest score of a row is found: ``"top_k"`` (``lax.top_k``,
    the definition) or ``"bisect"`` (`_kth_largest`: the same value without
    a sort, what a 32k-token pass on the chip can afford). ``kept``, a
    list, receives the rows each asked position kept in the last layer."""
    import jax
    import jax.numpy as jnp

    sa = config["sa_config"]
    sizes = tuple((k, config[k]) for k in _KEYS) + (
        ("rope_theta", float(config["rope_theta"])),
        ("indexer_num_heads", sa["indexer_num_heads"]),
        ("indexer_head_dim", sa["indexer_head_dim"]))
    embed, final_norm, head, prepare, block, experts = _functions(
        sizes, round_to)
    asked = np.asarray(list(positions), np.int32)
    n_layers, n = config["num_hidden_layers"], len(tokens)
    padded = np.zeros((pad_to,), np.int32)
    padded[:n] = tokens
    rows = min(_QUERY_ROWS, pad_to)
    # the query rows of a layer, a block each: every real position (the
    # padding's rows are computed by nobody and stay as they are), or in
    # the last layer the asked positions alone, padded with position 0
    live = [np.arange(lo, lo + rows, dtype=np.int32)
            for lo in range(0, n, rows)]
    last = np.zeros((-(-len(asked) // rows) * rows,), np.int32)
    last[:len(asked)] = asked
    how = dict(select=bool(select), topk=int(topk or sa["topk"]),
               relu=bool(relu), index_lag=bool(index_lag),
               tie_low=bool(tie_low), kth=str(kth))
    with jax.default_matmul_precision("highest"):
        h = embed(weights["embed"], padded)
        for i in range(n_layers):
            w = lambda part: weights[f"l{i}.{part}"]  # noqa: E731
            x, k, v, ki = prepare(
                h, w("input_layernorm"), w("k.w"), w("v.w"), w("k_norm"),
                w("index_k.w"), w("index_k_norm"), w("index_k_norm_b"),
                np.int32(bool(rotate_index_keys)))
            blocks = live if i < n_layers - 1 else list(
                last.reshape(-1, rows))
            outs = [block(x, k, v, ki, at, np.int32(n), w("q.w"),
                          w("q_norm"), w("o.w"), w("index_q.w"),
                          w("index_w.w"), **how) for at in blocks]
            at = np.concatenate(blocks)
            h = h[at] + jnp.concatenate([o for o, _c in outs])
            count = jnp.concatenate([c for _o, c in outs])
            # the experts a block of rows at a time (one compiled shape)
            h = jnp.concatenate([
                experts(h[lo:lo + rows], w("post_attention_layernorm"),
                        w("gate"), w("w1"), w("w3"), w("w2"),
                        offset=int(expert_offset))
                for lo in range(0, h.shape[0], rows)])
            if i < n_layers - 1 and h.shape[0] < pad_to:
                h = jnp.concatenate([h, jnp.zeros(
                    (pad_to - h.shape[0], h.shape[1]), h.dtype)])
        if kept is not None:
            kept.extend(int(v) for v in np.asarray(count)[:len(asked)])
        x = final_norm(h[:len(asked)], weights["norm"])
        table = weights["head.w"]                                  # [H, V]
        edges = np.linspace(0, table.shape[1], _HEAD_SLICES + 1).astype(int)
        return np.concatenate(
            [np.asarray(head(x, table[:, lo:hi]))
             for lo, hi in zip(edges[:-1], edges[1:])], axis=1)
