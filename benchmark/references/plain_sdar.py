"""The plain reference of the ``sdar_moe`` family (JetLM SDAR with routed
experts, a block-diffusion decoder: ``serving/decode/hybrid.py
build_sdar_model`` is the served form): the forward pass in ``jax.numpy``
and float32 under ``jax.default_matmul_precision("highest")``, the whole
sequence in one pass, no cache, no slots, no paging, no kernels, no batch.
It shares nothing with the program but the weights, which it is handed as
the served (bfloat16) arrays by name and upcasts LAYER BY LAYER, the head a
slice of the vocabulary at a time, so that it fits beside the engine on the
chip (the largest layer, 16 experts, is 0.30 GB in float32).

The equations (``config`` holds the published keys as they are run, and the
two the family's generation adds, ``block_len`` B and ``mask_token_id``;
``H`` the hidden size, ``D = head_dim``):

* ``h_0 = embed[token]``; layer ``l``: ``a = RMSNorm(h; input_layernorm)``;
  ``q``, ``k``, ``v`` to ``heads x D``, ``kv_heads x D`` twice; ``q <-
  RoPE(RMSNorm(q; q_norm [D]))`` per head, ``k`` likewise (``rope_theta``,
  the whole head, rotate-half: lane ``i < D / 2`` with lane ``i + D / 2``,
  angle ``position * theta^(-2 i / D)``); ``h += o . softmax(q k^T /
  sqrt(D) + M) v``, grouped-query; ``f = RMSNorm(h;
  post_attention_layernorm)``; ``p = softmax(gate . f)`` over ALL
  ``router_experts``; the top ``num_experts_per_tok`` kept, their weights
  over their sum (``norm_topk_prob``); ``h += sum_e w_e . w2_e (silu(w1_e
  f) * (w3_e f))`` (all three ``[F, H]``); ``logits = head . RMSNorm(h;
  norm)``, the head untied; ``rms_norm_eps``; no bias anywhere.
* **The mask.** ``M[i, j] = 0 if j // B <= i // B else -inf``: a position
  sees every earlier block and the WHOLE of its own, prompt and answer
  alike.
* **A pass** (`block_pass`): the sequence is a prefix of whole blocks and
  ONE block after it whose positions hold their token where they are
  decided and ``mask_token_id`` where not; the pass's rows are the logits
  AT the block's positions (position ``i``'s row scores the token AT ``i``,
  not the one after it). Generation (`fill_order`): among the positions not
  decided, the one whose top candidate (``mask_token_id``'s logit left out)
  has the highest softmax probability, the lowest position on a tie, takes
  that candidate; one position a pass.

Departures from the published description, each also under ``assumed`` in
the configuration's file:

* ``block_len``, the one-position-a-pass rule (the family's
  ``low_confidence_static`` with as many passes as positions), the
  ``mask_token_id``, its exclusion from the candidates, the unshifted rows
  and the QK-norm are the family's as remembered: the catalog's row gives
  none of them.
* of the ``router_experts`` experts only ``held`` (ids ``offset .. offset +
  held - 1``, the served share of an expert-parallel deployment) are summed:
  the router still scores all, chooses its top k over all and normalises
  over all k; what the absent experts would add is left out, here as in the
  program, and nothing stands in for them.
"""

import functools

import numpy as np

from benchmark.references.plain_ouro import _through

#: slices the vocabulary is taken in by the head: 151,936 x 2,048 float32
#: would be 1.24 GB at once
_HEAD_SLICES = 8


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
        # x [T, heads, D]: the whole head, rotate-half
        half = x.shape[-1] // 2
        freq = c["rope_theta"] ** (-jnp.arange(half, dtype=f32) / half)
        angle = positions.astype(f32)[:, None, None] * freq
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    @functools.partial(jax.jit, static_argnames=("causal",))
    def attention(h, n, norm_w, qw, kw, vw, qn, kn, ow, causal):
        norm_w, qw, kw, vw, qn, kn, ow = up(norm_w, qw, kw, vw, qn, kn, ow)
        nq, nkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                      c["head_dim"])
        t, B = h.shape[0], c["block_len"]
        at = jnp.arange(t)
        x = _rms(h, norm_w, eps)
        q = rotate(_rms((x @ qw).reshape(t, nq, d), qn, eps), at)
        k = rotate(_rms((x @ kw).reshape(t, nkv, d), kn, eps), at)
        q = q.reshape(t, nkv, nq // nkv, d)
        v = (x @ vw).reshape(t, nkv, d)
        scores = jnp.einsum("tgqd,sgd->gqts", q, k) / np.sqrt(d).astype(f32)
        # ``causal`` is a control's: the mask this family does NOT have
        sees = (at[None, :] <= at[:, None] if causal
                else at[None, :] // B <= at[:, None] // B)
        sees = sees & (at[None, :] < n)
        att = jax.nn.softmax(jnp.where(sees, scores, -jnp.inf), axis=-1)
        ctx = jnp.einsum("gqts,sgd->tgqd", att, v).reshape(t, nq * d)
        return h + ctx @ ow

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

    return embed, final_norm, head, attention, experts


_KEYS = ("rms_norm_eps", "num_attention_heads", "num_key_value_heads",
         "head_dim", "num_experts_per_tok", "norm_topk_prob", "block_len")


def logits(weights, config, tokens, positions, pad_to, expert_offset=0,
           round_to=None, mask="block"):
    """The logits ``[len(positions), vocabulary]`` AT each of ``positions``
    of ``tokens`` under the block mask (a CLEAN sequence: every position
    holds its token). ``weights`` by the program's names less their
    prefix; ``config`` the published keys as run, with ``block_len`` and
    ``mask_token_id``; ``expert_offset`` the id of the first held expert.
    The sequence is padded to ``pad_to``: the padding is masked from every
    position. ``round_to`` (a dtype's name) and ``mask="causal"`` are for
    the comparison's controls alone: the same pass with every weight
    rounded through a narrower dtype (``plain_ouro._through``), or under
    the mask this family does not have; a comparison worth its name has to
    tell either from the served model."""
    import jax

    sizes = tuple((k, config[k]) for k in _KEYS) + (
        ("rope_theta", float(config["rope_theta"])),)
    embed, final_norm, head, attention, experts = _functions(sizes, round_to)
    at = np.asarray(list(positions), np.int64)
    padded = np.zeros((pad_to,), np.int32)
    padded[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        h = embed(weights["embed"], padded)
        for i in range(config["num_hidden_layers"]):
            w = lambda part: weights[f"l{i}.{part}"]  # noqa: E731
            h = attention(h, np.int32(len(tokens)), w("input_layernorm"),
                          w("q.w"), w("k.w"), w("v.w"), w("q_norm"),
                          w("k_norm"), w("o.w"), causal=mask == "causal")
            h = experts(h, w("post_attention_layernorm"), w("gate"),
                        w("w1"), w("w3"), w("w2"), offset=int(expert_offset))
        x = final_norm(h[at], weights["norm"])
        table = weights["head.w"]                                  # [H, V]
        edges = np.linspace(0, table.shape[1], _HEAD_SLICES + 1).astype(int)
        return np.concatenate(
            [np.asarray(head(x, table[:, lo:hi]))
             for lo, hi in zip(edges[:-1], edges[1:])], axis=1)


def block_pass(weights, config, prefix, block, decided, pad_to, **how):
    """One pass's rows ``[block_len, vocabulary]``: ``prefix`` (whole
    blocks) and after it ONE block whose position ``j`` holds ``block[j]``
    where ``decided[j]`` and the mask token where not. ``how`` is
    `logits`'s."""
    B = config["block_len"]
    if len(prefix) % B or len(block) != B:
        raise ValueError(f"a prefix of {len(prefix)} and a block of "
                         f"{len(block)} positions are not whole blocks of {B}")
    held = [int(t) if d else config["mask_token_id"]
            for t, d in zip(block, decided)]
    return logits(weights, config, list(prefix) + held,
                  range(len(prefix), len(prefix) + B), pad_to, **how)


def candidates(rows, mask_token_id):
    """``(token [B], confidence [B])`` of a pass's ``rows``: each
    position's top candidate, the mask token left out, and its softmax
    probability in float32."""
    rows = np.array(rows, np.float32)
    rows[:, mask_token_id] = -np.inf
    top = rows.max(1)
    return rows.argmax(1), 1.0 / np.exp(rows - top[:, None]).sum(1)


def decide(rows, decided, mask_token_id):
    """``(position, token, margin)`` that a pass with ``rows`` decides
    among the positions not ``decided``: the most confident candidate, the
    lowest position on a tie; ``margin`` is its confidence less the
    runner-up position's (inf where it is the last)."""
    token, conf = candidates(rows, mask_token_id)
    conf = np.where(np.asarray(decided, bool), -1.0, conf)
    at = int(conf.argmax())
    rest = np.delete(conf, at)
    margin = conf[at] - rest.max() if (rest >= 0).any() else np.inf
    return at, int(token[at]), float(margin)


def fill_order(weights, config, prefix, block, decided, pad_to, **how):
    """The block filled as the reference fills it, from the state given to
    the block whole: ``[(position, token, margin)]``, a pass each."""
    block, decided = list(block), [bool(d) for d in decided]
    out = []
    while not all(decided):
        rows = block_pass(weights, config, prefix, block, decided, pad_to,
                          **how)
        at, token, margin = decide(rows, decided, config["mask_token_id"])
        out.append((at, token, margin))
        block[at], decided[at] = token, True
    return out
