"""Fused CPU-op parity family: compositions the reference hand-fused for
CPU inference (reference: paddle/fluid/operators/fused/{fusion_lstm_op.cc,
fusion_gru_op.cc, fused_embedding_seq_pool_op.cc,
fusion_seqconv_eltadd_relu_op.cc, fusion_repeated_fc_relu_op.cc,
fusion_squared_mat_sub_op.cc, fusion_seqpool_concat_op.cc,
fusion_seqpool_cvm_concat_op.cc}).

On TPU these are compositions of existing lowerings — XLA fuses the
arithmetic; registering the op names keeps reference programs loadable.
Padded+lengths tensor contract as ops/sequence.py.
"""

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import register_op
from paddle_tpu.ops.common import first, gelu, maybe
from paddle_tpu.utils.enforce import EnforceError


@register_op("fusion_lstm", nondiff_inputs=("Length",))
def _fusion_lstm(ins, attrs):
    """reference: fused/fusion_lstm_op.cc — LSTM with the x-projection
    folded in. X [B, S, M], WeightX [M, 4D], WeightH [D, 4D], Bias [1, 4D]
    (peepholes unsupported -> loud error). Gate order i, f, c, o
    (reference computeCtHt order ct = f*c + i*tanh(c_in))."""
    if attrs.get("use_peepholes", False):
        raise EnforceError("fusion_lstm: peephole connections unsupported")
    x = first(ins, "X")
    wx = first(ins, "WeightX")
    gx = jnp.einsum("bsm,mg->bsg", x, wx)
    return _lstm_recurrence(gx, ins)


def _lstm_recurrence(gx, ins):
    """Shared LSTM scan over PRE-PROJECTED gates gx [B, S, 4D] (used by
    fusion_lstm and fused_embedding_fc_lstm, whose embedding rows already
    ARE the projected input)."""
    wh = first(ins, "WeightH")
    b = maybe(ins, "Bias")
    lengths = maybe(ins, "Length")
    B, S = gx.shape[0], gx.shape[1]
    D = wh.shape[0]
    h0 = maybe(ins, "H0")
    c0 = maybe(ins, "C0")
    h = h0 if h0 is not None else jnp.zeros((B, D), gx.dtype)
    c = c0 if c0 is not None else jnp.zeros((B, D), gx.dtype)
    if b is not None:
        gx = gx + b.reshape(1, 1, -1)

    def step(carry, inp):
        h, c = carry
        g_x, t = inp
        gates = g_x + h @ wh
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
        c_new = f * c + i * jnp.tanh(g)
        h_new = o * jnp.tanh(c_new)
        if lengths is not None:
            alive = (t < lengths.reshape(-1, 1))
            h_new = jnp.where(alive, h_new, h)
            c_new = jnp.where(alive, c_new, c)
        return (h_new, c_new), (h_new, c_new)

    (_, _), (hs, cs) = jax.lax.scan(
        step, (h, c),
        (jnp.swapaxes(gx, 0, 1), jnp.arange(S)),
    )
    return {
        "Hidden": [jnp.swapaxes(hs, 0, 1)],
        "Cell": [jnp.swapaxes(cs, 0, 1)],
    }


@register_op("fusion_gru", nondiff_inputs=("Length",))
def _fusion_gru(ins, attrs):
    """reference: fused/fusion_gru_op.cc — GRU with folded x-projection,
    Paddle gate order (update u | reset r | candidate c),
    h = u*h_prev + (1-u)*c (origin_mode=False default matches gru_unit)."""
    x = first(ins, "X")
    wx = first(ins, "WeightX")   # [M, 3D]
    wh = first(ins, "WeightH")   # [D, 3D]
    b = maybe(ins, "Bias")
    lengths = maybe(ins, "Length")
    B, S, M = x.shape
    D = wh.shape[0]
    h0 = maybe(ins, "H0")
    h = h0 if h0 is not None else jnp.zeros((B, D), x.dtype)
    origin = attrs.get("origin_mode", False)
    gx = jnp.einsum("bsm,mg->bsg", x, wx)
    if b is not None:
        gx = gx + b.reshape(1, 1, -1)

    def step(h, inp):
        g_x, t = inp
        gates = g_x[:, : 2 * D] + h @ wh[:, : 2 * D]
        u = jax.nn.sigmoid(gates[:, :D])
        r = jax.nn.sigmoid(gates[:, D:])
        c = jnp.tanh(g_x[:, 2 * D:] + (r * h) @ wh[:, 2 * D:])
        if origin:
            h_new = (1.0 - u) * h + u * c
        else:
            h_new = u * h + (1.0 - u) * c
        if lengths is not None:
            h_new = jnp.where(t < lengths.reshape(-1, 1), h_new, h)
        return h_new, h_new

    _, hs = jax.lax.scan(
        step, h, (jnp.swapaxes(gx, 0, 1), jnp.arange(S))
    )
    return {"Hidden": [jnp.swapaxes(hs, 0, 1)]}


@register_op("fused_embedding_seq_pool", nondiff_inputs=("Ids", "Length"))
def _fused_embedding_seq_pool(ins, attrs):
    """reference: fused/fused_embedding_seq_pool_op.cc — lookup + sum-pool
    over the sequence axis. Ids [B, S] (+Length), W [V, D] -> [B, D]."""
    w = first(ins, "W")
    ids = first(ins, "Ids")
    if ids.ndim == 3 and ids.shape[-1] == 1:
        ids = ids[..., 0]
    lengths = maybe(ins, "Length")
    emb = jnp.take(w, ids, axis=0)  # [B, S, D]
    pad = attrs.get("padding_idx", -1)
    mask = jnp.ones(ids.shape, bool)
    if pad is not None and pad >= 0:
        mask = mask & (ids != pad)
    if lengths is not None:
        mask = mask & (
            jnp.arange(ids.shape[1])[None, :] < lengths.reshape(-1, 1)
        )
    return {"Out": [jnp.where(mask[..., None], emb, 0.0).sum(axis=1)]}


@register_op("fusion_seqconv_eltadd_relu", nondiff_inputs=("Length",))
def _fusion_seqconv_eltadd_relu(ins, attrs):
    """reference: fused/fusion_seqconv_eltadd_relu_op.cc — sequence_conv +
    bias + relu."""
    from paddle_tpu.core.registry import get_op_def

    conv = get_op_def("sequence_conv").lower(
        {k: v for k, v in ins.items() if k in ("X", "Filter", "Length")},
        {"contextLength": attrs.get("contextLength", 3),
         "contextStart": attrs.get("contextStart", -1),
         "contextStride": attrs.get("contextStride", 1)},
    )["Out"][0]
    b = first(ins, "Bias")
    return {"Out": [jax.nn.relu(conv + b.reshape(1, 1, -1))]}


@register_op("fusion_repeated_fc_relu")
def _fusion_repeated_fc_relu(ins, attrs):
    """reference: fused/fusion_repeated_fc_relu_op.cc — N x (fc + relu)."""
    x = first(ins, "X")
    ws = ins["W"]
    bs = ins["Bias"]
    for w, b in zip(ws, bs):
        x = jax.nn.relu(x @ w + b.reshape(1, -1))
    return {"Out": [x]}


@register_op("fusion_squared_mat_sub")
def _fusion_squared_mat_sub(ins, attrs):
    """reference: fused/fusion_squared_mat_sub_op.cc —
    scalar * ((x@y)^2 - (x^2)@(y^2)) (the pairwise-interaction trick)."""
    x = first(ins, "X")
    y = first(ins, "Y")
    s = attrs.get("scalar", 1.0)
    return {"Out": [s * (jnp.square(x @ y) - jnp.square(x) @ jnp.square(y))]}


@register_op("fusion_seqpool_concat", nondiff_inputs=("Length",))
def _fusion_seqpool_concat(ins, attrs):
    """reference: fused/fusion_seqpool_concat_op.cc — sum/avg/sqrt pool of
    each input sequence, concatenated on features."""
    pools = _pool_all(ins, attrs)
    return {"Out": [jnp.concatenate(pools, axis=1)]}


@register_op("fusion_seqpool_cvm_concat", nondiff_inputs=("CVM", "Length"))
def _fusion_seqpool_cvm_concat(ins, attrs):
    """reference: fused/fusion_seqpool_cvm_concat_op.cc — seqpool + CVM
    log transform + concat (the CTR tower input builder)."""
    from paddle_tpu.core.registry import get_op_def

    pools = _pool_all(ins, attrs)
    cvm = ins.get("CVM")
    outs = []
    for p in pools:
        if attrs.get("use_cvm", True) and cvm is not None:
            p = get_op_def("cvm").lower(
                {"X": [p], "CVM": cvm}, {"use_cvm": True}
            )["Y"][0]
        outs.append(p)
    return {"Out": [jnp.concatenate(outs, axis=1)]}


def _pool_all(ins, attrs):
    ptype = attrs.get("pooltype", "SUM").upper()
    lengths = ins.get("Length")
    pools = []
    for i, x in enumerate(ins["X"]):
        l = lengths[i] if lengths and i < len(lengths) else None
        mask = (
            jnp.arange(x.shape[1])[None, :] < l.reshape(-1, 1)
            if l is not None else jnp.ones(x.shape[:2], bool)
        )
        m = mask[..., None]
        s = jnp.where(m, x, 0.0).sum(axis=1)
        if ptype == "SUM":
            pools.append(s)
        else:
            n = jnp.maximum(mask.sum(axis=1, keepdims=True).astype(x.dtype),
                            1.0)
            pools.append(s / (jnp.sqrt(n) if ptype == "SQRT" else n))
    return pools


@register_op("attention_lstm", nondiff_inputs=("Length",))
def _attention_lstm(ins, attrs):
    """reference: paddle/fluid/operators/attention_lstm_op.cc — per step:
    score[j] = relu(atted_x[j] + <c_prev, w_c>) (optionally scaled +
    re-biased + relu'd), softmax over the sequence, context = sum_j a_j
    x_j, then one LSTM step on the context. Padded form: X [B, S, M] +
    Length; AttentionWeight [(M+D), 1]; LSTMWeight [(D+M), 4D] (rows
    [0:D] hidden, [D:] input; gate order forget|input|output|tilde)."""
    x = first(ins, "X")
    aw = first(ins, "AttentionWeight")            # [(M+D), 1]
    ab = maybe(ins, "AttentionBias")
    ascalar = maybe(ins, "AttentionScalar")
    asb = maybe(ins, "AttentionScalarBias")
    lw = first(ins, "LSTMWeight")                 # [(D+M), 4D]
    lb = first(ins, "LSTMBias")                   # [1, 4D]
    c0 = first(ins, "C0")                         # [B, D]
    h0 = maybe(ins, "H0")
    lengths = maybe(ins, "Length")
    B, S, M = x.shape
    D = c0.shape[1]
    w_x = aw[:M, 0]                               # [M]
    w_c = aw[M:, 0]                               # [D]
    atted = jnp.einsum("bsm,m->bs", x, w_x)
    if ab is not None:
        atted = atted + ab.reshape(())
    wh = lw[:D]                                   # [D, 4D]
    wx = lw[D:]                                   # [M, 4D]
    h_prev = h0 if h0 is not None else jnp.zeros((B, D), x.dtype)
    valid = (
        jnp.arange(S)[None, :] < lengths.reshape(-1, 1)
        if lengths is not None else jnp.ones((B, S), bool)
    )

    def step(carry, t):
        h, c = carry
        score = jax.nn.relu(atted + (c @ w_c)[:, None])     # [B, S]
        if ascalar is not None:
            score = score * ascalar.reshape(())
            if asb is not None:
                score = jax.nn.relu(score + asb.reshape(()))
        score = jnp.where(valid, score, -1e30)
        a = jax.nn.softmax(score, axis=1)
        ctxv = jnp.einsum("bs,bsm->bm", a, x)               # [B, M]
        gates = ctxv @ wx + h @ wh + lb.reshape(1, -1)
        f = jax.nn.sigmoid(gates[:, :D])
        i = jax.nn.sigmoid(gates[:, D:2 * D])
        o = jax.nn.sigmoid(gates[:, 2 * D:3 * D])
        g = jnp.tanh(gates[:, 3 * D:])
        c_new = f * c + i * g
        h_new = o * jnp.tanh(c_new)
        alive = (t < lengths.reshape(-1, 1)) if lengths is not None else \
            jnp.ones((B, 1), bool)
        h_new = jnp.where(alive, h_new, h)
        c_new = jnp.where(alive, c_new, c)
        return (h_new, c_new), (h_new, c_new)

    (_, _), (hs, cs) = jax.lax.scan(step, (h_prev, c0), jnp.arange(S))
    return {
        "Hidden": [jnp.swapaxes(hs, 0, 1)],
        "Cell": [jnp.swapaxes(cs, 0, 1)],
    }


@register_op("tree_conv", nondiff_inputs=("EdgeSet",))
def _tree_conv(ins, attrs):
    """reference: paddle/fluid/operators/tree_conv_op.h + math/tree2col.h —
    TBCNN continuous-binary-tree convolution. Patch of node n = n plus its
    direct children (the max_depth=2 window; deeper windows raise — the
    dominant TBCNN config). Mixing weights per patch member v:
    eta_t = (d - depth)/d, eta_l = (1-eta_t) * (idx-1)/(pclen-1) (0.5 when
    an only child), eta_r = (1-eta_t)(1-...). NodesVector [B, N, F],
    EdgeSet [B, E, 2] (parent, child; negative = padding),
    Filter [F, 3, O, K] -> Out [B, N, O*K]."""
    nodes = first(ins, "NodesVector")
    edges = first(ins, "EdgeSet").astype(jnp.int32)
    w = first(ins, "Filter")                      # [F, 3, O, K]
    max_depth = attrs.get("max_depth", 2)
    if max_depth != 2:
        raise EnforceError(
            f"tree_conv: only max_depth=2 (node + direct children) is "
            f"implemented; got {max_depth}"
        )
    B, N, F = nodes.shape
    E = edges.shape[1]
    O, K = w.shape[2], w.shape[3]
    wt, wl, wr = w[:, 0], w[:, 1], w[:, 2]        # [F, O, K]
    d = float(max_depth)

    def per_tree(x, es):
        parent = es[:, 0]
        child = es[:, 1]
        ev = (parent >= 0) & (child >= 0)
        # sibling stats per edge: count + 1-based order among same parent
        same = (parent[:, None] == parent[None, :]) & ev[:, None] & ev[None, :]
        pclen = same.sum(axis=1)
        order = jnp.tril(same).sum(axis=1)        # rank by edge position
        eta_t = (d - 1.0) / d
        frac = jnp.where(pclen == 1, 0.5,
                         (order - 1.0) / jnp.maximum(pclen - 1.0, 1.0))
        eta_l = (1.0 - eta_t) * frac
        eta_r = (1.0 - eta_t) * (1.0 - frac)
        # root term: depth 0 -> eta_t = 1
        out = jnp.einsum("nf,fok->nok", x, wt)
        # child contributions scattered to their parent
        xc = x[jnp.clip(child, 0, N - 1)]          # [E, F]
        contrib = (
            eta_t * jnp.einsum("ef,fok->eok", xc, wt).reshape(E, -1)
            + eta_l[:, None] * jnp.einsum("ef,fok->eok", xc, wl).reshape(E, -1)
            + eta_r[:, None] * jnp.einsum("ef,fok->eok", xc, wr).reshape(E, -1)
        )                                          # [E, O*K]
        contrib = jnp.where(ev[:, None], contrib, 0.0)
        out = out.reshape(N, -1).at[jnp.clip(parent, 0, N - 1)].add(contrib)
        return out

    out = jax.vmap(per_tree)(nodes, edges)   # [B, N, O*K]
    return {"Out": [out]}


@register_op("multihead_matmul", nondiff_inputs=("BiasQK",))
def _multihead_matmul(ins, attrs):
    """reference: fused/multihead_matmul_op.cc (inference fusion) — Input
    [B, S, 3*H*D] packed q|k|v projections (+ Bias [3*H*D]), BiasQK
    [B, H, S, S] additive attention bias. Without BiasQK the attention
    runs on the Pallas flash kernel; the full [B, H, S, S] bias form (no
    flash support for that shape) uses the XLA-fused jnp path."""
    x = first(ins, "Input")
    bias = maybe(ins, "Bias")
    bias_qk = maybe(ins, "BiasQK")
    H = attrs.get("head_number", 1)
    B, S, C3 = x.shape
    D = C3 // 3 // H
    if bias is not None:
        x = x + bias.reshape(1, 1, -1)
    qkv = x.reshape(B, S, 3, H, D)
    q = jnp.transpose(qkv[:, :, 0], (0, 2, 1, 3))      # [B, H, S, D]
    k = jnp.transpose(qkv[:, :, 1], (0, 2, 1, 3))
    v = jnp.transpose(qkv[:, :, 2], (0, 2, 1, 3))
    scale = attrs.get("alpha", 1.0)
    if bias_qk is None:
        from paddle_tpu.ops.pallas.flash_attention import flash_attention

        out = flash_attention(q, k, v, sm_scale=scale)
    else:
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        s = s + bias_qk
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
        out = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return {"Out": [jnp.transpose(out, (0, 2, 1, 3)).reshape(B, S, H * D)]}


@register_op("fused_embedding_eltwise_layernorm", nondiff_inputs=("Ids",))
def _fused_embedding_eltwise_layernorm(ins, attrs):
    """reference: fused/fused_embedding_eltwise_layernorm_op.cc — sum of N
    embedding lookups + layer_norm (the BERT input encoder fusion)."""
    ids_list = ins["Ids"]
    emb_list = ins["Embs"]
    scale = first(ins, "Scale")
    bias = first(ins, "Bias")
    eps = attrs.get("epsilon", 1e-5)
    total = None
    for ids, w in zip(ids_list, emb_list):
        idv = ids
        if idv.ndim == 3 and idv.shape[-1] == 1:
            idv = idv[..., 0]
        e = jnp.take(w, idv, axis=0)
        total = e if total is None else total + e
    mu = total.mean(axis=-1, keepdims=True)
    var = jnp.var(total, axis=-1, keepdims=True)
    out = (total - mu) / jnp.sqrt(var + eps) * scale + bias
    return {"Out": [out]}


@register_op("fused_embedding_fc_lstm", nondiff_inputs=("Ids", "Length"))
def _fused_embedding_fc_lstm(ins, attrs):
    """reference: fused/fused_embedding_fc_lstm_op.cc — embedding lookup +
    fused LSTM: the embedding rows already ARE the projected gates, so the
    lookup feeds the shared recurrence directly (no x-projection)."""
    emb = first(ins, "Embeddings")                 # [V, 4D]
    ids = first(ins, "Ids")
    if ids.ndim == 3 and ids.shape[-1] == 1:
        ids = ids[..., 0]
    if attrs.get("use_peepholes", False):
        raise EnforceError(
            "fused_embedding_fc_lstm: peephole connections unsupported"
        )
    gx = jnp.take(emb, ids, axis=0)                # [B, S, 4D]
    return _lstm_recurrence(gx, ins)


@register_op("fusion_seqexpand_concat_fc", nondiff_inputs=("Length",))
def _fusion_seqexpand_concat_fc(ins, attrs):
    """reference: fused/fusion_seqexpand_concat_fc_op.cc — X[0] is a
    sequence [B, S, M0], the rest are per-row vectors [B, Mi] broadcast
    over S; concat on features, then fc + activation."""
    xs = ins["X"]
    w = first(ins, "FCWeight")
    b = maybe(ins, "FCBias")
    seq = xs[0]
    B, S = seq.shape[0], seq.shape[1]
    parts = [seq]
    for t in xs[1:]:
        parts.append(jnp.broadcast_to(
            t[:, None, :], (B, S) + tuple(t.shape[1:])
        ))
    cat = jnp.concatenate(parts, axis=-1)
    out = jnp.einsum("bsm,mo->bso", cat, w)
    if b is not None:
        out = out + b.reshape(1, 1, -1)
    act = attrs.get("fc_activation", "identity")
    if act == "relu":
        out = jax.nn.relu(out)
    elif act == "tanh":
        out = jnp.tanh(out)
    return {"Out": [out]}


_FC_ACTS = {
    "": lambda x: x,
    "identity": lambda x: x,
    "relu": jax.nn.relu,
    "relu6": lambda x: jnp.clip(x, 0.0, 6.0),
    # exact (erf) form — matches the standalone gelu op's default
    # approximate=False (fc_fuse refuses to fold an approximate gelu)
    "gelu": gelu,
    "tanh": jnp.tanh,
    "sigmoid": jax.nn.sigmoid,
}


@register_op("fc")
def _fc(ins, attrs):
    """reference: paddle/fluid/operators/fc_op.cc — the target of the
    fc_fuse pass (mul + elementwise_add [+ act] collapsed at export,
    reference: paddle/fluid/framework/ir/fc_fuse_pass.cc:1)."""
    import math as _math

    x, w = first(ins, "Input"), first(ins, "W")
    b = maybe(ins, "Bias")
    k = attrs.get("in_num_col_dims", 1)
    x2 = x.reshape((_math.prod(x.shape[:k]), -1))
    out = x2 @ w
    if b is not None:
        out = out + b.reshape(1, -1)
    act = attrs.get("activation_type", "") or ""
    if act not in _FC_ACTS:
        raise EnforceError(f"fc: unsupported activation_type {act!r}")
    out = _FC_ACTS[act](out)
    return {"Out": [out.reshape(tuple(x.shape[:k]) + (w.shape[1],))]}
