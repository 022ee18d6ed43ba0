"""Flash attention: fused online-softmax attention as a Pallas TPU kernel.

The reference ships a hand-fused CUDA attention for inference only
(reference: paddle/fluid/operators/fused/multihead_matmul_op.cu — QK^T +
softmax + PV in one kernel, no training support, no memory scaling). This
kernel is the TPU-native upgrade: blocked over the KV length with online
softmax (never materializing the [S, S] score matrix in HBM), differentiable
via custom_vjp, causal + additive-bias support — the long-sequence building
block that SURVEY §5.7 calls out as new first-class work.

Layout: q, k, v are [B, H, S, D]; bias (optional) is [B, S] additive on key
positions (0 keep / -1e9 masked). The grid is (B*H / G, S/BLOCK_Q): a grid
step serves G (batch, head) pairs, one after the other, each streaming its
K/V blocks of BLOCK_K rows through VMEM and carrying (running max,
normalizer, accumulator) in registers — FLOPs land on the MXU, the running
state on the VPU. G comes from the shapes (``_heads_per_step``): a grid step
costs a fraction of a microsecond whatever it does, which at short sequences
is more than one head's work, so as many heads share a step as a fixed VMEM
budget holds, down to one where a head's K and V fill it alone.

On non-TPU backends the same kernel runs in Pallas interpret mode (tests).

Mosaic constraints this file is written against (checked chip-free by
tests/test_kernels_tpu_aot.py): the per-row statistics (lse, delta) and the
key bias live lane-major in HBM as ``(bh, 1, S)`` rows and are sliced along
lanes in 128-multiples, so the compiled path wants ``S % 128 == 0`` (other
lengths run ``_jnp_attention``, counted in ``kernel_fallbacks_total``); a row
becomes a column as an f32 ``x[:, None]`` — Mosaic relayouts 32-bit vectors
but refuses the same reshape on an i1 mask, so masks are compared AFTER the
reshape.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.common import vma_names

__all__ = ["flash_attention"]

_NEG = -1e30


def _dot(a, b, a_dim, b_dim):
    """MXU contraction in the operand dtype with f32 accumulation. bf16
    operands are exact on the MXU, and Mosaic rejects the fp32 contract
    precision a process-wide ``jax_default_matmul_precision`` would
    otherwise attach to them — so they pin DEFAULT."""
    return jax.lax.dot_general(
        a, b, (((a_dim,), (b_dim,)), ((), ())),
        precision=(jax.lax.Precision.DEFAULT if a.dtype == jnp.bfloat16
                   else None),
        preferred_element_type=jnp.float32,
    )


#: VMEM that ONE set of a grid step's blocks may take; Pallas keeps two sets
#: (the step being computed and the one being copied), and Mosaic's default
#: scoped limit is 16 MiB
_STEP_BYTES = 4 * 2 ** 20
#: heads of a grid step written out in one loop iteration: a head alone is a
#: chain of MXU passes and VPU softmax that wait for one another, and eight
#: side by side fill those waits (at BERT-base's shapes a forward call takes
#: 1,219 us with one, 701 with two, 649 with four, 500 with eight or sixteen)
_HEAD_UNROLL = 8


def _heads_per_step(bh, seq, block, head_dim, dtype, blocked=4):
    """(batch, head) pairs a grid step serves: the largest divisor of
    ``bh`` whose blocks fit ``_STEP_BYTES`` as VMEM holds them. A head has
    two whole ``(seq, head_dim)`` operands in every kernel (k and v, or q
    and dO) and ``blocked`` ``(block, head_dim)`` ones (forward 2: q, o;
    dq 3: q, dO, dq; dk/dv 4, the default: k, v, dk, dv), lanes padded to
    128, beside at most four float32 ``(1, seq)`` rows (bias, lse, delta,
    dbias), each a tile of 8 sublanes. Falls as ``seq`` grows and is 1
    where one head's K and V fill the budget alone."""
    lanes = -(-int(head_dim) // 128) * 128
    per_head = ((2 * int(seq) + blocked * int(block)) * lanes
                * jnp.dtype(dtype).itemsize + 4 * 8 * int(seq) * 4)
    cap = max(1, min(int(bh), _STEP_BYTES // per_head))
    return max(g for g in range(1, cap + 1) if bh % g == 0)


def _grid(kernel, bh, seq, block, head_dim, dtype, blocked):
    """``(G, grid)`` of one lowered call of ``kernel``, counted beside the
    registry's fallbacks (``flash_grid_steps_total``,
    ``flash_heads_per_step``)."""
    from paddle_tpu.kernels.registry import flash_grid_metrics

    heads = _heads_per_step(bh, seq, block, head_dim, dtype, blocked)
    grid = (bh // heads, seq // block)
    flash_grid_metrics(kernel, grid[0] * grid[1], heads)
    return heads, grid


def _per_head(heads, body):
    """Run ``body(h)`` for each of a grid step's ``heads`` (batch, head)
    pairs, in order, ``_HEAD_UNROLL`` of them to a loop iteration (Mosaic
    unrolls a loop wholly or not at all, so the heads of one iteration are
    written out here)."""
    unroll = max(u for u in range(1, _HEAD_UNROLL + 1) if heads % u == 0)

    def step(i, carry):
        for r in range(unroll):
            body(i * unroll + r)
        return carry

    jax.lax.fori_loop(0, heads // unroll, step, 0)


def _attention_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, *,
                      sm_scale, causal, block_q, block_k, seq_len):
    """One (group of batch*head, Q block) program: each head of the group
    streams its KV blocks by an online softmax."""
    qi = pl.program_id(1)
    nk = seq_len // block_k

    def head(h):
        # MXU discipline: dots run in the INPUT dtype (bf16 under AMP — full
        # MXU rate) with f32 accumulation via preferred_element_type; all
        # softmax math (max/exp/normalizer) stays f32
        q = q_ref[h]  # (BQ, D)

        def body(j, carry):
            m, l, acc = carry
            k = k_ref[h, pl.ds(j * block_k, block_k), :]
            v = v_ref[h, pl.ds(j * block_k, block_k), :]
            s = _dot(q, k, 1, 1) * sm_scale  # (BQ, BK) f32
            if bias_ref is not None:
                s = s + bias_ref[h, 0, pl.ds(j * block_k, block_k)][None, :]
            if causal:
                rows = qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0
                )
                cols = j * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1
                )
                s = jnp.where(cols <= rows, s, _NEG)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[:, None] + _dot(p.astype(v.dtype), v, 1, 0)
            return m_new, l_new, acc_new

        m0 = jnp.full((block_q,), _NEG, jnp.float32)
        l0 = jnp.zeros((block_q,), jnp.float32)
        acc0 = jnp.zeros((block_q, q_ref.shape[-1]), jnp.float32)
        if causal:
            # only KV blocks at or before this Q block contribute
            nk_eff = jnp.minimum((qi + 1) * block_q // block_k
                                 + (1 if block_q % block_k else 0), nk)
            m, l, acc = jax.lax.fori_loop(0, nk_eff, body, (m0, l0, acc0))
        else:
            m, l, acc = jax.lax.fori_loop(0, nk, body, (m0, l0, acc0))
        l_safe = jnp.where(l == 0, 1.0, l)
        o_ref[h] = (acc / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[h, 0] = m + jnp.log(l_safe)

    _per_head(q_ref.shape[0], head)


def _jnp_attention(q, k, v, bias, sm_scale, causal):
    """Unfused attention with the kernel's exact masking semantics — the
    off-TPU fallback when Pallas interpret mode cannot run (shard_map)."""
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * sm_scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)[:, None, None, :]
    if causal:
        S = q.shape[2]
        rows = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
        s = jnp.where((cols <= rows)[None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _sds(shape, dtype, *refs):
    """ShapeDtypeStruct for a pallas_call out_shape, annotated with the
    union of the refs' varying-mesh-axes: required when the kernel runs
    inside shard_map (e.g. the pipeline_stack stage body), whose vma
    checker rejects un-annotated out_shapes."""
    vma = frozenset()
    for r in refs:
        vma |= vma_names(r)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _bias_rows(bias, B, H, S):
    """The [B, S] key bias as lane-major float32 (bh, 1, S) rows: 3-D so the
    block's trailing dims satisfy TPU tiling (a (1, S) 2-D block has an
    untileable sublane dim of 1)."""
    return jnp.broadcast_to(
        bias.reshape(B, 1, S), (B, H, S)
    ).reshape(B * H, 1, S).astype(jnp.float32)


def _fwd_impl(q, k, v, bias, sm_scale, causal, block_q, block_k, interpret):
    B, H, S, D = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    bh = B * H
    q3 = q.reshape(bh, S, D)
    k3 = k.reshape(bh, S, D)
    v3 = v.reshape(bh, S, D)
    G, grid = _grid("fwd", bh, S, block_q, D, q.dtype, blocked=2)
    kw = {} if interpret else dict(memory_space=pltpu.VMEM)
    in_specs = [
        pl.BlockSpec((G, block_q, D), lambda b, i: (b, i, 0), **kw),
        pl.BlockSpec((G, S, D), lambda b, i: (b, 0, 0), **kw),
        pl.BlockSpec((G, S, D), lambda b, i: (b, 0, 0), **kw),
    ]
    args = [q3, k3, v3]
    if bias is not None:
        in_specs.append(
            pl.BlockSpec((G, 1, S), lambda b, i: (b, 0, 0), **kw)
        )
        args.append(_bias_rows(bias, B, H, S))
    if bias is not None:
        def kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref):
            _attention_kernel(
                q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                sm_scale=sm_scale, causal=causal, block_q=block_q,
                block_k=block_k, seq_len=S,
            )
    else:
        def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref):
            _attention_kernel(
                q_ref, k_ref, v_ref, None, o_ref, lse_ref,
                sm_scale=sm_scale, causal=causal, block_q=block_q,
                block_k=block_k, seq_len=S,
            )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((G, block_q, D), lambda b, i: (b, i, 0), **kw),
            pl.BlockSpec((G, 1, block_q), lambda b, i: (b, 0, i), **kw),
        ],
        out_shape=[
            _sds((bh, S, D), q.dtype, q3, k3, v3),
            _sds((bh, 1, S), jnp.float32, q3, k3, v3),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(*args)
    return out.reshape(B, H, S, D), lse.reshape(B, H, S)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, bias, sm_scale, causal, block_q, block_k, interpret):
    out, _ = _fwd_impl(q, k, v, bias, sm_scale, causal, block_q, block_k,
                       interpret)
    return out


def _flash_fwd(q, k, v, bias, sm_scale, causal, block_q, block_k, interpret):
    out, lse = _fwd_impl(q, k, v, bias, sm_scale, causal, block_q, block_k,
                         interpret)
    return out, (q, k, v, bias, out, lse)


def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, bias_ref, g_ref, lse_ref, delta_ref,
                     dk_ref, dv_ref, dbias_ref, *, sm_scale, causal, block_q,
                     block_k, seq_len):
    """One (group of batch*head, KV block) program: each head of the group
    streams its Q blocks and accumulates dk/dv (+ per-head dbias) for this
    KV block. Scores are recomputed from the saved LSE, so nothing O(S^2)
    ever reaches HBM."""
    j = pl.program_id(1)
    cols = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    nq = seq_len // block_q

    def head(h):
        # dots in input dtype, f32 accumulation (see _attention_kernel)
        k = k_ref[h]  # (BK, D)
        v = v_ref[h]

        def body(i, carry):
            dk, dv, dbias = carry
            q = q_ref[h, pl.ds(i * block_q, block_q), :]
            g = g_ref[h, pl.ds(i * block_q, block_q), :]
            lse = lse_ref[h, 0, pl.ds(i * block_q, block_q)]
            delta = delta_ref[h, 0, pl.ds(i * block_q, block_q)]
            s = _dot(q, k, 1, 1) * sm_scale  # (BQ, BK) f32
            if bias_ref is not None:
                s = s + bias_ref[h, 0, pl.ds(j * block_k, block_k)][None, :]
            if causal:
                rows = i * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0
                )
                s = jnp.where(cols <= rows, s, _NEG)
            # fully-masked rows have lse == _NEG: their fwd output was 0, so
            # their gradient contribution must be 0, not exp(s - _NEG)
            lse_col = lse[:, None]
            p = jnp.where(lse_col <= _NEG / 2, 0.0, jnp.exp(s - lse_col))
            dv_new = dv + _dot(p.astype(g.dtype), g, 0, 0)
            dp = _dot(g, v, 1, 1)
            ds = p * (dp - delta[:, None])
            dk_new = dk + _dot(ds.astype(q.dtype), q, 0, 0) * sm_scale
            dbias_new = dbias + ds.sum(axis=0)
            return dk_new, dv_new, dbias_new

        dk0 = jnp.zeros((block_k, k_ref.shape[-1]), jnp.float32)
        dv0 = jnp.zeros_like(dk0)
        db0 = jnp.zeros((block_k,), jnp.float32)
        if causal:
            # only Q blocks at or after this KV block contribute
            start = (j * block_k) // block_q
            dk, dv, dbias = jax.lax.fori_loop(start, nq, body,
                                              (dk0, dv0, db0))
        else:
            dk, dv, dbias = jax.lax.fori_loop(0, nq, body, (dk0, dv0, db0))
        dk_ref[h] = dk.astype(dk_ref.dtype)
        dv_ref[h] = dv.astype(dv_ref.dtype)
        if dbias_ref is not None:
            dbias_ref[h, 0] = dbias

    _per_head(k_ref.shape[0], head)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, bias_ref, g_ref, lse_ref, delta_ref,
                   dq_ref, *, sm_scale, causal, block_q, block_k, seq_len):
    """One (group of batch*head, Q block) program: each head of the group
    streams its KV blocks and accumulates dq."""
    i = pl.program_id(1)
    rows = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    nk = seq_len // block_k

    def head(h):
        # dots in input dtype, f32 accumulation (see _attention_kernel)
        q = q_ref[h]
        g = g_ref[h]
        lse_col = lse_ref[h, 0][:, None]
        delta_col = delta_ref[h, 0][:, None]

        def body(j, dq):
            k = k_ref[h, pl.ds(j * block_k, block_k), :]
            v = v_ref[h, pl.ds(j * block_k, block_k), :]
            s = _dot(q, k, 1, 1) * sm_scale
            if bias_ref is not None:
                s = s + bias_ref[h, 0, pl.ds(j * block_k, block_k)][None, :]
            if causal:
                cols = j * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1
                )
                s = jnp.where(cols <= rows, s, _NEG)
            p = jnp.where(lse_col <= _NEG / 2, 0.0, jnp.exp(s - lse_col))
            dp = _dot(g, v, 1, 1)
            ds = p * (dp - delta_col)
            return dq + _dot(ds.astype(k.dtype), k, 1, 0) * sm_scale

        dq0 = jnp.zeros((block_q, q_ref.shape[-1]), jnp.float32)
        if causal:
            nk_eff = jnp.minimum((i + 1) * block_q // block_k
                                 + (1 if block_q % block_k else 0), nk)
            dq = jax.lax.fori_loop(0, nk_eff, body, dq0)
        else:
            dq = jax.lax.fori_loop(0, nk, body, dq0)
        dq_ref[h] = dq.astype(dq_ref.dtype)

    _per_head(q_ref.shape[0], head)


def _flash_bwd(sm_scale, causal, block_q, block_k, interpret, res, g):
    """Blocked Pallas backward from the saved log-sum-exp (FlashAttention-2
    split: a dk/dv kernel gridded over KV blocks and a dq kernel gridded over
    Q blocks). Memory stays O(S · block) per program — the round-2 jnp
    backward materialized the full [B,H,S,S] score matrix in HBM."""
    q, k, v, bias, out, lse = res
    B, H, S, D = q.shape
    bq = min(block_q, S)
    bk = min(block_k, S)
    bh = B * H
    # delta = rowsum(dO * O) — cheap elementwise reduce, leave it to XLA
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # (B, H, S)
    q3, k3, v3 = (t.reshape(bh, S, D) for t in (q, k, v))
    g3 = g.reshape(bh, S, D)
    lse3 = lse.reshape(bh, 1, S)
    delta3 = delta.reshape(bh, 1, S)
    kw = {} if interpret else dict(memory_space=pltpu.VMEM)
    has_bias = bias is not None
    if has_bias:
        bias_bh = _bias_rows(bias, B, H, S)

    # ---- dk/dv (+ per-bh dbias) --------------------------------------
    G, grid = _grid("bwd_dkdv", bh, S, bk, D, q.dtype, blocked=4)
    full = lambda: pl.BlockSpec((G, S, D), lambda b, i: (b, 0, 0), **kw)
    row = lambda: pl.BlockSpec((G, 1, S), lambda b, i: (b, 0, 0), **kw)
    kv_block = lambda: pl.BlockSpec((G, bk, D), lambda b, j: (b, j, 0), **kw)
    in_specs = [full(), kv_block(), kv_block()]
    args = [q3, k3, v3]
    if has_bias:
        in_specs.append(row())
        args.append(bias_bh)
    in_specs += [full(), row(), row()]
    args += [g3, lse3, delta3]
    kv_out_specs = [kv_block(), kv_block()]
    kv_out_shapes = [
        _sds((bh, S, D), k.dtype, q3, k3, v3, g3),
        _sds((bh, S, D), v.dtype, q3, k3, v3, g3),
    ]
    if has_bias:
        kv_out_specs.append(
            pl.BlockSpec((G, 1, bk), lambda b, j: (b, 0, j), **kw)
        )
        kv_out_shapes.append(_sds((bh, 1, S), jnp.float32, q3, k3, v3, g3))

    def dkdv_kernel(*refs):
        if has_bias:
            (q_r, k_r, v_r, b_r, g_r, l_r, d_r, dk_r, dv_r, db_r) = refs
        else:
            (q_r, k_r, v_r, g_r, l_r, d_r, dk_r, dv_r) = refs
            b_r, db_r = None, None
        _bwd_dkdv_kernel(
            q_r, k_r, v_r, b_r, g_r, l_r, d_r, dk_r, dv_r, db_r,
            sm_scale=sm_scale, causal=causal, block_q=bq, block_k=bk,
            seq_len=S,
        )

    outs = pl.pallas_call(
        dkdv_kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=kv_out_specs,
        out_shape=kv_out_shapes,
        interpret=interpret,
        name="flash_attention_bwd_dkdv",
    )(*args)
    dk3, dv3 = outs[0], outs[1]
    dbias = None
    if has_bias:
        dbias = outs[2].reshape(B, H, S).sum(axis=1)
        # inside a shard_map that splits heads the bias is shared by the
        # head shards, so its cotangent sums over the axes q varies on
        # and the bias does not
        head_axes = tuple(vma_names(q) - vma_names(bias))
        if head_axes:
            dbias = jax.lax.psum(dbias, head_axes)

    # ---- dq ----------------------------------------------------------
    G, grid = _grid("bwd_dq", bh, S, bq, D, q.dtype, blocked=3)
    full = lambda: pl.BlockSpec((G, S, D), lambda b, i: (b, 0, 0), **kw)
    row = lambda: pl.BlockSpec((G, 1, S), lambda b, i: (b, 0, 0), **kw)
    q_block = lambda: pl.BlockSpec((G, bq, D), lambda b, i: (b, i, 0), **kw)
    q_row = lambda: pl.BlockSpec((G, 1, bq), lambda b, i: (b, 0, i), **kw)
    dq_in_specs = [q_block(), full(), full()]
    dq_args = [q3, k3, v3]
    if has_bias:
        dq_in_specs.append(row())
        dq_args.append(bias_bh)
    dq_in_specs += [q_block(), q_row(), q_row()]
    dq_args += [g3, lse3, delta3]

    def dq_kernel(*refs):
        if has_bias:
            (q_r, k_r, v_r, b_r, g_r, l_r, d_r, dq_r) = refs
        else:
            (q_r, k_r, v_r, g_r, l_r, d_r, dq_r) = refs
            b_r = None
        _bwd_dq_kernel(
            q_r, k_r, v_r, b_r, g_r, l_r, d_r, dq_r,
            sm_scale=sm_scale, causal=causal, block_q=bq, block_k=bk,
            seq_len=S,
        )

    dq3 = pl.pallas_call(
        dq_kernel,
        grid=grid,
        in_specs=dq_in_specs,
        out_specs=q_block(),
        out_shape=_sds((bh, S, D), q.dtype, q3, k3, v3, g3),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(*dq_args)

    return (
        dq3.reshape(B, H, S, D),
        dk3.reshape(B, H, S, D),
        dv3.reshape(B, H, S, D),
        # cotangent dtype must match the bias primal (custom_vjp contract)
        dbias.astype(bias.dtype) if dbias is not None else None,
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, bias=None, causal=False, sm_scale=None,
                    block_q=128, block_k=128, interpret=None):
    """Fused attention over [B, H, S, D] tensors. `bias` is an optional
    [B, S] additive key-position bias (padding mask). Differentiable."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    # kernel dots run in the operand dtype (bf16 stays on the MXU fast
    # path); mixed q/k/v dtypes are promoted once here so the dots agree
    dt = jnp.result_type(q.dtype, k.dtype, v.dtype)
    q, k, v = q.astype(dt), k.astype(dt), v.astype(dt)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # pallas interpret mode inside a shard_map region trips an MLIR
    # closed_call caching bug (KeyError in cached_primitive_lowerings), so
    # off-TPU under shard_map use the numerically-identical jnp path; the
    # real chip always runs the Pallas kernel
    if interpret and vma_names(q):
        return _jnp_attention(q, k, v, bias, float(sm_scale), bool(causal))
    S = q.shape[2]
    if not interpret and S % 128:
        # Mosaic slices the lane-major bias/lse rows in 128-multiples only
        from paddle_tpu.kernels.registry import fallback_counter

        fallback_counter().inc()
        return _jnp_attention(q, k, v, bias, float(sm_scale), bool(causal))
    bq = min(block_q, S)
    bk = min(block_k, S)
    while S % bq:
        bq //= 2
    while S % bk:
        bk //= 2
    return _flash(q, k, v, bias, float(sm_scale), bool(causal),
                  max(bq, 1), max(bk, 1), bool(interpret))
