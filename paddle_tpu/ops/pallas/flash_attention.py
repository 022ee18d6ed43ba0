"""Flash attention: fused online-softmax attention as a Pallas TPU kernel.

The reference ships a hand-fused CUDA attention for inference only
(reference: paddle/fluid/operators/fused/multihead_matmul_op.cu — QK^T +
softmax + PV in one kernel, no training support, no memory scaling). This
kernel is the TPU-native upgrade: blocked over the KV length with online
softmax (never materializing the [S, S] score matrix in HBM), differentiable
via custom_vjp, causal + additive-bias support — the long-sequence building
block that SURVEY §5.7 calls out as new first-class work.

Layout: q, k, v are [B, H, S, D]; bias (optional) is [B, S] additive on key
positions (0 keep / -1e9 masked). The grid is (B*H, S/BLOCK_Q); each program
streams K/V blocks of BLOCK_K rows through VMEM, carrying (running max,
normalizer, accumulator) in registers — FLOPs land on the MXU, the running
state on the VPU.

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


def _attention_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, *,
                      sm_scale, causal, block_q, block_k, seq_len):
    qi = pl.program_id(1)
    # MXU discipline: dots run in the INPUT dtype (bf16 under AMP — full MXU
    # rate) with f32 accumulation via preferred_element_type; all softmax
    # math (max/exp/normalizer) stays f32
    q = q_ref[0]  # (BQ, D)
    nk = seq_len // block_k

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = _dot(q, k, 1, 1) * sm_scale  # (BQ, BK) f32
        if bias_ref is not None:
            s = s + bias_ref[0, 0, pl.ds(j * block_k, block_k)][None, :]
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
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l_safe)


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


def _fwd_impl(q, k, v, bias, sm_scale, causal, block_q, block_k, interpret):
    B, H, S, D = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    bh = B * H
    q3 = q.reshape(bh, S, D)
    k3 = k.reshape(bh, S, D)
    v3 = v.reshape(bh, S, D)
    grid = (bh, S // block_q)
    kw = {} if interpret else dict(memory_space=pltpu.VMEM)
    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0), **kw),
        pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0), **kw),
        pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0), **kw),
    ]
    args = [q3, k3, v3]
    if bias is not None:
        # 3-D (bh, 1, S) so the block's trailing dims satisfy TPU tiling
        # (a (1, S) 2-D block has an untileable sublane dim of 1)
        bias_bh = jnp.broadcast_to(
            bias.reshape(B, 1, S), (B, H, S)
        ).reshape(bh, 1, S).astype(jnp.float32)
        in_specs.append(
            pl.BlockSpec((1, 1, S), lambda b, i: (b, 0, 0), **kw)
        )
        args.append(bias_bh)
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
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0), **kw),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i), **kw),
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
    """One (batch*head, KV block) program: stream Q blocks, accumulate
    dk/dv (+ per-head dbias) for this KV block. Scores are recomputed from
    the saved LSE, so nothing O(S^2) ever reaches HBM."""
    j = pl.program_id(1)
    # dots in input dtype, f32 accumulation (see _attention_kernel)
    k = k_ref[0]  # (BK, D)
    v = v_ref[0]
    cols = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )

    def body(i, carry):
        dk, dv, dbias = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :]
        g = g_ref[0, pl.ds(i * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.ds(i * block_q, block_q)]
        delta = delta_ref[0, 0, pl.ds(i * block_q, block_q)]
        s = _dot(q, k, 1, 1) * sm_scale  # (BQ, BK) f32
        if bias_ref is not None:
            s = s + bias_ref[0, 0, pl.ds(j * block_k, block_k)][None, :]
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
    nq = seq_len // block_q
    if causal:
        # only Q blocks at or after this KV block contribute
        start = (j * block_k) // block_q
        dk, dv, dbias = jax.lax.fori_loop(start, nq, body, (dk0, dv0, db0))
    else:
        dk, dv, dbias = jax.lax.fori_loop(0, nq, body, (dk0, dv0, db0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)
    if dbias_ref is not None:
        dbias_ref[0, 0] = dbias


def _bwd_dq_kernel(q_ref, k_ref, v_ref, bias_ref, g_ref, lse_ref, delta_ref,
                   dq_ref, *, sm_scale, causal, block_q, block_k, seq_len):
    """One (batch*head, Q block) program: stream KV blocks, accumulate dq."""
    i = pl.program_id(1)
    # dots in input dtype, f32 accumulation (see _attention_kernel)
    q = q_ref[0]
    g = g_ref[0]
    lse_col = lse_ref[0, 0][:, None]
    delta_col = delta_ref[0, 0][:, None]
    rows = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )

    def body(j, dq):
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = _dot(q, k, 1, 1) * sm_scale
        if bias_ref is not None:
            s = s + bias_ref[0, 0, pl.ds(j * block_k, block_k)][None, :]
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
    nk = seq_len // block_k
    if causal:
        nk_eff = jnp.minimum((i + 1) * block_q // block_k
                             + (1 if block_q % block_k else 0), nk)
        dq = jax.lax.fori_loop(0, nk_eff, body, dq0)
    else:
        dq = jax.lax.fori_loop(0, nk, body, dq0)
    dq_ref[0] = dq.astype(dq_ref.dtype)


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
    full = lambda: pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0), **kw)
    row = lambda: pl.BlockSpec((1, 1, S), lambda b, i: (b, 0, 0), **kw)
    has_bias = bias is not None
    if has_bias:
        bias_bh = jnp.broadcast_to(
            bias.reshape(B, 1, S), (B, H, S)
        ).reshape(bh, 1, S).astype(jnp.float32)

    # ---- dk/dv (+ per-bh dbias) --------------------------------------
    kv_block = lambda: pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0), **kw)
    in_specs = [full(), kv_block(), kv_block()]
    args = [q3, k3, v3]
    if has_bias:
        in_specs.append(row())
        args.append(bias_bh)
    in_specs += [full(), row(), row()]
    args += [g3, lse3, delta3]
    kv_out_specs = [
        pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0), **kw),
        pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0), **kw),
    ]
    kv_out_shapes = [
        _sds((bh, S, D), k.dtype, q3, k3, v3, g3),
        _sds((bh, S, D), v.dtype, q3, k3, v3, g3),
    ]
    if has_bias:
        kv_out_specs.append(
            pl.BlockSpec((1, 1, bk), lambda b, j: (b, 0, j), **kw)
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
        grid=(bh, S // bk),
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
    dq_in_specs = [
        pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0), **kw),
        full(), full(),
    ]
    dq_args = [q3, k3, v3]
    if has_bias:
        dq_in_specs.append(row())
        dq_args.append(bias_bh)
    dq_in_specs += [
        pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0), **kw),
        pl.BlockSpec((1, 1, bq), lambda b, i: (b, 0, i), **kw),
        pl.BlockSpec((1, 1, bq), lambda b, i: (b, 0, i), **kw),
    ]
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
        grid=(bh, S // bq),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0), **kw),
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
