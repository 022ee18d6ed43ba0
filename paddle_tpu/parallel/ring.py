"""Ring attention: exact blockwise attention over a sequence-sharded mesh axis.

New first-class work the 2020 reference lacks (SURVEY §5.7 — it handled long
sequences with LoD ragged tensors, not length scaling). Each device holds a
sequence shard of Q/K/V; K/V blocks rotate around the ring via
`lax.ppermute` (one ICI neighbor hop per step) while a numerically-stable
online softmax accumulates partial results — so attention memory stays
O(S_local^2) and the full sequence never materializes on one chip.

Differentiable: the rotation loop is a `lax.scan`, so reverse-mode AD
transposes the ring (gradients counter-rotate) without custom VJPs.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map as _shard_map
from jax.sharding import PartitionSpec as P


def _online_step(q, k_blk, v_blk, acc, m, l, scale, mask):
    """One blockwise online-softmax accumulation (stable: running max m,
    running denominator l)."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    p = jnp.exp(scores - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v_blk)
    return acc_new, m_new, l_new


def ring_attention_local(q, k, v, axis_name, causal=False, scale=None):
    """Runs INSIDE shard_map. q/k/v: [B, H, S_local, D] sequence shards on
    `axis_name`. Returns [B, H, S_local, D]."""
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    q32 = q.astype(jnp.float32)

    # initial accumulators must carry the same device-varying type (jax 0.9
    # vma) as q — over ALL manual axes q varies on — or the scan carry type
    # mismatches; derive them from q arithmetic
    acc0 = jnp.zeros((b, h, s_q, d), jnp.float32) + 0.0 * q32
    m0 = (
        jnp.full((b, h, s_q), jnp.finfo(jnp.float32).min, jnp.float32)
        + 0.0 * q32[..., 0]
    )
    l0 = jnp.zeros((b, h, s_q), jnp.float32) + 0.0 * q32[..., 0]
    q_pos = idx * s_q + jnp.arange(s_q)

    def step(carry, i):
        k_blk, v_blk, acc, m, l = carry
        src = (idx - i) % n
        mask = None
        if causal:
            k_pos = src * s_k + jnp.arange(s_k)
            mask = q_pos[:, None] >= k_pos[None, :]
            mask = mask[None, None]  # [1,1,Sq,Sk]
        acc, m, l = _online_step(
            q32,
            k_blk.astype(jnp.float32),
            v_blk.astype(jnp.float32),
            acc,
            m,
            l,
            scale,
            mask,
        )
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return (k_blk, v_blk, acc, m, l), None

    (k, v, acc, m, l), _ = lax.scan(
        step, (k, v, acc0, m0, l0), jnp.arange(n)
    )
    return (acc / l[..., None]).astype(q.dtype)


def ring_attention(q, k, v, mesh, seq_axis="seq", causal=False, scale=None,
                   batch_axis=None):
    """shard_map wrapper: q/k/v are GLOBAL [B, H, S, D] arrays (or sharded
    jax.Arrays); the sequence dim is sharded over `seq_axis` and the ring
    runs over it. Other mesh axes replicate."""
    batch = batch_axis if batch_axis in mesh.axis_names else None
    spec = P(batch, None, seq_axis, None)
    fn = functools.partial(
        ring_attention_local, axis_name=seq_axis, causal=causal, scale=scale
    )
    return _shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)
