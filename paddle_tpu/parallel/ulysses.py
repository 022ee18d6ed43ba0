"""Ulysses (DeepSpeed-style) sequence parallelism: head-scatter / seq-gather.

Alternative to the ring (SURVEY §5.7): instead of rotating K/V blocks,
one `lax.all_to_all` re-shards activations from sequence-sharded to
head-sharded, each device runs EXACT attention on full sequence for its
head group, and a second all_to_all restores sequence sharding. Two
all-to-alls per attention vs n-1 ppermutes for the ring; better when
heads >= devices and sequence is moderate, worse at extreme lengths
(full-sequence scores materialize per head group).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map as _shard_map
from jax.sharding import PartitionSpec as P


def _full_attention(q, k, v, scale, causal):
    """q/k/v: [B, H, S, D] — exact softmax attention."""
    s = q.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask[None, None], scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


def ulysses_attention_local(q, k, v, axis_name, causal=False, scale=None):
    """Runs INSIDE shard_map. q/k/v: [B, H, S_local, D], sequence sharded on
    `axis_name`; requires H % axis_size == 0. Returns [B, H, S_local, D]."""
    d = q.shape[3]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    def head_scatter(t):  # [B,H,S_loc,D] -> [B,H/n,S,D]
        return lax.all_to_all(t, axis_name, split_axis=1, concat_axis=2, tiled=True)

    def seq_scatter(t):  # [B,H/n,S,D] -> [B,H,S_loc,D]
        return lax.all_to_all(t, axis_name, split_axis=2, concat_axis=1, tiled=True)

    q, k, v = head_scatter(q), head_scatter(k), head_scatter(v)
    out = _full_attention(q, k, v, scale, causal)
    return seq_scatter(out)


def ulysses_attention(q, k, v, mesh, seq_axis="seq", causal=False, scale=None,
                      batch_axis=None):
    """shard_map wrapper over GLOBAL [B, H, S, D] arrays."""
    batch = batch_axis if batch_axis in mesh.axis_names else None
    spec = P(batch, None, seq_axis, None)
    fn = functools.partial(
        ulysses_attention_local, axis_name=seq_axis, causal=causal, scale=scale
    )
    return _shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)
