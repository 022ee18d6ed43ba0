"""Decode attention: the cached (slotted) and paged (block-arena) forms.

``cached_attention_composite`` / ``paged_attention_composite`` are THE
definition of both ops' math — ops/nn.py's lowerings call them.

One Pallas TPU kernel serves the ``[S, 1]`` decode step of slotted models:
``decode_attention`` — single-position attention of ``q`` ``[S, H]`` over a
dense slotted cache ``[S, L, H]`` under the additive ``-1e9`` bias, written
as ONE fused body (the composite verbatim) so the per-layer attention never
round-trips HBM between its stages, and so interpret mode is BIT-identical
to the composite (tests/test_kernels.py, tests/test_decode.py).

``paged_attention`` has NO kernel: the PR-15 body was the composite verbatim
with an in-kernel ``jnp.take``, which Pallas cannot lower for TPU. The
blocked kernel (grid over slot x kv block, block table in scalar prefetch,
online softmax) is ROADMAP 1.5; until it lands the composite is the one
path for paged models.

Eligibility: the fused body wants its whole workset resident in VMEM.
``fits_vmem`` gates the compiled-TPU path per static shape on the INPUT
bytes only (scores and the output are not counted) against ``VMEM_BUDGET``
= 12 MiB, under Mosaic's 16 MiB default scoped-VMEM limit; an oversized
geometry runs the composite, counted in ``kernel_fallbacks_total``.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels.registry import fallback_counter
from paddle_tpu.ops.common import vma_names

__all__ = [
    "cached_attention_composite", "paged_attention_composite",
    "decode_attention", "fits_vmem",
]

#: per-kernel budget (bytes) for the INPUT blocks; see the module docstring
VMEM_BUDGET = 12 * 1024 * 1024


def fits_vmem(*arrays):
    total = 0
    for a in arrays:
        total += int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
    return total <= VMEM_BUDGET


# ---------------------------------------------------------------------------
# the composite primitive sequences — THE definition of both ops' math.
# ops/nn.py's lowerings call these; the cached kernel body calls its one;
# bit-identity between the two paths is by construction, not by test luck
# (the tests then pin it).
# ---------------------------------------------------------------------------


def cached_attention_composite(q, k_cache, v_cache, bias, sm_scale):
    """Exactly the op sequence ``layers.cached_attention`` used to emit:
    unsqueeze -> matmul(transpose_y, alpha) -> elementwise_add -> softmax
    -> matmul -> squeeze, with each step lowered the way ops/math.py and
    ops/nn.py lower those ops."""
    q3 = jnp.expand_dims(q, 1)                        # unsqueeze [S,1,H]
    scores = jnp.matmul(q3, jnp.swapaxes(k_cache, -1, -2))
    if sm_scale != 1.0:                               # matmul alpha
        scores = scores * sm_scale
    att = jax.nn.softmax(scores + bias, axis=-1)      # add bias, softmax
    ctx = jnp.matmul(att, v_cache)                    # [S,1,H]
    return jnp.squeeze(ctx, 1)                        # [S,H]


def paged_attention_composite(q, k_arena, v_arena, rows, bias, seqs,
                              length, sm_scale):
    """``block_gather(k) ; block_gather(v) ; cached_attention`` as one
    function: gather rows byte-for-byte out of the flat arenas, then the
    cached-attention sequence over the gathered views."""
    flat = rows.reshape(-1)
    gk = jnp.take(k_arena, flat, axis=0).reshape(int(seqs), int(length), -1)
    gv = jnp.take(v_arena, flat, axis=0).reshape(int(seqs), int(length), -1)
    return cached_attention_composite(q, gk, gv, bias, sm_scale)


# ---------------------------------------------------------------------------
# fused kernel
# ---------------------------------------------------------------------------


def decode_attention(q, k_cache, v_cache, bias, sm_scale, interpret=False):
    """Fused ``[S, 1]`` cached attention: one program over full-array
    VMEM blocks (no grid — decode worksets are small; the win is fusion,
    not tiling). Falls back to the composite when the workset cannot be
    VMEM-resident on the compiled path or the call sits inside a manual
    (shard_map) region."""
    if vma_names(q) or (
        not interpret and not fits_vmem(q, k_cache, v_cache, bias)
    ):
        fallback_counter().inc()
        return cached_attention_composite(q, k_cache, v_cache, bias,
                                          sm_scale)

    def body(q_ref, k_ref, v_ref, b_ref, o_ref):
        o_ref[...] = cached_attention_composite(
            q_ref[...], k_ref[...], v_ref[...], b_ref[...], sm_scale
        ).astype(o_ref.dtype)

    kw = {} if interpret else {"memory_space": pltpu.VMEM}
    return pl.pallas_call(
        body,
        in_specs=[pl.BlockSpec(**kw) for _ in range(4)],
        out_specs=pl.BlockSpec(**kw),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="cached_attention",
    )(q, k_cache, v_cache, bias)
