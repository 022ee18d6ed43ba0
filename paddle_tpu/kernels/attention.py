"""Decode attention: the cached (slotted) and paged (block-arena) forms.

``cached_attention_composite`` / ``paged_attention_composite`` are THE
definition of both ops' math — ops/nn.py's lowerings call them, and they
are the CPU path and the ``off`` path of both ops.

Two Pallas TPU kernels serve the ``[S, 1]`` decode step:

``decode_attention`` — single-position attention of ``q`` ``[S, H]`` over a
dense slotted cache ``[S, L, H]`` under the additive ``-1e9`` bias, written
as ONE fused body (the composite verbatim) so the per-layer attention never
round-trips HBM between its stages, and so interpret mode is BIT-identical
to the composite (tests/test_kernels.py).

``paged_attention`` — the same attention over the flat ``[R, H]`` block
arenas, which stay in HBM: the grid runs over slots, a slot's block table
and length ride in scalar prefetch, and the body brings the slot's LIVE
blocks into a double-buffered VMEM scratch by ``make_async_copy``, a group
of blocks at a time, the next group's copies in flight while this one is
reduced by an online softmax. The trip count comes from the slot's length:
a dead block is never read, no ``[S * L, H]`` view is ever written, and a
free slot (length 0) reads nothing and writes zeros. The bias tile of every
visited group is added to the scores, so the kernel computes exactly
``softmax(q K^T scale + bias) V`` for ANY bias; the length only bounds which
blocks are touched, and every position skipped is one whose weight is
``exp(-1e9 - m) = 0`` in float32. Its parity contract is a TOLERANCE
(1e-5 both ways, as for flash): an online softmax regroups float32 sums, so
it is close to the composite and not bit-identical with it. What stays
bit-exact is one compiled program replayed under any admission order, and
resume after park (tests/test_decode.py). The body takes dtype and widths
from its operands and reduces in float32 on the VPU (a one-row GEMV wastes
the MXU), which is never a lower precision than the composite's.

Eligibility: ``decode_attention`` wants its whole workset resident in VMEM;
``fits_vmem`` gates the compiled-TPU path per static shape on the INPUT
bytes only (scores and the output are not counted) against ``VMEM_BUDGET``
= 12 MiB, under Mosaic's 16 MiB default scoped-VMEM limit. The paged
kernel's scratch is two groups of K and of V (2 MiB at hidden 1024, block
16, float32), held to the same budget by shrinking the group; a block
Mosaic cannot tile (rows not a multiple of the dtype's sublane tile, hidden
not a multiple of 128) runs the composite. Every such fallback is counted
in ``kernel_fallbacks_total``.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels.registry import fallback_counter
from paddle_tpu.ops.common import vma_names

#: a bias at or under this is a closed position: the paged kernel takes a
#: slot's length as 1 + the last position its bias row opens (half the
#: repo-wide -1e9 padding bias, so any sum of a score and -1e9 is closed)
_CLOSED = -5e8

__all__ = [
    "cached_attention_composite", "paged_attention_composite",
    "decode_attention", "paged_attention", "fits_vmem",
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


# ---------------------------------------------------------------------------
# blocked paged kernel
# ---------------------------------------------------------------------------

#: rows of K (and of V) one reduction of the paged kernel covers: a lane
#: tile, so the group's bias tile is one (1, 128) row
_GROUP_ROWS = 128


def _paged_group(block_size, blocks_per_slot, hidden, dtype):
    """Blocks per group of the paged kernel: a lane tile's worth of rows,
    fewer when two groups of K and of V would not fit ``VMEM_BUDGET``;
    0 when not even single blocks do."""
    g = max(1, min(int(blocks_per_slot), _GROUP_ROWS // int(block_size)))
    per_block = 4 * int(block_size) * int(hidden) * jnp.dtype(dtype).itemsize
    return min(g, VMEM_BUDGET // per_block)


def _mosaic_tiles(block_size, hidden, dtype):
    """Can Mosaic copy one arena block into a VMEM tile? Rows in whole
    sublane tiles of the dtype (8 at 4 bytes, 16 at 2), lanes in 128s."""
    sublanes = 8 * (4 // jnp.dtype(dtype).itemsize)
    return block_size % sublanes == 0 and hidden % 128 == 0


def _paged_body(bt_ref, len_ref, q_ref, b_ref, k_hbm, v_hbm, o_ref,
                kbuf, vbuf, sem, *, sm_scale, block, group, per_slot):
    s = pl.program_id(0)
    live = pl.cdiv(len_ref[s], block)             # blocks to read
    ngroups = pl.cdiv(live, group)

    @pl.when(s == 0)
    def _():
        # a short last group leaves rows of the scratch unwritten; their
        # weight is exp(-1e9 - m) = 0, so they only have to be finite
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    def copies(grp, half, act):
        """``start`` or ``wait`` for the copies of group ``grp``'s live
        blocks, K and V, into ``half`` of the scratch."""
        for j in range(group):
            blk = grp * group + j
            row0 = pl.multiple_of(
                bt_ref[s * per_slot + jnp.minimum(blk, per_slot - 1)]
                * block, block)
            dst = (half, pl.ds(j * block, block))

            @pl.when(blk < live)
            def _():
                for n, (arena, buf) in enumerate(((k_hbm, kbuf),
                                                  (v_hbm, vbuf))):
                    getattr(pltpu.make_async_copy(
                        arena.at[pl.ds(row0, block)], buf.at[dst],
                        sem.at[n, half]), act)()

    @pl.when(ngroups > 0)
    def _():
        copies(0, 0, "start")

    q = q_ref[...].astype(jnp.float32)            # [1, H]

    def reduce_group(grp, carry):
        m, l, acc = carry
        half = grp % 2

        @pl.when(grp + 1 < ngroups)
        def _():
            copies(grp + 1, 1 - half, "start")

        copies(grp, half, "wait")
        k = kbuf[half].astype(jnp.float32)        # [rows, H]
        v = vbuf[half].astype(jnp.float32)
        sc = jnp.sum(k * q, axis=-1, keepdims=True)            # [rows, 1]
        if sm_scale != 1.0:
            sc = sc * sm_scale
        sc = sc + b_ref[pl.ds(grp, 1), :].astype(jnp.float32).reshape(-1, 1)
        m_new = jnp.maximum(m, jnp.max(sc, axis=0, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new)
        l = l * alpha + jnp.sum(p, axis=0, keepdims=True)
        acc = acc * alpha + jnp.sum(p * v, axis=0, keepdims=True)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, ngroups, reduce_group, (
        jnp.full((1, 1), -jnp.inf, jnp.float32),
        jnp.zeros((1, 1), jnp.float32),
        jnp.zeros(q.shape, jnp.float32)))
    o_ref[...] = jnp.where(l > 0, acc / l, 0.0).astype(o_ref.dtype)


def paged_attention(q, k_arena, v_arena, rows, bias, seqs, length,
                    block_size, sm_scale, interpret=False):
    """Blocked paged attention: ``paged_attention_composite`` computed
    from the live blocks alone (see the module docstring). ``rows`` must
    be block-aligned, as the engine's row maps are: every ``block_size``
    positions of a slot name consecutive arena rows from a multiple of
    ``block_size``. Falls back to the composite when Mosaic cannot tile
    the geometry or the call sits inside a manual (shard_map) region."""
    S, L, bs = int(seqs), int(length), int(block_size)
    H = q.shape[-1]
    per_slot = -(-L // bs)
    group = _paged_group(bs, per_slot, H, k_arena.dtype)
    if vma_names(q) or group == 0 or (
        not interpret and not _mosaic_tiles(bs, H, k_arena.dtype)
    ):
        fallback_counter().inc()
        return paged_attention_composite(q, k_arena, v_arena, rows, bias,
                                         S, L, sm_scale)
    grows = group * bs
    ngroups = -(-per_slot // group)
    table = (rows.reshape(S, L)[:, ::bs] // bs).astype(jnp.int32)
    bias2 = bias.reshape(S, L)
    lengths = jnp.max(
        jnp.where(bias2 > _CLOSED, jnp.arange(1, L + 1, dtype=jnp.int32), 0),
        axis=-1)
    tiles = jnp.pad(bias2, ((0, 0), (0, ngroups * grows - L)),
                    constant_values=-1e9).reshape(S, ngroups, grows)
    row = pl.BlockSpec((None, 1, H), lambda s, *_: (s, 0, 0))
    out = pl.pallas_call(
        lambda *refs: _paged_body(
            *refs, sm_scale=sm_scale, block=bs, group=group,
            per_slot=per_slot),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[
                row,
                pl.BlockSpec((None, ngroups, grows),
                             lambda s, *_: (s, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=row,
            scratch_shapes=[
                pltpu.VMEM((2, grows, H), k_arena.dtype),
                pltpu.VMEM((2, grows, H), v_arena.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, 1, H), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(table.reshape(-1), lengths, q.reshape(S, 1, H), tiles,
      k_arena, v_arena)
    return out.reshape(S, H)
