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
the MXU), which is never a lower precision than the composite's. With
``kv_heads`` (grouped-query attention: rows of ``kv_heads`` K/V heads side
by side, a whole number of query heads to each) a group's query heads are
the rows of one MXU product per K/V head and block group, scores and
softmax in float32; heads narrower than the 128 lanes go two (or more) to
a product (``grouped_layout``).

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

import functools

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
    "chunk_attention_composite", "decode_attention", "paged_attention",
    "fits_vmem", "grouped_layout",
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
                              length, sm_scale, kv_heads=0):
    """``block_gather(k) ; block_gather(v) ; cached_attention`` as one
    function: gather rows byte-for-byte out of the flat arenas, then the
    cached-attention sequence over the gathered views. With ``kv_heads``
    the rows hold that many K (V) heads side by side and ``q`` a whole
    number of query heads to each (grouped-query attention): every query
    head attends over its group's K/V head, under the one bias row."""
    if kv_heads:
        return _grouped_composite(q, k_arena, v_arena, rows, bias, seqs,
                                  length, sm_scale, kv_heads)
    flat = rows.reshape(-1)
    gk = jnp.take(k_arena, flat, axis=0).reshape(int(seqs), int(length), -1)
    gv = jnp.take(v_arena, flat, axis=0).reshape(int(seqs), int(length), -1)
    return cached_attention_composite(q, gk, gv, bias, sm_scale)


def _grouped_composite(q, k_arena, v_arena, rows, bias, seqs, length,
                       sm_scale, kv_heads):
    """``q`` ``[S, heads * D]`` against arenas ``[R, kv_heads * D]``;
    scores and softmax in float32, the two products in the arenas' dtype
    accumulated in float32."""
    s, l, g = int(seqs), int(length), int(kv_heads)
    d = k_arena.shape[-1] // g
    f32 = jnp.float32
    prec = jax.lax.Precision.HIGHEST if k_arena.dtype == f32 else None
    flat = rows.reshape(-1)
    gk = jnp.take(k_arena, flat, axis=0).reshape(s, l, g, d)
    gv = jnp.take(v_arena, flat, axis=0).reshape(s, l, g, d)
    q4 = q.reshape(s, g, -1, d).astype(k_arena.dtype)
    scores = jnp.einsum("sgqd,slgd->sgql", q4, gk,
                        preferred_element_type=f32, precision=prec)
    att = jax.nn.softmax(scores * sm_scale
                         + bias.reshape(s, 1, 1, l).astype(f32), axis=-1)
    ctx = jnp.einsum("sgql,slgd->sgqd", att.astype(v_arena.dtype), gv,
                     preferred_element_type=f32, precision=prec)
    return ctx.reshape(s, -1).astype(q.dtype)


def chunk_attention_composite(q, k_arena, v_arena, rows, bias, sm_scale,
                              kv_heads):
    """A prompt chunk's ``C`` queries ``[C, heads * D]`` over ONE
    sequence's ``L`` arena rows (``rows`` ``[L]``) under the host's causal
    bias ``[1, C, L]``, grouped as ``_grouped_composite`` groups a step's
    heads."""
    c, l, g = q.shape[0], rows.shape[0], int(kv_heads)
    d = k_arena.shape[-1] // g
    f32 = jnp.float32
    prec = jax.lax.Precision.HIGHEST if k_arena.dtype == f32 else None
    gk = jnp.take(k_arena, rows, axis=0).reshape(l, g, d)
    gv = jnp.take(v_arena, rows, axis=0).reshape(l, g, d)
    q4 = q.reshape(c, g, -1, d).astype(k_arena.dtype)
    scores = jnp.einsum("cgqd,lgd->cgql", q4, gk, preferred_element_type=f32,
                        precision=prec)
    att = jax.nn.softmax(scores * sm_scale
                         + bias.reshape(c, 1, 1, l).astype(f32), axis=-1)
    ctx = jnp.einsum("cgql,lgd->cgqd", att.astype(v_arena.dtype), gv,
                     preferred_element_type=f32, precision=prec)
    return ctx.reshape(c, -1).astype(q.dtype)


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


def _block_copies(bt_ref, arenas, bufs, sem, s, live, grp, half, act, *,
                  block, group, per_slot):
    """``start`` or ``wait`` (``act``) for the copies of group ``grp``'s
    live blocks of slot ``s``, K and V, into ``half`` of the scratch."""
    for j in range(group):
        blk = grp * group + j
        row0 = pl.multiple_of(
            bt_ref[s * per_slot + jnp.minimum(blk, per_slot - 1)]
            * block, block)
        dst = (half, pl.ds(j * block, block))

        @pl.when(blk < live)
        def _():
            for n, (arena, buf) in enumerate(zip(arenas, bufs)):
                getattr(pltpu.make_async_copy(
                    arena.at[pl.ds(row0, block)], buf.at[dst],
                    sem.at[n, half]), act)()


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
        _block_copies(bt_ref, (k_hbm, v_hbm), (kbuf, vbuf), sem, s, live,
                      grp, half, act, block=block, group=group,
                      per_slot=per_slot)

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


def _paged_grouped_body(bt_ref, len_ref, q_ref, b_ref, k_hbm, v_hbm, o_ref,
                        kbuf, vbuf, sem, *, sm_scale, block, group, per_slot,
                        kv_heads):
    """``_paged_body`` with a head axis: the rows hold ``kv_heads`` K (V)
    heads of ``D`` side by side, ``q_ref`` is ``[kv_heads, per, D]``, and a
    group of blocks is reduced once per K/V head on the MXU, ``per`` query
    rows at a time (scores ``[per, rows]``, so the bias tile is a row)."""
    s = pl.program_id(0)
    live = pl.cdiv(len_ref[s], block)
    ngroups = pl.cdiv(live, group)
    d = q_ref.shape[-1]
    f32 = jnp.float32
    # a process-wide matmul precision reaches inside the body, and Mosaic
    # refuses a float32 one on bfloat16 operands: pin the operands' own
    prec = (jax.lax.Precision.HIGHEST if kbuf.dtype == f32
            else jax.lax.Precision.DEFAULT)

    @pl.when(s == 0)
    def _():
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    def copies(grp, half, act):
        _block_copies(bt_ref, (k_hbm, v_hbm), (kbuf, vbuf), sem, s, live,
                      grp, half, act, block=block, group=group,
                      per_slot=per_slot)

    @pl.when(ngroups > 0)
    def _():
        copies(0, 0, "start")

    def reduce_group(grp, carry):
        half = grp % 2

        @pl.when(grp + 1 < ngroups)
        def _():
            copies(grp + 1, 1 - half, "start")

        copies(grp, half, "wait")
        tile = b_ref[pl.ds(grp, 1), :].astype(f32)            # [1, rows]
        out = []
        for g in range(kv_heads):
            m, l, acc = carry[g]
            k = kbuf[half, :, g * d:(g + 1) * d]              # [rows, D]
            v = vbuf[half, :, g * d:(g + 1) * d]
            sc = jax.lax.dot_general(
                q_ref[g], k, (((1,), (1,)), ((), ())), precision=prec,
                preferred_element_type=f32)                   # [per, rows]
            if sm_scale != 1.0:
                sc = sc * sm_scale
            sc = sc + tile
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(sc - m_new)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                        precision=prec,
                                        preferred_element_type=f32)
            out.append((m_new, l, acc))
        return tuple(out)

    per = q_ref.shape[1]
    carry = jax.lax.fori_loop(0, ngroups, reduce_group, tuple(
        (jnp.full((per, 1), -jnp.inf, f32), jnp.zeros((per, 1), f32),
         jnp.zeros((per, d), f32)) for _ in range(kv_heads)))
    for g, (_m, l, acc) in enumerate(carry):
        o_ref[g] = jnp.where(l > 0, acc / l, 0.0).astype(o_ref.dtype)


def grouped_layout(width, kv_heads, q_width, dtype, interpret=False):
    """How the grouped body sees rows of ``kv_heads`` K/V heads (``width``
    lanes in all) and ``q_width // width`` query heads to each: ``(pack,
    rows)``. The body slices whole 128-lane tiles out of a row, so heads
    narrower than a tile go ``pack`` to a slice (two heads of 64), their
    query rows stacked in one block, each head's in its own lanes and zeros
    in its neighbours' (the neighbours' products vanish); ``rows`` is the
    block's query rows, padded to whole sublane tiles of ``dtype``. ``(0,
    0)`` says that no packing gives whole tiles and the composite runs: THE
    eligibility test of the grouped form, by geometry. ``interpret`` has no
    Mosaic to please and takes any head as it is."""
    d, per = width // kv_heads, q_width // width
    pack = 128 // d if d < 128 and 128 % d == 0 else 1
    if kv_heads % pack or (pack * d) % 128:
        return (1, per) if interpret else (0, 0)
    sublanes = 1 if interpret else 8 * (4 // jnp.dtype(dtype).itemsize)
    return pack, -(-pack * per // sublanes) * sublanes


def _pack_heads(q, pack, rows):
    """``q`` ``[S, G, per, D]`` as ``[S, G / pack, rows, pack * D]``: head
    ``j`` of a pack in rows ``j * per ..`` and lanes ``j * D ..``, zeros
    elsewhere."""
    s, g, per, d = q.shape
    if pack == 1:
        return q if rows == per else jnp.pad(
            q, ((0, 0), (0, 0), (0, rows - per), (0, 0)))
    eye = jnp.eye(pack, dtype=q.dtype)[None, None, :, None, :, None]
    wide = (q.reshape(s, g // pack, pack, per, 1, d) * eye).reshape(
        s, g // pack, pack * per, pack * d)
    return jnp.pad(wide, ((0, 0), (0, 0), (0, rows - pack * per), (0, 0)))


def _unpack_heads(out, pack, per):
    """``_pack_heads``'s inverse on the body's output: each head's rows, its
    own lanes of them; ``[S, G, per, D]``."""
    s, pairs, _rows, lanes = out.shape
    d = lanes // pack
    blocks = out[:, :, :pack * per].reshape(s, pairs, pack, per, pack, d)
    return jnp.stack([blocks[:, :, j, :, j] for j in range(pack)],
                     axis=2).reshape(s, pairs * pack, per, d)


def paged_attention(q, k_arena, v_arena, rows, bias, seqs, length,
                    block_size, sm_scale, interpret=False, kv_heads=0):
    """Blocked paged attention: ``paged_attention_composite`` computed
    from the live blocks alone (see the module docstring). ``rows`` must
    be block-aligned, as the engine's row maps are: every ``block_size``
    positions of a slot name consecutive arena rows from a multiple of
    ``block_size``. Falls back to the composite when Mosaic cannot tile
    the geometry or the call sits inside a manual (shard_map) region."""
    S, L, bs = int(seqs), int(length), int(block_size)
    H = k_arena.shape[-1]
    G = int(kv_heads)
    per_slot = -(-L // bs)
    group = _paged_group(bs, per_slot, H, k_arena.dtype)
    pack, qrows = grouped_layout(H, G, q.shape[-1], k_arena.dtype,
                                 interpret) if G else (1, 1)
    if vma_names(q) or group == 0 or not pack or (
            not interpret and not _mosaic_tiles(bs, H, k_arena.dtype)):
        fallback_counter().inc()
        return paged_attention_composite(q, k_arena, v_arena, rows, bias,
                                         S, L, sm_scale, kv_heads=G)
    grows = group * bs
    ngroups = -(-per_slot // group)
    table = (rows.reshape(S, L)[:, ::bs] // bs).astype(jnp.int32)
    bias2 = bias.reshape(S, L)
    lengths = jnp.max(
        jnp.where(bias2 > _CLOSED, jnp.arange(1, L + 1, dtype=jnp.int32), 0),
        axis=-1)
    tiles = jnp.pad(bias2, ((0, 0), (0, ngroups * grows - L)),
                    constant_values=-1e9).reshape(S, ngroups, grows)
    if G:
        lanes = pack * (H // G)
        row = pl.BlockSpec((None, G // pack, qrows, lanes),
                           lambda s, *_: (s, 0, 0, 0))
        body = functools.partial(_paged_grouped_body, kv_heads=G // pack)
        q_in = _pack_heads(q.reshape(S, G, -1, H // G), pack,
                           qrows).astype(k_arena.dtype)
    else:
        row = pl.BlockSpec((None, 1, H), lambda s, *_: (s, 0, 0))
        body, q_in = _paged_body, q.reshape(S, 1, H)
    out = pl.pallas_call(
        lambda *refs: body(
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
        out_shape=jax.ShapeDtypeStruct(q_in.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(table.reshape(-1), lengths, q_in, tiles, k_arena, v_arena)
    if G:
        out = _unpack_heads(out, pack, q.shape[-1] // H)
    return out.reshape(q.shape)
