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
arenas, which stay in HBM. What it computes is exactly ``softmax(q K^T scale
+ bias) V`` for ANY bias, from a slot's LIVE blocks alone: the bias tile of
every visited row tile is added to the scores, the slot's length (1 + the
last position its bias row opens) only bounds which blocks are touched, and
every position skipped is one whose weight is ``exp(-1e9 - m) = 0`` in
float32. A dead block is never read, no ``[S * L, H]`` view is ever written,
and a free slot (length 0) reads nothing and writes zeros.

The copy pipeline (``_paged_pipeline``, one implementation for both bodies).
The unit of a copy is a COPY UNIT (``_paged_group``): whole reduce tiles of
blocks, about a megabyte of K plus V from the block size, the row width and
the dtype (one 128-row tile at 2,048 bf16 or 1,024 float32 lanes, four at
512 bf16, eight at 256), never more than a slot holds, and small enough that
two halves of K and of V (the double-buffered VMEM scratch) fit the budget
below. A unit's live blocks are started a RUN at a time: an aligned group of
``_RUN_BLOCKS`` table entries that the arena holds side by side, ascending,
and that are all live goes in ONE ``make_async_copy`` an arena (the
wrapper flags such groups from the block table on the device, exactly:
``_copy_runs``), every other block in one of its own; all on the half's
semaphore, and waited for together: a DMA semaphore counts bytes, so ONE
wait an arena of the unit's size serves a full unit (a short one takes a
wait per power of two). The grid runs over the slots, ``_STEP_SLOTS`` to a
grid step (a grid step costs the same whether its slots are live or free);
the block table, its run flags, the lengths and each slot's NEXT LIVE slot
ride in scalar prefetch. What is in flight when: the
next live unit, always. The first grid step zeroes the scratch and starts the
first live slot's first unit; from then on, when a unit's reduce begins, the
unit after it is started into the other half — the slot's own next unit or,
under the slot's LAST unit, the first unit of the next live slot, which may
stand any number of free slots (and grid steps) later and finds its copy
started. Which half that is goes from slot to slot through one SMEM word.
So only a call's first unit is waited for with nothing to overlap it. The
reduce walks a unit in tiles of ``_TILE_ROWS`` rows (the bias tile stays a
``(1, 128)`` row) by an online softmax, and walks only tiles that hold a
live position, so a short slot in a large unit does no more arithmetic than
its tiles. Rows of the scratch that a short unit leaves unwritten are stale:
they hold zeros or an EARLIER unit's live rows (this slot's or another's,
never a dead block's), their weight is 0 and they are finite, so a slot's
output bytes do not depend on which slots precede it nor on the half its
first unit landed in.

Its parity contract is a TOLERANCE (1e-5 both ways, as for flash): an online
softmax regroups float32 sums, so it is close to the composite and not
bit-identical with it. What stays bit-exact is one compiled program
replayed under any admission order, and resume after park
(tests/test_decode.py). The body takes dtype and widths from its operands
and reduces in float32 on the VPU (a one-row GEMV wastes the MXU), which is
never a lower precision than the composite's. With ``kv_heads``
(grouped-query attention: rows of ``kv_heads`` K/V heads side by side, a
whole number of query heads to each) a group's query heads are the rows of
one MXU product per K/V head and row tile, scores and softmax in float32;
heads narrower than the 128 lanes go two (or more) to a product
(``grouped_layout``).

Eligibility: ``decode_attention`` wants its whole workset resident in VMEM;
``fits_vmem`` gates the compiled-TPU path per static shape on the INPUT
bytes only (scores and the output are not counted) against ``VMEM_BUDGET``
= 12 MiB, under Mosaic's 16 MiB default scoped-VMEM limit. The paged
kernel's scratch is two units of K and of V (2 MiB at every serving cell's
geometry), held to the same budget by shrinking the unit, then the tile; a
block Mosaic cannot tile (rows not a multiple of the dtype's sublane tile,
hidden not a multiple of 128) runs the composite. Every such fallback is
counted in ``kernel_fallbacks_total``.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels.registry import (
    fallback_counter, latent_chunk_counter,
)
from paddle_tpu.ops.common import vma_names

#: a bias at or under this is a closed position: the paged kernel takes a
#: slot's length as 1 + the last position its bias row opens (half the
#: repo-wide -1e9 padding bias, so any sum of a score and -1e9 is closed)
_CLOSED = -5e8

__all__ = [
    "cached_attention_composite", "paged_attention_composite",
    "chunk_attention_composite", "decode_attention", "paged_attention",
    "chunk_attention", "chunk_attention_by_span", "chunk_horizon",
    "chunk_mask_bias", "chunk_floor", "fits_vmem", "grouped_layout",
    "paged_copy_unit", "paged_run_blocks",
    "absorb_queries", "project_values", "latent_chunk_expanded",
    "latent_chunk_attention",
]

#: per-kernel budget (bytes) for the INPUT blocks; see the module docstring
VMEM_BUDGET = 12 * 1024 * 1024

#: the ``jax.named_scope`` of what ``paged_attention`` derives from ``rows``
#: and ``bias`` alone, the same for every call of a step program
TABLES_SCOPE = "paged_attention_tables"


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
                              length, sm_scale, kv_heads=0, v_width=0):
    """``block_gather(k) ; block_gather(v) ; cached_attention`` as one
    function: gather rows byte-for-byte out of the flat arenas, then the
    cached-attention sequence over the gathered views. With ``kv_heads``
    the rows hold that many K (V) heads side by side and ``q`` a whole
    number of query heads to each (grouped-query attention): every query
    head attends over its group's K/V head, under the one bias row.
    Handed ONE arena (``v_arena`` None: a latent cache, the last section)
    every query head attends over the one row a token, and a token's value
    is the first ``v_width`` lanes of that same row."""
    if v_arena is None:
        kv_heads = 1
    if kv_heads:
        return _grouped_composite(q, k_arena, v_arena, rows, bias, seqs,
                                  length, sm_scale, kv_heads, v_width)
    flat = rows.reshape(-1)
    gk = jnp.take(k_arena, flat, axis=0).reshape(int(seqs), int(length), -1)
    gv = jnp.take(v_arena, flat, axis=0).reshape(int(seqs), int(length), -1)
    return cached_attention_composite(q, gk, gv, bias, sm_scale)


def _grouped_composite(q, k_arena, v_arena, rows, bias, seqs, length,
                       sm_scale, kv_heads, v_width=0):
    """``q`` ``[S, heads * D]`` against arenas ``[R, kv_heads * D]``;
    scores and softmax in float32, the two products in the arenas' dtype
    accumulated in float32."""
    s, l, g = int(seqs), int(length), int(kv_heads)
    d = k_arena.shape[-1] // g
    f32 = jnp.float32
    prec = jax.lax.Precision.HIGHEST if k_arena.dtype == f32 else None
    flat = rows.reshape(-1)
    gk = jnp.take(k_arena, flat, axis=0).reshape(s, l, g, d)
    gv = (gk[..., :int(v_width)] if v_arena is None
          else jnp.take(v_arena, flat, axis=0).reshape(s, l, g, d))
    v_arena = k_arena if v_arena is None else v_arena
    q4 = q.reshape(s, g, -1, d).astype(k_arena.dtype)
    scores = jnp.einsum("sgqd,slgd->sgql", q4, gk,
                        preferred_element_type=f32, precision=prec)
    att = jax.nn.softmax(scores * sm_scale
                         + bias.reshape(s, 1, 1, l).astype(f32), axis=-1)
    ctx = jnp.einsum("sgql,slgd->sgqd", att.astype(v_arena.dtype), gv,
                     preferred_element_type=f32, precision=prec)
    return ctx.reshape(s, -1).astype(q.dtype)


def chunk_horizon(span, chunk, length, block_len=1):
    """THE rule of the chunk program's mask, on the device: ``span`` is the
    chunk's ``(start, real)``, its first position and its count of real
    positions; query ``c < real`` stands at ``start + c`` and sees what
    lies at or before it and, with ``block_len`` B, the whole of its own
    block: the positions below ``(at // B + 1) * B``. Returns that bound
    for each of the ``chunk`` queries, int32 ``[C]``, 0 for a query past
    the real ones (it sees nothing)."""
    b = int(block_len)
    c = jnp.arange(int(chunk), dtype=jnp.int32)
    start, real = span[0].astype(jnp.int32), span[1].astype(jnp.int32)
    bound = jnp.minimum(((start + c) // b + 1) * b, int(length))
    return jnp.where(c < real, bound, 0)


def chunk_floor(span, chunk, window):
    """The LOWER edge of the chunk program's mask for layers that see the
    last ``window`` positions, the query's own among them: query ``c`` at
    ``start + c`` sees nothing below ``start + c - window + 1``. int32
    ``[C]``, 0 where the window reaches the sequence's start."""
    c = jnp.arange(int(chunk), dtype=jnp.int32)
    return jnp.maximum(span[0].astype(jnp.int32) + c - int(window) + 1, 0)


def chunk_mask_bias(span, chunk, length, block_len=1, window=0):
    """``chunk_horizon`` (and, with ``window``, ``chunk_floor``) as the
    additive float32 ``[1, C, L]`` bias a composite adds to its scores: 0.0
    where a query sees, ``-1e9`` where not."""
    at = jnp.arange(int(length), dtype=jnp.int32)[None, :]
    sees = at < chunk_horizon(span, chunk, length, block_len)[:, None]
    if window:
        sees &= at >= chunk_floor(span, chunk, window)[:, None]
    return jnp.where(sees, 0.0, -1e9).astype(jnp.float32)[None]


def chunk_attention_composite(q, k_arena, v_arena, rows, bias, sm_scale,
                              kv_heads):
    """A prompt chunk's ``C`` queries ``[C, heads * D]`` over ONE
    sequence's ``L`` arena rows (``rows`` ``[L]``) under the additive bias
    ``[1, C, L]`` (``chunk_mask_bias``), grouped as ``_grouped_composite``
    groups a step's heads. Work and memory are ``C x L`` whatever the
    prompt's length so far: the reference lowering, the CPU path and the
    path of the short geometries (``chunk_attention``)."""
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


def chunk_attention_by_span(q, k_arena, v_arena, rows, span, sm_scale,
                            kv_heads, block_len=1, window=0):
    """``chunk_attention_composite`` under the bias the rule makes of the
    chunk's ``span``: THE definition of the ``chunk_paged_attention`` op."""
    return chunk_attention_composite(
        q, k_arena, v_arena, rows,
        chunk_mask_bias(span, q.shape[0], rows.shape[0], block_len, window),
        sm_scale, kv_heads)


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
#: tile, so a tile's bias is one (1, 128) row
_TILE_ROWS = 128

#: slots one grid step serves (a divisor of the slot count up to this): a
#: grid step costs ~0.2 us whether its slot is live or free, 128 of them
#: were a third of a call at 24 live slots
_STEP_SLOTS = 8

#: rows one reduction covers where the kernel is handed ONE latent arena:
#: all the heads' queries (32 rows, not a K/V head's few) stand against a
#: tile, and at a lane tile's 128 rows a slot of 8,192 positions is 64
#: products of [32, 384] x [384, 128], each waited for alone: 0.73 ms for 16
#: such slots, 14 % of what their rows' bytes take (my chip run, PR 56)
_LATENT_TILE_ROWS = 512

#: bytes of K plus V a copy unit aims at: what one wait brings in, large
#: enough that the descriptors' issue and the DMA's latency are a small
#: part of it whatever the row width
_UNIT_BYTES = 1 << 20


def _paged_tile(block_size, blocks_per_slot, hidden, dtype,
                tile_rows=None):
    """Blocks per reduce tile of the paged kernel: a lane tile's worth of
    rows (``tile_rows``: another count), fewer when two halves of K and of
    V would not fit ``VMEM_BUDGET``; 0 when not even single blocks do."""
    t = max(1, min(int(blocks_per_slot),
                   (tile_rows or _TILE_ROWS) // int(block_size)))
    per_block = 4 * int(block_size) * int(hidden) * jnp.dtype(dtype).itemsize
    return min(t, VMEM_BUDGET // per_block)


def _paged_group(block_size, blocks_per_slot, hidden, dtype, tile_rows=None):
    """Blocks per COPY UNIT of the paged kernel (what is started, and
    waited for, together): whole reduce tiles, about ``_UNIT_BYTES`` of K
    plus V from the row width and dtype, no more than a slot has and than
    two halves of K and of V fit ``VMEM_BUDGET``; 0 when no block does.
    The engine counts a step's units with the same function."""
    tile = _paged_tile(block_size, blocks_per_slot, hidden, dtype, tile_rows)
    if not tile:
        return 0
    tile_bytes = (2 * tile * int(block_size) * int(hidden)
                  * jnp.dtype(dtype).itemsize)
    tiles = min(_UNIT_BYTES // tile_bytes, -(-int(blocks_per_slot) // tile),
                VMEM_BUDGET // (2 * tile_bytes))
    return max(1, tiles) * tile


def _tile_rows(arenas):
    """Rows a reduce tile covers by how many arenas a layer's cache is: ONE
    is a latent cache's (``_LATENT_TILE_ROWS``)."""
    return _LATENT_TILE_ROWS if arenas == 1 else _TILE_ROWS


def paged_copy_unit(block_size, blocks_per_slot, hidden, dtype, arenas=2):
    """Blocks per copy unit of ``paged_attention`` over a cache of
    ``arenas`` arenas a layer (K and V, or a latent cache's one): what the
    kernel runs and what the engine counts a step's units by."""
    return _paged_group(block_size, blocks_per_slot, hidden, dtype,
                        _tile_rows(arenas))


def _mosaic_tiles(block_size, hidden, dtype):
    """Can Mosaic copy one arena block into a VMEM tile? Rows in whole
    sublane tiles of the dtype (8 at 4 bytes, 16 at 2), lanes in 128s."""
    sublanes = 8 * (4 // jnp.dtype(dtype).itemsize)
    return block_size % sublanes == 0 and hidden % 128 == 0


#: blocks ONE copy descriptor brings in where the block table names them
#: side by side in the arena, ascending (a RUN). Issuing a descriptor costs
#: the scalar core ~20 ns whatever it moves, NOT hidden under the reduce: a
#: 16-row block at a time that was a third of a call at 1 KB rows and four
#: fifths of ``index_scores``' at 256 B ones. READ ON THE CHIP
#: (tools/check_paged_copies.py; PERF.md section 6, PR 64; us a call of 16
#: slots at 14k over an ascending | a shuffled table, the parent's 1,467):
#: 4 blocks 1,089 | 1,654, 8 blocks 992 | 1,551, 16 blocks 944 | 1,499, 32
#: blocks 919 | 1,473 with a loop a group that is no run, and 8 blocks
#: 1,005 | 1,378 with the group straight-line, as it is now. 8 is the most
#: that divides every serving cell's copy unit (decoder_1024x24's is 8)
_RUN_BLOCKS = 8


def paged_run_blocks(unit, arena_blocks):
    """Blocks ONE descriptor of a paged kernel brings in where its block
    table names them side by side, at copy units of ``unit`` blocks over an
    arena of ``arena_blocks``: ``_RUN_BLOCKS``, or 0 (every block alone)
    where a unit is not whole groups of that many or the arena holds
    fewer. What the kernels run and what the engine counts a step's run
    blocks by."""
    run = _RUN_BLOCKS
    return run if run > 1 and unit % run == 0 and arena_blocks >= run else 0


def _copy_runs(table, unit, arena, block):
    """``(run, flags)`` of a block table ``[.., per_slot]`` read in copy
    units of ``unit`` blocks: ``run`` is ``paged_run_blocks``' (0: every
    block goes alone and ``flags`` is a placeholder); ``flags`` int32
    ``[slots * groups]`` says, an aligned group of ``run`` table entries
    each, that every entry is its neighbour's plus one. Exact for ANY
    table; a slot's last, padded group is never wholly live and never
    read."""
    run = paged_run_blocks(unit, arena.shape[0] // block)
    if not run:
        return 0, jnp.zeros((1,), jnp.int32)
    per_slot = table.shape[-1]
    groups = -(-per_slot // run)
    t = jnp.pad(table.reshape(-1, per_slot),
                ((0, 0), (0, groups * run - per_slot)))
    t = t.reshape(-1, groups, run)
    return run, jnp.all(t[..., 1:] - t[..., :-1] == 1,
                        axis=-1).astype(jnp.int32).reshape(-1)


def _start_copies(bt_ref, run_ref, len_ref, arenas, bufs, sem, slot, unit_no,
                  half, *, block, unit, per_slot, run):
    """Start the copies of the live blocks of ``slot``'s copy unit
    ``unit_no``, K and V, into ``half`` of the scratch, all of a half's on
    one semaphore an arena: an aligned group of ``run`` blocks that is
    wholly live and flagged a run (``run_ref``: ``_copy_runs``) goes in ONE
    descriptor an arena, every other block in one of its own (``run`` 0:
    all of them)."""
    live = pl.cdiv(len_ref[slot], block)
    first = unit_no * unit
    count = jnp.minimum(live - first, unit)

    def copy(j, blocks):
        row0 = pl.multiple_of(
            bt_ref[slot * per_slot + first + j] * block, block)
        dst = (half, pl.ds(pl.multiple_of(j * block, block), blocks * block))
        for n, (arena, buf) in enumerate(zip(arenas, bufs)):
            pltpu.make_async_copy(
                arena.at[pl.ds(row0, blocks * block)], buf.at[dst],
                sem.at[n, half]).start()

    def one(j, c):
        copy(j, 1)
        return c

    if not run:
        jax.lax.fori_loop(0, count, one, 0)
        return
    flag0 = slot * (-(-per_slot // run)) + first // run

    def group(g, c):
        is_run = run_ref[flag0 + g] != 0

        @pl.when(is_run)
        def _():
            copy(g * run, run)

        @pl.when(jnp.logical_not(is_run))
        def _():
            # (straight-line: a loop a group cost a shuffled table's call
            # 6-27 % on the chip, PERF.md section 6, PR 64)
            for j in range(run):
                copy(g * run + j, 1)

        return c

    whole = jnp.maximum(count, 0) // run
    jax.lax.fori_loop(0, whole, group, 0)
    jax.lax.fori_loop(whole * run, count, one, 0)


def _wait_copies(len_ref, bufs, sem, slot, unit_no, half, *, block, unit):
    """Wait for what ``_start_copies`` started for that unit. A DMA
    semaphore counts bytes, and a wait needs only a descriptor of the size
    it waits for (here the scratch rows onto themselves: an arena may hold
    fewer rows than a unit): the unit's live blocks are waited for in
    power-of-two runs, ONE wait an arena for a full unit, not block by
    block."""
    count = jnp.minimum(pl.cdiv(len_ref[slot], block) - unit_no * unit, unit)
    run = 1 << (unit.bit_length() - 1)
    while run:
        @pl.when(count & run != 0)
        def _(run=run):
            for n, buf in enumerate(bufs):
                rows = buf.at[half, pl.ds(0, run * block)]
                pltpu.make_async_copy(rows, rows, sem.at[n, half]).wait()
        run >>= 1


def _paged_pipeline(bt_ref, run_ref, len_ref, nxt_ref, arenas, bufs, sem,
                    half_ref, init, reduce_tile, finish, *, block, tile,
                    unit, per_slot, step_slots, run):
    """The copy pipeline both bodies share. A grid step serves
    ``step_slots`` slots, one after the other: ``carry = init(i)``, then
    the slot's live reduce tiles (``tile`` blocks each) go through
    ``reduce_tile(i, half, row0, tile_no, carry)``, then ``finish(i,
    carry)`` writes the slot's output. While a copy unit (``unit`` blocks)
    is reduced, the NEXT live unit is in flight into the other half of the
    scratch: the slot's own next unit or, under its last, the first unit of
    the next live slot (``nxt_ref``: the next slot with a position, the
    slot count when there is none), in this grid step or a later one, which
    then finds it started. ``half_ref`` (SMEM) carries from slot to slot
    which half the next live slot's first unit is in; the first grid step
    zeroes the scratch and starts the first live slot's."""
    # (the interpreter resolves grid primitives at the body's top level only)
    step0 = pl.program_id(0) * step_slots
    seqs = pl.num_programs(0) * step_slots
    trows = tile * block
    per_unit = unit // tile

    def start(slot, unit_no, half):
        _start_copies(bt_ref, run_ref, len_ref, arenas, bufs, sem, slot,
                      unit_no, half, block=block, unit=unit,
                      per_slot=per_slot, run=run)

    @pl.when(step0 == 0)
    def _():
        # rows of the scratch that a short unit leaves unwritten carry
        # weight exp(-1e9 - m) = 0, so they only have to be finite: zeros
        # here, and later some earlier unit's live rows
        for buf in bufs:
            buf[...] = jnp.zeros_like(buf)
        half_ref[0] = 0
        first = jnp.where(len_ref[0] > 0, 0, nxt_ref[0])

        @pl.when(first < seqs)
        def _():
            start(first, 0, 0)

    def one_slot(i, c):
        s = step0 + i
        ntiles = pl.cdiv(len_ref[s], trows)
        nunits = pl.cdiv(ntiles, per_unit)
        half0 = half_ref[0]

        def step(t, carry):
            u = t // per_unit
            half = (half0 + u) % 2

            @pl.when(t % per_unit == 0)
            def _():
                last = u + 1 == nunits
                slot = jnp.where(last, nxt_ref[s], s)

                @pl.when(slot < seqs)
                def _():
                    start(jnp.minimum(slot, seqs - 1),
                          jnp.where(last, 0, u + 1), 1 - half)

                _wait_copies(len_ref, bufs, sem, s, u, half, block=block,
                             unit=unit)

            row0 = pl.multiple_of((t % per_unit) * trows, trows)
            return reduce_tile(i, half, row0, t, carry)

        finish(i, jax.lax.fori_loop(0, ntiles, step, init(i)))

        @pl.when(nunits > 0)
        def _():
            half_ref[0] = (half0 + nunits) % 2

        return c

    jax.lax.fori_loop(0, step_slots, one_slot, 0)


def _paged_body(bt_ref, run_ref, len_ref, nxt_ref, q_ref, b_ref, k_hbm, v_hbm,
                o_ref, kbuf, vbuf, sem, half_ref, *, sm_scale, **geometry):
    f32 = jnp.float32
    trows = geometry["tile"] * geometry["block"]

    def init(i):
        return (jnp.full((1, 1), -jnp.inf, f32), jnp.zeros((1, 1), f32),
                jnp.zeros(q_ref.shape[1:], f32))

    def reduce_tile(i, half, row0, t, carry):
        m, l, acc = carry
        q = q_ref[i].astype(f32)                               # [1, H]
        k = kbuf[half, pl.ds(row0, trows)].astype(f32)         # [rows, H]
        v = vbuf[half, pl.ds(row0, trows)].astype(f32)
        sc = jnp.sum(k * q, axis=-1, keepdims=True)            # [rows, 1]
        if sm_scale != 1.0:
            sc = sc * sm_scale
        sc = sc + b_ref[i, pl.ds(t, 1), :].astype(f32).reshape(-1, 1)
        m_new = jnp.maximum(m, jnp.max(sc, axis=0, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new)
        l = l * alpha + jnp.sum(p, axis=0, keepdims=True)
        acc = acc * alpha + jnp.sum(p * v, axis=0, keepdims=True)
        return m_new, l, acc

    def finish(i, carry):
        _m, l, acc = carry
        o_ref[i] = jnp.where(l > 0, acc / l, 0.0).astype(o_ref.dtype)

    _paged_pipeline(bt_ref, run_ref, len_ref, nxt_ref, (k_hbm, v_hbm),
                    (kbuf, vbuf), sem, half_ref, init, reduce_tile, finish,
                    **geometry)


def _paged_grouped_body(bt_ref, run_ref, len_ref, nxt_ref, q_ref, b_ref,
                        *refs, sm_scale, kv_heads, arenas, **geometry):
    """``_paged_body`` with a head axis: the rows hold ``kv_heads`` K (V)
    heads of ``D`` side by side, a slot's ``q_ref[i]`` is ``[kv_heads, per,
    D]``, and a tile of rows is reduced once per K/V head on the MXU,
    ``per`` query rows at a time (scores ``[per, rows]``, so the bias tile
    is a row). ``refs``: the ``arenas`` arenas in HBM, the output, their
    scratches, the semaphores and the half word. With ONE arena (a latent
    cache) a row is its token's key AND, in its first lanes (the output's
    width), its value: the row is copied once and read twice."""
    hbm, (o_ref, *bufs, sem, half_ref) = refs[:arenas], refs[arenas:]
    kbuf, vbuf = bufs[0], bufs[-1]
    per, d = q_ref.shape[2:]
    dv = o_ref.shape[-1]
    trows = geometry["tile"] * geometry["block"]
    f32 = jnp.float32
    # a process-wide matmul precision reaches inside the body, and Mosaic
    # refuses a float32 one on bfloat16 operands: pin the operands' own
    prec = (jax.lax.Precision.HIGHEST if kbuf.dtype == f32
            else jax.lax.Precision.DEFAULT)

    def init(i):
        return tuple(
            (jnp.full((per, 1), -jnp.inf, f32), jnp.zeros((per, 1), f32),
             jnp.zeros((per, dv), f32)) for _ in range(kv_heads))

    def reduce_tile(i, half, row0, t, carry):
        bias = b_ref[i, pl.ds(t, 1), :].astype(f32)           # [1, rows]
        out = []
        for g in range(kv_heads):
            m, l, acc = carry[g]
            k = kbuf[half, pl.ds(row0, trows), g * d:(g + 1) * d]  # [rows, D]
            v = vbuf[half, pl.ds(row0, trows), g * dv:(g + 1) * dv]
            sc = jax.lax.dot_general(
                q_ref[i, g], k, (((1,), (1,)), ((), ())), precision=prec,
                preferred_element_type=f32)                   # [per, rows]
            if sm_scale != 1.0:
                sc = sc * sm_scale
            sc = sc + bias
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(sc - m_new)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                        precision=prec,
                                        preferred_element_type=f32)
            out.append((m_new, l, acc))
        return tuple(out)

    def finish(i, carry):
        for g, (_m, l, acc) in enumerate(carry):
            o_ref[i, g] = jnp.where(l > 0, acc / l, 0.0).astype(o_ref.dtype)

    _paged_pipeline(bt_ref, run_ref, len_ref, nxt_ref, hbm, bufs, sem,
                    half_ref, init, reduce_tile, finish, **geometry)


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


#: the paged kernel's name when it serves ONE latent arena: a device trace
#: tells it from the two-arena calls by name
LATENT_STEP_KERNEL = "latent_paged_attention"

#: the ``jax.named_scope`` of the expanded form's loops
EXPANDED_SCOPE = "latent_chunk_expanded"

#: the expanded form's kernel, by the name its events carry in a device trace
LATENT_CHUNK_KERNEL = "latent_chunk_attention"


def paged_attention(q, k_arena, v_arena, rows, bias, seqs, length,
                    block_size, sm_scale, interpret=False, kv_heads=0,
                    v_width=0):
    """Blocked paged attention: ``paged_attention_composite`` computed
    from the live blocks alone (see the module docstring). ``rows`` must
    be block-aligned, as the engine's row maps are: every ``block_size``
    positions of a slot name consecutive arena rows from a multiple of
    ``block_size``. Handed ONE arena (``v_arena`` None) the grouped body
    copies a token's row once and reads it as the key of every query head
    and, its first ``v_width`` lanes, as the value. Falls back to the
    composite when Mosaic cannot tile the geometry or the call sits inside
    a manual (shard_map) region."""
    S, L, bs = int(seqs), int(length), int(block_size)
    H = k_arena.shape[-1]
    latent = v_arena is None
    G = 1 if latent else int(kv_heads)
    VW = int(v_width) if latent else H
    arenas = (k_arena,) if latent else (k_arena, v_arena)
    per_slot = -(-L // bs)
    tile = _paged_tile(bs, per_slot, H, k_arena.dtype,
                       _tile_rows(len(arenas)))
    unit = paged_copy_unit(bs, per_slot, H, k_arena.dtype, len(arenas))
    pack, qrows = grouped_layout(H, G, q.shape[-1], k_arena.dtype,
                                 interpret) if G else (1, 1)
    if vma_names(q) or unit == 0 or not pack or (
            not interpret and not (_mosaic_tiles(bs, H, k_arena.dtype)
                                   and VW % 128 == 0)):
        fallback_counter().inc()
        return paged_attention_composite(q, k_arena, v_arena, rows, bias,
                                         S, L, sm_scale, kv_heads=G,
                                         v_width=VW)
    trows = tile * bs
    ntiles = -(-per_slot // tile)
    step_slots = max(n for n in range(1, _STEP_SLOTS + 1) if S % n == 0)
    # what every call over one ``rows`` and ``bias`` derives alike (XLA
    # computes it once a program: tests/test_hlo.py): the block table, its
    # runs, the lengths, each slot's next live slot and the bias in tiles
    with jax.named_scope(TABLES_SCOPE):
        table = (rows.reshape(S, L)[:, ::bs] // bs).astype(jnp.int32)
        run, runs = _copy_runs(table, unit, k_arena, bs)
        bias2 = bias.reshape(S, L)
        lengths = jnp.max(jnp.where(
            bias2 > _CLOSED, jnp.arange(1, L + 1, dtype=jnp.int32), 0),
            axis=-1)
        slot = jnp.arange(S, dtype=jnp.int32)
        nxt = jnp.min(jnp.where(
            (lengths > 0)[None, :] & (slot[None, :] > slot[:, None]),
            slot[None, :], S), axis=-1)
        tiles = jnp.pad(bias2, ((0, 0), (0, ntiles * trows - L)),
                        constant_values=-1e9).reshape(S, ntiles, trows)
    if G:
        lanes = pack * (H // G)
        row = pl.BlockSpec((step_slots, G // pack, qrows, lanes),
                           lambda s, *_: (s, 0, 0, 0))
        out_row = pl.BlockSpec((step_slots, G // pack, qrows, VW // G * pack),
                               lambda s, *_: (s, 0, 0, 0))
        body = functools.partial(_paged_grouped_body, kv_heads=G // pack,
                                 arenas=len(arenas))
        q_in = _pack_heads(q.reshape(S, G, -1, H // G), pack,
                           qrows).astype(k_arena.dtype)
        out_shape = q_in.shape[:-1] + (VW // G * pack,)
    else:
        row = out_row = pl.BlockSpec((step_slots, 1, H),
                                     lambda s, *_: (s, 0, 0))
        body, q_in = _paged_body, q.reshape(S, 1, H)
        out_shape = q_in.shape
    out = pl.pallas_call(
        functools.partial(body, sm_scale=sm_scale, block=bs, tile=tile,
                          unit=unit, per_slot=per_slot,
                          step_slots=step_slots, run=run),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(S // step_slots,),
            in_specs=[
                row,
                pl.BlockSpec((step_slots, ntiles, trows),
                             lambda s, *_: (s, 0, 0)),
            ] + [pl.BlockSpec(memory_space=pl.ANY) for _ in arenas],
            out_specs=out_row,
            scratch_shapes=[pltpu.VMEM((2, unit * bs, H), a.dtype)
                            for a in arenas] + [
                pltpu.SemaphoreType.DMA((len(arenas), 2)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(out_shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention" if not latent else LATENT_STEP_KERNEL,
    )(table.reshape(-1), runs, lengths, nxt, q_in, tiles, *arenas)
    if G:
        out = _unpack_heads(out, pack, q.shape[-1] // H)
    return out.reshape(S, -1)


# ---------------------------------------------------------------------------
# chunk kernel: a prompt chunk's queries over the live blocks of ONE slot
# ---------------------------------------------------------------------------

#: query rows (chunk positions x query rows a position) one grid step of the
#: chunk kernel holds: their running max, sum and accumulator live in VMEM
_CHUNK_QUERY_ROWS = 1024

#: rows of K (and of V) one reduction of the chunk kernel covers
_CHUNK_TILE_ROWS = 256

#: the chunk kernel's scoped-VMEM limit: its query tile's running state is
#: some 10 MiB beside the double-buffered rows, over Mosaic's 16 MiB default
_CHUNK_VMEM_LIMIT = 48 * 1024 * 1024

#: ``C x L`` from which the chunk kernel serves a geometry. Read on a v5e
#: (``tools/check_chunk_attention.py``, a call's device time, kernel |
#: composite, ms; PERF.md section 6, PR 51): at the accepted serving cells'
#: 128 x 2,048 (8 x 4 x 64) 0.079 | 0.066 behind 1,024 rows and 0.100 |
#: 0.060 behind 1,920, (2 x 16 x 128) 0.086 | 0.082 and 0.104 | 0.080, at
#: 128 x 1,024 under a block mask of 4 0.061 | 0.060 and 0.069 | 0.051, at
#: 256 x 1,024 (16 x 1 x 128) 0.065 | 0.047 and 0.065 | 0.044: the
#: composite's dense ``[C, heads, L]`` scores are 8-33 MB there and it is
#: never the slower; at 512 x 16,896 (8 x 4 x 64) 0.12 | 4.75 at the
#: prompt's start, 0.54 | 4.75 behind 4,096 rows, 1.82 | 4.75 behind 16,384
CHUNK_KERNEL_MIN_WORK = 1 << 20


def _chunk_body(bt_ref, run_ref, len_ref, nt_ref, q_ref, hz_ref, k_hbm, v_hbm,
                o_ref, kbuf, vbuf, sem, m_ref, l_ref, acc_ref, *, sm_scale,
                block, tile, per_slot, run, ft_ref=None, lo_ref=None):
    """One tile of a chunk's queries (``q_ref`` ``[pairs, rows, lanes]``:
    ``grouped_layout``'s packing, a position's query rows together) against
    the slot's live rows, a copy tile of ``tile`` blocks at a time, the
    next one in flight while this one is reduced: an online softmax whose
    mask is ``key position < hz_ref`` (``chunk_horizon``, a row each).
    Under a window (``_windowed_chunk_body`` hands ``ft_ref`` and
    ``lo_ref``) the query tile starts at copy tile ``ft_ref[i]``, the one
    that holds its first query's lower edge, and the mask is ``lo_ref <=
    key position < hz_ref``: no tile wholly under that edge is copied."""
    i = pl.program_id(0)
    pairs, rows, lanes = q_ref.shape
    trows = tile * block
    f32 = jnp.float32
    prec = (jax.lax.Precision.HIGHEST if kbuf.dtype == f32
            else jax.lax.Precision.DEFAULT)
    nk = nt_ref[i]
    t0 = 0 if ft_ref is None else ft_ref[i]

    @pl.when(i == 0)
    def _():
        # rows a short tile leaves unwritten carry weight 0 and only have
        # to be finite (``_paged_pipeline``)
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, f32)
    l_ref[...] = jnp.zeros(l_ref.shape, f32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    def start(tile_no, half):
        _start_copies(bt_ref, run_ref, len_ref, (k_hbm, v_hbm), (kbuf, vbuf),
                      sem, 0, tile_no, half, block=block, unit=tile,
                      per_slot=per_slot, run=run)

    @pl.when(nk > t0)
    def _():
        start(t0, 0)

    def step(t, c):
        half = t % 2 if ft_ref is None else (t - t0) % 2

        @pl.when(t + 1 < nk)
        def _():
            start(t + 1, 1 - half)

        _wait_copies(len_ref, (kbuf, vbuf), sem, 0, t, half, block=block,
                     unit=tile)
        at = t * trows + jax.lax.broadcasted_iota(jnp.int32, (1, trows), 1)
        sees = at < hz_ref[...]                              # [rows, trows]
        if lo_ref is not None:
            sees &= at >= lo_ref[...]
        for g in range(pairs):
            k = kbuf[half, :, g * lanes:(g + 1) * lanes]    # [trows, lanes]
            v = vbuf[half, :, g * lanes:(g + 1) * lanes]
            sc = jax.lax.dot_general(
                q_ref[g], k, (((1,), (1,)), ((), ())), precision=prec,
                preferred_element_type=f32)                  # [rows, trows]
            if sm_scale != 1.0:
                sc = sc * sm_scale
            sc = jnp.where(sees, sc, -1e9)
            m = m_ref[g]
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(sc - m_new)
            l_ref[g] = l_ref[g] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[g] = acc_ref[g] * alpha + jnp.dot(
                p.astype(v.dtype), v, precision=prec,
                preferred_element_type=f32)
            m_ref[g] = m_new
        return c

    jax.lax.fori_loop(t0, nk, step, 0)
    # a query that sees nothing (past the real ones) gives zeros
    real = hz_ref[...] > 0
    for g in range(pairs):
        l = jnp.where(real, l_ref[g], 1.0)
        o_ref[g] = jnp.where(real, acc_ref[g] / l, 0.0).astype(o_ref.dtype)


def _windowed_chunk_body(bt_ref, run_ref, len_ref, nt_ref, ft_ref, q_ref,
                         hz_ref, lo_ref, *refs, **static):
    """``_chunk_body`` under a window: a fourth prefetched vector, each
    query tile's first copy tile, and each query row's lower edge beside
    its horizon."""
    _chunk_body(bt_ref, run_ref, len_ref, nt_ref, q_ref, hz_ref, *refs,
                ft_ref=ft_ref, lo_ref=lo_ref, **static)


#: the chunk kernel's name under a window: another executable, and a device
#: trace tells its events from the unwindowed calls' by name (which does
#: NOT contain that kernel's name: a pattern for one misses the other)
WINDOWED_CHUNK_KERNEL = "windowed_chunk_attn"


def chunk_attention(q, k_arena, v_arena, rows, span, block_size, sm_scale,
                    kv_heads, block_len=1, interpret=False, window=0):
    """``chunk_attention_composite`` under ``chunk_mask_bias(span)``
    computed from the slot's LIVE blocks alone: a prompt chunk's ``C``
    queries ``[C, heads * D]`` over the rows that ``rows`` ``[L]`` (block
    aligned, as ``paged_attention``'s) names up to the chunk's last
    horizon, read through the block table in copy tiles of
    ``_CHUNK_TILE_ROWS`` rows, double-buffered, with running max and sum
    over the tiles; float32 accumulation, operands in the arenas' dtype;
    heads grouped and packed as the step kernel's (``grouped_layout``). A
    tile of queries visits the tiles of rows up to ITS last horizon, so a
    chunk's cost follows the prompt so far and not ``L``. A query past the
    real ones reads nothing and gives zeros (the composite averages
    garbage there). Falls back to the composite, counted, where Mosaic
    cannot tile the geometry or inside a manual region; a geometry whose
    ``C x L`` is under ``CHUNK_KERNEL_MIN_WORK`` takes the composite by
    choice (it is the faster one there, as measured), uncounted.

    With ``window`` W (a static size; 0: none, and the kernel is what it
    was) query ``c`` sees ``[max(0, start + c - W + 1), start + c]``
    (``chunk_floor``): a tile of queries starts at the copy tile that holds
    ITS first query's lower edge, so it copies at most ``W + queries - 1``
    rows rounded out to copy tiles, whatever lies behind; the executable is
    another (``WINDOWED_CHUNK_KERNEL``)."""
    C, L, bs, G = q.shape[0], rows.shape[0], int(block_size), int(kv_heads)
    H = k_arena.shape[-1]
    W = int(window)

    def composite():
        return chunk_attention_by_span(q, k_arena, v_arena, rows, span,
                                       sm_scale, G, block_len, W)

    if not interpret and C * L < CHUNK_KERNEL_MIN_WORK:
        return composite()
    pack, _rows = grouped_layout(H, G, q.shape[-1], k_arena.dtype, interpret)
    per = q.shape[-1] // H
    rpp = pack * per                       # query rows a position and pair
    sublanes = 1 if interpret else 8 * (4 // jnp.dtype(k_arena.dtype).itemsize)
    qt = max(1, min(C, _CHUNK_QUERY_ROWS // rpp))
    while C % qt:
        qt -= 1
    per_slot = -(-L // bs)
    tile = max(1, min(per_slot, _CHUNK_TILE_ROWS // bs))
    if vma_names(q) or not pack or (qt * rpp) % sublanes or (
            not interpret and not _mosaic_tiles(bs, H, k_arena.dtype)):
        fallback_counter().inc()
        return composite()
    trows = tile * bs
    lanes = pack * (H // G)
    pairs = G // pack
    table = (rows[::bs] // bs).astype(jnp.int32)
    run, runs = _copy_runs(table, tile, k_arena, bs)
    horizon = chunk_horizon(span, C, L, block_len)
    # rows the copies may touch: up to the chunk's last horizon
    live = jnp.max(horizon).reshape(1)
    ntiles = -(-jnp.max(horizon.reshape(C // qt, qt), axis=-1) // trows)
    hz = jnp.repeat(horizon, rpp).reshape(C * rpp, 1)
    q_in = _pack_heads(q.reshape(C, G, per, H // G), pack, rpp).astype(
        k_arena.dtype)                              # [C, pairs, rpp, lanes]
    q_in = jnp.swapaxes(q_in, 0, 1).reshape(pairs, C * rpp, lanes)
    spec = pl.BlockSpec((pairs, qt * rpp, lanes), lambda i, *_: (0, i, 0))
    edge = pl.BlockSpec((qt * rpp, 1), lambda i, *_: (i, 0))
    prefetch = (table, runs, live, ntiles.astype(jnp.int32))
    operands = (q_in, hz)
    if W:
        floor = chunk_floor(span, C, W)
        # a query tile's first copy tile: its first query's edge is its
        # lowest (a query past the real ones sees nothing either way)
        prefetch += ((floor.reshape(C // qt, qt)[:, 0] // trows).astype(
            jnp.int32),)
        operands += (jnp.repeat(floor, rpp).reshape(C * rpp, 1),)
    out = pl.pallas_call(
        functools.partial(_windowed_chunk_body if W else _chunk_body,
                          sm_scale=sm_scale, block=bs,
                          tile=tile, per_slot=per_slot, run=run),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(C // qt,),
            in_specs=[spec] + [edge] * (len(operands) - 1) + [
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=spec,
            scratch_shapes=[
                pltpu.VMEM((2, trows, H), k_arena.dtype),
                pltpu.VMEM((2, trows, H), v_arena.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((pairs, qt * rpp, 1), jnp.float32),
                pltpu.VMEM((pairs, qt * rpp, 1), jnp.float32),
                pltpu.VMEM((pairs, qt * rpp, lanes), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q_in.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_CHUNK_VMEM_LIMIT),
        interpret=interpret,
        name=WINDOWED_CHUNK_KERNEL if W else "chunk_attention",
    )(*prefetch, *operands, k_arena, v_arena)
    out = jnp.swapaxes(out.reshape(pairs, C, rpp, lanes), 0, 1)
    return _unpack_heads(out, pack, per).reshape(q.shape)


# ---------------------------------------------------------------------------
# latent attention over ONE arena: a token's row is [c (latent) | k^R (rope)
# | zeros], c the normalised compressed K/V and k^R the one rotated key all
# heads share. Head h's key is [c . W_UK,h | k^R] and its value c . W_UV,h.
# The decode step attends ABSORBED (the up-projections moved onto the query
# and the output: q~_h = [q^N_h . W_UK,h^T | q^R_h] against the row as it
# lies, the context (sum_j a_j c_j) . W_UV,h: ``absorb_queries``,
# ``paged_attention`` handed one arena, ``project_values``); a prompt chunk
# attends EXPANDED (every context row up-projected to its heads' keys and
# values first): through ONE kernel, ``latent_chunk_attention`` (the module's
# last section), whose fallback is the same form in XLA's loops,
# ``latent_chunk_expanded``, which is also, dense, the op's definition.
# ---------------------------------------------------------------------------

#: context rows the LOOPS of the expanded form (the kernel's fallback)
#: up-project and reduce at a time, and the queries that stand against them
#: at a time. READ ON THE CHIP (tools/check_latent_attention.py; PERF.md
#: section 6, PR 56), ms a call behind 8,192 rows at 32 heads of 64 + 64 |
#: 128 over a latent of 256: 512 queries 1.12, 1,024 queries in ONE tile
#: 4.57, 2,048 in one 19.8; in tiles of 512 queries 2.23 and 4.69. Up to a
#: [512, heads, 512] tile of scores XLA keeps the loop's body on the chip,
#: past it the scores and the running sums go through HBM. So the queries go
#: 512 at a time, each tile with a loop of its own over the rows ITS last
#: query sees, and each loop up-projects every row it sees again: what the
#: kernel does once a chunk. (The absorbed form through the chunk kernel
#: read 1.96, 4.09 and 8.58 there: 1.6 x the operations a pair; it is not
#: kept for chunks.)
_EXPAND_TILE_ROWS = 512
_EXPAND_QUERY_TILE = 512


def _latent_parts(q, w_uk, rope):
    """``q`` ``[C, heads * (nope + rope)]`` as ``(q^N [C, heads, nope], q^R
    [C, heads, rope])``."""
    heads, nope = w_uk.shape[:2]
    q3 = q.reshape(q.shape[0], heads, nope + int(rope))
    return q3[..., :nope], q3[..., nope:]


def absorb_queries(q, w_uk, rope, width):
    """``q~`` ``[C, heads * width]`` in ``q``'s dtype: head h's ``[q^N_h .
    W_UK,h | q^R_h | zeros]``, what stands against a latent arena's rows of
    ``width`` lanes (``w_uk`` ``[heads, nope, latent]``)."""
    f32 = jnp.float32
    prec = jax.lax.Precision.HIGHEST if q.dtype == f32 else None
    qn, qr = _latent_parts(q, w_uk, rope)
    # (the product leaves in ``q``'s dtype: the accumulation is the
    # chip's own, float32)
    qa = jnp.einsum("chn,hnl->chl", qn, w_uk.astype(q.dtype), precision=prec)
    pad = int(width) - qa.shape[-1] - qr.shape[-1]
    wide = jnp.concatenate(
        [qa, qr, jnp.zeros(qr.shape[:-1] + (pad,), q.dtype)], axis=-1)
    return wide.reshape(q.shape[0], -1)


def project_values(ctx, w_uv, dtype):
    """The absorbed form's context ``[C, heads * latent]`` through each
    head's ``W_UV`` (``[heads, latent, value]``): ``[C, heads * value]``."""
    f32 = jnp.float32
    prec = jax.lax.Precision.HIGHEST if ctx.dtype == f32 else None
    heads, latent, _value = w_uv.shape
    out = jnp.einsum("chl,hlv->chv", ctx.reshape(-1, heads, latent),
                     w_uv.astype(ctx.dtype), precision=prec)
    return out.reshape(ctx.shape[0], -1).astype(dtype)


def _expand_rows(g, w_uk, w_uv, rope):
    """Arena rows ``g`` ``[T, W]`` as every head's ``(k^N [T, heads, nope],
    k^R [T, rope], v [T, heads, value])``, in ``g``'s dtype."""
    f32 = jnp.float32
    prec = jax.lax.Precision.HIGHEST if g.dtype == f32 else None
    latent = w_uk.shape[-1]
    c = g[:, :latent]
    kn = jnp.einsum("tl,hnl->thn", c, w_uk.astype(g.dtype), precision=prec)
    v = jnp.einsum("tl,hlv->thv", c, w_uv.astype(g.dtype), precision=prec)
    return kn, g[:, latent:latent + int(rope)], v


def latent_chunk_expanded(q, w_uk, w_uv, arena, rows, span, sm_scale, rope,
                          tile_rows=None):
    """The EXPANDED form of a prompt chunk's latent attention in XLA's own
    loops (``latent_chunk_attention``'s fallback and, with ``tile_rows``
    None, the op's definition): the slot's rows up-projected ``tile_rows``
    at a time and reduced by an online softmax, ``_EXPAND_QUERY_TILE``
    queries at a time, each tile of queries in a loop whose trip count is
    ITS last horizon, so nothing of a ``[C, L]`` size is made and the work
    follows the prompt so far.
    ``tile_rows`` None: every row and every query at once (the dense
    composite, for the CPU and the tests). Scores and sums float32,
    products in the arena's dtype."""
    f32 = jnp.float32
    C, L = q.shape[0], rows.shape[0]
    prec = jax.lax.Precision.HIGHEST if arena.dtype == f32 else None
    qn, qr = _latent_parts(q.astype(arena.dtype), w_uk, rope)
    horizon = chunk_horizon(span, C, L)                        # [C]
    T = L if tile_rows is None else int(tile_rows)
    Q = C if tile_rows is None or C % _EXPAND_QUERY_TILE else min(
        C, _EXPAND_QUERY_TILE)
    padded = jnp.pad(rows, (0, -L % T))
    heads, value = w_uv.shape[0], w_uv.shape[-1]

    def tile_of_queries(qn, qr, horizon):
        def reduce(t, carry):
            m, l, acc = carry
            at = t * T + jnp.arange(T, dtype=jnp.int32)
            g = jnp.take(arena,
                         jax.lax.dynamic_slice(padded, (t * T,), (T,)),
                         axis=0)
            kn, kr, v = _expand_rows(g, w_uk, w_uv, rope)
            sc = (jnp.einsum("chn,thn->cht", qn, kn,
                             preferred_element_type=f32, precision=prec)
                  + jnp.einsum("chr,tr->cht", qr, kr,
                               preferred_element_type=f32,
                               precision=prec)) * sm_scale
            sc = jnp.where((at[None, :] < horizon[:, None])[:, None, :], sc,
                           -1e9)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(sc - m_new)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.einsum(
                "cht,thv->chv", p.astype(v.dtype), v,
                preferred_element_type=f32, precision=prec)
            return m_new, l, acc

        init = (jnp.full((Q, heads, 1), -jnp.inf, f32),
                jnp.zeros((Q, heads, 1), f32),
                jnp.zeros((Q, heads, value), f32))
        trips = -(-jnp.max(horizon) // T)
        _m, l, acc = jax.lax.fori_loop(0, trips, reduce, init)
        real = (horizon > 0)[:, None, None]
        return jnp.where(real, acc / jnp.where(real, l, 1.0), 0.0)

    with jax.named_scope(EXPANDED_SCOPE):
        out = jnp.concatenate([
            tile_of_queries(qn[c:c + Q], qr[c:c + Q], horizon[c:c + Q])
            for c in range(0, C, Q)])
    return out.reshape(C, -1).astype(q.dtype)


# ---------------------------------------------------------------------------
# the expanded form as a kernel: every row a chunk sees read and up-projected
# once a chunk and a group of heads; keys, values, scores and sums stay in VMEM
# ---------------------------------------------------------------------------

#: heads one call of the latent chunk kernel holds: their queries, their
#: running max, sum and accumulator, and the rows' keys and values for them
_LATENT_CHUNK_HEADS = 8

#: queries of a head that stand against a tile of rows at a time (a sub-tile
#: whose last horizon lies before the tile skips it) and the rows a tile is
_LATENT_CHUNK_QUERY_ROWS = 256
_LATENT_CHUNK_TILE_ROWS = 512

#: what a call's blocks and scratch may take of ``_CHUNK_VMEM_LIMIT``
#: (the rest is Mosaic's own): a chunk of 2,048 queries holds 8 heads in 33
#: MB
_LATENT_CHUNK_VMEM = 40 * 1024 * 1024


def _latent_chunk_body(bt_ref, run_ref, live_ref, first_ref, last_ref, q_ref,
                       hz_ref, wk_ref, wv_ref, arena, o_ref, rowbuf, sem,
                       key_ref, val_ref, m_ref, l_ref, acc_ref, *, sm_scale,
                       block, tile, per_slot, run):
    """One group of heads against the slot's live rows, a copy tile of
    ``tile`` blocks at a time, the next one in flight while this one is
    up-projected and reduced. A tile's rows ``[c | k^R | zeros]`` become,
    ONCE for all the chunk's queries, every head's key ``[k^N_h | k^R]``
    (``[rows, nope + rope]``: the score is one contraction over those lanes)
    and value, kept transposed (``[value, rows]``). Then each sub-tile of
    queries whose last horizon (``last_ref``) reaches the tile takes it head
    by head through an online softmax that runs TRANSPOSED: the queries are
    handed over as ``[heads, sub-tiles, nope + rope, queries]``, the scores
    are ``[rows, queries]``, so a query's running max and sum are lanes of
    a ``[1, queries]`` row, the reductions over a tile's rows run down the
    sublanes, and the accumulator is ``[value, queries]``. A tile is masked
    by ``key position < hz_ref`` only where the sub-tile's first horizon
    (``first_ref``) does not clear it."""
    heads, trows, width = key_ref.shape
    nope, latent = width // 2, wv_ref.shape[2]
    subtiles = q_ref.shape[1]
    f32 = jnp.float32
    dt = rowbuf.dtype
    prec = (jax.lax.Precision.HIGHEST if dt == f32
            else jax.lax.Precision.DEFAULT)
    rowwise = (((1,), (1,)), ((), ()))
    nk = pl.cdiv(live_ref[0], trows)
    # rows a short tile leaves unwritten lie past every horizon and only
    # have to be finite (``_paged_pipeline``)
    rowbuf[...] = jnp.zeros_like(rowbuf)
    m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, f32)
    l_ref[...] = jnp.zeros(l_ref.shape, f32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    def start(tile_no, half):
        _start_copies(bt_ref, run_ref, live_ref, (arena,), (rowbuf,), sem, 0,
                      tile_no, half, block=block, unit=tile,
                      per_slot=per_slot, run=run)

    @pl.when(nk > 0)
    def _():
        start(0, 0)

    def step(t, c):
        half = t % 2

        @pl.when(t + 1 < nk)
        def _():
            start(t + 1, 1 - half)

        _wait_copies(live_ref, (rowbuf,), sem, 0, t, half, block=block,
                     unit=tile)
        c_kv = rowbuf[half, :, :latent]                      # [trows, latent]
        # two heads' k^N side by side fill the lanes of one key: the even
        # head's stays where it is, the odd one's is turned into its place,
        # and the row's k^R, turned past them, fills the rest. (Products
        # leave in the arena's dtype, accumulated in float32: the loops'
        # ``_expand_rows``.)
        shared = pltpu.roll(
            rowbuf[half, :, latent:latent + width].astype(f32), nope, 1)
        low = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) < nope
        for pair in range(heads // 2):
            both = jnp.dot(c_kv, wk_ref[pair], precision=prec,
                           preferred_element_type=f32)       # [trows, width]
            key_ref[2 * pair] = jnp.where(low, both, shared).astype(dt)
            key_ref[2 * pair + 1] = jnp.where(
                low, pltpu.roll(both, nope, 1), shared).astype(dt)
        for h in range(heads):
            val_ref[h] = jax.lax.dot_general(
                wv_ref[h], c_kv, rowwise, precision=prec,
                preferred_element_type=f32).astype(dt)       # [value, trows]
        row0 = t * trows
        at = row0 + jax.lax.broadcasted_iota(jnp.int32, (trows, 1), 0)

        def reduce(s, masked):
            sees = at < hz_ref[s] if masked else None        # [trows, qs]
            for h in range(heads):
                sc = jnp.dot(key_ref[h], q_ref[h, s], precision=prec,
                             preferred_element_type=f32)     # [trows, qs]
                if sm_scale != 1.0:
                    sc = sc * sm_scale
                if masked:
                    sc = jnp.where(sees, sc, -1e9)
                m = m_ref[h, s]                              # [1, qs]
                m_new = jnp.maximum(m, jnp.max(sc, axis=0, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(sc - m_new)
                l_ref[h, s] = l_ref[h, s] * alpha + jnp.sum(
                    p, axis=0, keepdims=True)
                acc_ref[h, s] = acc_ref[h, s] * alpha + jnp.dot(
                    val_ref[h], p.astype(dt), precision=prec,
                    preferred_element_type=f32)              # [value, qs]
                m_ref[h, s] = m_new

        def subtile(s, c):
            reaches = last_ref[s] > row0
            clears = first_ref[s] >= row0 + trows

            @pl.when(reaches & clears)
            def _():
                reduce(s, False)

            @pl.when(reaches & jnp.logical_not(clears))
            def _():
                reduce(s, True)

            return c

        jax.lax.fori_loop(0, subtiles, subtile, 0)
        return c

    jax.lax.fori_loop(0, nk, step, 0)
    # a query that sees nothing (past the real ones) gives zeros
    for s in range(subtiles):
        real = hz_ref[s] > 0                                 # [1, qs]
        for h in range(heads):
            l = jnp.where(real, l_ref[h, s], 1.0)
            o_ref[h, s] = jnp.where(real, acc_ref[h, s] / l,
                                    0.0).astype(o_ref.dtype)


def latent_chunk_attention(q, w_uk, w_uv, arena, rows, span, block_size,
                           sm_scale, rope, interpret=False):
    """``latent_chunk_expanded`` as ONE kernel: a prompt chunk's ``C``
    queries ``[C, heads * (nope + rope)]`` over the rows that ``rows``
    ``[L]`` (block aligned, as ``paged_attention``'s) names up to the
    chunk's last horizon, read through the block table in copy tiles of
    ``_LATENT_CHUNK_TILE_ROWS`` rows, double-buffered. A call serves a
    group of ``_LATENT_CHUNK_HEADS`` heads (as many as ``_LATENT_CHUNK_VMEM``
    takes): it walks the live tiles once, up-projects each ONCE for all the
    chunk's queries and keeps keys, values, scores and the running max, sum
    and accumulator in VMEM (``_latent_chunk_body``); the groups go one
    after the other in a loop of XLA's. The mathematics and the roundings are
    the loops': rows, keys, values and probabilities in the arena's dtype,
    scores and sums float32, a query past the real ones gives zeros. Falls
    back to the loops, counted, where the op names no block size, a head's
    two key parts differ in width, Mosaic cannot tile the geometry, or
    inside a manual region."""
    C, L = q.shape[0], rows.shape[0]
    heads, nope, latent = w_uk.shape
    value, rope, bs = w_uv.shape[-1], int(rope), int(block_size or 0)
    W, dt = arena.shape[-1], arena.dtype
    width = nope + rope
    lanes = 1 if interpret else 128
    qs = max(n for n in range(1, min(C, _LATENT_CHUNK_QUERY_ROWS) + 1)
             if C % n == 0)
    per_slot = -(-L // max(bs, 1))
    tile = max(1, min(per_slot, _LATENT_CHUNK_TILE_ROWS // max(bs, 1)))
    trows = tile * bs
    size = jnp.dtype(dt).itemsize

    def scratch(group):
        """VMEM bytes of a call that holds ``group`` heads: the two
        buffers of its queries, output and weights, the accumulators, the
        row tiles, their keys and values, and a sub-tile's scores."""
        return (2 * C * group * (width + value) * size
                + group * C * (value + 16) * 4
                + 2 * group * (nope + value) * latent * size
                + (2 * W + group * (width + value)) * trows * size
                + 4 * trows * (3 * qs + 2 * width))

    group = max([n for n in range(2, _LATENT_CHUNK_HEADS + 1, 2)
                 if heads % n == 0 and scratch(n) <= _LATENT_CHUNK_VMEM],
                default=0)
    if vma_names(q) or not bs or not group or nope != rope or (
            not interpret and not (
                _mosaic_tiles(bs, W, dt) and qs % lanes == 0
                and width % lanes == 0 and value % lanes == 0
                and latent % lanes == 0)):
        fallback_counter().inc()
        return latent_chunk_expanded(q, w_uk, w_uv, arena, rows, span,
                                     sm_scale, rope,
                                     tile_rows=_EXPAND_TILE_ROWS)
    latent_chunk_counter().inc()
    subtiles, groups = C // qs, heads // group
    table = (rows[::bs] // bs).astype(jnp.int32)
    run, runs = _copy_runs(table, tile, arena, bs)
    horizon = chunk_horizon(span, C, L).reshape(subtiles, qs)
    scalars = (table, runs, jnp.max(horizon).reshape(1), horizon[:, 0],
               jnp.max(horizon, axis=-1))
    whole = lambda *shape: pl.BlockSpec(                     # noqa: E731
        shape, lambda g, *_: (0,) * len(shape))
    call = pl.pallas_call(
        functools.partial(_latent_chunk_body, sm_scale=sm_scale, block=bs,
                          tile=tile, per_slot=per_slot, run=run),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(1,),
            in_specs=[
                whole(group, subtiles, width, qs),
                whole(subtiles, 1, qs),
                whole(group // 2, latent, width),
                whole(group, value, latent),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=whole(group, subtiles, value, qs),
            scratch_shapes=[
                pltpu.VMEM((2, trows, W), dt),
                pltpu.SemaphoreType.DMA((1, 2)),
                pltpu.VMEM((group, trows, width), dt),
                pltpu.VMEM((group, value, trows), dt),
                pltpu.VMEM((group, subtiles, 1, qs), jnp.float32),
                pltpu.VMEM((group, subtiles, 1, qs), jnp.float32),
                pltpu.VMEM((group, subtiles, value, qs), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((group, subtiles, value, qs), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_CHUNK_VMEM_LIMIT),
        interpret=interpret,
        name=LATENT_CHUNK_KERNEL,
    )
    # [groups, group heads, sub-tiles, nope + rope, queries]
    turned = jnp.transpose(
        q.astype(dt).reshape(subtiles, qs, groups, group, width),
        (2, 3, 0, 4, 1))
    # a pair of heads' W_UK side by side: [latent, 2 * nope] a pair
    pairs = jnp.swapaxes(w_uk.astype(dt).reshape(
        groups, group // 2, width, latent), 2, 3)
    values = jnp.swapaxes(w_uv.astype(dt), 1, 2).reshape(
        groups, group, value, latent)
    hz = horizon.reshape(subtiles, 1, qs)

    def of_group(g, out):
        return out.at[g].set(call(*scalars, turned[g], hz, pairs[g],
                                  values[g], arena))

    # ONE call a group of heads, in a loop of XLA's and not in the kernel's
    # grid, for what stands AROUND the call in a chunk program: XLA keeps
    # nothing in VMEM across a loop, and across a lone custom call it kept
    # enough there that the next sub-layer's largest intermediates fell out
    # of it (the chunk launch lost what the kernel won: PERF.md section 6,
    # PR 57)
    out = jnp.zeros((groups, group, subtiles, value, qs), q.dtype)
    out = (of_group(0, out) if groups == 1
           else jax.lax.fori_loop(0, groups, of_group, out))
    return jnp.transpose(out, (2, 4, 0, 1, 3)).reshape(C, heads * value)
