"""Learned sparse attention over the paged arenas: an indexer's scores, the
exact top-k rows a query keeps, and a prompt chunk's attention under the
selection's mask.

A layer with an indexer keeps a THIRD paged arena beside K and V: a token's
one index key (``[R, index_width]``, under the same block table). For a
query with index queries ``qI`` (``heads`` of them), weights ``w`` and the
keys ``kI_s`` of the positions ``s`` it may see,

    ``I(s) = sum_j w_j . relu(qI_j . kI_s)``     (float32)

and the query attends to the ``min(topk, rows it sees)`` positions of
largest ``I``, a tie to the LOWER position (as ``lax.top_k``). Nothing is
approximated and nothing is chosen by blocks: the set is exactly
``lax.top_k``'s (tests/test_keye_vl_serving.py).

**No sort.** ``index_select`` finds the k-th largest score a row by
bisection over the scores' ORDERED bit pattern (``ordered_keys``: a float32
as an int32 whose order is the float's): 32 counting passes fix the
threshold ``T`` (the largest value that at least k scores reach), then the
tie rule by position: of the scores equal to ``T`` the first ``k - #(score >
T)`` by position, found by bisecting the position bound (one counting pass a
bit of the length). The composite (``index_select_composite``) runs the same
passes as XLA reductions; the kernel keeps a tile of rows' keys in VMEM.

**Both forms are masks.** A decode step hands ``paged_attention`` a bias
whose unselected positions are closed (``-1e9``): the kernel reads a slot's
live blocks as it ever did and the selection only masks. Gathering the 2,048
rows themselves would move 1/8 of the bytes at 16k of context, a row (1 KB
of K, 1 KB of V at 4 K/V heads of 128 in bfloat16) a copy descriptor; the
paged kernel moves a RUN of 8 neighbouring 16-row blocks a descriptor where
the block table names them side by side in the arena, and a block a
descriptor elsewhere (``kernels/attention.py _start_copies``: 0.99 against
1.47 ms a layer at 16 slots x 14k over an ascending table, PERF.md section
6, PR 64), which puts the row form's crossing further past this repo's
contexts than PR 63 read it. A prompt chunk attends under the ``[C, L]`` mask
(``masked_chunk_attention``): per (query, row) the mask form costs
operations where the gather form costs bytes, and under ~61k of context the
operations are the cheaper (PERF.md section 4).

Three kernels, each with its composite (the op's definition, the CPU path
and the fallback, counted in ``kernel_fallbacks_total``):

``index_scores``  — ``I`` for a step's slots (a slot a grid step, its live
  index blocks in double-buffered copy tiles, one ``[heads, width] x [width,
  rows]`` product a tile) or a chunk's queries (a tile of queries a grid
  step against the one sequence's rows up to ITS last horizon).
``index_select``  — the selection above over ``[N, L]`` scores, a tile of
  rows a grid step, as an int8 mask.
``masked_chunk_attn`` — ``chunk_attention``'s online softmax over copy
  tiles with the mask's tile copied beside K and V; a K/V head's query
  heads are the rows of one product, each head's positions together, so
  the mask's tile is repeated a head and not a row.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels.attention import (
    _CLOSED, _copy_runs, _mosaic_tiles, _start_copies, _wait_copies,
    chunk_attention_composite, chunk_horizon,
)
from paddle_tpu.kernels.registry import fallback_counter
from paddle_tpu.ops.common import vma_names

__all__ = [
    "ordered_keys", "index_scores_composite", "index_select_composite",
    "index_scores", "index_select", "masked_chunk_attention",
    "masked_chunk_composite", "step_lengths",
    "INDEX_SCORES_KERNEL", "INDEX_SELECT_KERNEL", "MASKED_CHUNK_KERNEL",
]

#: the kernels' names in a device trace (none contains another's, nor
#: ``paged_attention``'s or ``chunk_attention``'s)
INDEX_SCORES_KERNEL = "index_scores"
INDEX_SELECT_KERNEL = "index_select"
MASKED_CHUNK_KERNEL = "masked_chunk_attn"

_INT_MIN = -(1 << 31)

#: index rows one product of ``index_scores`` covers
_SCORE_TILE_ROWS = 512
#: a chunk's queries one grid step of ``index_scores`` holds: their scores
#: over the whole length stay in VMEM until the step ends
_SCORE_QUERY_ROWS = 64
#: rows of scores a grid step of ``index_select`` bisects together (whole
#: int8 tiles of 32 sublanes), and the lanes one counting step covers. READ
#: ON THE CHIP (my chip run, PR 63; 1,024 rows of 32,768 scores, top 2,048,
#: ms a call behind 8,192 | 24,576 rows): 32 rows x 1,024 lanes 1.37 |
#: 3.30, 32 x 2,048 1.20 | 2.57, 64 x 1,024 0.93 | 2.19
_SELECT_ROWS = 64
_SELECT_LANES = 1024
#: the masked chunk kernel's query positions a grid step and rows a copy
#: tile. READ ON THE CHIP (same run; 1,024 queries of 32 heads of 128, ms a
#: call behind 8,192 | 24,576 rows): 128 x 256 3.10 | 8.31, 128 x 512 2.44
#: | 6.39, 256 x 256 2.39 | 6.27, 256 x 512 2.31 | 6.00; the unmasked
#: ``chunk_attention`` reads 2.09 | 5.53 there
_MASKED_QUERY_ROWS = 256
_MASKED_TILE_ROWS = 512
_VMEM_LIMIT = 64 * 1024 * 1024


def ordered_keys(scores):
    """float32 ``scores`` as int32 whose signed order is the floats' (a
    negative float's magnitude bits flipped); ``-0.0`` is taken as ``0.0``
    first, so equal floats have equal keys."""
    s = scores.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(
        jnp.where(s == 0, jnp.float32(0.0), s), jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def step_lengths(bias):
    """A step's lengths ``[S]`` from its additive ``[S, 1, L]`` bias: 1 +
    the last position a slot's row opens, 0 for a slot that does not
    step."""
    s, l = bias.shape[0], bias.shape[-1]
    return jnp.max(jnp.where(
        bias.reshape(s, l) > _CLOSED,
        jnp.arange(1, l + 1, dtype=jnp.int32), 0), axis=-1)


# ---------------------------------------------------------------------------
# composites: THE definition of the ops' math
# ---------------------------------------------------------------------------

def index_scores_composite(q, w, arena, rows, seqs):
    """``I`` ``[N, L]`` (float32) of ``N = seqs x Q`` queries: ``q`` ``[N,
    heads * width]`` index queries, ``w`` ``[N, heads]`` float32 weights,
    ``arena`` ``[R, width]`` index keys, ``rows`` ``[seqs * L]`` each
    sequence's row map (a step: a query a sequence; a chunk: ``seqs`` 1).
    Products in the arena's dtype accumulated in float32, a head at a
    time."""
    g, width = int(seqs), arena.shape[-1]
    n, heads = q.shape[0], q.shape[-1] // width
    l = rows.shape[0] // g
    f32 = jnp.float32
    prec = jax.lax.Precision.HIGHEST if arena.dtype == f32 else None
    keys = jnp.take(arena, rows, axis=0).reshape(g, l, width)
    q4 = q.reshape(g, n // g, heads, width).astype(arena.dtype)
    w3 = w.reshape(g, n // g, heads).astype(f32)
    out = jnp.zeros((g, n // g, l), f32)
    for j in range(heads):
        dots = jnp.einsum("gqd,gld->gql", q4[:, :, j], keys,
                          preferred_element_type=f32, precision=prec)
        out = out + w3[:, :, j, None] * jnp.maximum(dots, 0.0)
    return out.reshape(n, l)


def _bisect(count, k, length):
    """The selection's two bisections over any layout: ``count(pred)`` sums
    ``pred(key, position)`` a row (``[N, 1]`` int32), ``k`` ``[N, 1]`` is
    how many a row keeps. Returns ``(T, P)``: a row keeps ``key > T`` and,
    of ``key == T``, the positions under ``P``."""
    i32 = jnp.int32
    zero = jnp.zeros_like(k)
    t = jnp.where(count(lambda key, _p: key >= zero) >= k, zero,
                  zero + i32(_INT_MIN))

    def raise_t(i, t):
        cand = t + jax.lax.shift_left(i32(1), i32(30) - i)
        return jnp.where(count(lambda key, _p: key >= cand) >= k, cand, t)

    t = jax.lax.fori_loop(0, 31, raise_t, t)
    above = count(lambda key, _p: key > t)
    reach = count(lambda key, _p: key >= t)
    quota = k - above
    bits = int(length).bit_length()

    def raise_p(i, p):
        cand = p + jax.lax.shift_left(i32(1), i32(bits - 1) - i)
        ties = count(lambda key, pos: (key == t) & (pos < cand))
        return jnp.where((cand <= int(length)) & (ties <= quota), cand, p)

    # the tie rule's passes only where some row has more scores at its
    # threshold than it may keep (two equal float32 sums of 16 products are
    # rare; a row of exact zeros under the ReLU is not)
    return t, jax.lax.cond(
        jnp.max(reach - k) > 0,
        lambda: jax.lax.fori_loop(0, bits, raise_p, zero),
        lambda: zero + i32(int(length)))


def index_select_composite(scores, horizon, topk):
    """The rows each query keeps, bool ``[N, L]``: of the positions under
    ``horizon`` ``[N]`` the ``min(topk, horizon)`` of largest ``scores``
    ``[N, L]``, a tie to the lower position: ``lax.top_k``'s set, found
    without a sort (module docstring)."""
    n, l = scores.shape
    pos = jnp.arange(l, dtype=jnp.int32)[None, :]
    hz = horizon.astype(jnp.int32).reshape(n, 1)
    key = jnp.where(pos < hz, ordered_keys(scores), jnp.int32(_INT_MIN))

    def count(pred):
        return jnp.sum(pred(key, pos).astype(jnp.int32), axis=1,
                       keepdims=True)

    t, p = _bisect(count, jnp.minimum(jnp.int32(topk), hz), l)
    return (key > t) | ((key == t) & (pos < p))


def masked_chunk_composite(q, k_arena, v_arena, rows, mask, sm_scale,
                           kv_heads):
    """``chunk_attention_composite`` under the additive bias of the
    selection's ``mask`` ``[C, L]`` (nonzero: the query sees the row)."""
    bias = jnp.where(mask != 0, 0.0, -1e9).astype(jnp.float32)[None]
    return chunk_attention_composite(q, k_arena, v_arena, rows, bias,
                                     sm_scale, kv_heads)


# ---------------------------------------------------------------------------
# index_scores
# ---------------------------------------------------------------------------

def _scores_body(bt_ref, run_ref, len_ref, nt_ref, q_ref, w_ref, arena, o_ref,
                 kbuf, sem, *, block, tile, per_slot, tiles_a_seq, run):
    """The scores of one grid step's queries (a step: ``q_ref`` ``[1,
    heads, width]``, the slot's one query; a chunk: ``[heads, tq, width]``)
    against their sequence's index rows, ``tile`` blocks a product, the
    next tile's copies in flight; ``nt_ref`` says how many tiles this grid
    step's queries see. Rows past them are left as they were: the
    selection masks by horizon."""
    g, i = pl.program_id(0), pl.program_id(1)
    trows = tile * block
    f32 = jnp.float32
    prec = (jax.lax.Precision.HIGHEST if kbuf.dtype == f32
            else jax.lax.Precision.DEFAULT)
    nk = nt_ref[g * tiles_a_seq + i]
    step = q_ref.shape[0] == 1

    def start(t, half):
        _start_copies(bt_ref, run_ref, len_ref, (arena,), (kbuf,), sem, g, t,
                      half, block=block, unit=tile, per_slot=per_slot,
                      run=run)

    @pl.when(nk > 0)
    def _():
        start(0, 0)

    def one(t, c):
        half = t % 2

        @pl.when(t + 1 < nk)
        def _():
            start(t + 1, 1 - half)

        _wait_copies(len_ref, (kbuf,), sem, g, t, half, block=block,
                     unit=tile)
        keys = kbuf[half]                                 # [trows, width]
        at = pl.ds(pl.multiple_of(t * trows, trows), trows)
        if step:
            dots = jax.lax.dot_general(
                q_ref[0], keys, (((1,), (1,)), ((), ())), precision=prec,
                preferred_element_type=f32)               # [heads, trows]
            o_ref[0, :, at] = jnp.sum(
                w_ref[0] * jnp.maximum(dots, 0.0), axis=0, keepdims=True)
        else:
            acc = jnp.zeros((q_ref.shape[1], trows), f32)
            for j in range(q_ref.shape[0]):
                dots = jax.lax.dot_general(
                    q_ref[j], keys, (((1,), (1,)), ((), ())),
                    precision=prec, preferred_element_type=f32)
                acc = acc + w_ref[j] * jnp.maximum(dots, 0.0)
            o_ref[0, :, at] = acc
        return c

    jax.lax.fori_loop(0, nk, one, 0)


def index_scores(q, w, arena, rows, seqs, block_size, horizon,
                 interpret=False):
    """``index_scores_composite`` from each sequence's LIVE index blocks
    alone, as ``[N, Lp]`` (``Lp``: ``L`` rounded up to whole copy tiles):
    ``horizon`` ``[N]`` bounds the rows a query sees, and a position at or
    past a query's tile bound holds whatever the buffer did (the selection
    never reads it). Falls back to the composite, counted, where Mosaic
    cannot tile the geometry or inside a manual region."""
    G, bs = int(seqs), int(block_size)
    width = arena.shape[-1]
    N, heads = q.shape[0], q.shape[-1] // width
    Q, L = N // G, rows.shape[0] // G
    per_slot = -(-L // bs)
    tile = max(1, min(per_slot, _SCORE_TILE_ROWS // bs))
    trows = tile * bs
    ntiles = -(-per_slot // tile)
    Lp = ntiles * trows
    tq = 1 if Q == 1 else max(1, min(Q, _SCORE_QUERY_ROWS))
    while Q % tq:
        tq -= 1
    sub = 1 if interpret else 8
    if vma_names(q) or (Q > 1 and tq % sub) or (
            not interpret and not (_mosaic_tiles(bs, width, arena.dtype)
                                   and trows % 128 == 0)):
        fallback_counter().inc()
        return index_scores_composite(q, w, arena, rows, G)
    i32 = jnp.int32
    table = (rows.reshape(G, L)[:, ::bs] // bs).astype(i32)
    run, runs = _copy_runs(table, tile, arena, bs)
    hz = horizon.astype(i32).reshape(G, Q // tq, tq)
    bound = jnp.max(hz, axis=-1)                          # [G, Q / tq]
    live = jnp.max(bound, axis=-1)                        # [G]
    nt = -(-bound // trows)
    q4 = q.reshape(G, Q, heads, width).astype(arena.dtype)
    w3 = w.reshape(G, Q, heads).astype(jnp.float32)
    if Q == 1:
        q_in, w_in = q4.reshape(G, heads, width), w3.reshape(G, heads, 1)
        q_spec = pl.BlockSpec((1, heads, width), lambda g, i, *_: (g, 0, 0))
        w_spec = pl.BlockSpec((1, heads, 1), lambda g, i, *_: (g, 0, 0))
    else:
        # a chunk is ONE sequence's: head-major, a head's queries together
        q_in = jnp.swapaxes(q4[0], 0, 1)                  # [heads, Q, width]
        w_in = jnp.swapaxes(w3[0], 0, 1)[..., None]       # [heads, Q, 1]
        q_spec = pl.BlockSpec((heads, tq, width), lambda g, i, *_: (0, i, 0))
        w_spec = pl.BlockSpec((heads, tq, 1), lambda g, i, *_: (0, i, 0))
    out = pl.pallas_call(
        functools.partial(_scores_body, block=bs, tile=tile,
                          per_slot=per_slot, tiles_a_seq=Q // tq, run=run),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(G, Q // tq),
            in_specs=[q_spec, w_spec, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, tq, Lp), lambda g, i, *_: (g, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, trows, width), arena.dtype),
                pltpu.SemaphoreType.DMA((1, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((G, Q, Lp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=INDEX_SCORES_KERNEL,
    )(table.reshape(-1), runs, live, nt.reshape(-1).astype(i32), q_in, w_in,
      arena)
    return out.reshape(N, Lp)


# ---------------------------------------------------------------------------
# index_select
# ---------------------------------------------------------------------------

def _select_body(nl_ref, s_ref, hz_ref, o_ref, key_ref, *, topk, lanes,
                 length):
    """``index_select_composite`` for a tile of rows: their keys made once
    into ``key_ref``, every counting pass a walk over the ``nl_ref[i]``
    lane chunks that hold a live position."""
    i = pl.program_id(0)
    rows = s_ref.shape[0]
    i32 = jnp.int32
    hz = hz_ref[...]                                      # [rows, 1]
    nl = nl_ref[i]

    def chunk(c):
        return pl.ds(pl.multiple_of(c * lanes, lanes), lanes)

    def positions(c):
        return c * lanes + jax.lax.broadcasted_iota(i32, (1, lanes), 1)

    def make(c, carry):
        key_ref[:, chunk(c)] = jnp.where(
            positions(c) < hz, ordered_keys(s_ref[:, chunk(c)]),
            i32(_INT_MIN))
        return carry

    jax.lax.fori_loop(0, nl, make, 0)

    def count(pred):
        def add(c, acc):
            return acc + pred(key_ref[:, chunk(c)], positions(c)).astype(i32)

        acc = jax.lax.fori_loop(0, nl, add, jnp.zeros((rows, lanes), i32))
        return jnp.sum(acc, axis=1, keepdims=True)

    t, p = _bisect(count, jnp.minimum(i32(topk), hz), length)

    def write(c, carry):
        key = key_ref[:, chunk(c)]
        keep = (key > t) | ((key == t) & (positions(c) < p))
        o_ref[:, chunk(c)] = keep.astype(i32).astype(o_ref.dtype)
        return carry

    o_ref[...] = jnp.zeros_like(o_ref)
    jax.lax.fori_loop(0, nl, write, 0)


def index_select(scores, horizon, topk, interpret=False):
    """``index_select_composite`` as an int8 mask ``[N, Lp]`` (1: kept),
    ``scores`` ``[N, Lp]`` as ``index_scores`` leaves them. Falls back to
    the composite, counted, where the rows or the lanes are no whole
    tiles."""
    N, Lp = scores.shape
    lanes = min(_SELECT_LANES, Lp)
    rows = N if N <= _SELECT_ROWS else _SELECT_ROWS
    if vma_names(scores) or N % rows or Lp % lanes or (
            not interpret and (lanes % 128 or rows % 8)):
        fallback_counter().inc()
        return index_select_composite(scores, horizon, topk).astype(jnp.int8)
    hz = horizon.astype(jnp.int32).reshape(N, 1)
    nl = -(-jnp.max(hz.reshape(N // rows, rows), axis=-1) // lanes)
    return pl.pallas_call(
        functools.partial(_select_body, topk=int(topk), lanes=lanes,
                          length=Lp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N // rows,),
            in_specs=[pl.BlockSpec((rows, Lp), lambda i, *_: (i, 0)),
                      pl.BlockSpec((rows, 1), lambda i, *_: (i, 0))],
            out_specs=pl.BlockSpec((rows, Lp), lambda i, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((rows, Lp), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((N, Lp), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=INDEX_SELECT_KERNEL,
    )(nl.astype(jnp.int32), scores, hz)


# ---------------------------------------------------------------------------
# masked chunk attention
# ---------------------------------------------------------------------------

def _masked_chunk_body(bt_ref, run_ref, len_ref, nt_ref, q_ref, mask, k_hbm,
                       v_hbm, o_ref, kbuf, vbuf, mbuf, sem, m_ref, l_ref,
                       acc_ref, *, sm_scale, block, tile, per_slot, run):
    """One tile of a chunk's queries (``q_ref`` ``[G, per, qt, D]``: a K/V
    head's ``per`` query heads, each head's ``qt`` positions together)
    against the slot's live rows, a copy tile at a time with the mask's
    ``[qt, rows]`` tile beside it: ``chunk_attention``'s online softmax, a
    K/V head's ``per x qt`` query rows one product, the mask's tile
    repeated a head."""
    i = pl.program_id(0)
    groups, per, qt, d = q_ref.shape
    trows = tile * block
    f32 = jnp.float32
    prec = (jax.lax.Precision.HIGHEST if kbuf.dtype == f32
            else jax.lax.Precision.DEFAULT)
    nk = nt_ref[i]

    @pl.when(i == 0)
    def _():
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, f32)
    l_ref[...] = jnp.zeros(l_ref.shape, f32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    def mask_copy(t, half):
        return pltpu.make_async_copy(
            mask.at[pl.ds(pl.multiple_of(i * qt, qt), qt),
                    pl.ds(pl.multiple_of(t * trows, trows), trows)],
            mbuf.at[half], sem.at[2, half])

    def start(t, half):
        _start_copies(bt_ref, run_ref, len_ref, (k_hbm, v_hbm), (kbuf, vbuf),
                      sem, 0, t, half, block=block, unit=tile,
                      per_slot=per_slot, run=run)
        mask_copy(t, half).start()

    @pl.when(nk > 0)
    def _():
        start(0, 0)

    def step(t, c):
        half = t % 2

        @pl.when(t + 1 < nk)
        def _():
            start(t + 1, 1 - half)

        _wait_copies(len_ref, (kbuf, vbuf), sem, 0, t, half, block=block,
                     unit=tile)
        mask_copy(t, half).wait()
        one = mbuf[half].astype(jnp.int32) != 0              # [qt, trows]
        sees = jnp.concatenate([one] * per, axis=0)          # [per * qt, ..]
        for g in range(groups):
            k = kbuf[half, :, g * d:(g + 1) * d]             # [trows, D]
            v = vbuf[half, :, g * d:(g + 1) * d]
            sc = jax.lax.dot_general(
                q_ref[g].reshape(per * qt, d), k, (((1,), (1,)), ((), ())),
                precision=prec, preferred_element_type=f32)
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

    jax.lax.fori_loop(0, nk, step, 0)
    for g in range(groups):
        # a query that kept nothing (past the real ones) gives zeros
        real = m_ref[g] > _CLOSED
        l = jnp.where(real, l_ref[g], 1.0)
        o_ref[g] = jnp.where(real, acc_ref[g] / l, 0.0).astype(
            o_ref.dtype).reshape(per, qt, d)


def masked_chunk_attention(q, k_arena, v_arena, rows, span, mask, block_size,
                           sm_scale, kv_heads, interpret=False):
    """``masked_chunk_composite`` from the slot's LIVE blocks alone: a
    prompt chunk's ``C`` queries ``[C, heads * D]`` over the rows ``rows``
    ``[L]`` names up to the chunk's last horizon (``span``), each query
    under its row of ``mask`` ``[C, >= L]`` int8 (``index_select``'s). A
    query that kept nothing gives zeros. Falls back to the composite,
    counted, where Mosaic cannot tile the geometry or inside a manual
    region."""
    C, L, bs, G = q.shape[0], rows.shape[0], int(block_size), int(kv_heads)
    H = k_arena.shape[-1]
    D = H // G
    per = q.shape[-1] // H
    qt = max(1, min(C, _MASKED_QUERY_ROWS))
    while C % qt:
        qt -= 1
    per_slot = -(-L // bs)
    tile = max(1, min(per_slot, _MASKED_TILE_ROWS // bs))
    trows = tile * bs
    Lp = -(-per_slot // tile) * trows
    if vma_names(q) or q.shape[-1] % H or (not interpret and not (
            _mosaic_tiles(bs, H, k_arena.dtype) and D % 128 == 0
            and qt % 32 == 0 and trows % 128 == 0)):
        fallback_counter().inc()
        return masked_chunk_composite(q, k_arena, v_arena, rows,
                                      mask[:, :L], sm_scale, G)
    if mask.shape[1] < Lp:
        mask = jnp.pad(mask, ((0, 0), (0, Lp - mask.shape[1])))
    table = (rows[::bs] // bs).astype(jnp.int32)
    run, runs = _copy_runs(table, tile, k_arena, bs)
    horizon = chunk_horizon(span, C, L)
    live = jnp.max(horizon).reshape(1)
    ntiles = -(-jnp.max(horizon.reshape(C // qt, qt), axis=-1) // trows)
    # a K/V head's query heads together, a head's positions together
    q_in = jnp.transpose(q.reshape(C, G, per, D), (1, 2, 0, 3)).astype(
        k_arena.dtype)
    spec = pl.BlockSpec((G, per, qt, D), lambda i, *_: (0, 0, i, 0))
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_masked_chunk_body, sm_scale=sm_scale, block=bs,
                          tile=tile, per_slot=per_slot, run=run),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(C // qt,),
            in_specs=[spec, any_, any_, any_],
            out_specs=spec,
            scratch_shapes=[
                pltpu.VMEM((2, trows, H), k_arena.dtype),
                pltpu.VMEM((2, trows, H), v_arena.dtype),
                pltpu.VMEM((2, qt, trows), mask.dtype),
                pltpu.SemaphoreType.DMA((3, 2)),
                pltpu.VMEM((G, per * qt, 1), jnp.float32),
                pltpu.VMEM((G, per * qt, 1), jnp.float32),
                pltpu.VMEM((G, per * qt, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q_in.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=MASKED_CHUNK_KERNEL,
    )(table, runs, live, ntiles.astype(jnp.int32), q_in, mask, k_arena,
      v_arena)
    return jnp.transpose(out, (2, 0, 1, 3)).reshape(q.shape)
