"""Routed experts of which this chip holds a share (expert parallelism,
seen from one rank).

``route`` scores every token against ALL the router's experts (sigmoid
scores, or a softmax over them all; a selection bias that moves the choice
and not the weight, top-k,
the chosen scores normalised over all k and scaled). ``held_weights`` keeps
of each token's k weights those of the ``held`` experts that live here
(ids ``offset .. offset + held - 1``): a ``[T, held]`` matrix, mostly zeros.
The layer's result here is the held experts' part alone,
``sum_e c[t, e] * W_down,e . act_e(x_t)``; what the absent experts would add
is left out (it is another rank's to compute), and nothing stands in for
them. An expert's activation follows its operands: handed a gate matrix it
is gated, ``silu(W_gate,e . x) * (W_up,e . x)`` (SwiGLU, three matrices);
handed none, ``relu(W_up,e . x)^2`` (two).

``moe_grouped`` is the prompt chunk's GROUPED product (below): the
(token, held expert) pairs sorted by expert, each expert's rows padded to
whole row tiles, and one product a row tile over that expert's matrices:
the operations follow the pairs the routing made, not ``tokens x held``.
The sorted rows exist in VMEM alone, a row tile at a time: the kernel keeps
the call's tokens and their float32 sums resident, picks a tile's rows out
of the one and adds its pairs into the other. ``takes_grouped`` is THE rule
of when it runs.

``experts_composite`` computes that part densely (every held expert over
every token, weighted by ``c``): the reference lowering, the CPU path, the
``off`` path, and the prompt chunk's path. ``moe_experts`` is the decode
step's Pallas kernel: the grid's expert axis is as long as the step's
work, a row for each held expert that some token chose (their ids and
their count are scalar prefetches, the count the grid's traced extent)
and no row for any other, and a row streams its expert's matrices through
VMEM in tiles of the hidden size (``hidden_tile``: the hidden size's
own); an expert no token chose is never read, and costs no grid step.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels.registry import fallback_counter
from paddle_tpu.ops.common import vma_names

__all__ = ["route", "held_weights", "routing_counts", "experts_composite",
           "moe_experts", "hidden_tile", "grouped_rows", "takes_grouped",
           "group_pairs", "moe_grouped", "grouped_counts", "ROW_TILE"]

_HI = jax.lax.Precision.HIGHEST
#: bytes of VMEM the kernel's weight blocks may take, both buffers of every
#: matrix: what is left of Mosaic's 16 MiB is the tokens, the output and
#: the two ``[T, F]`` scratches
_WEIGHT_BLOCKS = 8 * 1024 * 1024
#: tokens in one sublane tile of bfloat16: Mosaic takes the kernel's token
#: blocks in whole ones
_TOKEN_TILE = 16


#: how a router turns its products into an expert's score
_SCORES = {"sigmoid": jax.nn.sigmoid,
           "softmax": lambda z: jax.nn.softmax(z, axis=-1)}


def route(x, gate_w, select_bias, k, scale, normalize, eps=1e-20,
          score="sigmoid"):
    """``(expert ids [T, k], weights [T, k])`` over all of ``gate_w``'s
    experts (``gate_w`` ``[E, H]`` and ``select_bias`` ``[E]`` float32):
    scores (``score``: each expert's ``sigmoid``, or a ``softmax`` over all
    the experts) and weights in float32; ``eps`` stands under the sum that
    the chosen scores are normalised by."""
    s = _SCORES[score](jnp.matmul(x.astype(jnp.float32), gate_w.T,
                                  precision=_HI))
    _, idx = jax.lax.top_k(s + select_bias, k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if normalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
    return idx, w * scale


def held_weights(idx, w, mask, offset, held):
    """``c [T, held]``: token ``t``'s weight for held expert ``e``, 0 where
    it did not choose it or ``mask[t]`` is false."""
    local = idx - offset                                       # [T, k]
    hit = (local[:, :, None] == jnp.arange(held)[None, None, :])
    c = jnp.sum(jnp.where(hit, w[:, :, None], 0.0), axis=1)
    return jnp.where(mask[:, None], c, 0.0)


def routing_counts(idx, c, mask):
    """int32 ``[4]``: assignments (masked tokens x k), those that landed on
    a held expert, held experts with at least one token, and the busiest
    held expert's tokens (``c`` is ``held_weights``'s: a chosen expert's
    weight is never 0)."""
    chosen = (c != 0.0).astype(jnp.int32)
    per_expert = jnp.sum(chosen, axis=0)
    return jnp.stack([
        jnp.sum(mask.astype(jnp.int32)) * idx.shape[1],
        jnp.sum(per_expert),
        jnp.sum((per_expert > 0).astype(jnp.int32)),
        jnp.max(per_expert)])


def _act(h, g, c):
    """An expert's activation times its tokens' weights: gated by ``g``
    (``silu(g) * h``) where there is one, squared relu where ``g`` is
    None."""
    if g is None:
        return jnp.square(jnp.maximum(h, 0.0)) * c
    return jax.nn.silu(g) * h * c


def experts_composite(x, c, w_up, w_down, w_gate=None):
    """``x`` ``[T, H]``, ``c`` ``[T, E]`` float32, ``w_up``, ``w_down`` and
    ``w_gate`` (or None: module docstring) all ``[E, F, H]`` (the hidden
    size is the minor dimension of each: a minor dimension that is no
    multiple of the 128 lanes, as an expert width may be, costs the kernel
    a copy of the whole array at every call); float32 ``[T, H]``. Products
    in the weights' dtype, accumulated in float32."""
    f32 = jnp.float32
    prec = _HI if w_up.dtype == f32 else None

    def into(w):
        return jnp.einsum("th,efh->etf", x.astype(w.dtype), w,
                          preferred_element_type=f32, precision=prec)

    a = _act(into(w_up), None if w_gate is None else into(w_gate),
             c.T[:, :, None]).astype(w_down.dtype)
    return jnp.einsum("etf,efh->th", a, w_down, preferred_element_type=f32,
                      precision=prec)


def _kernel_precision(dtype):
    """A process-wide ``jax_default_matmul_precision`` reaches inside a
    Pallas body, and Mosaic refuses a float32 precision on bfloat16
    operands: pin the operands' own."""
    return _HI if dtype == jnp.float32 else jax.lax.Precision.DEFAULT


def hidden_tile(tokens, hidden, ffn, dtype, matrices, interpret=False):
    """Columns of the hidden size one grid step of the kernel covers for
    ``matrices`` (2, or 3 with a gate) ``[ffn, hidden]`` matrices an
    expert: the widest whole number of 128-lane tiles that divides
    ``hidden`` and keeps both buffers of every matrix's block within
    ``_WEIGHT_BLOCKS``. 0 says that Mosaic cannot take the geometry
    (bfloat16, tokens in whole sublane tiles of it, which ``moe_experts``
    pads a step's tokens up to, a hidden size in whole lane tiles) and the
    composite runs: THE eligibility test, by geometry.
    ``interpret`` has no Mosaic to please: any dtype and token count, and
    a hidden size that no lane tile divides is one tile."""
    size = jnp.dtype(dtype).itemsize
    room = _WEIGHT_BLOCKS // (2 * matrices * ffn * size)
    fits = [t for t in range(128, hidden + 1, 128)
            if hidden % t == 0 and t <= room]
    if interpret:
        return max(fits, default=hidden)
    if jnp.dtype(dtype) != jnp.bfloat16 or tokens % _TOKEN_TILE:
        return 0
    return max(fits, default=0)


def _experts_body(eid_ref, n_ref, x_ref, c_ref, *refs, tiles, gated):
    """``refs``: the up (and, ``gated``, the gate) matrix's tile, the down
    matrix's, the output, then the scratches: the up product (and the
    gate's) ``[T, F]`` float32 and the activation in the weights' dtype.
    Every grid row (a touched expert) adds into the ONE resident output;
    the one row of a step that touched none adds nothing."""
    ins, (o_ref, *acc, a_ref) = refs[:2 + gated], refs[2 + gated:]
    firsts, down_ref = ins[:-1], ins[-1]
    g, j = pl.program_id(0), pl.program_id(1)
    prec = _kernel_precision(down_ref.dtype)

    @pl.when((g == 0) & (j == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_ref[0] > 0)
    def _():
        @pl.when(j == 0)
        def _():
            for h_ref in acc:
                h_ref[...] = jnp.zeros_like(h_ref)

        @pl.when(j < tiles)
        def _():
            xt = x_ref[jnp.minimum(j, tiles - 1)]
            for w_ref, h_ref in zip(firsts, acc):
                h_ref[...] += jax.lax.dot_general(
                    xt, w_ref[...], (((1,), (1,)), ((), ())), precision=prec,
                    preferred_element_type=jnp.float32)

        @pl.when(j == tiles)
        def _():
            a_ref[...] = _act(
                acc[0][...], acc[1][...] if gated else None,
                c_ref[...]).astype(a_ref.dtype)

        @pl.when(j >= tiles)
        def _():
            jj = jnp.maximum(j - tiles, 0)
            out = jnp.dot(a_ref[...], down_ref[...], precision=prec,
                          preferred_element_type=jnp.float32)
            o_ref[jj] += out


def moe_experts(x, c, w_up, w_down, w_gate=None, interpret=False):
    """``experts_composite`` reading the weights of the held experts that
    some token chose, and no others. Grid ``(touched, 2 * tiles)``, its
    first extent TRACED: the count of held experts with a token, so the
    walk ends at the last of them (two of 32 held: 2 rows, not 32). For
    one expert, ``tiles`` steps accumulate ``x . W_up`` (and ``x .
    W_gate``, where there is a gate) over tiles of the hidden size into
    ``[T, F]`` scratches, then ``tiles`` steps write the down product's
    columns, tile by tile, into the resident output. A step that touched
    no held expert still walks ONE row (the output is zeroed at the first
    grid step): its block indices stay at one tile and its body adds
    nothing. A token count that is no whole number of sublane tiles (24
    slots) is padded up to one with tokens that choose no expert; their
    rows are cut off the result."""
    real, hidden = x.shape
    held, ffn, _ = w_up.shape
    firsts = [w_up] if w_gate is None else [w_up, w_gate]
    pad = -real % _TOKEN_TILE
    t = real + pad
    tile = hidden_tile(t, hidden, ffn, w_up.dtype, len(firsts) + 1,
                       interpret)
    if vma_names(x) or not tile:
        fallback_counter().inc()
        return experts_composite(x, c, w_up, w_down, w_gate)
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        c = jnp.pad(c, ((0, pad), (0, 0)))
    tiles = hidden // tile
    touched = jnp.any(c != 0.0, axis=0)                        # [E]
    order = jnp.argsort(jnp.logical_not(touched),
                        stable=True).astype(jnp.int32)
    count = jnp.sum(touched.astype(jnp.int32))
    xs = jnp.swapaxes(x.astype(w_up.dtype).reshape(t, tiles, tile), 0, 1)
    cols = c.astype(jnp.float32).T[:, :, None]                 # [E, T, 1]

    def step(j, n_ref):
        return jnp.where(n_ref[0] > 0, j, 2 * tiles - 1)

    first = pl.BlockSpec(
        (None, ffn, tile), lambda g, j, e, n: (
            e[g], 0, jnp.minimum(step(j, n), tiles - 1)))
    out = pl.pallas_call(
        functools.partial(_experts_body, tiles=tiles,
                          gated=w_gate is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(jnp.maximum(count, 1), 2 * tiles),
            in_specs=[
                pl.BlockSpec((tiles, t, tile), lambda g, j, e, n: (0, 0, 0)),
                pl.BlockSpec((None, t, 1), lambda g, j, e, n: (e[g], 0, 0)),
                *[first for _ in firsts],
                pl.BlockSpec(
                    (None, ffn, tile), lambda g, j, e, n: (
                        e[g], 0, jnp.maximum(step(j, n) - tiles, 0))),
            ],
            out_specs=pl.BlockSpec((tiles, t, tile),
                                   lambda g, j, e, n: (0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((t, ffn), jnp.float32)
                            for _ in firsts]
            + [pltpu.VMEM((t, ffn), w_down.dtype)],
        ),
        out_shape=jax.ShapeDtypeStruct((tiles, t, tile), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="moe_experts",
    )(order, count.reshape(1), xs, cols, *firsts, w_down)
    out = jnp.swapaxes(out, 0, 1).reshape(t, hidden)
    return out[:real] if pad else out


# ---------------------------------------------------------------------------
# the grouped product: a prompt chunk's (token, held expert) pairs by expert
# ---------------------------------------------------------------------------

#: rows of one expert a grid step of the grouped kernel multiplies: an
#: expert's pairs are padded to whole tiles of this many
ROW_TILE = 128

#: the grouped kernel's scoped-VMEM limit: beside a row tile's two ``[rows,
#: F]`` float32 products, its activation and both buffers of three weight
#: blocks (past Mosaic's 16 MiB default at an expert width of 2,048 and a
#: hidden size of 4,096) the call keeps its tokens and their float32 sums
#: RESIDENT (``_grouped_resident``: 24 MiB at 1,024 tokens of 4,096).
#: (Weight tiles of 1,024 lanes in place of ``hidden_tile``'s 256 read the
#: same time on the chip: 2.11 ms for 2.07 at 512 tokens, PR 56.)
_GROUPED_VMEM_LIMIT = 64 * 1024 * 1024
#: what of that limit is left to Mosaic's own scratch
_GROUPED_VMEM_SPARE = 4 * 1024 * 1024


def grouped_rows(tokens, k, held, row_tile=ROW_TILE):
    """Rows of the grouped product's layout (pairs sorted by expert: row
    INDICES, no buffer of rows is made of them): every pair the routing
    can make (``tokens x min(k, held)``: dropless whatever the imbalance)
    in whole row tiles, and a ragged last tile for each held expert."""
    pairs = tokens * min(k, held)
    return (-(-pairs // row_tile) + held) * row_tile


#: how many times fewer rows than the dense product the grouped one has to
#: multiply before it is taken. READ ON THE CHIP (PR 56,
#: tools/check_latent_attention.py: 16 held gated experts of width 2,048 at
#: hidden 4,096, bfloat16; ms grouped | dense): a quarter of the dense rows
#: (512 tokens, 2,048 of 8,192) 2.07 | 2.30, an eighth 2.51 | 4.56, a
#: sixteenth 4.73 | 9.08, and with every token on one expert (2,432 of
#: 8,192 rows, 3.4 x fewer) 2.34 | 2.27: the grouped product streams every
#: touched expert's matrices a row tile at a time and never runs under
#: ~2 ms there, while XLA's dense einsum runs at 91 % of the chip's peak
_GROUPED_FEWER_ROWS = 4


def takes_grouped(tokens, k, held, router, row_tile=ROW_TILE):
    """THE rule of whether a routed-experts layer over ``tokens`` tokens
    runs the grouped product or the dense one, by the rows each multiplies:
    the dense product ``tokens x held``, the grouped one, under a balanced
    routing, each held expert's ``tokens x k / router`` pairs in whole row
    tiles. Grouped from ``1 / _GROUPED_FEWER_ROWS`` of the dense rows down:
    where the chip read it the faster one."""
    per_expert = -(-tokens * k // router)
    expected = held * -(-per_expert // row_tile) * row_tile
    return _GROUPED_FEWER_ROWS * expected <= tokens * held


def _held_pairs(idx, mask, offset, held):
    """``(valid [T, k], hit [T, k, held])``: which of a token's choices is a
    pair (a held expert, ``mask[t]`` true), and on which held expert."""
    local = idx - offset
    valid = (local >= 0) & (local < held) & mask[:, None]
    return valid, valid[:, :, None] & (
        local[:, :, None] == jnp.arange(held)[None, None, :])


def group_pairs(idx, w, mask, offset, held, rows, row_tile=ROW_TILE):
    """The routing's ``[T, k]`` choices as the grouped product's layout.
    A pair is a token and one of its chosen experts that is held here
    (``offset .. offset + held - 1``) with ``mask[t]`` true. Pairs are
    grouped by expert, in token order within one, and expert ``e``'s group
    starts at a multiple of ``row_tile``. Returns ``(dest [T, k]: a pair's
    row in the layout, ``rows`` where there is no pair; token
    [rows]: the token each row reads (0 for padding); weight [rows]: its
    routing weight (0.0 for padding); tile_expert [rows / row_tile]: the
    expert whose matrices a row tile multiplies; tile_pairs [rows /
    row_tile]: the pairs a row tile holds, its first rows (0 past the last
    used tile); tiles: how many row tiles hold a pair)``."""
    T, k = idx.shape
    valid, hit = _held_pairs(idx, mask, offset, held)
    flat = hit.reshape(T * k, held).astype(jnp.int32)
    before = jnp.cumsum(flat, axis=0) - flat        # pairs of e before this
    counts = jnp.sum(flat, axis=0)                              # [E]
    tiles_of = -(-counts // row_tile)
    ends = jnp.cumsum(tiles_of)
    start = (ends - tiles_of) * row_tile                        # [E]
    dest = jnp.sum(flat * (start[None, :] + before), axis=-1)
    dest = jnp.where(valid.reshape(-1), dest, rows)             # [T * k]
    token = jnp.zeros((rows,), jnp.int32).at[dest].set(
        jnp.repeat(jnp.arange(T, dtype=jnp.int32), k), mode="drop")
    weight = jnp.zeros((rows,), jnp.float32).at[dest].set(
        w.reshape(-1).astype(jnp.float32), mode="drop")
    tiles = ends[-1]
    # a tile past the last used one names the last used tile's expert: its
    # block index does not move, so nothing is copied for it
    at = jnp.minimum(jnp.arange(rows // row_tile), jnp.maximum(tiles - 1, 0))
    # (compared against every end at once: the default method is a loop,
    # and the chunk program's only loops are its attention's)
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, at, side="right", method="compare_all"),
        held - 1)
    first_row = jnp.arange(rows // row_tile) * row_tile
    tile_pairs = jnp.where(
        first_row < tiles * row_tile,
        jnp.clip((start + counts)[tile_expert] - first_row, 0, row_tile), 0)
    return (dest.reshape(T, k), token, weight,
            tile_expert.astype(jnp.int32), tile_pairs.astype(jnp.int32),
            tiles.astype(jnp.int32))


def grouped_counts(idx, mask, offset, held, row_tile=ROW_TILE):
    """int32 ``[3]``: the (token, held expert) pairs the routing made, the
    rows the grouped product multiplies for them (each held expert's pairs
    in whole row tiles), and the held experts with a pair (whose matrices
    have to be read)."""
    _valid, hit = _held_pairs(idx, mask, offset, held)
    counts = jnp.sum(hit, axis=(0, 1)).astype(jnp.int32)
    return jnp.stack([jnp.sum(counts),
                      jnp.sum(-(-counts // row_tile)) * row_tile,
                      jnp.sum((counts > 0).astype(jnp.int32))])


def _grouped_body(eid_ref, n_ref, pairs_ref, token_ref, x_hbm, tok_ref,
                  c_ref, *refs, tiles, gated):
    """``refs``: the up (and, ``gated``, the gate) matrix's tile, the down
    matrix's, the output ``[T, H]`` in HBM, then the scratches: the tokens
    and their float32 sums, both ``[tiles, T, tile]`` and RESIDENT for the
    whole call; the row tile's pick of its tokens ``[rows, T]`` (one 1.0 a
    row), the up product (and the gate's) ``[rows, F]`` float32, the
    activation in the weights' dtype, the down product ``[tiles, rows,
    tile]`` float32; the copies' semaphores. Nothing of ``rows`` rows is in
    HBM: a row tile picks its tokens' rows out of the resident ones on the
    MXU (exact: a row of the pick is one 1.0, the sum float32) and adds its
    down product's first ``pairs_ref[m]`` rows, the pairs, each into its
    token's sum."""
    ins, o_hbm = refs[:2 + gated], refs[2 + gated]
    xs_ref, sum_ref, pick_ref, *acc, a_ref, y_ref, sem = refs[3 + gated:]
    firsts, down_ref = ins[:-1], ins[-1]
    m, j = pl.program_id(0), pl.program_id(1)
    last = (m == pl.num_programs(0) - 1) & (j == 2 * tiles - 1)
    prec = _kernel_precision(down_ref.dtype)
    rows, tile = y_ref.shape[1:]

    def copies(inward):
        """A hidden tile's columns of the tokens in, or of the sums out:
        the ``[T, H]`` array is turned by the copy engine, not by XLA."""
        for n in range(tiles):
            columns = (x_hbm if inward else o_hbm).at[:, pl.ds(n * tile, tile)]
            yield pltpu.make_async_copy(
                *((columns, xs_ref.at[n]) if inward
                  else (sum_ref.at[n], columns)), sem.at[n])

    @pl.when((m == 0) & (j == 0))
    def _():
        for copy in copies(True):
            copy.start()
        sum_ref[...] = jnp.zeros_like(sum_ref)
        for copy in copies(True):
            copy.wait()

    @pl.when(m < n_ref[0])
    def _():
        @pl.when(j == 0)
        def _():
            for h_ref in acc:
                h_ref[...] = jnp.zeros_like(h_ref)
            at = jax.lax.broadcasted_iota(jnp.int32, pick_ref.shape, 1)
            pick_ref[...] = (at == tok_ref[...]).astype(pick_ref.dtype)

        @pl.when(j < tiles)
        def _():
            xt = jnp.dot(pick_ref[...], xs_ref[jnp.minimum(j, tiles - 1)],
                         precision=prec, preferred_element_type=jnp.float32
                         ).astype(pick_ref.dtype)
            for w_ref, h_ref in zip(firsts, acc):
                h_ref[...] += jax.lax.dot_general(
                    xt, w_ref[...], (((1,), (1,)), ((), ())), precision=prec,
                    preferred_element_type=jnp.float32)

        @pl.when(j == tiles)
        def _():
            a_ref[...] = _act(
                acc[0][...], acc[1][...] if gated else None,
                c_ref[...]).astype(a_ref.dtype)

        @pl.when(j >= tiles)
        def _():
            y_ref[jnp.maximum(j - tiles, 0)] = jnp.dot(
                a_ref[...], down_ref[...], precision=prec,
                preferred_element_type=jnp.float32)

        @pl.when(j == 2 * tiles - 1)
        def _():
            def add(r, carry):
                to = pl.ds(token_ref[m * rows + r], 1)
                sum_ref[:, to, :] += y_ref[:, pl.ds(r, 1), :]
                return carry

            jax.lax.fori_loop(0, pairs_ref[m], add, 0)

    @pl.when(last)
    def _():
        for copy in copies(False):
            copy.start()
        for copy in copies(False):
            copy.wait()


def _grouped_resident(hidden, ffn, tile, dtype, matrices, row_tile):
    """Tokens one call of the grouped kernel can keep resident (their rows
    in the weights' dtype, their float32 sums and a column of every row
    tile's pick) beside what a row tile takes (its down product, its up
    products and activation, both buffers of the weight blocks) under
    ``_GROUPED_VMEM_LIMIT``, in whole lane tiles of the pick; 0: none."""
    size = jnp.dtype(dtype).itemsize
    a_tile = (row_tile * (4 * hidden + ffn * (4 * (matrices - 1) + size))
              + 2 * matrices * ffn * tile * size)
    room = _GROUPED_VMEM_LIMIT - _GROUPED_VMEM_SPARE - a_tile
    return max(room // (hidden * (size + 4) + row_tile * size), 0) // 128 * 128


def moe_grouped(x, idx, w, mask, offset, w_up, w_down, w_gate=None,
                interpret=False, row_tile=ROW_TILE):
    """The held experts' part of a routed layer as a GROUPED product:
    ``experts_composite(x, held_weights(idx, w, mask, offset, held), ...)``
    computed over the pairs the routing made (``group_pairs``). Grid ``(row
    tiles, 2 * hidden tiles)``: a row tile's ``x . W_up`` (and ``x .
    W_gate``) accumulate over tiles of the hidden size into ``[rows, F]``
    scratches, then the down product's columns leave tile by tile; a row
    tile past the last used one does nothing and copies nothing. The
    tokens' rows and their sums stay in VMEM for the whole call
    (``_grouped_body``): only ``[T, H]`` arrays enter and leave, whatever
    the rows. Dropless: the layout holds every pair the routing can make
    (``grouped_rows``), so all tokens on one expert, or an expert with
    none, are the same program. More tokens than a call can keep resident
    (``_grouped_resident``) go in as many calls as it takes, each over its
    own tokens' pairs. Falls back to the dense composite, counted, where
    Mosaic cannot take the geometry (``hidden_tile``) or inside a manual
    region."""
    t, hidden = x.shape
    held, ffn, _ = w_up.shape
    firsts = [w_up] if w_gate is None else [w_up, w_gate]
    tile = hidden_tile(row_tile, hidden, ffn, w_up.dtype, len(firsts) + 1,
                       interpret)
    resident = tile and _grouped_resident(hidden, ffn, tile, w_up.dtype,
                                          len(firsts) + 1, row_tile)
    if vma_names(x) or not resident:
        fallback_counter().inc()
        return experts_composite(x, held_weights(idx, w, mask, offset, held),
                                 w_up, w_down, w_gate)
    if t > resident:
        calls = -(-t // resident)
        each = -(-t // calls)
        return jnp.concatenate([moe_grouped(
            x[at:at + each], idx[at:at + each], w[at:at + each],
            mask[at:at + each], offset, w_up, w_down, w_gate, interpret,
            row_tile) for at in range(0, t, each)])
    pad = -t % 128
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    rows = grouped_rows(t, idx.shape[1], held, row_tile)
    _dest, token, weight, tile_expert, tile_pairs, used = group_pairs(
        idx, w, mask, offset, held, rows, row_tile)
    tiles, M = hidden // tile, rows // row_tile

    def tile_of(m, n_ref):
        return jnp.minimum(m, jnp.maximum(n_ref[0] - 1, 0))

    def step(m, j, n_ref):
        return jnp.where(m < n_ref[0], j, 2 * tiles - 1)

    a_row = pl.BlockSpec((row_tile, 1),
                         lambda m, j, e, n, *_: (tile_of(m, n), 0))
    first = pl.BlockSpec(
        (None, ffn, tile), lambda m, j, e, n, *_: (
            e[m], 0, jnp.minimum(step(m, j, n), tiles - 1)))
    out = pl.pallas_call(
        functools.partial(_grouped_body, tiles=tiles,
                          gated=w_gate is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(M, 2 * tiles),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY), a_row, a_row,
                *[first for _ in firsts],
                pl.BlockSpec(
                    (None, ffn, tile), lambda m, j, e, n, *_: (
                        e[m], 0, jnp.maximum(step(m, j, n) - tiles, 0))),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((tiles, t + pad, tile), w_up.dtype),
                            pltpu.VMEM((tiles, t + pad, tile), jnp.float32),
                            pltpu.VMEM((row_tile, t + pad), w_up.dtype)]
            + [pltpu.VMEM((row_tile, ffn), jnp.float32) for _ in firsts]
            + [pltpu.VMEM((row_tile, ffn), w_down.dtype),
               pltpu.VMEM((tiles, row_tile, tile), jnp.float32),
               pltpu.SemaphoreType.DMA((tiles,))],
        ),
        out_shape=jax.ShapeDtypeStruct((t + pad, hidden), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_GROUPED_VMEM_LIMIT),
        interpret=interpret,
        name="moe_grouped",
    )(tile_expert, used.reshape(1), tile_pairs, token,
      x.astype(w_up.dtype), token.reshape(rows, 1), weight.reshape(rows, 1),
      *firsts, w_down)
    return out[:t] if pad else out
