"""Mamba-2 (state-space duality) mixer: the recurrence's three evaluations.

Per head, with a state ``h`` of ``[P, N]`` (head dim x state size)::

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * x_t (outer) B_t
    y_t = h_t . C_t + D * x_t

``ssm_scan_sequential`` is that recurrence one token at a time: THE
definition. ``ssm_scan_chunked`` evaluates it exactly by chunks of ``Q``
tokens (decay-masked ``C B^T`` inside a chunk, the carried state between
chunks): the prefill's form, a loop over the chunks whose body is einsums
or, on the chip, the ``ssm_scan`` Pallas kernel (a scan chunk for all heads,
a head block a grid step, the ``[Q, Q]`` decay-masked products kept in
VMEM). ``ssm_update`` is the decode step's one-token update over a ``[S,
H, P, N]`` batch of per-slot states, a Pallas kernel that reads and writes
the states of the STEPPING slots alone, in place, a grid step as many heads
of one slot as a VMEM budget takes (``_update_heads``: a slot's whole state
at the published 64 heads x 64 x 128); ``ssm_update_composite`` is its
reference lowering, its CPU path and its ``off`` path.

``mixer_chunk`` / ``mixer_step`` are the whole mixer between its two
projections (convolution, activations, dt, the scan, the gated grouped
RMSNorm) for a ``[T, ...]`` prompt chunk of ONE slot and for a ``[S, ...]``
decode step, over the per-slot state arrays:

* SSM state ``[S, H, P, N]`` float32.
* convolution tail ``[S, K - 1, D]``: the last ``K - 1`` inputs of the
  causal depthwise convolution, oldest first (time before channels: a
  ``[.., D, K - 1]`` array pads its minor dimension of 3 to a lane tile of
  128 on the TPU).

A position whose ``mask`` is false moves neither state: its ``dt`` is 0
(decay 1, no input) and the tail is taken at the last real token.

``conv_tail_chunk`` / ``conv_tail_step`` are that convolution over its
per-slot tail alone, and ``short_conv_chunk`` / ``short_conv_step`` the
gated short convolution (the ``lfm2`` family's operator) that is nothing
else: ``C * conv(B * u)`` for ``B | C | u`` the input projection's thirds,
no bias, no activation, no other state.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels.registry import fallback_counter
from paddle_tpu.ops.common import vma_names

__all__ = [
    "ssm_scan_sequential", "ssm_scan_chunked", "ssm_update_composite",
    "ssm_update", "mixer_chunk", "mixer_step", "gated_group_norm",
    "conv_tail_chunk", "conv_tail_step", "short_conv_chunk",
    "short_conv_step",
]

_HI = jax.lax.Precision.HIGHEST


def _per_head(g, heads):
    """``[.., G, N]`` group values as ``[.., H, N]``: head ``h`` reads
    group ``h // (H / G)``."""
    return jnp.repeat(g, heads // g.shape[-2], axis=-2)


def ssm_scan_sequential(x, dt, a, b, c, h0):
    """The recurrence token by token. ``x`` ``[T, H, P]``, ``dt`` ``[T, H]``
    (after softplus; 0 at a masked position), ``a`` ``[H]`` (negative),
    ``b``, ``c`` ``[T, G, N]``, ``h0`` ``[H, P, N]``; returns ``y``
    ``[T, H, P]`` (without the ``D x`` term) and the last state."""
    heads = x.shape[1]

    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = (jnp.exp(dtt * a)[:, None, None] * h
             + (dtt[:, None] * xt)[:, :, None] * _per_head(bt, heads)[:, None])
        return h, jnp.sum(h * _per_head(ct, heads)[:, None], axis=-1)

    h, y = jax.lax.scan(step, h0, (x, dt, b, c))
    return y, h


def ssm_scan_chunked(x, dt, a, b, c, h0, chunk, kernel=None):
    """The same recurrence, exactly, by chunks of ``chunk`` tokens: inside
    a chunk ``y_t = sum_{s<=t} exp(cs_t - cs_s) (C_t . B_s) dt_s x_s`` with
    ``cs`` the running sum of ``dt * A``, plus what the carried state
    gives, ``exp(cs_t) C_t . h``; between chunks the state moves by the
    whole chunk at once. Any length: the tail is padded with ``dt = 0``.
    ``kernel`` is None (the scan's body is the einsums below) or the
    interpret flag of the ``ssm_scan`` kernel, which is that body with a
    chunk's ``[Q, Q]`` tiles kept in VMEM."""
    t_real, heads = x.shape[0], x.shape[1]
    q = min(int(chunk), t_real)
    pad = -t_real % q
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                       for v in (x, dt, b, c))
    n = x.shape[0] // q
    x, dt, b, c = (v.reshape((n, q) + v.shape[1:]) for v in (x, dt, b, c))
    tril = jnp.tril(jnp.ones((q, q), bool))[:, :, None]

    def step(h, inp):
        xq, dtq, bq, cq = inp
        cs = jnp.cumsum(dtq * a, axis=0)                       # [Q, H]
        decay = jnp.where(tril, jnp.exp(cs[:, None] - cs[None]), 0.0)
        cb = jnp.einsum("tgn,sgn->tsg", cq, bq, precision=_HI)
        w = decay * jnp.repeat(cb, heads // cb.shape[-1], axis=-1)
        xdt = xq * dtq[:, :, None]                             # [Q, H, P]
        y = jnp.einsum("tsh,shp->thp", w, xdt, precision=_HI)
        y = y + jnp.exp(cs)[:, :, None] * jnp.einsum(
            "thn,hpn->thp", _per_head(cq, heads), h, precision=_HI)
        to_end = jnp.exp(cs[-1][None] - cs)                    # [Q, H]
        h = jnp.exp(cs[-1])[:, None, None] * h + jnp.einsum(
            "shp,shn->hpn", xdt * to_end[:, :, None], _per_head(bq, heads),
            precision=_HI)
        return h, y

    if kernel is None or not _scan_kernel_serves(x, b, kernel):
        h, y = jax.lax.scan(step, h0, (x, dt, b, c))
    else:
        y, h = _scan_by_kernel(x, dt, a, b, c, h0, kernel)
    return y.reshape((n * q,) + y.shape[2:])[:t_real], h


#: VMEM that the blocks of one ``ssm_scan`` grid step may take, both sets
#: (the step being computed and the one being copied); the body's own
#: values (``b c^T``, what the state gives and takes for the block's heads,
#: a head's ``w`` tiles) come to ~3 MiB more, under Mosaic's default scoped
#: limit of 16 MiB
_SCAN_BLOCK_BYTES = 8 * 2 ** 20
#: the most heads a grid step of ``ssm_scan`` holds: the body is unrolled
#: over them
_SCAN_HEADS = 16


def _scan_heads(heads, per_group, q, p, n_state):
    """Heads a grid step of ``ssm_scan`` holds, from the operands' shapes:
    the largest divisor of ``heads`` up to ``_SCAN_HEADS`` that is whole
    groups or a whole part of one and whose ``x``, ``y`` and state blocks
    fit ``_SCAN_BLOCK_BYTES``."""
    per_head = 2 * 4 * (2 * q * p + 2 * p * n_state)
    cap = max(1, min(_SCAN_HEADS, heads, _SCAN_BLOCK_BYTES // per_head))
    return max(g for g in range(1, cap + 1) if heads % g == 0
               and (g % per_group == 0 or per_group % g == 0))


def _scan_kernel_serves(x, b, interpret):
    """Whether the kernel takes these chunks ``x`` ``[n, Q, H, P]``, ``b``
    ``[n, Q, G, N]``: not inside a manual mesh region, and compiled only
    where a scan chunk's tokens and the state's columns are whole lane
    tiles and a head's rows whole sublane tiles."""
    _n, q, _heads, p = x.shape
    if vma_names(x) or (not interpret and (
            q % 128 or b.shape[-1] % 128 or p % 8)):
        fallback_counter().inc()
        return False
    return True


def _scan_body(i_ref, x_ref, row_ref, col_ref, end_ref, b_ref, c_ref, h_ref,
               _y_in, y_ref, ho_ref, *, block, p, per_group):
    """One head block of one scan chunk, TIME ON THE LANES: ``x_ref``
    ``[block * P, Q]`` (a head's ``P`` rows one under the other),
    ``row_ref`` ``[4 * block, Q]`` (per head a row each of ``cs``,
    ``exp(cs)``, ``dt`` and the decay to the chunk's end), ``col_ref``
    ``[Q, block]`` (``cs`` as columns), ``end_ref`` ``[block, N]`` (the
    whole chunk's decay), ``b_ref``, ``c_ref`` ``[groups of the block, Q,
    N]``, ``h_ref`` ``[block * P, N]``. A head's ``w^T`` ``[Q, Q]`` (``s``
    down, ``t`` across) stays where it is made and the head's ``P`` rows of
    ``dt x`` stream past it; what the state gives and what it takes are one
    product a group each. ``w^T`` is made a lane tile of ``t`` at a time,
    down to the last ``s`` the mask lets that tile see: the tokens after it
    are zeros by the mask and are neither made nor multiplied."""
    del i_ref
    f32 = jnp.float32
    q = x_ref.shape[1]
    dot = functools.partial(jax.lax.dot_general, precision=_HI,
                            preferred_element_type=f32)
    live = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
            <= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    tile = 128 if q % 128 == 0 else q
    in_group = min(block, per_group)
    for g in range(block // in_group):
        b, c = b_ref[g], c_ref[g]                              # [Q, N]
        bc = dot(b, c, (((1,), (1,)), ((), ())))               # [Q s, Q t]
        lo = g * in_group * p
        from_state = dot(h_ref[lo:lo + in_group * p, :], c,
                         (((1,), (1,)), ((), ())))             # [.. P, Q]
        to_state = []
        for k in range(in_group):
            i, rows = g * in_group + k, slice(lo + k * p, lo + (k + 1) * p)
            ecs, dt, to_end = (
                row_ref[m * block + i:m * block + i + 1, :]
                for m in range(1, 4))                          # [1, Q]
            xdt = x_ref[rows, :] * dt                          # [P, Q]
            y = []
            for t0 in range(0, q, tile):
                t, s = slice(t0, t0 + tile), slice(0, t0 + tile)
                w = jnp.where(
                    live[s, t],
                    jnp.exp(row_ref[i:i + 1, t] - col_ref[s, i:i + 1]),
                    0.0) * bc[s, t]
                y.append(dot(xdt[:, s], w, (((1,), (0,)), ((), ()))))
            y_ref[rows, :] = (jnp.concatenate(y, axis=1)
                              + ecs * from_state[k * p:(k + 1) * p])
            to_state.append(xdt * to_end)
        new = dot(jnp.concatenate(to_state, axis=0), b,
                  (((1,), (0,)), ((), ())))                    # [.. P, N]
        for k in range(in_group):
            i, rows = g * in_group + k, slice(lo + k * p, lo + (k + 1) * p)
            ho_ref[rows, :] = (end_ref[i:i + 1, :] * h_ref[rows, :]
                               + new[k * p:(k + 1) * p])


def _scan_by_kernel(x, dt, a, b, c, h0, interpret):
    """The scan over the chunks ``x`` ``[n, Q, H, P]``, ``dt`` ``[n, Q, H]``,
    ``b``, ``c`` ``[n, Q, G, N]`` with the ``ssm_scan`` kernel as its body:
    a trip is one call whose grid runs over head blocks. Everything that is
    a number a token a head (``cs`` and what is made of it: kilobytes) is
    made here for all chunks at once, as the rows and columns the body
    broadcasts from. ``x`` and ``y`` go in and out as ``[H * P, n * Q]``,
    time last: the layout XLA gives the projections around a mixer at these
    shapes, so neither is copied to be turned; they stay whole arrays that
    a trip reads and writes its chunk of in place (the trip's index is a
    scalar the block maps read), so the loop copies nothing in or out."""
    f32 = jnp.float32
    n, q, heads, p = x.shape
    groups, n_state = b.shape[2], b.shape[3]
    per_group = heads // groups
    block = _scan_heads(heads, per_group, q, p, n_state)
    nb = heads // block
    cs = jnp.cumsum(dt * a, axis=1)                            # [n, Q, H]
    rows = jnp.stack([cs, jnp.exp(cs), dt, jnp.exp(cs[:, -1:] - cs)], 1)
    rows = rows.reshape(n, 4, q, nb, block).transpose(0, 3, 1, 4, 2)
    rows = rows.reshape(n, nb, 4 * block, q)
    cols = cs.reshape(n, q, nb, block).swapaxes(1, 2)          # [n,nb,Q,blk]
    end = jnp.broadcast_to(jnp.exp(cs[:, -1]).reshape(n, nb, block, 1),
                           (n, nb, block, n_state))
    b, c = (jnp.swapaxes(v.astype(f32), 1, 2) for v in (b, c))  # [n,G,Q,N]
    group_block = max(1, block // per_group)
    blocks_a_group = max(1, per_group // block)
    xy_spec = pl.BlockSpec((block * p, q), lambda j, i_ref: (j, i_ref[0]))
    small = lambda *shape: pl.BlockSpec(  # noqa: E731
        (None, None) + shape, lambda j, i_ref: (i_ref[0], j, 0, 0))
    bc_spec = pl.BlockSpec(
        (None, group_block, q, n_state),
        lambda j, i_ref: (i_ref[0], j // blocks_a_group, 0, 0))
    h_spec = pl.BlockSpec((block * p, n_state), lambda j, i_ref: (j, 0))
    call = pl.pallas_call(
        functools.partial(_scan_body, block=block, p=p,
                          per_group=per_group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb,),
            in_specs=[xy_spec, small(4 * block, q), small(q, block),
                      small(block, n_state), bc_spec, bc_spec, h_spec,
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[xy_spec, h_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((heads * p, n * q), f32),
                   jax.ShapeDtypeStruct((heads * p, n_state), f32)],
        input_output_aliases={7: 1, 8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ssm_scan",
    )
    x = x.astype(f32).reshape(n * q, heads * p).T

    def trip(carry, i):
        h, y = carry
        y, h = call(i.reshape(1), x, rows, cols, end, b, c, h, y)
        return (h, y), None

    (h, y), _ = jax.lax.scan(
        trip, (h0.astype(f32).reshape(heads * p, n_state),
               jnp.zeros_like(x)), jnp.arange(n, dtype=jnp.int32))
    return y.T.reshape(n, q, heads, p), h.reshape(heads, p, n_state)


def ssm_update_composite(state, xdt, decay, bh, ch, mask):
    """One token per slot. ``state`` ``[S, H, P, N]``, ``xdt`` ``[S, H, P]``
    (``dt * x``), ``decay`` ``[S, H]`` (``exp(dt * A)``), ``bh``, ``ch``
    ``[S, H, N]``, ``mask`` ``[S]``: a slot that does not step keeps its
    state bit for bit and reads ``y`` 0."""
    new = (decay[:, :, None, None] * state
           + xdt[:, :, :, None] * bh[:, :, None, :])
    y = jnp.sum(new * ch[:, :, None, :], axis=-1)
    keep = mask[:, None, None]
    return jnp.where(keep[..., None], new, state), jnp.where(keep, y, 0.0)


#: VMEM that the state blocks of one ``ssm_update`` grid step may take, all
#: four (the block being updated and the one being copied, in and out); the
#: five small operands' blocks come to ~0.4 MiB more at 64 heads, under
#: Mosaic's default scoped limit of 16 MiB
_UPDATE_BLOCK_BYTES = 8 * 2 ** 20


def _update_heads(heads, p, n_state):
    """Heads of ONE slot that a grid step of ``ssm_update`` holds, from the
    operands' shapes: the largest divisor of ``heads`` whose float32 state
    blocks fit ``_UPDATE_BLOCK_BYTES``."""
    cap = max(1, min(heads, _UPDATE_BLOCK_BYTES // (4 * 4 * p * n_state)))
    return max(g for g in range(1, cap + 1) if heads % g == 0)


def _ssm_body(sid_ref, n_ref, st_ref, x_ref, da_ref, b_ref, c_ref,
              out_ref, y_ref, *, block, group):
    g = pl.program_id(0)
    n = n_ref[0]

    @pl.when(g < n)
    def _():
        ones = jnp.ones((8, st_ref.shape[-1]), jnp.float32)
        for r in range(block // group):
            prods = []
            for i in range(r * group, (r + 1) * group):
                new = (da_ref[:, i:i + 1] * st_ref[i]
                       + x_ref[:, i:i + 1] * b_ref[i:i + 1, :])
                out_ref[i] = new
                prods.append(new * c_ref[i:i + 1, :])
            # the sums over the state dimension of ``group`` heads' rows as
            # ONE row of ``y``: ``ones . prods^T`` on the MXU, which has
            # nothing else to do (float32 at HIGHEST: a product goes in as
            # its bfloat16 pieces and is summed in float32). A reduction
            # along the lanes and a ``[P, 1]`` column store a head cost a
            # ninth of a call; this hides under the state's copies
            # (tools/check_ssm_update.py)
            y_ref[r:r + 1, :] = jax.lax.dot_general(
                ones, jnp.concatenate(prods, axis=0),
                (((1,), (1,)), ((), ())), precision=_HI,
                preferred_element_type=jnp.float32)[0:1]

    @pl.when(n == 0)
    def _():
        # no slot steps: every grid step names one block, which goes back
        # as it came
        out_ref[...] = st_ref[...]


def ssm_update(state, xdt, decay, bh, ch, mask, interpret=False, block=None):
    """``ssm_update_composite`` with the states of the stepping slots read
    and written in place and no others touched: the grid runs over the
    slots in an order that puts the stepping ones first (scalar prefetch),
    a grid step ``block`` heads of one slot (None: as many as
    ``_update_heads`` gives from the shapes; at 64 heads x 64 x 128 a
    slot's whole state, 2 MB in and 2 MB out); past the last stepping slot
    every grid step names the block of the step before, so nothing is
    copied. Inside, a head's state is one ``[P, N]`` tile: ``x`` comes
    transposed (``[P, heads]``) so that a head's column broadcasts along
    the lanes, and ``y`` leaves as rows of ``128 / P`` heads' ``P`` values
    side by side (``[S, H, P]`` as it lies in memory)."""
    s, heads, p, n_state = state.shape
    if block is None:
        block = _update_heads(heads, p, n_state)
    if vma_names(state) or heads % block or (not interpret and (
            p % 8 or n_state % 128 or state.dtype != jnp.float32)):
        fallback_counter().inc()
        return ssm_update_composite(state, xdt, decay, bh, ch, mask)
    nb = heads // block
    # heads whose ``y`` share a row of 128 lanes
    group = 128 // p if 128 % p == 0 and block % (128 // p) == 0 else 1
    order = jnp.argsort(jnp.logical_not(mask), stable=True).astype(jnp.int32)
    count = jnp.sum(mask.astype(jnp.int32))
    last = jnp.maximum(count - 1, 0)
    sid = jnp.where(jnp.arange(s) < count, order, order[last])
    f32 = jnp.float32
    # [S, nb, P, block]: a head block's x as columns; decay as one row
    xt = jnp.swapaxes(xdt.astype(f32).reshape(s, nb, block, p), 2, 3)
    da = decay.astype(f32).reshape(s, nb, 1, block)
    bh = bh.astype(f32).reshape(s, nb, block, n_state)
    ch = ch.astype(f32).reshape(s, nb, block, n_state)

    def where(g, j, sid_ref, n_ref):
        live = g < n_ref[0]
        return sid_ref[g], jnp.where(live, j, nb - 1)

    def small(shape):
        return pl.BlockSpec(
            (None, None) + shape,
            lambda g, j, sid_ref, n_ref: where(g, j, sid_ref, n_ref)
            + (0, 0))

    st_spec = pl.BlockSpec(
        (None, block, p, n_state),
        lambda g, j, sid_ref, n_ref: where(g, j, sid_ref, n_ref) + (0, 0))
    y_block = (block // group, group * p)
    new, y = pl.pallas_call(
        functools.partial(_ssm_body, block=block, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s, nb),
            in_specs=[st_spec, small((p, block)), small((1, block)),
                      small((block, n_state)), small((block, n_state))],
            out_specs=[st_spec, small(y_block)],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((s, nb) + y_block, f32)],
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssm_update",
    )(sid, count.reshape(1), state.reshape(s, heads, p, n_state), xt, da,
      bh, ch)
    return new, jnp.where(mask[:, None, None], y.reshape(s, heads, p), 0.0)


def gated_group_norm(y, z, weight, groups, eps):
    """``RMSNorm(y * silu(z))`` over ``groups`` equal groups of the last
    dimension, times ``weight``; float32 inside."""
    f32 = jnp.float32
    g = y.astype(f32) * jax.nn.silu(z.astype(f32))
    shape = g.shape
    g = g.reshape(shape[:-1] + (groups, shape[-1] // groups))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(shape) * weight.astype(f32)


def _float32(params):
    """The mixer's small parameters, in the order the two forms unpack."""
    return (params[k].astype(jnp.float32) for k in
            ("conv_w", "conv_b", "dt_bias", "a_log", "d", "norm_w"))


def _split(zxbcdt, heads, head_dim, groups, n_state):
    d_inner = heads * head_dim
    conv_dim = d_inner + 2 * groups * n_state
    return (zxbcdt[..., :d_inner], zxbcdt[..., d_inner:d_inner + conv_dim],
            zxbcdt[..., d_inner + conv_dim:])


def _split_xbc(xbc, heads, head_dim, groups, n_state):
    d_inner, gn = heads * head_dim, groups * n_state
    lead = xbc.shape[:-1]
    return (xbc[..., :d_inner].reshape(lead + (heads, head_dim)),
            xbc[..., d_inner:d_inner + gn].reshape(lead + (groups, n_state)),
            xbc[..., d_inner + gn:].reshape(lead + (groups, n_state)))


def conv_tail_chunk(x, conv_w, conv_state, slot, mask, keep):
    """The causal depthwise convolution of a prompt chunk ``x`` ``[T, D]``
    (float32) of ONE slot over ``conv_w`` ``[K, D]`` (tap ``K - 1`` on the
    current token), continued from that slot's tail times ``keep`` (0.0
    where the chunk opens the prompt). Returns the ``[T, D]`` sums (no
    bias, no activation) and the tail array with the slot's rows replaced
    by the last ``K - 1`` inputs up to the last real position of ``mask``
    (a prefix)."""
    taps, t = conv_w.shape[0], x.shape[0]
    tail = jax.lax.dynamic_index_in_dim(conv_state, slot, 0, False)
    ext = jnp.concatenate([tail.astype(jnp.float32) * keep, x], axis=0)
    conv = sum(conv_w[k] * ext[k:k + t] for k in range(taps))
    real = jnp.sum(mask.astype(jnp.int32))
    new_tail = jax.lax.dynamic_slice_in_dim(ext, real, taps - 1, axis=0)
    return conv, jax.lax.dynamic_update_index_in_dim(
        conv_state, new_tail.astype(conv_state.dtype), slot, 0)


def conv_tail_step(x, conv_w, conv_state, mask):
    """One token per slot ``x`` ``[S, D]`` (float32) against every slot's
    tail; the slots of ``mask`` ``[S]`` move theirs on by it."""
    ext = jnp.concatenate([conv_state.astype(jnp.float32), x[:, None]],
                          axis=1)
    conv = jnp.sum(conv_w[None] * ext, axis=1)
    return conv, jnp.where(mask[:, None, None],
                           ext[:, 1:].astype(conv_state.dtype), conv_state)


def _gate_thirds(bcu):
    """``B * u`` and ``C`` of the input projection's ``B | C | u``."""
    b, c, u = jnp.split(bcu.astype(jnp.float32), 3, axis=-1)
    return b * u, c


def short_conv_chunk(bcu, conv_w, conv_state, slot, mask, reset, out_dtype):
    """The gated short convolution over a prompt chunk ``[T, 3 D]`` of ONE
    slot: ``C * conv(B * u)``; ``reset`` says the chunk opens the prompt."""
    bu, c = _gate_thirds(bcu)
    conv, conv_state = conv_tail_chunk(
        bu, conv_w.astype(jnp.float32), conv_state, slot, mask,
        jnp.where(reset, 0.0, 1.0).astype(jnp.float32))
    return (c * conv).astype(out_dtype), conv_state


def short_conv_step(bcu, conv_w, conv_state, mask, out_dtype):
    """The same for one token of every slot ``[S, 3 D]``."""
    bu, c = _gate_thirds(bcu)
    conv, conv_state = conv_tail_step(bu, conv_w.astype(jnp.float32),
                                      conv_state, mask)
    return (c * conv).astype(out_dtype), conv_state


def mixer_chunk(zxbcdt, params, conv_state, ssm_state, slot, mask, reset, *,
                heads, head_dim, groups, n_state, chunk, eps, out_dtype,
                kernel=None):
    """A prompt chunk ``[T, in_proj width]`` of ONE slot against that slot's
    rows of the state arrays. ``mask`` ``[T]`` marks the real positions (a
    prefix), ``reset`` says the chunk opens the prompt: the slot's states
    start from zero, whatever a retired request left there. ``kernel`` is
    None (the composite scan) or the interpret flag of the ``ssm_scan``
    kernel. Returns the gated, normed ``y`` ``[T, d_inner]`` and both state
    arrays with the slot's rows replaced."""
    f32 = jnp.float32
    conv_w, conv_b, dt_bias, a_log, d_skip, norm_w = _float32(params)
    z, xbc, dt = _split(zxbcdt.astype(f32), heads, head_dim, groups, n_state)
    keep = jnp.where(reset, 0.0, 1.0).astype(f32)
    h0 = jax.lax.dynamic_index_in_dim(ssm_state, slot, 0, False)
    h0 = h0.astype(f32) * keep
    t = xbc.shape[0]
    conv, conv_state = conv_tail_chunk(xbc, conv_w, conv_state, slot, mask,
                                       keep)
    x, b, c = _split_xbc(jax.nn.silu(conv + conv_b), heads, head_dim, groups,
                         n_state)
    dt = jnp.where(mask[:, None], jax.nn.softplus(dt + dt_bias), 0.0)
    y, h = ssm_scan_chunked(x, dt, -jnp.exp(a_log), b, c, h0, chunk,
                            kernel=kernel)
    y = (y + d_skip[:, None] * x).reshape(t, heads * head_dim)
    out = gated_group_norm(y, z, norm_w, groups, eps).astype(out_dtype)
    ssm_state = jax.lax.dynamic_update_index_in_dim(
        ssm_state, h.astype(ssm_state.dtype), slot, 0)
    return out, conv_state, ssm_state


def mixer_step(zxbcdt, params, conv_state, ssm_state, mask, *, heads,
               head_dim, groups, n_state, eps, out_dtype, kernel=None):
    """One token per slot ``[S, in_proj width]``; ``mask`` ``[S]`` marks the
    slots that step. ``kernel`` is None (the composite) or the interpret
    flag of the ``ssm_update`` kernel."""
    f32 = jnp.float32
    conv_w, conv_b, dt_bias, a_log, d_skip, norm_w = _float32(params)
    z, xbc, dt = _split(zxbcdt.astype(f32), heads, head_dim, groups, n_state)
    conv, conv_state = conv_tail_step(xbc, conv_w, conv_state, mask)
    x, b, c = _split_xbc(jax.nn.silu(conv + conv_b), heads, head_dim, groups,
                         n_state)
    dt = jax.nn.softplus(dt + dt_bias)                        # [S, H]
    args = (dt[:, :, None] * x, jnp.exp(-dt * jnp.exp(a_log)),
            _per_head(b, heads), _per_head(c, heads), mask)
    if kernel is None or ssm_state.dtype != f32:
        new, y = ssm_update_composite(ssm_state.astype(f32), *args)
        new = new.astype(ssm_state.dtype)
    else:
        new, y = ssm_update(ssm_state, *args, interpret=kernel)
    y = (y + d_skip[:, None] * x).reshape(x.shape[0], heads * head_dim)
    out = gated_group_norm(y, z, norm_w, groups, eps).astype(out_dtype)
    return out, conv_state, new
