"""Routed experts of which this chip holds a share (expert parallelism,
seen from one rank).

``route`` scores every token against ALL the router's experts (sigmoid
scores, a selection bias that moves the choice and not the weight, top-k,
the chosen scores normalised over all k and scaled). ``held_weights`` keeps
of each token's k weights those of the ``held`` experts that live here
(ids ``offset .. offset + held - 1``): a ``[T, held]`` matrix, mostly zeros.
The layer's result here is the held experts' part alone,
``sum_e c[t, e] * W_down,e . relu(W_up,e . x_t)^2``; what the absent experts
would add is left out (it is another rank's to compute), and nothing stands
in for them.

``experts_composite`` computes that part densely (every held expert over
every token, weighted by ``c``): the reference lowering, the CPU path, the
``off`` path, and the prompt chunk's path. ``moe_experts`` is the decode
step's Pallas kernel: the grid runs over the held experts that some token
chose, touched ones first (scalar prefetch), and streams each one's two
matrices through VMEM in tiles of the hidden size; an expert no token chose
is never read.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels.registry import fallback_counter
from paddle_tpu.ops.common import vma_names

__all__ = ["route", "held_weights", "routing_counts", "experts_composite",
           "moe_experts"]

_HI = jax.lax.Precision.HIGHEST
#: columns of the hidden size one grid step of the kernel covers
_HIDDEN_TILE = 384


def route(x, gate_w, select_bias, k, scale, normalize):
    """``(expert ids [T, k], weights [T, k])`` over all of ``gate_w``'s
    experts (``gate_w`` ``[E, H]`` and ``select_bias`` ``[E]`` float32):
    scores and weights in float32."""
    s = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32), gate_w.T,
                                  precision=_HI))
    _, idx = jax.lax.top_k(s + select_bias, k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if normalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * scale


def held_weights(idx, w, mask, offset, held):
    """``c [T, held]``: token ``t``'s weight for held expert ``e``, 0 where
    it did not choose it or ``mask[t]`` is false."""
    local = idx - offset                                       # [T, k]
    hit = (local[:, :, None] == jnp.arange(held)[None, None, :])
    c = jnp.sum(jnp.where(hit, w[:, :, None], 0.0), axis=1)
    return jnp.where(mask[:, None], c, 0.0)


def routing_counts(idx, c, mask):
    """int32 ``[3]``: assignments (masked tokens x k), those that landed on
    a held expert, held experts with at least one token (``c`` is
    ``held_weights``'s: a chosen expert's weight is never 0)."""
    chosen = c != 0.0
    return jnp.stack([
        jnp.sum(mask.astype(jnp.int32)) * idx.shape[1],
        jnp.sum(chosen.astype(jnp.int32)),
        jnp.sum(jnp.any(chosen, axis=0).astype(jnp.int32))])


def _act(h, c):
    return jnp.square(jnp.maximum(h, 0.0)) * c


def experts_composite(x, c, w_up, w_down):
    """``x`` ``[T, H]``, ``c`` ``[T, E]`` float32, ``w_up`` and ``w_down``
    both ``[E, F, H]`` (the hidden size is the minor dimension of both: a
    minor dimension that is no multiple of the 128 lanes, as an expert
    width may be, costs the kernel a copy of the whole array at every
    call); float32 ``[T, H]``. Products in the weights' dtype, accumulated
    in float32."""
    f32 = jnp.float32
    prec = _HI if w_up.dtype == f32 else None
    h = jnp.einsum("th,efh->etf", x.astype(w_up.dtype), w_up,
                   preferred_element_type=f32, precision=prec)
    a = _act(h, c.T[:, :, None]).astype(w_down.dtype)
    return jnp.einsum("etf,efh->th", a, w_down, preferred_element_type=f32,
                      precision=prec)


def _kernel_precision(dtype):
    """A process-wide ``jax_default_matmul_precision`` reaches inside a
    Pallas body, and Mosaic refuses a float32 precision on bfloat16
    operands: pin the operands' own."""
    return _HI if dtype == jnp.float32 else jax.lax.Precision.DEFAULT


def _experts_body(eid_ref, n_ref, x_ref, c_ref, up_ref, down_ref, o_ref,
                  h_ref, a_ref, *, tiles):
    g, j = pl.program_id(0), pl.program_id(1)
    prec = _kernel_precision(up_ref.dtype)

    @pl.when((g == 0) & (j == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(g < n_ref[0])
    def _():
        @pl.when(j == 0)
        def _():
            h_ref[...] = jnp.zeros_like(h_ref)

        @pl.when(j < tiles)
        def _():
            h_ref[...] += jax.lax.dot_general(
                x_ref[jnp.minimum(j, tiles - 1)], up_ref[...],
                (((1,), (1,)), ((), ())), precision=prec,
                preferred_element_type=jnp.float32)

        @pl.when(j == tiles)
        def _():
            a_ref[...] = _act(h_ref[...], c_ref[...]).astype(a_ref.dtype)

        @pl.when(j >= tiles)
        def _():
            jj = jnp.maximum(j - tiles, 0)
            o_ref[jj] += jnp.dot(a_ref[...], down_ref[...], precision=prec,
                                 preferred_element_type=jnp.float32)


def moe_experts(x, c, w_up, w_down, interpret=False):
    """``experts_composite`` reading the weights of the held experts that
    some token chose, and no others. Grid ``(experts, 2 * tiles)``: for one
    expert, ``tiles`` steps accumulate ``x . W_up`` over tiles of the hidden
    size into a ``[T, F]`` scratch, then ``tiles`` steps write the down
    product's columns, tile by tile, into the resident output. Past the
    last touched expert every block index stays where it was: no copy."""
    t, hidden = x.shape
    held, ffn, _ = w_up.shape
    tile = _HIDDEN_TILE if hidden % _HIDDEN_TILE == 0 else hidden
    if vma_names(x) or (not interpret and (
            tile != _HIDDEN_TILE or t % 16 or w_up.dtype != jnp.bfloat16)):
        fallback_counter().inc()
        return experts_composite(x, c, w_up, w_down)
    tiles = hidden // tile
    touched = jnp.any(c != 0.0, axis=0)                        # [E]
    order = jnp.argsort(jnp.logical_not(touched),
                        stable=True).astype(jnp.int32)
    count = jnp.sum(touched.astype(jnp.int32))
    last = jnp.maximum(count - 1, 0)
    eid = jnp.where(jnp.arange(held) < count, order, order[last])
    xs = jnp.swapaxes(x.astype(w_up.dtype).reshape(t, tiles, tile), 0, 1)
    cols = c.astype(jnp.float32).T[:, :, None]                 # [E, T, 1]

    def step(g, j, n_ref):
        return jnp.where(g < n_ref[0], j, 2 * tiles - 1)

    out = pl.pallas_call(
        functools.partial(_experts_body, tiles=tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(held, 2 * tiles),
            in_specs=[
                pl.BlockSpec((tiles, t, tile), lambda g, j, e, n: (0, 0, 0)),
                pl.BlockSpec((None, t, 1), lambda g, j, e, n: (e[g], 0, 0)),
                pl.BlockSpec(
                    (None, ffn, tile), lambda g, j, e, n: (
                        e[g], 0, jnp.minimum(step(g, j, n), tiles - 1))),
                pl.BlockSpec(
                    (None, ffn, tile), lambda g, j, e, n: (
                        e[g], 0, jnp.maximum(step(g, j, n) - tiles, 0))),
            ],
            out_specs=pl.BlockSpec((tiles, t, tile),
                                   lambda g, j, e, n: (0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((t, ffn), jnp.float32),
                            pltpu.VMEM((t, ffn), w_down.dtype)],
        ),
        out_shape=jax.ShapeDtypeStruct((tiles, t, tile), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="moe_experts",
    )(eid, count.reshape(1), xs, cols, w_up, w_down)
    return jnp.swapaxes(out, 0, 1).reshape(t, hidden)
