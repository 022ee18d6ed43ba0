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

``experts_composite`` computes that part densely (every held expert over
every token, weighted by ``c``): the reference lowering, the CPU path, the
``off`` path, and the prompt chunk's path. ``moe_experts`` is the decode
step's Pallas kernel: the grid runs over the held experts that some token
chose, touched ones first (scalar prefetch), and streams each one's
matrices through VMEM in tiles of the hidden size (``hidden_tile``: the
hidden size's own); an expert no token chose is never read.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels.registry import fallback_counter
from paddle_tpu.ops.common import vma_names

__all__ = ["route", "held_weights", "routing_counts", "experts_composite",
           "moe_experts", "hidden_tile"]

_HI = jax.lax.Precision.HIGHEST
#: bytes of VMEM the kernel's weight blocks may take, both buffers of every
#: matrix: what is left of Mosaic's 16 MiB is the tokens, the output and
#: the two ``[T, F]`` scratches
_WEIGHT_BLOCKS = 8 * 1024 * 1024


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
    (bfloat16, tokens in whole sublane tiles of it, a hidden size in whole
    lane tiles) and the composite runs: THE eligibility test, by geometry.
    ``interpret`` has no Mosaic to please: any dtype and token count, and
    a hidden size that no lane tile divides is one tile."""
    size = jnp.dtype(dtype).itemsize
    room = _WEIGHT_BLOCKS // (2 * matrices * ffn * size)
    fits = [t for t in range(128, hidden + 1, 128)
            if hidden % t == 0 and t <= room]
    if interpret:
        return max(fits, default=hidden)
    if jnp.dtype(dtype) != jnp.bfloat16 or tokens % 16:
        return 0
    return max(fits, default=0)


def _experts_body(eid_ref, n_ref, x_ref, c_ref, *refs, tiles, gated):
    """``refs``: the up (and, ``gated``, the gate) matrix's tile, the down
    matrix's, the output, then the scratches: the up product (and the
    gate's) ``[T, F]`` float32 and the activation in the weights' dtype."""
    ins, (o_ref, *acc, a_ref) = refs[:2 + gated], refs[2 + gated:]
    firsts, down_ref = ins[:-1], ins[-1]
    g, j = pl.program_id(0), pl.program_id(1)
    prec = _kernel_precision(down_ref.dtype)

    @pl.when((g == 0) & (j == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(g < n_ref[0])
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
            o_ref[jj] += jnp.dot(a_ref[...], down_ref[...], precision=prec,
                                 preferred_element_type=jnp.float32)


def moe_experts(x, c, w_up, w_down, w_gate=None, interpret=False):
    """``experts_composite`` reading the weights of the held experts that
    some token chose, and no others. Grid ``(experts, 2 * tiles)``: for one
    expert, ``tiles`` steps accumulate ``x . W_up`` (and ``x . W_gate``,
    where there is a gate) over tiles of the hidden size into ``[T, F]``
    scratches, then ``tiles`` steps write the down product's columns, tile
    by tile, into the resident output. Past the last touched expert every
    block index stays where it was: no copy."""
    t, hidden = x.shape
    held, ffn, _ = w_up.shape
    firsts = [w_up] if w_gate is None else [w_up, w_gate]
    tile = hidden_tile(t, hidden, ffn, w_up.dtype, len(firsts) + 1,
                       interpret)
    if vma_names(x) or not tile:
        fallback_counter().inc()
        return experts_composite(x, c, w_up, w_down, w_gate)
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

    first = pl.BlockSpec(
        (None, ffn, tile), lambda g, j, e, n: (
            e[g], 0, jnp.minimum(step(g, j, n), tiles - 1)))
    out = pl.pallas_call(
        functools.partial(_experts_body, tiles=tiles,
                          gated=w_gate is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(held, 2 * tiles),
            in_specs=[
                pl.BlockSpec((tiles, t, tile), lambda g, j, e, n: (0, 0, 0)),
                pl.BlockSpec((None, t, 1), lambda g, j, e, n: (e[g], 0, 0)),
                *[first for _ in firsts],
                pl.BlockSpec(
                    (None, ffn, tile), lambda g, j, e, n: (
                        e[g], 0, jnp.maximum(step(g, j, n) - tiles, 0))),
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
    )(eid, count.reshape(1), xs, cols, *firsts, w_down)
    return jnp.swapaxes(out, 0, 1).reshape(t, hidden)
