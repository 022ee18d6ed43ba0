"""Tensor creation / manipulation / comparison / random op lowerings.

Replaces the reference's tensor kernels (reference: paddle/fluid/operators/
reshape_op.cc, transpose_op.cc, concat_op.cc, gather_op.cu, cast_op.cu,
fill_constant_op.cc, gaussian_random_op.cu, uniform_random_op.cu ...).
Random ops are counter-based: they consume a key the executor derives from
(program seed, run counter, op index) — deterministic replay without the
reference's per-device curand generator state.
"""

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import register_op
from paddle_tpu.ops.common import first, maybe, np_dtype, rng_key

# -- creation ---------------------------------------------------------------


@register_op("fill_constant")
def _fill_constant(ins, attrs):
    shape = maybe(ins, "ShapeTensor", attrs.get("shape", [1]))
    dtype = np_dtype(attrs)
    return {"Out": [jnp.full(tuple(shape), attrs.get("value", 0.0), dtype=dtype)]}


@register_op("fill_constant_batch_size_like")
def _fill_constant_bsl(ins, attrs):
    x = first(ins, "Input")
    shape = list(attrs.get("shape"))
    in_idx = attrs.get("input_dim_idx", 0)
    out_idx = attrs.get("output_dim_idx", 0)
    shape[out_idx] = x.shape[in_idx]
    return {"Out": [jnp.full(tuple(shape), attrs.get("value", 0.0), dtype=np_dtype(attrs))]}


@register_op("fill_zeros_like")
def _fill_zeros_like(ins, attrs):
    return {"Out": [jnp.zeros_like(first(ins, "X"))]}


@register_op("assign")
def _assign(ins, attrs):
    return {"Out": [first(ins, "X")]}


@register_op("assign_value")
def _assign_value(ins, attrs):
    import numpy as np

    values = np.array(attrs["values"], dtype=np_dtype(attrs)).reshape(attrs["shape"])
    return {"Out": [jnp.asarray(values)]}


@register_op("range", nondiff_inputs=("Start", "End", "Step"))
def _range(ins, attrs):
    start, end, step = first(ins, "Start"), first(ins, "End"), first(ins, "Step")
    # shapes must be static under XLA: require concrete (constant) bounds;
    # reshape to () first - jax refuses float() on [1]-shaped arrays
    return {
        "Out": [
            jnp.arange(
                float(jnp.reshape(start, ())),
                float(jnp.reshape(end, ())),
                float(jnp.reshape(step, ())),
            ).astype(start.dtype)
        ]
    }


@register_op("linspace")
def _linspace(ins, attrs):
    start, stop, num = first(ins, "Start"), first(ins, "Stop"), first(ins, "Num")
    return {"Out": [jnp.linspace(float(start), float(stop), int(num))]}


@register_op("eye")
def _eye(ins, attrs):
    return {
        "Out": [
            jnp.eye(attrs["num_rows"], attrs.get("num_columns"), dtype=np_dtype(attrs))
        ]
    }


# -- manipulation -----------------------------------------------------------


@register_op("reshape2")
def _reshape2(ins, attrs):
    x = first(ins, "X")
    shape = maybe(ins, "Shape", attrs.get("shape"))
    shape = [x.shape[i] if s == 0 else s for i, s in enumerate(shape)]
    return {"Out": [x.reshape(tuple(int(s) for s in shape))], "XShape": [jnp.zeros((0,) + x.shape, dtype=x.dtype)]}


@register_op("reshape")
def _reshape(ins, attrs):
    out = _reshape2(ins, attrs)
    return {"Out": out["Out"]}


@register_op("transpose2")
def _transpose2(ins, attrs):
    x = first(ins, "X")
    return {
        "Out": [jnp.transpose(x, attrs["axis"])],
        "XShape": [jnp.zeros((0,) + x.shape, dtype=x.dtype)],
    }


@register_op("transpose")
def _transpose(ins, attrs):
    return {"Out": [jnp.transpose(first(ins, "X"), attrs["axis"])]}


@register_op("flatten2")
def _flatten2(ins, attrs):
    x = first(ins, "X")
    axis = attrs.get("axis", 1)
    import math

    out = x.reshape((math.prod(x.shape[:axis]) if axis else 1, -1))
    return {"Out": [out], "XShape": [jnp.zeros((0,) + x.shape, dtype=x.dtype)]}


@register_op("squeeze2")
def _squeeze2(ins, attrs):
    x = first(ins, "X")
    axes = attrs.get("axes", [])
    axes = [a % x.ndim for a in axes] if axes else [
        i for i, s in enumerate(x.shape) if s == 1
    ]
    return {
        "Out": [jnp.squeeze(x, tuple(a for a in axes if x.shape[a] == 1))],
        "XShape": [jnp.zeros((0,) + x.shape, dtype=x.dtype)],
    }


@register_op("unsqueeze2")
def _unsqueeze2(ins, attrs):
    x = first(ins, "X")
    out = x
    # reference inserts axes in DECLARATION order, each against the rank
    # grown so far (unsqueeze_op.cc GetOutputShape) — do not sort
    for a in attrs.get("axes", []):
        out = jnp.expand_dims(out, a)
    return {"Out": [out], "XShape": [jnp.zeros((0,) + x.shape, dtype=x.dtype)]}


@register_op("concat")
def _concat(ins, attrs):
    axis = int(maybe(ins, "AxisTensor", attrs.get("axis", 0)))
    return {"Out": [jnp.concatenate(ins["X"], axis=axis)]}


@register_op("split")
def _split(ins, attrs):
    x = first(ins, "X")
    axis = attrs.get("axis", 0)
    num = attrs.get("num", 0)
    sections = attrs.get("sections", [])
    if sections:
        # the offsets are the program's, not the trace's: plain integers
        idx = [sum(sections[:i + 1]) for i in range(len(sections) - 1)]
        outs = jnp.split(x, idx, axis=axis)
    else:
        outs = jnp.split(x, num, axis=axis)
    return {"Out": list(outs)}


@register_op("stack")
def _stack(ins, attrs):
    return {"Y": [jnp.stack(ins["X"], axis=attrs.get("axis", 0))]}


@register_op("unstack")
def _unstack(ins, attrs):
    x = first(ins, "X")
    axis = attrs.get("axis", 0)
    parts = jnp.split(x, x.shape[axis], axis=axis)
    return {"Y": [jnp.squeeze(p, axis) for p in parts]}


@register_op("slice")
def _slice(ins, attrs):
    x = first(ins, "Input")
    axes = attrs["axes"]
    starts = attrs["starts"]
    ends = attrs["ends"]
    idx = [slice(None)] * x.ndim
    for a, s, e in zip(axes, starts, ends):
        dim = x.shape[a]
        s = max(s + dim, 0) if s < 0 else min(s, dim)
        e = max(e + dim, 0) if e < 0 else min(e, dim)
        idx[a] = slice(s, e)
    return {"Out": [x[tuple(idx)]]}


@register_op("strided_slice")
def _strided_slice(ins, attrs):
    x = first(ins, "Input")
    idx = [slice(None)] * x.ndim
    for a, s, e, st in zip(
        attrs["axes"], attrs["starts"], attrs["ends"], attrs["strides"]
    ):
        idx[a] = slice(s, e, st)
    return {"Out": [x[tuple(idx)]]}


@register_op("expand")
def _expand(ins, attrs):
    x = first(ins, "X")
    times = attrs.get("expand_times")
    return {"Out": [jnp.tile(x, tuple(times))]}


@register_op("expand_as")
def _expand_as(ins, attrs):
    x, target = first(ins, "X"), first(ins, "target_tensor")
    return {"Out": [jnp.broadcast_to(x, target.shape)]}


@register_op("tile")
def _tile(ins, attrs):
    return {"Out": [jnp.tile(first(ins, "X"), tuple(attrs["repeat_times"]))]}


@register_op("gather", nondiff_inputs=("Index",))
def _gather(ins, attrs):
    x, index = first(ins, "X"), first(ins, "Index")
    return {"Out": [jnp.take(x, index.reshape(-1), axis=attrs.get("axis", 0))]}


@register_op("gather_nd", nondiff_inputs=("Index",))
def _gather_nd(ins, attrs):
    x, index = first(ins, "X"), first(ins, "Index")
    return {"Out": [x[tuple(jnp.moveaxis(index, -1, 0))]]}


@register_op("scatter", nondiff_inputs=("Ids",))
def _scatter(ins, attrs):
    x, ids, updates = first(ins, "X"), first(ins, "Ids"), first(ins, "Updates")
    # mode="drop" silently skips out-of-range rows — the paged decode
    # arena's "this batch slot writes nowhere" encoding (feed row R)
    kw = {"mode": attrs["mode"]} if attrs.get("mode") else {}
    if attrs.get("overwrite", True):
        out = x.at[ids.reshape(-1)].set(updates, **kw)
    else:
        out = x.at[ids.reshape(-1)].add(updates, **kw)
    return {"Out": [out]}


@register_op("scatter_nd_add", nondiff_inputs=("Index",))
def _scatter_nd_add(ins, attrs):
    x, index, updates = first(ins, "X"), first(ins, "Index"), first(ins, "Updates")
    return {"Out": [x.at[tuple(jnp.moveaxis(index, -1, 0))].add(updates)]}


@register_op("index_select", nondiff_inputs=("Index",))
def _index_select(ins, attrs):
    x, index = first(ins, "X"), first(ins, "Index")
    return {"Out": [jnp.take(x, index, axis=attrs.get("dim", 0))]}


@register_op("flip")
def _flip(ins, attrs):
    return {"Out": [jnp.flip(first(ins, "X"), tuple(attrs["axis"]))]}


@register_op("roll")
def _roll(ins, attrs):
    return {
        "Out": [
            jnp.roll(
                first(ins, "X"), tuple(attrs["shifts"]), tuple(attrs.get("axis", [0]))
            )
        ]
    }


@register_op("pad")
def _pad(ins, attrs):
    x = first(ins, "X")
    p = attrs["paddings"]
    pads = tuple((p[2 * i], p[2 * i + 1]) for i in range(x.ndim))
    return {"Out": [jnp.pad(x, pads, constant_values=attrs.get("pad_value", 0.0))]}


@register_op("pad2d")
def _pad2d(ins, attrs):
    x = first(ins, "X")
    p = attrs["paddings"]
    mode = attrs.get("mode", "constant")
    pads = ((0, 0), (0, 0), (p[0], p[1]), (p[2], p[3]))
    if mode == "constant":
        return {"Out": [jnp.pad(x, pads, constant_values=attrs.get("pad_value", 0.0))]}
    jmode = {"reflect": "reflect", "edge": "edge"}[mode]
    return {"Out": [jnp.pad(x, pads, mode=jmode)]}


@register_op("cast")
def _cast(ins, attrs):
    x = first(ins, "X")
    return {"Out": [x.astype(np_dtype(attrs, "out_dtype"))]}


@register_op("shape", nondiff_inputs=("Input",))
def _shape(ins, attrs):
    x = first(ins, "Input")
    return {"Out": [jnp.asarray(x.shape, dtype=jnp.int32)]}


@register_op("where", nondiff_inputs=("Condition",))
def _where(ins, attrs):
    cond, x, y = first(ins, "Condition"), first(ins, "X"), first(ins, "Y")
    return {"Out": [jnp.where(cond, x, y)]}


@register_op("where_index", nondiff_inputs=("Condition",))
def _where_index(ins, attrs):
    cond = first(ins, "Condition")
    return {"Out": [jnp.argwhere(cond).astype(jnp.int64)]}


# -- comparison / logical ---------------------------------------------------


def _compare(name, fn):
    @register_op(name, nondiff_inputs=("X", "Y"))
    def _lower(ins, attrs, _fn=fn):
        return {"Out": [_fn(first(ins, "X"), first(ins, "Y"))]}


_compare("equal", jnp.equal)
_compare("not_equal", jnp.not_equal)
_compare("less_than", jnp.less)
_compare("less_equal", jnp.less_equal)
_compare("greater_than", jnp.greater)
_compare("greater_equal", jnp.greater_equal)


@register_op("logical_and", nondiff_inputs=("X", "Y"))
def _logical_and(ins, attrs):
    return {"Out": [jnp.logical_and(first(ins, "X"), first(ins, "Y"))]}


@register_op("logical_or", nondiff_inputs=("X", "Y"))
def _logical_or(ins, attrs):
    return {"Out": [jnp.logical_or(first(ins, "X"), first(ins, "Y"))]}


@register_op("logical_not", nondiff_inputs=("X",))
def _logical_not(ins, attrs):
    return {"Out": [jnp.logical_not(first(ins, "X"))]}


@register_op("isfinite", nondiff_inputs=("X",))
def _isfinite(ins, attrs):
    # reference: paddle/fluid/operators/isfinite_op.cc — reduces to a single
    # bool: "all finite"
    return {"Out": [jnp.all(jnp.isfinite(first(ins, "X"))).reshape((1,))]}


@register_op("isfinite_v2", nondiff_inputs=("X",))
def _isfinite_v2(ins, attrs):
    return {"Out": [jnp.isfinite(first(ins, "X"))]}


# -- random (stateful) ------------------------------------------------------


from paddle_tpu.ops.common import seeded_rng_key as _key_for


@register_op("gaussian_random", stateful=True)
def _gaussian_random(ins, attrs):
    shape = tuple(maybe(ins, "ShapeTensor", attrs.get("shape")))
    dtype = np_dtype(attrs)
    out = attrs.get("mean", 0.0) + attrs.get("std", 1.0) * jax.random.normal(
        _key_for(ins, attrs), shape, dtype=jnp.float32
    )
    return {"Out": [out.astype(dtype)]}


@register_op("uniform_random", stateful=True)
def _uniform_random(ins, attrs):
    shape = tuple(maybe(ins, "ShapeTensor", attrs.get("shape")))
    dtype = np_dtype(attrs)
    out = jax.random.uniform(
        _key_for(ins, attrs),
        shape,
        minval=attrs.get("min", -1.0),
        maxval=attrs.get("max", 1.0),
        dtype=jnp.float32,
    )
    return {"Out": [out.astype(dtype)]}


@register_op("truncated_gaussian_random", stateful=True)
def _truncated_gaussian_random(ins, attrs):
    shape = tuple(attrs.get("shape"))
    std = attrs.get("std", 1.0)
    mean = attrs.get("mean", 0.0)
    out = mean + std * jax.random.truncated_normal(
        _key_for(ins, attrs), -2.0, 2.0, shape, dtype=jnp.float32
    )
    return {"Out": [out.astype(np_dtype(attrs))]}


@register_op("randint", stateful=True)
def _randint(ins, attrs):
    shape = tuple(attrs.get("shape"))
    out = jax.random.randint(
        _key_for(ins, attrs), shape, attrs.get("low", 0), attrs.get("high", 100)
    )
    return {"Out": [out.astype(np_dtype(attrs, default="int64"))]}


@register_op("randperm", stateful=True)
def _randperm(ins, attrs):
    n = attrs["n"]
    return {
        "Out": [
            jax.random.permutation(_key_for(ins, attrs), n).astype(
                np_dtype(attrs, default="int64")
            )
        ]
    }


@register_op("bernoulli", stateful=True)
def _bernoulli(ins, attrs):
    x = first(ins, "X")
    return {
        "Out": [jax.random.bernoulli(_key_for(ins, attrs), x).astype(x.dtype)]
    }


@register_op("print")
def _print(ins, attrs):
    """Debug print via jax.debug (reference: paddle/fluid/operators/
    print_op.cc + platform/lodtensor_printer.cc)."""
    x = first(ins, "In")
    jax.debug.print(attrs.get("message", "print") + ": {x}", x=x)
    return {"Out": [x]}


@register_op("batched_gather", nondiff_inputs=("Index",))
def _batched_gather(ins, attrs):
    """Per-row gather along axis 1: X [B, S, ...] + Index [B, P] ->
    [B, P, ...] (the masked-position gather BERT-style pretraining needs;
    the reference reaches the same result with LoD + sequence ops).

    ROWS move, not elements: the index stays [B, P, 1, ...], so the
    gather's slice covers every trailing dimension of X (B*P contiguous
    copies) and its vjp is a row scatter-add. Broadcasting the index to
    the output's shape instead makes every element its own fetch and the
    gradient an element scatter behind a sort over B*P*H indices (a fifth
    of BERT-base's step on a v5e, PERF.md PR 26). A float X narrower than
    float32 goes through float32, which is exact forward and keeps
    positions named twice in one row from summing their gradients in
    bfloat16."""
    x = first(ins, "X")
    idx = first(ins, "Index").astype(jnp.int32)
    idx_e = idx.reshape(idx.shape + (1,) * (x.ndim - 2))
    wide = x
    if jnp.issubdtype(x.dtype, jnp.floating):
        wide = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    return {"Out": [
        jnp.take_along_axis(wide, idx_e, axis=1).astype(x.dtype)
    ]}
