"""Elementwise / matmul / reduction op lowerings.

Replaces the reference's hand-written CPU/CUDA kernels
(reference: paddle/fluid/operators/elementwise/, math/blas.h,
reduce_ops/) with jnp lowerings traced into the whole-block XLA computation —
elementwise chains fuse into neighboring matmuls, and matmuls hit the MXU in
bf16/fp32 via lax.dot_general with no per-op dispatch.
"""

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import register_op
from paddle_tpu.ops.common import broadcast_y, first, maybe, reduce_axes


def _elementwise(name, fn):
    @register_op(name)
    def _lower(ins, attrs, _fn=fn):
        x, y = first(ins, "X"), first(ins, "Y")
        y = broadcast_y(x, y, attrs.get("axis", -1))
        return {"Out": [_fn(x, y)]}


_elementwise("elementwise_add", jnp.add)
_elementwise("elementwise_sub", jnp.subtract)
_elementwise("elementwise_mul", jnp.multiply)
_elementwise("elementwise_div", jnp.divide)
_elementwise("elementwise_min", jnp.minimum)
_elementwise("elementwise_max", jnp.maximum)
_elementwise("elementwise_pow", jnp.power)
_elementwise("elementwise_mod", jnp.mod)
_elementwise("elementwise_floordiv", jnp.floor_divide)


@register_op("matmul")
def _matmul(ins, attrs):
    x, y = first(ins, "X"), first(ins, "Y")
    if attrs.get("transpose_X", False):
        x = jnp.swapaxes(x, -1, -2) if x.ndim > 1 else x
    if attrs.get("transpose_Y", False):
        y = jnp.swapaxes(y, -1, -2) if y.ndim > 1 else y
    # ``out_dtype``: a narrow product accumulated, and handed on, in a
    # wider dtype (``mul`` has the same)
    out = jnp.matmul(x, y, preferred_element_type=attrs.get("out_dtype"))
    alpha = attrs.get("alpha", 1.0)
    if alpha != 1.0:
        out = out * alpha
    return {"Out": [out]}


@register_op("mul")
def _mul(ins, attrs):
    """FC-style matmul with input flattening
    (reference: paddle/fluid/operators/mul_op.cc)."""
    import math

    x, y = first(ins, "X"), first(ins, "Y")
    xnc = attrs.get("x_num_col_dims", 1)
    ync = attrs.get("y_num_col_dims", 1)
    xs, ys = x.shape, y.shape
    x2 = x.reshape((math.prod(xs[:xnc]), -1))
    y2 = y.reshape((math.prod(ys[:ync]), -1))
    if attrs.get("out_dtype"):
        # a narrow product accumulated, and handed on, in a wider dtype
        out = jnp.matmul(x2, y2, preferred_element_type=attrs["out_dtype"])
    else:
        out = x2 @ y2
    out_shape = tuple(xs[:xnc]) + tuple(ys[ync:])
    return {"Out": [out.reshape(out_shape)]}


@register_op("scale")
def _scale(ins, attrs):
    x = first(ins, "X")
    scale = maybe(ins, "ScaleTensor", attrs.get("scale", 1.0))
    bias = attrs.get("bias", 0.0)
    if attrs.get("bias_after_scale", True):
        return {"Out": [x * scale + bias]}
    return {"Out": [(x + bias) * scale]}


@register_op("sum")
def _sum(ins, attrs):
    xs = ins["X"]
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return {"Out": [out]}


def _unary(name, fn):
    @register_op(name)
    def _lower(ins, attrs, _fn=fn):
        return {"Out": [_fn(first(ins, "X"))]}


_unary("sqrt", jnp.sqrt)
_unary("rsqrt", jax.lax.rsqrt)
_unary("square", jnp.square)
_unary("abs", jnp.abs)
_unary("log", jnp.log)
_unary("log2", jnp.log2)
_unary("log1p", jnp.log1p)
_unary("exp", jnp.exp)
_unary("floor", jnp.floor)
_unary("ceil", jnp.ceil)
_unary("round", jnp.round)
_unary("reciprocal", jnp.reciprocal)
_unary("sign", jnp.sign)
_unary("sin", jnp.sin)
_unary("cos", jnp.cos)
_unary("erf", jax.scipy.special.erf)


@register_op("pow")
def _pow(ins, attrs):
    x = first(ins, "X")
    factor = maybe(ins, "FactorTensor", attrs.get("factor", 1.0))
    return {"Out": [jnp.power(x, factor)]}


@register_op("clip")
def _clip(ins, attrs):
    x = first(ins, "X")
    return {"Out": [jnp.clip(x, attrs.get("min"), attrs.get("max"))]}


@register_op("clip_by_norm")
def _clip_by_norm(ins, attrs):
    x = first(ins, "X")
    max_norm = attrs["max_norm"]
    norm = jnp.sqrt(jnp.sum(jnp.square(x)))
    return {"Out": [jnp.where(norm > max_norm, x * (max_norm / norm), x)]}


@register_op("squared_l2_norm")
def _squared_l2_norm(ins, attrs):
    x = first(ins, "X")
    return {"Out": [jnp.sum(jnp.square(x)).reshape((1,))]}


@register_op("mean")
def _mean(ins, attrs):
    return {"Out": [jnp.mean(first(ins, "X")).reshape((1,))]}


def _reduce(name, fn):
    @register_op(name)
    def _lower(ins, attrs, _fn=fn):
        x = first(ins, "X")
        axes = reduce_axes(attrs, x.ndim)
        out = _fn(x, axis=axes, keepdims=attrs.get("keep_dim", False))
        if out.ndim == 0:
            out = out.reshape((1,)) if not attrs.get("keep_scalar", False) else out
        return {"Out": [out]}


_reduce("reduce_sum", jnp.sum)
_reduce("reduce_mean", jnp.mean)
_reduce("reduce_max", jnp.max)
_reduce("reduce_min", jnp.min)
_reduce("reduce_prod", jnp.prod)


@register_op("arg_max", nondiff_inputs=("X",))
def _arg_max(ins, attrs):
    x = first(ins, "X")
    return {"Out": [jnp.argmax(x, axis=attrs.get("axis", -1)).astype(jnp.int64)]}


@register_op("arg_min", nondiff_inputs=("X",))
def _arg_min(ins, attrs):
    x = first(ins, "X")
    return {"Out": [jnp.argmin(x, axis=attrs.get("axis", -1)).astype(jnp.int64)]}


@register_op("top_k", nondiff_inputs=())
def _top_k(ins, attrs):
    x = first(ins, "X")
    k = int(maybe(ins, "K", attrs.get("k", 1)))
    vals, idx = jax.lax.top_k(x, k)
    return {"Out": [vals], "Indices": [idx.astype(jnp.int64)]}


@register_op("p_norm")
def _p_norm(ins, attrs):
    x = first(ins, "X")
    p = attrs.get("porder", 2.0)
    axis = attrs.get("axis", -1)
    keepdim = attrs.get("keepdim", False)
    out = jnp.power(
        jnp.sum(jnp.power(jnp.abs(x), p), axis=axis, keepdims=keepdim), 1.0 / p
    )
    return {"Out": [out]}


@register_op("cumsum")
def _cumsum(ins, attrs):
    x = first(ins, "X")
    axis = attrs.get("axis", -1)
    out = jnp.cumsum(x, axis=axis)
    if attrs.get("reverse", False):
        out = jnp.flip(jnp.cumsum(jnp.flip(x, axis), axis=axis), axis)
    if attrs.get("exclusive", False):
        out = out - x
    return {"Out": [out]}


@register_op("dot")
def _dot(ins, attrs):
    x, y = first(ins, "X"), first(ins, "Y")
    return {"Out": [jnp.sum(x * y, axis=-1, keepdims=True)]}
