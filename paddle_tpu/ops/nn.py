"""Neural-network op lowerings: conv/pool/norm/activation/loss/embedding.

Replaces the reference's cuDNN-backed kernels (reference:
paddle/fluid/operators/conv_cudnn_op.cu, pool_op.cu, batch_norm_op.cu,
softmax_with_cross_entropy_op.cu, lookup_table_op.cu) with lax/jnp lowerings:
convs hit the MXU via lax.conv_general_dilated, norms/activations fuse into
their neighbors under whole-block XLA compilation.
"""

import math

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import OpDef, OpRegistry, register_op, register_grad
from paddle_tpu.ops.common import (
    DATA_AXIS_NAMES,
    first,
    gelu,
    keep_mask,
    maybe,
    mesh_axes_dividing,
    normalize_padding,
    rng_key,
    seeded_rng_key,
    vma_names,
)
from paddle_tpu.utils.enforce import EnforceError

# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------


def _activation(name, fn):
    @register_op(name)
    def _lower(ins, attrs, _fn=fn):
        return {"Out": [_fn(first(ins, "X"), attrs)]}


_activation("relu", lambda x, a: jax.nn.relu(x))
_activation("relu6", lambda x, a: jnp.minimum(jax.nn.relu(x), a.get("threshold", 6.0)))
_activation("sigmoid", lambda x, a: jax.nn.sigmoid(x))
_activation("tanh", lambda x, a: jnp.tanh(x))
_activation("gelu", lambda x, a: gelu(
    x, approximate=a.get("approximate", False),
    round_dtype=a.get("round_dtype")))
_activation("leaky_relu", lambda x, a: jax.nn.leaky_relu(x, a.get("alpha", 0.02)))
_activation("elu", lambda x, a: jax.nn.elu(x, a.get("alpha", 1.0)))
_activation("softplus", lambda x, a: jax.nn.softplus(x))
_activation("softsign", lambda x, a: jax.nn.soft_sign(x))
_activation("silu", lambda x, a: jax.nn.silu(x))
_activation("swish", lambda x, a: x * jax.nn.sigmoid(a.get("beta", 1.0) * x))
_activation(
    "hard_sigmoid",
    lambda x, a: jnp.clip(a.get("slope", 0.2) * x + a.get("offset", 0.5), 0.0, 1.0),
)
_activation(
    "hard_swish",
    lambda x, a: x
    * jnp.clip(x + a.get("offset", 3.0), 0.0, a.get("threshold", 6.0))
    / a.get("scale", 6.0),
)
_activation("mish", lambda x, a: x * jnp.tanh(jax.nn.softplus(x)))


@register_op("softmax")
def _softmax(ins, attrs):
    return {"Out": [jax.nn.softmax(first(ins, "X"), axis=attrs.get("axis", -1))]}


@register_op("log_softmax")
def _log_softmax(ins, attrs):
    return {"Out": [jax.nn.log_softmax(first(ins, "X"), axis=attrs.get("axis", -1))]}


@register_op("prelu")
def _prelu(ins, attrs):
    x, alpha = first(ins, "X"), first(ins, "Alpha")
    if attrs.get("mode", "all") == "channel" and alpha.ndim == 1:
        alpha = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    return {"Out": [jnp.where(x > 0, x, alpha * x)]}


# ---------------------------------------------------------------------------
# conv / pool
# ---------------------------------------------------------------------------


@register_op("conv2d")
def _conv2d(ins, attrs):
    """reference: paddle/fluid/operators/conv_op.cc (NCHW, OIHW filters)."""
    x, w = first(ins, "Input"), first(ins, "Filter")
    strides = attrs.get("strides", [1, 1])
    dilations = attrs.get("dilations", [1, 1])
    groups = attrs.get("groups", 1)
    layout = attrs.get("data_format", "NCHW")
    if layout == "NHWC":
        dn = ("NHWC", "HWIO", "NHWC")
        spatial = x.shape[1:3]
    else:
        dn = ("NCHW", "OIHW", "NCHW")
        spatial = x.shape[2:4]
    ksize = w.shape[2:4] if layout == "NCHW" else w.shape[0:2]
    padding = normalize_padding(attrs, 2, ksize, strides, spatial)
    out = jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=tuple(strides),
        padding=padding,
        rhs_dilation=tuple(dilations),
        dimension_numbers=dn,
        feature_group_count=groups,
        preferred_element_type=None,
    )
    return {"Output": [out]}


@register_op("depthwise_conv2d")
def _depthwise_conv2d(ins, attrs):
    attrs = dict(attrs)
    x = first(ins, "Input")
    channels = x.shape[1] if attrs.get("data_format", "NCHW") == "NCHW" else x.shape[-1]
    attrs["groups"] = channels
    return {"Output": _conv2d(ins, attrs)["Output"]}


@register_op("conv2d_transpose")
def _conv2d_transpose(ins, attrs):
    """Transposed conv as an input-dilated forward conv (supports groups,
    which lax.conv_transpose does not). Filter layout follows the reference:
    [in_c, out_c/groups, kh, kw] (reference: paddle/fluid/operators/
    conv_transpose_op.cc)."""
    x, w = first(ins, "Input"), first(ins, "Filter")
    strides = tuple(attrs.get("strides", [1, 1]))
    groups = attrs.get("groups", 1)
    pads = attrs.get("paddings", [0, 0])
    if len(pads) == 2:
        ph, pw = pads
        pads4 = (ph, ph, pw, pw)
    else:
        pads4 = tuple(pads)
    in_c, oc_per_g, kh, kw = w.shape
    # [in_c, out_c/g, kh, kw] -> flipped, grouped OIHW [out_c, in_c/g, kh, kw]
    wf = jnp.flip(w, (2, 3))
    wf = wf.reshape(groups, in_c // groups, oc_per_g, kh, kw)
    wf = jnp.swapaxes(wf, 1, 2).reshape(groups * oc_per_g, in_c // groups, kh, kw)
    padding = (
        (kh - 1 - pads4[0], kh - 1 - pads4[1]),
        (kw - 1 - pads4[2], kw - 1 - pads4[3]),
    )
    out = jax.lax.conv_general_dilated(
        x,
        wf,
        window_strides=(1, 1),
        padding=padding,
        lhs_dilation=strides,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups,
    )
    return {"Output": [out]}


@register_op("pool2d")
def _pool2d(ins, attrs):
    """reference: paddle/fluid/operators/pool_op.cc."""
    x = first(ins, "X")
    ptype = attrs.get("pooling_type", "max")
    layout = attrs.get("data_format", "NCHW")
    if layout != "NCHW":
        x = jnp.transpose(x, (0, 3, 1, 2))
    spatial = x.shape[2:4]
    if attrs.get("global_pooling", False) or (
        attrs.get("adaptive", False) and list(attrs.get("ksize", [1, 1])) == [1, 1]
    ):
        red = jnp.max if ptype == "max" else jnp.mean
        out = red(x, axis=(2, 3), keepdims=True)
    elif attrs.get("adaptive", False):
        oh, ow = attrs["ksize"]
        red = jnp.max if ptype == "max" else jnp.mean
        # adaptive pooling with uniform regions (exact when divisible)
        n, c, h, wd = x.shape
        out = red(
            x[:, :, : (h // oh) * oh, : (wd // ow) * ow].reshape(
                n, c, oh, h // oh, ow, wd // ow
            ),
            axis=(3, 5),
        )
    else:
        ksize = tuple(attrs.get("ksize", [2, 2]))
        strides = tuple(attrs.get("strides", ksize))
        padding = normalize_padding(attrs, 2, ksize, strides, spatial)
        window = (1, 1) + ksize
        strides4 = (1, 1) + strides
        pads4 = ((0, 0), (0, 0)) + padding
        if ptype == "max":
            out = jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, window, strides4, pads4
            )
            out = out.astype(x.dtype)
        else:
            summed = jax.lax.reduce_window(
                x, 0.0, jax.lax.add, window, strides4, pads4
            )
            if attrs.get("exclusive", True) and any(p != (0, 0) for p in padding):
                ones = jnp.ones_like(x)
                counts = jax.lax.reduce_window(
                    ones, 0.0, jax.lax.add, window, strides4, pads4
                )
                out = summed / counts
            else:
                out = summed / (ksize[0] * ksize[1])
    if layout != "NCHW":
        out = jnp.transpose(out, (0, 2, 3, 1))
    return {"Out": [out]}


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------


@register_op("batch_norm", nondiff_inputs=("Mean", "Variance"))
def _batch_norm(ins, attrs):
    """reference: paddle/fluid/operators/batch_norm_op.cc. Running stats are
    data outputs (MeanOut/VarianceOut), not side effects — functional-state
    threading replaces the reference's in-place variable mutation."""
    x = first(ins, "X")
    scale, bias = first(ins, "Scale"), first(ins, "Bias")
    mean, var = first(ins, "Mean"), first(ins, "Variance")
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    layout = attrs.get("data_layout", "NCHW")
    axes = (
        tuple(i for i in range(x.ndim) if i != 1)
        if layout == "NCHW"
        else tuple(range(x.ndim - 1))
    )
    shape = (1, -1) + (1,) * (x.ndim - 2) if layout == "NCHW" else (-1,)
    if attrs.get("is_test", False) or attrs.get("use_global_stats", False):
        use_mean, use_var = mean, var
        mean_out, var_out = mean, var
        saved_mean = jnp.zeros_like(mean)
        saved_var = jnp.zeros_like(var)
    else:
        compute = x.astype(jnp.float32)
        use_mean = jnp.mean(compute, axis=axes)
        use_var = jnp.var(compute, axis=axes)
        mean_out = momentum * mean + (1.0 - momentum) * use_mean.astype(mean.dtype)
        var_out = momentum * var + (1.0 - momentum) * use_var.astype(var.dtype)
        saved_mean = use_mean
        saved_var = 1.0 / jnp.sqrt(use_var + eps)
    inv = 1.0 / jnp.sqrt(use_var.astype(jnp.float32) + eps)
    y = (x.astype(jnp.float32) - use_mean.reshape(shape)) * inv.reshape(shape)
    y = y * scale.reshape(shape) + bias.reshape(shape)
    return {
        "Y": [y.astype(x.dtype)],
        "MeanOut": [mean_out],
        "VarianceOut": [var_out],
        "SavedMean": [saved_mean],
        "SavedVariance": [saved_var],
    }


@register_op("layer_norm")
def _layer_norm(ins, attrs):
    x = first(ins, "X")
    begin = attrs.get("begin_norm_axis", 1)
    eps = attrs.get("epsilon", 1e-5)
    axes = tuple(range(begin, x.ndim))
    compute = x.astype(jnp.float32)
    mean = jnp.mean(compute, axis=axes, keepdims=True)
    var = jnp.var(compute, axis=axes, keepdims=True)
    y = (compute - mean) / jnp.sqrt(var + eps)
    scale, bias = maybe(ins, "Scale"), maybe(ins, "Bias")
    norm_shape = x.shape[begin:]
    if scale is not None:
        y = y * scale.reshape(norm_shape).astype(jnp.float32)
    if bias is not None:
        y = y + bias.reshape(norm_shape).astype(jnp.float32)
    return {
        "Y": [y.astype(x.dtype)],
        "Mean": [jnp.squeeze(mean, axes)],
        "Variance": [jnp.squeeze(var, axes)],
    }


@register_op("instance_norm")
def _instance_norm(ins, attrs):
    x = first(ins, "X")
    eps = attrs.get("epsilon", 1e-5)
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    y = (x - mean) / jnp.sqrt(var + eps)
    scale, bias = maybe(ins, "Scale"), maybe(ins, "Bias")
    shape = (1, -1) + (1,) * (x.ndim - 2)
    if scale is not None:
        y = y * scale.reshape(shape)
    if bias is not None:
        y = y + bias.reshape(shape)
    return {"Y": [y], "SavedMean": [mean], "SavedVariance": [var]}


@register_op("group_norm")
def _group_norm(ins, attrs):
    x = first(ins, "X")
    groups = attrs.get("groups", 1)
    eps = attrs.get("epsilon", 1e-5)
    n, c = x.shape[0], x.shape[1]
    g = x.reshape((n, groups, c // groups) + x.shape[2:])
    axes = tuple(range(2, g.ndim))
    mean = jnp.mean(g, axis=axes, keepdims=True)
    var = jnp.var(g, axis=axes, keepdims=True)
    y = ((g - mean) / jnp.sqrt(var + eps)).reshape(x.shape)
    scale, bias = maybe(ins, "Scale"), maybe(ins, "Bias")
    shape = (1, -1) + (1,) * (x.ndim - 2)
    if scale is not None:
        y = y * scale.reshape(shape)
    if bias is not None:
        y = y + bias.reshape(shape)
    return {"Y": [y], "Mean": [mean.reshape(n, groups)], "Variance": [var.reshape(n, groups)]}


# ---------------------------------------------------------------------------
# dropout (stateful: consumes the executor-provided rng key)
# ---------------------------------------------------------------------------


@register_op("dropout", stateful=True)
def _dropout(ins, attrs):
    """reference: paddle/fluid/operators/dropout_op.cc. Both implementations
    of the reference are supported; mask is a saved output consumed by the
    custom grad (so backward reuses the forward mask instead of re-sampling)."""
    x = first(ins, "X")
    p = attrs.get("dropout_prob", 0.5)
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if attrs.get("is_test", False):
        out = x if impl == "upscale_in_train" else x * (1.0 - p)
        return {"Out": [out], "Mask": [jnp.ones_like(x)]}
    keep = keep_mask(seeded_rng_key(ins, attrs), 1.0 - p, x.shape)
    mask = keep.astype(x.dtype)
    if impl == "upscale_in_train":
        out = jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)
    else:
        out = x * mask
    return {"Out": [out], "Mask": [mask]}


@register_grad("dropout")
def _dropout_grad(ins, attrs):
    dout = first(ins, "Out@GRAD")
    mask = first(ins, "Mask")
    p = attrs.get("dropout_prob", 0.5)
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if attrs.get("is_test", False):
        dx = dout if impl == "upscale_in_train" else dout * (1.0 - p)
    elif impl == "upscale_in_train":
        dx = dout * mask / (1.0 - p)
    else:
        dx = dout * mask
    return {"X@GRAD": [dx]}


# ---------------------------------------------------------------------------
# embedding
# ---------------------------------------------------------------------------


@register_op("lookup_table_v2", nondiff_inputs=("Ids",))
def _lookup_table(ins, attrs):
    """reference: paddle/fluid/operators/lookup_table_op.cc. Dense gather on
    TPU; the billion-feature sparse path lives in the PS stack instead
    (SelectedRows grads are a host-side concern there)."""
    w, ids = first(ins, "W"), first(ins, "Ids")
    out = jnp.take(w, ids, axis=0)
    padding_idx = attrs.get("padding_idx", -1)
    if padding_idx is not None and padding_idx >= 0:
        out = jnp.where((ids == padding_idx)[..., None], 0.0, out)
    return {"Out": [out]}


@register_op("lookup_table", nondiff_inputs=("Ids",))
def _lookup_table_v1(ins, attrs):
    w, ids = first(ins, "W"), first(ins, "Ids")
    if ids.ndim == 2 and ids.shape[-1] == 1:
        ids = ids[..., 0]
    return _lookup_table({"W": [w], "Ids": [ids]}, attrs)


@register_op("lookup_table_ps", nondiff_inputs=("Idx",))
def _lookup_table_ps(ins, attrs):
    """PS-backed embedding lookup: `Rows` are the batch's unique embedding
    vectors pulled from the parameter server by the worker (host side,
    fleet/parameter_server.py), `Idx` maps each id occurrence to its row.
    The gather's vjp sums duplicate-id grads into per-row grads — exactly
    the SelectedRows grad aggregation the reference does in
    lookup_table_grad (reference: paddle/fluid/operators/lookup_table_op.h
    LookupTableGradKernel) but expressed as dense XLA."""
    rows, idx = first(ins, "Rows"), first(ins, "Idx")
    return {"Out": [jnp.take(rows, idx, axis=0)]}


def _sdpa_seq_parallel(ins, attrs):
    """Sequence-parallel route: when the op carries seq_parallel='ring' |
    'ulysses' and the active mesh (CompiledProgram.with_parallel) has the
    named seq axis >1, attention runs sequence-sharded — ring rotation via
    ppermute or Ulysses head-scatter all_to_alls (parallel/ring.py,
    parallel/ulysses.py). Returns None when the plain single-shard path
    should run (no mesh, axis absent/size 1). SURVEY §5.7 IR-path form."""
    mode = attrs.get("seq_parallel")
    if not mode:
        return None
    from paddle_tpu.parallel import env as penv

    mesh = penv.current_mesh()
    axis = attrs.get("seq_axis", "seq")
    if mesh is None or axis not in mesh.axis_names:
        return None
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if sizes.get(axis, 1) <= 1:
        return None
    q, k, v = first(ins, "Q"), first(ins, "K"), first(ins, "V")
    if vma_names(q):
        raise EnforceError(
            "seq_parallel scaled_dot_product_attention cannot run inside an "
            "already-manual region (e.g. a pipeline_stack body); shard the "
            "sequence axis on the outer program instead"
        )
    if ins.get("Bias"):
        raise EnforceError(
            "seq_parallel scaled_dot_product_attention does not take Bias; "
            "fold padding into the sequence instead"
        )
    causal = attrs.get("causal", False)
    scale = attrs.get("sm_scale")
    if mode == "ring":
        from paddle_tpu.parallel.ring import ring_attention

        out = ring_attention(q, k, v, mesh, seq_axis=axis, causal=causal,
                             scale=scale, batch_axis="data")
    elif mode == "ulysses":
        from paddle_tpu.parallel.ulysses import ulysses_attention

        out = ulysses_attention(q, k, v, mesh, seq_axis=axis, causal=causal,
                                scale=scale, batch_axis="data")
    else:
        raise EnforceError(
            f"unknown seq_parallel mode {mode!r} (want 'ring' or 'ulysses')"
        )
    return {"Out": [out]}


def _sdpa_reference(ins, attrs):
    """Unfused attention (XLA-fused path): q,k,v [B,H,S,D], optional additive
    key bias [B,S]."""
    sp = _sdpa_seq_parallel(ins, attrs)
    if sp is not None:
        return sp
    q, k, v = first(ins, "Q"), first(ins, "K"), first(ins, "V")
    bias = first(ins, "Bias") if ins.get("Bias") else None
    scale = attrs.get("sm_scale") or 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if bias is not None:
        s = s + bias[:, None, None, :]
    if attrs.get("causal", False):
        S = q.shape[2]
        m = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(m[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return {"Out": [jnp.einsum("bhqk,bhkd->bhqd", p, v)]}


def _flash_per_shard(mesh, q, k, v, bias, **kw):
    """GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    shard_map"), so under a multi-device mesh the compiled kernel runs
    per shard: batch over the data axes ('dcn' x 'data') and heads over
    the tensor-parallel axis (the Megatron split) where they divide,
    every other axis replicated; GSPMD reshards around the region if the
    operands arrive laid out otherwise."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    from paddle_tpu.parallel.spec_layout import TP_AXIS_NAMES

    batch = mesh_axes_dividing(mesh, DATA_AXIS_NAMES, q.shape[0])
    spec = P(batch, mesh_axes_dividing(mesh, TP_AXIS_NAMES, q.shape[1]),
             None, None)
    args, in_specs = (q, k, v), (spec, spec, spec)
    if bias is not None:
        args, in_specs = args + (bias,), in_specs + (P(batch, None),)

    def local(q, k, v, *b):
        return flash_attention(q, k, v, bias=b[0] if b else None, **kw)

    return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                         out_specs=spec)(*args)


def _sdpa_pallas(ins, attrs):
    from paddle_tpu.kernels import registry as kernel_registry
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    from paddle_tpu.parallel import env as penv

    sp = _sdpa_seq_parallel(ins, attrs)
    if sp is not None:
        return sp
    sel = kernel_registry.selected("flash_attention")
    if sel is None:
        # composite fallback is mandatory: PADDLE_TPU_KERNELS=off, or
        # auto off-TPU (interpret mode is a parity tool, not a fast path)
        return _sdpa_reference(ins, attrs)
    q, k, v = first(ins, "Q"), first(ins, "K"), first(ins, "V")
    bias = first(ins, "Bias") if ins.get("Bias") else None
    kw = dict(causal=attrs.get("causal", False),
              sm_scale=attrs.get("sm_scale"), interpret=sel.interpret)
    mesh = penv.current_mesh()
    if (mesh is not None and mesh.devices.size > 1 and not sel.interpret
            and not vma_names(q)):
        return {"Out": [_flash_per_shard(mesh, q, k, v, bias, **kw)]}
    return {"Out": [flash_attention(q, k, v, bias=bias, **kw)]}


OpRegistry.register(
    OpDef(
        "scaled_dot_product_attention",
        _sdpa_reference,
        pallas=_sdpa_pallas,
        nondiff_inputs=(),
    )
)


# ---------------------------------------------------------------------------
# fused decode attention (paddle_tpu/kernels/): cached (dense slotted) and
# paged (block-arena row feeds). The reference lowerings ARE the composite
# primitive sequences the old layer composites emitted. The cached kernel
# is that sequence verbatim (bit-identical by shared definition); the
# paged kernel is an online softmax over the live blocks, held to the
# composite within a tolerance (kernels/attention.py).
# ---------------------------------------------------------------------------


def _cached_attention_reference(ins, attrs):
    from paddle_tpu.kernels import attention as fused

    q, k, v = first(ins, "Q"), first(ins, "KCache"), first(ins, "VCache")
    bias = first(ins, "Bias")
    return {"Out": [fused.cached_attention_composite(
        q, k, v, bias, attrs.get("sm_scale", 1.0))]}


def _cached_attention_pallas(ins, attrs):
    from paddle_tpu.kernels import attention as fused
    from paddle_tpu.kernels import registry as kernel_registry

    sel = kernel_registry.selected("cached_attention")
    if sel is None:
        return _cached_attention_reference(ins, attrs)
    q, k, v = first(ins, "Q"), first(ins, "KCache"), first(ins, "VCache")
    bias = first(ins, "Bias")
    return {"Out": [fused.decode_attention(
        q, k, v, bias, attrs.get("sm_scale", 1.0),
        interpret=sel.interpret)]}


OpRegistry.register(
    OpDef(
        "cached_attention",
        _cached_attention_reference,
        pallas=_cached_attention_pallas,
        nondiff_inputs=("Bias",),
    )
)


def _paged_attention_reference(ins, attrs):
    from paddle_tpu.kernels import attention as fused

    q = first(ins, "Q")
    ka, va = first(ins, "KArena"), first(ins, "VArena")
    rows, bias = first(ins, "Rows"), first(ins, "Bias")
    return {"Out": [fused.paged_attention_composite(
        q, ka, va, rows, bias, attrs["seqs"], attrs["length"],
        attrs.get("sm_scale", 1.0), kv_heads=attrs.get("kv_heads", 0))]}


def _paged_attention_pallas(ins, attrs):
    from paddle_tpu import kernels
    from paddle_tpu.kernels import attention as fused

    sel = kernels.selected_for("paged_attention", attrs)
    if sel is None:
        return _paged_attention_reference(ins, attrs)
    q = first(ins, "Q")
    ka, va = first(ins, "KArena"), first(ins, "VArena")
    rows, bias = first(ins, "Rows"), first(ins, "Bias")
    return {"Out": [fused.paged_attention(
        q, ka, va, rows, bias, attrs["seqs"], attrs["length"],
        attrs["block_size"], attrs.get("sm_scale", 1.0),
        interpret=sel.interpret, kv_heads=attrs.get("kv_heads", 0))]}


OpRegistry.register(
    OpDef(
        "paged_attention",
        _paged_attention_reference,
        pallas=_paged_attention_pallas,
        nondiff_inputs=("Rows", "Bias"),
    )
)


@register_op("paged_step_feeds", nondiff_inputs=("Packed", "Token"))
def _paged_step_feeds(ins, attrs):
    """What a paged decode step reads of its slots, from the ONE integer
    array the host puts a step: ``Packed`` ``[S, 4 + T]`` holds a slot's
    token (negative: take ``Token``'s, the device array of the step
    before), its position, its attention length (0: the slot does not
    step), its write row, then its block table (block ids, T =
    ``ceil(length / block_size)``). Gives the ``[S, 1]`` tokens and
    positions, the additive ``[S, 1, L]`` bias (0.0 below a slot's length,
    -1e9 from it on), the ``[S * L]`` row map (position ``p`` reads row
    ``table[p // block_size] * block_size + p % block_size``) and the
    ``[S]`` write rows."""
    packed, token = first(ins, "Packed"), first(ins, "Token")
    L, bs = int(attrs["length"]), int(attrs["block_size"])
    S = packed.shape[0]
    head = packed[:, :4, None]                                  # [S, 4, 1]
    table = packed[:, 4:]
    own = head[:, 0]
    within = jnp.arange(bs, dtype=packed.dtype)
    rows = (table[:, :, None] * bs + within).reshape(S, -1)[:, :L]
    open_ = jnp.arange(L, dtype=packed.dtype) < head[:, 2]      # [S, L]
    return {
        "TokenOut": [jnp.where(own < 0, token, own)],
        "Position": [head[:, 1]],
        "Bias": [jnp.where(open_, 0.0, -1e9).astype(jnp.float32)[:, None]],
        "Rows": [rows.reshape(-1)],
        "WriteRows": [packed[:, 3]],
    }


@register_op("paged_window_feeds", nondiff_inputs=("Packed",))
def _paged_window_feeds(ins, attrs):
    """What a paged decode step reads of its slots in ONE window group
    (serving/decode/model.py ``KVGroup``), from the columns of ``Packed``
    that ``paged_step_feeds`` leaves alone: from ``column`` on a slot's
    ``length, low, write_row`` and a table of ``blocks`` block ids that
    starts at the slot's first LIVE block. Counted from that block's first
    position the step's query sees rows ``[low, length)``: ``low`` masks the
    rows of the oldest block that have left the window, a ``length`` of 0
    everything (the slot does not step). Gives the additive ``[S, 1, blocks
    * block_size]`` bias, the row map over those rows and the ``[S]`` write
    rows: what ``paged_attention`` takes for a layer of the group."""
    packed = first(ins, "Packed")
    at, n, bs = (int(attrs["column"]), int(attrs["blocks"]),
                 int(attrs["block_size"]))
    S = packed.shape[0]
    head = packed[:, at:at + 3]
    table = packed[:, at + 3:at + 3 + n]
    within = jnp.arange(bs, dtype=packed.dtype)
    rows = (table[:, :, None] * bs + within).reshape(S, -1)
    j = jnp.arange(n * bs, dtype=packed.dtype)
    open_ = (j >= head[:, 1:2]) & (j < head[:, 0:1])            # [S, n * bs]
    return {
        "Bias": [jnp.where(open_, 0.0, -1e9).astype(jnp.float32)[:, None]],
        "Rows": [rows.reshape(-1)],
        "WriteRows": [head[:, 2]],
    }


@register_op("paged_block_feeds", nondiff_inputs=("Packed", "State"))
def _paged_block_feeds(ins, attrs):
    """``paged_step_feeds`` for a step that runs a BLOCK of ``B`` positions
    a slot, all of which see every earlier block and the whole of their
    own. ``Packed`` ``[S, 4 + B + T]`` holds a slot's flag (negative: its
    block is ``State``'s, the device array of the pass before), the block's
    first position, its attention length (first position + B; 0: the slot
    does not step), its first write row (the block's B rows are
    consecutive), then the block's B tokens as the host knows them (-1: not
    decided), then its block table. ``State`` ``[S, 2 B]``: the block's
    tokens, then a 0/1 per position that is decided. Gives the ``[S, B]``
    tokens the layers read (``mask_token`` where a position is not
    decided) and positions, the ``[S, 1, L]`` bias, the ``[S * L]`` row
    map, the ``[S * B]`` write rows, and the block's ``[S, B]`` tokens and
    decided bits for ``block_fill_decide``."""
    packed, state = first(ins, "Packed"), first(ins, "State")
    L, bs = int(attrs["length"]), int(attrs["block_size"])
    B = int(attrs["block_len"])
    S = packed.shape[0]
    head, given, table = packed[:, :4], packed[:, 4:4 + B], packed[:, 4 + B:]
    own = head[:, :1] < 0
    held = jnp.where(own, state[:, :B].astype(packed.dtype), given)
    decided = jnp.where(own, state[:, B:].astype(packed.dtype),
                        (given >= 0).astype(packed.dtype))
    within = jnp.arange(bs, dtype=packed.dtype)
    rows = (table[:, :, None] * bs + within).reshape(S, -1)[:, :L]
    open_ = jnp.arange(L, dtype=packed.dtype) < head[:, 2:3]     # [S, L]
    at = jnp.arange(B, dtype=packed.dtype)
    return {
        "TokenOut": [jnp.where(decided > 0, held, int(attrs["mask_token"]))],
        "Position": [head[:, 1:2] + at],
        "Bias": [jnp.where(open_, 0.0, -1e9).astype(jnp.float32)[:, None]],
        "Rows": [rows.reshape(-1)],
        "WriteRows": [(head[:, 3:4] + at).reshape(-1)],
        "Held": [held],
        "Decided": [decided],
    }


@register_op("block_fill_decide",
             nondiff_inputs=("Logits", "Held", "Decided"))
def _block_fill_decide(ins, attrs):
    """One pass's decision over a block of ``B`` positions a slot
    (low-confidence-first, one position a pass): among the positions not
    decided, the one whose top candidate (``mask_token``'s logit left out)
    has the highest softmax probability, in float32, the lowest position on
    a tie, takes that candidate. A slot whose block was whole when the pass
    began (its commit pass) opens the next block: nothing decided.
    ``State`` ``[S, 2 B]`` is the block after the pass (tokens, then
    decided bits: the next pass's ``paged_block_feeds`` input); ``Host``
    int32 ``[2 S]`` the position each slot decided (-1: a commit pass), then
    its token."""
    logits = first(ins, "Logits").astype(jnp.float32)          # [S, B, V]
    held, decided = first(ins, "Held"), first(ins, "Decided") > 0
    B = held.shape[1]
    vocab = jnp.arange(logits.shape[-1])
    logits = jnp.where(vocab == int(attrs["mask_token"]), -jnp.inf, logits)
    cand = jnp.argmax(logits, axis=-1).astype(held.dtype)       # [S, B]
    conf = jnp.exp(jnp.max(logits, axis=-1)
                   - jax.nn.logsumexp(logits, axis=-1))
    pick = jnp.argmax(jnp.where(decided, -1.0, conf), axis=-1)  # [S]
    commit = jnp.all(decided, axis=-1)                          # [S]
    chosen = jnp.arange(B)[None, :] == pick[:, None]
    token = jnp.take_along_axis(cand, pick[:, None], axis=1)[:, 0]
    tokens = jnp.where(chosen, cand, held)
    bits = (decided | chosen) & ~commit[:, None]
    state = jnp.concatenate([tokens, bits.astype(held.dtype)], axis=1)
    host = jnp.concatenate([jnp.where(commit, -1, pick), token])
    return {"State": [state], "Host": [host.astype(jnp.int32)]}


@register_op("chunk_mask_bias", nondiff_inputs=("Span",))
def _chunk_mask_bias(ins, attrs):
    """The chunk program's additive ``[1, C, L]`` bias from the chunk's
    ``Span`` ``(start, real)``, on the device (kernels/attention.py
    ``chunk_horizon``: THE rule), for a chunk program that attends by plain
    matmul and softmax ops."""
    from paddle_tpu.kernels import attention as fused

    return {"Out": [fused.chunk_mask_bias(
        first(ins, "Span"), attrs["chunk"], attrs["length"],
        attrs.get("block_len", 1))]}


def _index_select(ins, attrs, sel):
    """The rows an indexer lets each query attend to (kernels/sparse.py):
    ``Q`` ``[N, heads * width]`` index queries and ``W`` ``[N, heads]``
    their float32 weights against the index keys ``Arena`` ``[R, width]``
    under the row map ``Rows``. A decode step hands its additive ``Bias``
    ``[S, 1, L]`` (a query a slot) and gets it back with every position
    outside the query's ``topk`` closed; a prompt chunk hands its ``Span``
    (one sequence's ``Rows`` ``[L]``) and gets the int8 mask ``[C, >= L]``
    that ``chunk_paged_attention`` takes as ``Mask``. ``sel`` says whether
    the kernels serve it."""
    from paddle_tpu.kernels import sparse

    q, w, arena = first(ins, "Q"), first(ins, "W"), first(ins, "Arena")
    rows, bias = first(ins, "Rows"), maybe(ins, "Bias")
    topk = int(attrs["topk"])
    if bias is not None:
        seqs, horizon = bias.shape[0], sparse.step_lengths(bias)
    else:
        from paddle_tpu.kernels.attention import chunk_horizon

        seqs = 1
        horizon = chunk_horizon(first(ins, "Span"), q.shape[0],
                                rows.shape[0])
    if sel is not None and attrs.get("block_size"):
        scores = sparse.index_scores(q, w, arena, rows, seqs,
                                     attrs["block_size"], horizon,
                                     interpret=sel.interpret)
        keep = sparse.index_select(scores, horizon, topk,
                                   interpret=sel.interpret)
    else:
        keep = sparse.index_select_composite(
            sparse.index_scores_composite(q, w, arena, rows, seqs), horizon,
            topk).astype(jnp.int8)
    if bias is None:
        return {"Out": [keep]}
    length = bias.shape[-1]
    return {"Out": [jnp.where(keep[:, :length] != 0, bias.reshape(
        seqs, length), -1e9).astype(bias.dtype)[:, None]]}


def _index_select_pallas(ins, attrs):
    from paddle_tpu import kernels

    return _index_select(ins, attrs, kernels.selected("index_select"))


OpRegistry.register(
    OpDef(
        "sparse_index_select",
        lambda ins, attrs: _index_select(ins, attrs, None),
        pallas=_index_select_pallas,
        nondiff_inputs=("Q", "W", "Arena", "Rows", "Bias", "Span"),
    )
)


def _chunk_paged_attention_reference(ins, attrs):
    from paddle_tpu.kernels import attention as fused

    if maybe(ins, "Mask") is not None:
        from paddle_tpu.kernels import sparse

        rows = first(ins, "Rows")
        return {"Out": [sparse.masked_chunk_composite(
            first(ins, "Q"), first(ins, "KArena"), first(ins, "VArena"),
            rows, first(ins, "Mask")[:, :rows.shape[0]],
            attrs.get("sm_scale", 1.0), attrs["kv_heads"])]}
    return {"Out": [fused.chunk_attention_by_span(
        first(ins, "Q"), first(ins, "KArena"), first(ins, "VArena"),
        first(ins, "Rows"), first(ins, "Span"), attrs.get("sm_scale", 1.0),
        attrs["kv_heads"], attrs.get("block_len", 1),
        attrs.get("window", 0))]}


def _chunk_paged_attention_pallas(ins, attrs):
    from paddle_tpu import kernels
    from paddle_tpu.kernels import attention as fused

    sel = kernels.selected_for("chunk_paged_attention", attrs)
    if sel is None:
        return _chunk_paged_attention_reference(ins, attrs)
    if maybe(ins, "Mask") is not None:
        from paddle_tpu.kernels import sparse

        return {"Out": [sparse.masked_chunk_attention(
            first(ins, "Q"), first(ins, "KArena"), first(ins, "VArena"),
            first(ins, "Rows"), first(ins, "Span"), first(ins, "Mask"),
            attrs["block_size"], attrs.get("sm_scale", 1.0),
            attrs["kv_heads"], interpret=sel.interpret)]}
    return {"Out": [fused.chunk_attention(
        first(ins, "Q"), first(ins, "KArena"), first(ins, "VArena"),
        first(ins, "Rows"), first(ins, "Span"), attrs["block_size"],
        attrs.get("sm_scale", 1.0), attrs["kv_heads"],
        block_len=attrs.get("block_len", 1), interpret=sel.interpret,
        window=attrs.get("window", 0))]}


# a prompt chunk's queries ``[C, heads * D]`` over one sequence's rows of
# the paged arenas under the mask of the chunk's ``Span`` (grouped-query;
# kernels/attention.py ``chunk_attention`` and its composite)
OpRegistry.register(
    OpDef(
        "chunk_paged_attention",
        _chunk_paged_attention_reference,
        pallas=_chunk_paged_attention_pallas,
        nondiff_inputs=("Rows", "Span", "Mask"),
    )
)


@register_op("rms_norm")
def _rms_norm(ins, attrs):
    """``x / sqrt(mean(x^2) + eps) * weight`` over the last dimension, in
    float32; ``out_dtype`` names the result's dtype (default: ``x``'s)."""
    x, w = first(ins, "X"), first(ins, "Scale")
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(
        jnp.mean(xf * xf, axis=-1, keepdims=True) + attrs.get("epsilon", 1e-5))
    y = y * w.astype(jnp.float32)
    return {"Out": [y.astype(attrs.get("out_dtype") or x.dtype)]}


@register_op("rotary_embedding", nondiff_inputs=("Positions",))
def _rotary_embedding(ins, attrs):
    """Rotary positions over the WHOLE head: ``X`` ``[..., heads, D]`` at
    ``Positions`` ``[...]`` (one a token). Rotate-half pairing: lane ``i <
    D / 2`` of a head is paired with lane ``i + D / 2`` and the pair turned
    by ``position * theta^(-2 i / D)``. With ``freqs`` (``D / 2`` numbers: a
    table, e.g. a base blended frequency by frequency with a stretched one)
    pair ``i`` turns by ``position * freqs[i]`` instead, and with
    ``interleaved`` pair ``i`` is lanes ``(2 i, 2 i + 1)``. Angles, sines
    and the rotation in float32; ``out_dtype`` names the result's dtype
    (default: ``X``'s)."""
    x, pos = first(ins, "X"), first(ins, "Positions")
    half = x.shape[-1] // 2
    if attrs.get("freqs"):
        freq = jnp.asarray(attrs["freqs"], jnp.float32)
    else:
        freq = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                       * (-jnp.log(jnp.float32(attrs["theta"])) / half))
    angle = pos.astype(jnp.float32)[..., None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    xf = x.astype(jnp.float32)
    if attrs.get("interleaved"):
        pairs = xf.reshape(xf.shape[:-1] + (half, 2))
        a, b = pairs[..., 0], pairs[..., 1]
        y = jnp.stack([a * cos - b * sin, b * cos + a * sin],
                      axis=-1).reshape(xf.shape)
    else:
        a, b = xf[..., :half], xf[..., half:]
        y = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return {"Out": [y.astype(attrs.get("out_dtype") or x.dtype)]}


@register_op("position_log_scale", nondiff_inputs=("Positions",))
def _position_log_scale(ins, attrs):
    """``X`` ``[..., W]`` at ``Positions`` ``[...]`` times ``1 + beta * ln(1
    + floor(position / period))``: a query's scale that grows with the
    logarithm of how many ``period``s of context lie before it (1 below the
    first). In float32; ``out_dtype`` names the result's dtype."""
    x, pos = first(ins, "X"), first(ins, "Positions")
    periods = jnp.floor_divide(pos, int(attrs["period"])).astype(jnp.float32)
    by = 1.0 + float(attrs["beta"]) * jnp.log1p(periods)
    by = by.reshape(by.shape + (1,) * (x.ndim - by.ndim))
    y = x.astype(jnp.float32) * by
    return {"Out": [y.astype(attrs.get("out_dtype") or x.dtype)]}


def _paged_latent_attention(ins, attrs, kernel):
    from paddle_tpu.kernels import attention as fused

    q, arena = first(ins, "Q"), first(ins, "Arena")
    w_uk, w_uv = first(ins, "WUK"), first(ins, "WUV")
    rows, bias = first(ins, "Rows"), first(ins, "Bias")
    latent, sm = w_uk.shape[-1], attrs.get("sm_scale", 1.0)
    wide = fused.absorb_queries(q.astype(arena.dtype), w_uk, attrs["rope"],
                                arena.shape[-1])
    if kernel is None:
        ctx = fused.paged_attention_composite(
            wide, arena, None, rows, bias, attrs["seqs"], attrs["length"],
            sm, v_width=latent)
    else:
        ctx = fused.paged_attention(
            wide, arena, None, rows, bias, attrs["seqs"], attrs["length"],
            attrs["block_size"], sm, interpret=kernel, v_width=latent)
    return {"Out": [fused.project_values(ctx, w_uv, q.dtype)]}


def _paged_latent_pallas(ins, attrs):
    from paddle_tpu import kernels

    sel = kernels.selected_for("paged_attention", attrs)
    return _paged_latent_attention(ins, attrs,
                                   None if sel is None else sel.interpret)


# a decode step's queries ``[S, heads * (nope + rope)]`` over ONE latent
# arena (a token's row: its normalised compressed K/V, then the one rotated
# key all heads share), ABSORBED: ``WUK`` moved onto the query, ``WUV`` onto
# the context (kernels/attention.py ``paged_attention`` handed one arena)
OpRegistry.register(
    OpDef(
        "paged_latent_attention",
        lambda ins, attrs: _paged_latent_attention(ins, attrs, None),
        pallas=_paged_latent_pallas,
        nondiff_inputs=("Rows", "Bias"),
    )
)


def _chunk_latent_operands(ins):
    return [first(ins, slot)
            for slot in ("Q", "WUK", "WUV", "Arena", "Rows", "Span")]


def _chunk_latent_attention(ins, attrs):
    """THE definition: the dense expanded composite, every row and every
    query at once."""
    from paddle_tpu.kernels import attention as fused

    return {"Out": [fused.latent_chunk_expanded(
        *_chunk_latent_operands(ins), attrs.get("sm_scale", 1.0),
        attrs["rope"])]}


def _chunk_latent_pallas(ins, attrs):
    """Where kernels serve the program (any mode but ``off``) the chunk runs
    kernels/attention.py ``latent_chunk_attention``: a Pallas kernel that
    up-projects a tile of the slot's live rows once for all the chunk's
    queries, or, for a shape it refuses (counted), its fallback, the same
    form by XLA's loops over tiles of queries and of rows; ``off`` runs the
    dense definition."""
    from paddle_tpu import kernels
    from paddle_tpu.kernels import attention as fused

    sel = kernels.selected("latent_chunk_attention")
    if sel is None:
        return _chunk_latent_attention(ins, attrs)
    return {"Out": [fused.latent_chunk_attention(
        *_chunk_latent_operands(ins), attrs.get("block_size"),
        attrs.get("sm_scale", 1.0), attrs["rope"],
        interpret=sel.interpret)]}


# a prompt chunk's queries over one sequence's rows of a latent arena,
# EXPANDED: every row a query sees up-projected to its heads' keys and
# values (the step attends absorbed, above; the count and the chip both
# favour expanded for a chunk: PERF.md section 6, PR 56)
OpRegistry.register(
    OpDef(
        "chunk_latent_attention",
        _chunk_latent_attention,
        pallas=_chunk_latent_pallas,
        nondiff_inputs=("Rows", "Span"),
    )
)


@register_op("relu2")
def _relu2(ins, attrs):
    """Squared relu: ``max(x, 0)^2``."""
    return {"Out": [jnp.square(jnp.maximum(first(ins, "X"), 0))]}


@register_op("one_hot", nondiff_inputs=("X",))
def _one_hot(ins, attrs):
    x = first(ins, "X")
    depth = attrs.get("depth")
    if x.ndim >= 2 and x.shape[-1] == 1:
        x = x[..., 0]
    return {"Out": [jax.nn.one_hot(x, depth, dtype=jnp.float32)]}


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------


@register_op("cross_entropy", nondiff_inputs=("Label",))
def _cross_entropy(ins, attrs):
    x, label = first(ins, "X"), first(ins, "Label")
    eps = 1e-8
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * jnp.log(x + eps), axis=-1, keepdims=True)
    else:
        if label.ndim == x.ndim:
            label = label[..., 0]
        picked = jnp.take_along_axis(x, label[..., None].astype(jnp.int32), axis=-1)
        loss = -jnp.log(picked + eps)
        ignore = attrs.get("ignore_index", -100)
        loss = jnp.where(label[..., None] == ignore, 0.0, loss)
    return {"Y": [loss]}


@register_op("softmax_with_cross_entropy", nondiff_inputs=("Label",))
def _softmax_with_ce(ins, attrs):
    """reference: paddle/fluid/operators/softmax_with_cross_entropy_op.cu —
    fused, numerically stable via log-sum-exp."""
    logits, label = first(ins, "Logits"), first(ins, "Label")
    axis = attrs.get("axis", -1)
    axis = axis % logits.ndim
    log_probs = jax.nn.log_softmax(logits, axis=axis)
    softmax = jnp.exp(log_probs)
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * log_probs, axis=axis, keepdims=True)
    else:
        # label has a size-1 class axis when its rank matches the logits
        squeezed = (
            jnp.squeeze(label, axis=axis) if label.ndim == logits.ndim else label
        )
        idx = jnp.expand_dims(squeezed.astype(jnp.int32), axis)
        picked = jnp.take_along_axis(log_probs, idx, axis=axis)
        loss = -picked
        ignore = attrs.get("ignore_index", -100)
        loss = jnp.where(jnp.expand_dims(squeezed, axis) == ignore, 0.0, loss)
    return {"Softmax": [softmax], "Loss": [loss]}


@register_op("sigmoid_cross_entropy_with_logits")
def _sigmoid_ce(ins, attrs):
    x, label = first(ins, "X"), first(ins, "Label")
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ignore = attrs.get("ignore_index", -100)
    loss = jnp.where(label == ignore, 0.0, loss)
    if attrs.get("normalize", False):
        norm = jnp.maximum(jnp.sum(label != ignore).astype(loss.dtype), 1.0)
        loss = loss / norm
    return {"Out": [loss]}


@register_op("square_error_cost")
def _square_error_cost(ins, attrs):
    x, y = first(ins, "X"), first(ins, "Y")
    return {"Out": [jnp.square(x - y)]}


@register_op("huber_loss")
def _huber_loss(ins, attrs):
    x, y = first(ins, "X"), first(ins, "Y")
    delta = attrs.get("delta", 1.0)
    diff = y - x
    absd = jnp.abs(diff)
    loss = jnp.where(
        absd <= delta, 0.5 * jnp.square(diff), delta * (absd - 0.5 * delta)
    )
    return {"Out": [loss], "Residual": [diff]}


@register_op("smooth_l1_loss")
def _smooth_l1(ins, attrs):
    x, y = first(ins, "X"), first(ins, "Y")
    sigma2 = attrs.get("sigma", 1.0) ** 2
    diff = x - y
    absd = jnp.abs(diff)
    loss = jnp.where(
        absd < 1.0 / sigma2, 0.5 * sigma2 * jnp.square(diff), absd - 0.5 / sigma2
    )
    return {"Out": [jnp.sum(loss, axis=tuple(range(1, x.ndim)), keepdims=False).reshape(-1, 1)], "Diff": [diff]}


@register_op("kldiv_loss")
def _kldiv_loss(ins, attrs):
    x, target = first(ins, "X"), first(ins, "Target")
    loss = target * (jnp.log(jnp.maximum(target, 1e-10)) - x)
    reduction = attrs.get("reduction", "mean")
    if reduction == "mean":
        loss = jnp.mean(loss).reshape((1,))
    elif reduction == "sum":
        loss = jnp.sum(loss).reshape((1,))
    elif reduction == "batchmean":
        loss = (jnp.sum(loss) / x.shape[0]).reshape((1,))
    return {"Loss": [loss]}


# ---------------------------------------------------------------------------
# metrics (reference: paddle/fluid/operators/metrics/)
# ---------------------------------------------------------------------------


@register_op("accuracy", nondiff_inputs=("Out", "Indices", "Label"))
def _accuracy(ins, attrs):
    idx, label = first(ins, "Indices"), first(ins, "Label")
    if label.ndim == 1:
        label = label[:, None]
    correct = jnp.any(idx == label, axis=1)
    num_correct = jnp.sum(correct.astype(jnp.float32))
    total = jnp.asarray(idx.shape[0], jnp.float32)
    return {
        "Accuracy": [(num_correct / total).reshape((1,))],
        "Correct": [num_correct.astype(jnp.int32).reshape((1,))],
        "Total": [jnp.asarray([idx.shape[0]], jnp.int32)],
    }


@register_op("auc", nondiff_inputs=("Predict", "Label"))
def _auc(ins, attrs):
    """Streaming AUC via fixed histogram buckets
    (reference: paddle/fluid/operators/metrics/auc_op.cc)."""
    pred, label = first(ins, "Predict"), first(ins, "Label")
    stat_pos, stat_neg = first(ins, "StatPos"), first(ins, "StatNeg")
    num_thresholds = attrs.get("num_thresholds", 4095)
    pos_score = pred[:, -1] if pred.ndim == 2 else pred
    bucket = jnp.clip(
        (pos_score * num_thresholds).astype(jnp.int64), 0, num_thresholds
    )
    lab = label.reshape(-1).astype(jnp.int64)
    pos_inc = jnp.zeros_like(stat_pos).at[bucket].add(lab)
    neg_inc = jnp.zeros_like(stat_neg).at[bucket].add(1 - lab)
    new_pos = stat_pos + pos_inc
    new_neg = stat_neg + neg_inc
    # integrate trapezoid over descending threshold
    tp = jnp.cumsum(new_pos[::-1])
    fp = jnp.cumsum(new_neg[::-1])
    total_pos, total_neg = tp[-1], fp[-1]
    tpr = tp / jnp.maximum(total_pos, 1)
    fpr = fp / jnp.maximum(total_neg, 1)
    auc = jnp.trapezoid(tpr, fpr)
    return {
        "AUC": [auc.reshape((1,))],
        "StatPosOut": [new_pos],
        "StatNegOut": [new_neg],
    }
