"""User-facing neural-net layer functions.

API surface modeled on the reference's fluid.layers
(reference: python/paddle/fluid/layers/nn.py — fc at :205, ~200 layers).
Every function appends OpDescs to the current block via LayerHelper; no
computation happens at build time.
"""

from paddle_tpu.core.dtypes import convert_dtype
from paddle_tpu.layer_helper import LayerHelper
from paddle_tpu.utils import unique_name
from paddle_tpu.utils.enforce import enforce

__all__ = [
    "fc",
    "conv2d",
    "conv2d_transpose",
    "pool2d",
    "batch_norm",
    "layer_norm",
    "instance_norm",
    "group_norm",
    "embedding",
    "sparse_embedding",
    "distributed_embedding",
    "sharded_embedding",
    "scaled_dot_product_attention",
    "kv_cache_write",
    "masked_write",
    "logits_mask_add",
    "cached_attention",
    "paged_attention",
    "chunk_paged_attention",
    "sparse_index_select",
    "chunk_mask_bias",
    "paged_step_feeds",
    "paged_block_feeds",
    "paged_window_feeds",
    "block_fill_decide",
    "rms_norm",
    "rotary_embedding",
    "position_log_scale",
    "paged_latent_attention",
    "chunk_latent_attention",
    "gated_short_conv",
    "relu2",
    "moe_routed_experts",
    "mamba2_mixer",
    "block_gather",
    "block_scatter_write",
    "moe_ffn",
    "dropout",
    "softmax",
    "log_softmax",
    "matmul",
    "mul",
    "relu",
    "relu6",
    "sigmoid",
    "tanh",
    "gelu",
    "leaky_relu",
    "elu",
    "swish",
    "hard_swish",
    "hard_sigmoid",
    "softplus",
    "softsign",
    "prelu",
    "cross_entropy",
    "softmax_with_cross_entropy",
    "sigmoid_cross_entropy_with_logits",
    "square_error_cost",
    "huber_loss",
    "kldiv_loss",
    "mse_loss",
    "accuracy",
    "auc",
    "topk",
    "one_hot",
    "l2_normalize",
    "clip",
    "clip_by_norm",
    "mean",
    "reduce_sum",
    "reduce_mean",
    "reduce_max",
    "reduce_min",
    "reduce_prod",
    "elementwise_op",
    "elementwise_add",
    "elementwise_sub",
    "elementwise_mul",
    "elementwise_div",
    "elementwise_max",
    "elementwise_min",
    "elementwise_pow",
    "scale",
    "sqrt",
    "square",
    "abs",
    "exp",
    "log",
    "sin",
    "cos",
    "erf",
    "pow",
    "argmax",
    "argmin",
    "unsqueeze",
    "squeeze",
]


def _single_op(op_type, x, attrs=None, out_dtype=None, name=None, extra_inputs=None):
    helper = LayerHelper(op_type, name=name)
    out = helper.create_variable_for_type_inference(out_dtype or x.dtype)
    inputs = {"X": [x.name]}
    if extra_inputs:
        inputs.update(extra_inputs)
    helper.append_op(op_type, inputs, {"Out": [out.name]}, attrs or {})
    return out


# -- dense / conv -----------------------------------------------------------


def fc(
    input,
    size,
    num_flatten_dims=1,
    param_attr=None,
    bias_attr=None,
    act=None,
    name=None,
    out_dtype=None,
):
    """reference: python/paddle/fluid/layers/nn.py:205. ``out_dtype``
    (e.g. "float32" over bfloat16 operands) is the dtype the product is
    accumulated and handed on in."""
    helper = LayerHelper(
        "fc", param_attr=param_attr, bias_attr=bias_attr, act=act, name=name
    )
    dtype = input.dtype
    input_shape = input.shape
    enforce(
        input_shape is not None,
        f"fc input '{input.name}' has no inferred shape, so the weight "
        "size is unknown at build time. Stack fc on layers that propagate "
        "shape, or set the var's .shape explicitly",
    )
    feature_dims = list(input_shape[num_flatten_dims:])
    enforce(
        all(int(d) > 0 for d in feature_dims),
        f"fc input '{input.name}' flattened feature dims {feature_dims} "
        "contain a dynamic -1 dim; fc needs static feature dims (choose "
        "num_flatten_dims so only leading dims are dynamic)",
    )
    in_features = 1
    for d in feature_dims:
        in_features *= d
    w = helper.create_parameter(
        helper.param_attr, shape=[in_features, size], dtype=dtype
    )
    out = helper.create_variable_for_type_inference(out_dtype or dtype)
    attrs = {"x_num_col_dims": num_flatten_dims, "y_num_col_dims": 1}
    if out_dtype:
        attrs["out_dtype"] = out_dtype
    helper.append_op(
        "mul",
        {"X": [input.name], "Y": [w.name]},
        {"Out": [out.name]},
        attrs,
    )
    if helper.bias_attr is not False:
        b = helper.create_parameter(
            helper.bias_attr, shape=[size], dtype=dtype, is_bias=True
        )
        out = helper.append_bias_op(out, b, axis=num_flatten_dims)
    return helper.append_activation(out)


def conv2d(
    input,
    num_filters,
    filter_size,
    stride=1,
    padding=0,
    dilation=1,
    groups=1,
    param_attr=None,
    bias_attr=None,
    act=None,
    name=None,
    data_format="NCHW",
):
    """reference: python/paddle/fluid/layers/nn.py conv2d."""
    helper = LayerHelper(
        "conv2d", param_attr=param_attr, bias_attr=bias_attr, act=act, name=name
    )
    dtype = input.dtype
    if isinstance(filter_size, int):
        filter_size = [filter_size, filter_size]
    if isinstance(stride, int):
        stride = [stride, stride]
    if isinstance(padding, int):
        padding = [padding, padding]
    if isinstance(dilation, int):
        dilation = [dilation, dilation]
    channels = input.shape[1] if data_format == "NCHW" else input.shape[-1]
    enforce(channels % groups == 0, "channels must divide groups")
    filter_shape = [num_filters, channels // groups] + list(filter_size)
    import math

    fan_in = (channels // groups) * filter_size[0] * filter_size[1]
    from paddle_tpu.initializer import NormalInitializer

    default_init = NormalInitializer(0.0, math.sqrt(2.0 / fan_in))
    w = helper.create_parameter(
        helper.param_attr,
        shape=filter_shape,
        dtype=dtype,
        default_initializer=default_init,
    )
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        "conv2d",
        {"Input": [input.name], "Filter": [w.name]},
        {"Output": [out.name]},
        {
            "strides": stride,
            "paddings": padding,
            "dilations": dilation,
            "groups": groups,
            "data_format": data_format,
        },
    )
    if helper.bias_attr is not False:
        b = helper.create_parameter(
            helper.bias_attr, shape=[num_filters], dtype=dtype, is_bias=True
        )
        out = helper.append_bias_op(out, b, axis=1 if data_format == "NCHW" else 3)
    return helper.append_activation(out)


def conv2d_transpose(
    input,
    num_filters,
    filter_size,
    stride=1,
    padding=0,
    groups=1,
    param_attr=None,
    bias_attr=None,
    act=None,
    name=None,
):
    helper = LayerHelper(
        "conv2d_transpose",
        param_attr=param_attr,
        bias_attr=bias_attr,
        act=act,
        name=name,
    )
    dtype = input.dtype
    if isinstance(filter_size, int):
        filter_size = [filter_size, filter_size]
    if isinstance(stride, int):
        stride = [stride, stride]
    if isinstance(padding, int):
        padding = [padding, padding]
    channels = input.shape[1]
    filter_shape = [channels, num_filters // groups] + list(filter_size)
    w = helper.create_parameter(helper.param_attr, shape=filter_shape, dtype=dtype)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        "conv2d_transpose",
        {"Input": [input.name], "Filter": [w.name]},
        {"Output": [out.name]},
        {"strides": stride, "paddings": padding, "groups": groups},
    )
    if helper.bias_attr is not False:
        b = helper.create_parameter(
            helper.bias_attr, shape=[num_filters], dtype=dtype, is_bias=True
        )
        out = helper.append_bias_op(out, b, axis=1)
    return helper.append_activation(out)


def pool2d(
    input,
    pool_size=-1,
    pool_type="max",
    pool_stride=1,
    pool_padding=0,
    global_pooling=False,
    exclusive=True,
    adaptive=False,
    name=None,
):
    helper = LayerHelper("pool2d", name=name)
    if isinstance(pool_size, int):
        pool_size = [pool_size, pool_size]
    if isinstance(pool_stride, int):
        pool_stride = [pool_stride, pool_stride]
    if isinstance(pool_padding, int):
        pool_padding = [pool_padding, pool_padding]
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "pool2d",
        {"X": [input.name]},
        {"Out": [out.name]},
        {
            "pooling_type": pool_type,
            "ksize": pool_size,
            "strides": pool_stride,
            "paddings": pool_padding,
            "global_pooling": global_pooling,
            "exclusive": exclusive,
            "adaptive": adaptive,
        },
    )
    return out


def batch_norm(
    input,
    act=None,
    is_test=False,
    momentum=0.9,
    epsilon=1e-5,
    param_attr=None,
    bias_attr=None,
    data_layout="NCHW",
    name=None,
    moving_mean_name=None,
    moving_variance_name=None,
    use_global_stats=False,
):
    """reference: python/paddle/fluid/layers/nn.py batch_norm. Running stats
    are persistable non-trainable parameters updated through MeanOut/
    VarianceOut (functionally, via scope write-back)."""
    from paddle_tpu.initializer import ConstantInitializer
    from paddle_tpu.param_attr import ParamAttr

    helper = LayerHelper(
        "batch_norm", param_attr=param_attr, bias_attr=bias_attr, act=act, name=name
    )
    dtype = input.dtype if input.dtype != "float16" else "float32"
    channels = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    scale = helper.create_parameter(
        helper.param_attr,
        shape=[channels],
        dtype=dtype,
        default_initializer=ConstantInitializer(1.0),
    )
    bias = helper.create_parameter(
        helper.bias_attr, shape=[channels], dtype=dtype, is_bias=True
    )
    mean = helper.create_parameter(
        ParamAttr(
            name=moving_mean_name,
            initializer=ConstantInitializer(0.0),
            trainable=False,
        ),
        shape=[channels],
        dtype=dtype,
    )
    variance = helper.create_parameter(
        ParamAttr(
            name=moving_variance_name,
            initializer=ConstantInitializer(1.0),
            trainable=False,
        ),
        shape=[channels],
        dtype=dtype,
    )
    mean.stop_gradient = True
    variance.stop_gradient = True
    out = helper.create_variable_for_type_inference(input.dtype)
    saved_mean = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    saved_var = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    helper.append_op(
        "batch_norm",
        {
            "X": [input.name],
            "Scale": [scale.name],
            "Bias": [bias.name],
            "Mean": [mean.name],
            "Variance": [variance.name],
        },
        {
            "Y": [out.name],
            "MeanOut": [mean.name],
            "VarianceOut": [variance.name],
            "SavedMean": [saved_mean.name],
            "SavedVariance": [saved_var.name],
        },
        {
            "momentum": momentum,
            "epsilon": epsilon,
            "is_test": is_test,
            "data_layout": data_layout,
            "use_global_stats": use_global_stats,
        },
    )
    return helper.append_activation(out)


def layer_norm(
    input,
    scale=True,
    shift=True,
    begin_norm_axis=1,
    epsilon=1e-5,
    param_attr=None,
    bias_attr=None,
    act=None,
    name=None,
):
    from paddle_tpu.initializer import ConstantInitializer

    helper = LayerHelper(
        "layer_norm", param_attr=param_attr, bias_attr=bias_attr, act=act, name=name
    )
    dtype = input.dtype
    import math

    in_shape = list(input.shape) if input.shape is not None else None
    enforce(
        in_shape is not None,
        "layer_norm input has no inferred shape; build it from layers "
        "that propagate shape (fluid.data, fc, elementwise ops)",
    )
    if begin_norm_axis < 0:
        begin_norm_axis += len(in_shape)
    enforce(
        0 < begin_norm_axis < len(in_shape),
        f"begin_norm_axis {begin_norm_axis} out of range for input rank "
        f"{len(in_shape)}",
    )
    norm_dims = in_shape[begin_norm_axis:]
    if scale or shift:
        # the scale/bias parameter is sized by the normalized region —
        # a dynamic (-1) dim there has no buildable parameter shape
        enforce(
            all(int(d) > 0 for d in norm_dims),
            f"layer_norm normalizes over dims {norm_dims} "
            f"(begin_norm_axis={begin_norm_axis}) which contain a dynamic "
            "-1 dim, so the Scale/Bias parameter size is unknown at build "
            "time. Normalize over trailing static dims (e.g. "
            "begin_norm_axis=-1 for the feature axis) or pass "
            "scale=False, shift=False",
        )
    norm_shape = [int(math.prod(norm_dims))]
    inputs = {"X": [input.name]}
    if scale:
        s = helper.create_parameter(
            helper.param_attr,
            shape=norm_shape,
            dtype=dtype,
            default_initializer=ConstantInitializer(1.0),
        )
        inputs["Scale"] = [s.name]
    if shift:
        b = helper.create_parameter(
            helper.bias_attr, shape=norm_shape, dtype=dtype, is_bias=True
        )
        inputs["Bias"] = [b.name]
    out = helper.create_variable_for_type_inference(dtype)
    mean = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    var = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    helper.append_op(
        "layer_norm",
        inputs,
        {"Y": [out.name], "Mean": [mean.name], "Variance": [var.name]},
        {"begin_norm_axis": begin_norm_axis, "epsilon": epsilon},
    )
    # layer_norm is shape-preserving: guarantee the output shape even when
    # abstract evaluation could not run (dynamic dims), so fc and friends
    # stacked on top can always read .shape at build time
    if out.shape is None:
        out.shape = tuple(in_shape)
    if mean.shape is None:
        mean.shape = tuple(in_shape[:begin_norm_axis])
        var.shape = tuple(in_shape[:begin_norm_axis])
    return helper.append_activation(out)


def instance_norm(input, epsilon=1e-5, param_attr=None, bias_attr=None, name=None):
    from paddle_tpu.initializer import ConstantInitializer

    helper = LayerHelper(
        "instance_norm", param_attr=param_attr, bias_attr=bias_attr, name=name
    )
    channels = input.shape[1]
    s = helper.create_parameter(
        helper.param_attr,
        shape=[channels],
        dtype=input.dtype,
        default_initializer=ConstantInitializer(1.0),
    )
    b = helper.create_parameter(
        helper.bias_attr, shape=[channels], dtype=input.dtype, is_bias=True
    )
    out = helper.create_variable_for_type_inference(input.dtype)
    sm = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    sv = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    helper.append_op(
        "instance_norm",
        {"X": [input.name], "Scale": [s.name], "Bias": [b.name]},
        {"Y": [out.name], "SavedMean": [sm.name], "SavedVariance": [sv.name]},
        {"epsilon": epsilon},
    )
    return out


def group_norm(
    input, groups, epsilon=1e-5, param_attr=None, bias_attr=None, act=None, name=None
):
    from paddle_tpu.initializer import ConstantInitializer

    helper = LayerHelper(
        "group_norm", param_attr=param_attr, bias_attr=bias_attr, act=act, name=name
    )
    channels = input.shape[1]
    s = helper.create_parameter(
        helper.param_attr,
        shape=[channels],
        dtype=input.dtype,
        default_initializer=ConstantInitializer(1.0),
    )
    b = helper.create_parameter(
        helper.bias_attr, shape=[channels], dtype=input.dtype, is_bias=True
    )
    out = helper.create_variable_for_type_inference(input.dtype)
    m = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    v = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    helper.append_op(
        "group_norm",
        {"X": [input.name], "Scale": [s.name], "Bias": [b.name]},
        {"Y": [out.name], "Mean": [m.name], "Variance": [v.name]},
        {"groups": groups, "epsilon": epsilon},
    )
    return helper.append_activation(out)


def embedding(
    input,
    size,
    is_sparse=False,
    is_distributed=False,
    padding_idx=None,
    param_attr=None,
    dtype="float32",
    name=None,
):
    """reference: python/paddle/fluid/layers/nn.py embedding. is_sparse is
    accepted for API parity; dense gather is the TPU path (the PS stack
    handles the huge-table case)."""
    helper = LayerHelper("embedding", param_attr=param_attr, name=name)
    w = helper.create_parameter(helper.param_attr, shape=list(size), dtype=dtype)
    w.is_distributed = is_distributed
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        "lookup_table_v2",
        {"W": [w.name], "Ids": [input.name]},
        {"Out": [out.name]},
        {"padding_idx": -1 if padding_idx is None else padding_idx},
    )
    return out


def scaled_dot_product_attention(q, k, v, bias=None, causal=False,
                                 sm_scale=None, seq_parallel=None,
                                 seq_axis="seq", name=None):
    """Fused attention over [B, H, S, D] tensors; `bias` is an optional
    [B, S] additive key bias (padding mask). Lowers to the Pallas flash
    attention kernel on TPU (ops/pallas/flash_attention.py), or an
    XLA-fused reference implementation otherwise. The reference's analog is
    inference-only (paddle/fluid/operators/fused/multihead_matmul_op.cc);
    this one is differentiable.

    seq_parallel='ring' | 'ulysses' runs attention sequence-sharded over
    mesh axis `seq_axis` when the program is compiled with
    CompiledProgram.with_parallel on a mesh carrying that axis (SURVEY
    §5.7): ring rotates K/V blocks via ppermute, Ulysses head-scatters via
    all_to_all. Off-mesh the plain path runs — identical math."""
    helper = LayerHelper("scaled_dot_product_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    inputs = {"Q": [q.name], "K": [k.name], "V": [v.name]}
    if bias is not None:
        inputs["Bias"] = [bias.name]
    attrs = {"causal": causal}
    if sm_scale is not None:
        attrs["sm_scale"] = float(sm_scale)
    if seq_parallel:
        attrs["seq_parallel"] = seq_parallel
        attrs["seq_axis"] = seq_axis
    helper.append_op(
        "scaled_dot_product_attention", inputs, {"Out": [out.name]}, attrs
    )
    return out


def kv_cache_write(cache, new_kv, write_onehot, name=None):
    """Write one new key/value row per sequence into a slotted KV cache,
    functionally: ``out[s, l] = new_kv[s] if write_onehot[s, l] else
    cache[s, l]``. ``cache`` is ``[S, L, H]``, ``new_kv`` ``[S, H]``, and
    ``write_onehot`` a ``[S, L]`` float mask that is one-hot at each
    sequence's write cursor (an all-zero row leaves that sequence's cache
    bit-untouched — how a dense slotted cache freezes inactive slots;
    the serving engine's PAGED arena uses `block_scatter_write` with
    row indices instead, same exactness contract).

    Returns the updated cache; callers persist it with
    ``layers.assign(out, output=cache_var)`` so the lowering donates the
    arena and the update happens in place on device."""
    mask = unsqueeze(write_onehot, [2], name=name)       # [S, L, 1]
    new_row = unsqueeze(new_kv, [1])                     # [S, 1, H]
    return masked_write(cache, new_row, mask)


def masked_write(cache, new, mask, name=None):
    """``cache*(1-mask) + new*mask`` for a 0/1 float ``mask``
    broadcastable against both operands — THE bit-exactness-critical
    masked update for dense slotted-arena writes (`kv_cache_write`'s
    per-position one-hot; the paged decode programs scatter by row
    index instead — `block_scatter_write`).

    Composes multiply/add on existing ops instead of a scatter. Both
    branches are exact in IEEE arithmetic (``x*1.0 == x``,
    ``x + 0.0 == x``), which is what makes continuous-batching decode
    bit-identical to offline decode — positions where the mask is zero
    are never perturbed by writes addressed elsewhere."""
    keep = scale(mask, scale=-1.0, bias=1.0, name=name)  # 1 - mask
    return elementwise_add(
        elementwise_mul(cache, keep),
        elementwise_mul(new, mask),
    )


def block_gather(arena, rows, seqs, length, name=None):
    """Gather a per-sequence KV view out of a flat paged arena:
    ``arena`` ``[R, H]`` + flat row indices ``rows`` ``[seqs * length]``
    -> ``[seqs, length, H]``. The row feed is the device half of a block
    table (vLLM's PagedAttention layout): position ``p`` of sequence
    ``s`` reads arena row ``rows[s * length + p]`` =
    ``block_table[s][p // bs] * bs + p % bs``. Rows at masked positions
    (beyond the sequence's cursor) may point anywhere — the additive
    ``-1e9`` attention bias makes their contribution exactly 0.0, the
    same contract that hides stale rows in the slotted design.

    Gather relocates rows byte-for-byte, so attention over the gathered
    view is bit-identical to attention over a dense per-slot arena
    holding the same rows — the paged rebuild's exactness argument."""
    from paddle_tpu.layers.tensor import gather, reshape

    flat = gather(arena, rows, name=name)              # [seqs*length, H]
    return reshape(flat, [int(seqs), int(length), -1])


def block_scatter_write(arena, rows, new_rows, name=None):
    """Write ``new_rows`` ``[N, H]`` into flat paged arena ``arena``
    ``[R, H]`` at row indices ``rows`` ``[N]``, functionally (callers
    persist with ``assign`` so the lowering donates the arena and XLA
    updates in place). An index >= R means "this row writes NOWHERE"
    (``mode="drop"``) — how retired/inactive batch slots stay
    bit-untouched without changing the compiled shape."""
    from paddle_tpu.layers.tensor import scatter

    return scatter(arena, rows, new_rows, overwrite=True, mode="drop",
                   name=name)


def logits_mask_add(logits, mask, name=None):
    """Additive logits mask for constrained decode: ``logits + mask``
    where ``mask`` is host-built, 0.0 at allowed tokens and ``-1e9`` at
    banned ones (``[S, 1, V]`` against the decode step's logits). The
    same exactness contract as the attention bias: ``x + 0.0 == x`` in
    IEEE float32, so an all-zeros mask (no grammar active) leaves every
    logit bit-untouched, and the host applying the identical float32
    add to prefill-fetched logits reproduces the device result
    byte-for-byte — which is what keeps grammar-constrained decode
    bit-comparable to the offline reference. The mask enters as DATA
    through a fixed-shape feed, so per-step grammar state changes never
    retrace."""
    return elementwise_add(logits, mask, name=name)


def cached_attention(q, k_cache, v_cache, attn_bias, sm_scale=1.0,
                     fused=False, name=None):
    """Single-position attention of ``q`` ``[S, H]`` over a slotted KV
    cache ``[S, L, H]`` — the decode-step half of cached (incremental)
    attention; `kv_cache_write` is the other half. ``attn_bias`` is an
    additive ``[S, 1, L]`` mask fed from the host scheduler: 0.0 at
    positions ``<= cursor``, -1e9 beyond (exp underflows to exactly 0.0,
    the repo-wide padding contract), so stale cache positions are
    bit-invisible. Returns the ``[S, H]`` context vectors.

    ``fused=True`` emits ONE ``cached_attention`` op instead of the
    matmul/softmax composite: the op's reference lowering is the exact
    composite sequence (bit-identical), and the kernel registry
    (paddle_tpu/kernels/) may serve it with a fused Pallas kernel under
    ``PADDLE_TPU_KERNELS``."""
    if fused:
        helper = LayerHelper("cached_attention", name=name)
        out = helper.create_variable_for_type_inference(q.dtype)
        helper.append_op(
            "cached_attention",
            {"Q": [q.name], "KCache": [k_cache.name],
             "VCache": [v_cache.name], "Bias": [attn_bias.name]},
            {"Out": [out.name]},
            {"sm_scale": float(sm_scale)},
        )
        return out
    q3 = unsqueeze(q, [1], name=name)                    # [S, 1, H]
    scores = matmul(q3, k_cache, transpose_y=True, alpha=float(sm_scale))
    att = softmax(elementwise_add(scores, attn_bias), axis=-1)
    return squeeze(matmul(att, v_cache), [1])            # [S, H]


def paged_attention(q, k_arena, v_arena, rows, attn_bias, seqs, length,
                    sm_scale=1.0, block_size=None, kv_heads=0, name=None):
    """Fused paged attention: ``q`` ``[S, H]`` attends over rows of the
    flat ``[R, H]`` block arenas addressed by the ``[S * L]`` row feed —
    ``block_gather(k) ; block_gather(v) ; cached_attention`` as ONE op.
    The reference lowering is that exact composite. ``block_size`` says
    that ``rows`` is block-aligned (every ``block_size`` positions of a
    slot name consecutive arena rows from a multiple of ``block_size``):
    with it the kernel registry may serve the op by the blocked kernel of
    kernels/attention.py, which reads each slot's live blocks in place
    and never writes the dense ``[S, L, H]`` views. ``kv_heads`` > 0 is
    grouped-query attention: the arenas' rows hold that many K (V) heads
    side by side and ``q`` a whole number of query heads to each."""
    helper = LayerHelper("paged_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    attrs = {"sm_scale": float(sm_scale), "seqs": int(seqs),
             "length": int(length)}
    if block_size:
        attrs["block_size"] = int(block_size)
    if kv_heads:
        attrs["kv_heads"] = int(kv_heads)
    helper.append_op(
        "paged_attention",
        {"Q": [q.name], "KArena": [k_arena.name], "VArena": [v_arena.name],
         "Rows": [rows.name], "Bias": [attn_bias.name]},
        {"Out": [out.name]},
        attrs,
    )
    return out


def paged_step_feeds(packed, token, length, block_size, name=None):
    """A paged decode step's per-slot inputs, built on the device from the
    ONE ``[S, 4 + ceil(length / block_size)]`` int32 array the host puts a
    step (ops/nn.py ``paged_step_feeds``: a slot's token or -1, position,
    attention length, write row, then its block table) and ``token``, the
    ``[S, 1]`` tokens a slot with -1 takes (the step before's own output,
    still on the device). Returns ``(token [S, 1], position [S, 1], bias
    [S, 1, L] float32, rows [S * L], write_rows [S])``: what
    ``embedding``, ``paged_attention`` and ``block_scatter_write`` take."""
    helper = LayerHelper("paged_step_feeds", name=name)
    outs = {slot: helper.create_variable_for_type_inference(
        "float32" if slot == "Bias" else packed.dtype, stop_gradient=True)
        for slot in ("TokenOut", "Position", "Bias", "Rows", "WriteRows")}
    helper.append_op(
        "paged_step_feeds",
        {"Packed": [packed.name], "Token": [token.name]},
        {slot: [v.name] for slot, v in outs.items()},
        {"length": int(length), "block_size": int(block_size)},
    )
    return tuple(outs.values())


def paged_window_feeds(packed, column, blocks, block_size, name=None):
    """A paged decode step's inputs for ONE window group of attention
    layers (ops/nn.py ``paged_window_feeds``), from the columns of
    ``packed`` (``paged_step_feeds``'s array) that start at ``column``: a
    slot's ``length, low, write_row`` and a table of ``blocks`` block ids
    from its first live block. Returns ``(bias [S, 1, blocks * block_size]
    float32, rows [S * blocks * block_size], write_rows [S])``."""
    helper = LayerHelper("paged_window_feeds", name=name)
    outs = {slot: helper.create_variable_for_type_inference(
        "float32" if slot == "Bias" else packed.dtype, stop_gradient=True)
        for slot in ("Bias", "Rows", "WriteRows")}
    helper.append_op(
        "paged_window_feeds", {"Packed": [packed.name]},
        {slot: [v.name] for slot, v in outs.items()},
        {"column": int(column), "blocks": int(blocks),
         "block_size": int(block_size)},
    )
    return tuple(outs.values())


def paged_block_feeds(packed, state, length, block_size, block_len,
                      mask_token, name=None):
    """``paged_step_feeds`` for a step that runs ``block_len`` positions a
    slot which see one another (ops/nn.py ``paged_block_feeds``):
    ``packed`` ``[S, 4 + block_len + ceil(length / block_size)]`` int32 from
    the host, ``state`` ``[S, 2 * block_len]`` the pass before's block
    (tokens, decided bits), still on the device. Returns ``(token [S, B],
    position [S, B], bias [S, 1, L] float32, rows [S * L], write_rows
    [S * B], held [S, B], decided [S, B])``."""
    helper = LayerHelper("paged_block_feeds", name=name)
    outs = {slot: helper.create_variable_for_type_inference(
        "float32" if slot == "Bias" else packed.dtype, stop_gradient=True)
        for slot in ("TokenOut", "Position", "Bias", "Rows", "WriteRows",
                     "Held", "Decided")}
    helper.append_op(
        "paged_block_feeds",
        {"Packed": [packed.name], "State": [state.name]},
        {slot: [v.name] for slot, v in outs.items()},
        {"length": int(length), "block_size": int(block_size),
         "block_len": int(block_len), "mask_token": int(mask_token)},
    )
    return tuple(outs.values())


def block_fill_decide(logits, held, decided, mask_token, name=None):
    """One pass's decision over every slot's block (ops/nn.py
    ``block_fill_decide``): ``logits`` ``[S, B, V]`` float32, ``held`` and
    ``decided`` ``[S, B]`` as ``paged_block_feeds`` gave them. Returns
    ``(state [S, 2 B]``, the next pass's block; ``host`` int32 ``[2 S]``,
    each slot's decided position (-1: a commit pass) then its token)."""
    helper = LayerHelper("block_fill_decide", name=name)
    state = helper.create_variable_for_type_inference(held.dtype,
                                                      stop_gradient=True)
    host = helper.create_variable_for_type_inference("int32",
                                                     stop_gradient=True)
    helper.append_op(
        "block_fill_decide",
        {"Logits": [logits.name], "Held": [held.name],
         "Decided": [decided.name]},
        {"State": [state.name], "Host": [host.name]},
        {"mask_token": int(mask_token)},
    )
    return state, host


def chunk_mask_bias(span, chunk, length, block_len=1, name=None):
    """The chunk program's additive float32 ``[1, chunk, length]`` bias,
    made on the device from the chunk's ``span`` ``(start, real)`` (ops/nn.py
    ``chunk_mask_bias``): for a chunk program that attends by plain ops."""
    helper = LayerHelper("chunk_mask_bias", name=name)
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        "chunk_mask_bias", {"Span": [span.name]}, {"Out": [out.name]},
        {"chunk": int(chunk), "length": int(length),
         "block_len": int(block_len)})
    return out


def sparse_index_select(q, w, arena, rows, topk, block_size, bias=None,
                        span=None, name=None):
    """The rows an indexer lets each query attend to (ops/nn.py
    ``sparse_index_select``; kernels/sparse.py): ``q`` ``[N, heads *
    width]`` index queries and ``w`` ``[N, heads]`` their float32 weights
    against the index keys ``arena`` ``[R, width]`` under the row map
    ``rows``; a query keeps the ``topk`` positions of largest ``sum_j w_j
    relu(q_j . key)`` among those it sees, a tie to the lower position, all
    of them where it sees fewer. A decode step hands its additive ``bias``
    ``[S, 1, L]`` and gets it back with the positions not kept closed (what
    ``paged_attention`` takes); a prompt chunk hands its ``span`` and gets
    the int8 ``[C, >= L]`` mask that ``chunk_paged_attention`` takes."""
    helper = LayerHelper("sparse_index_select", name=name)
    step = bias is not None
    out = helper.create_variable_for_type_inference(
        bias.dtype if step else "int8")
    helper.append_op(
        "sparse_index_select",
        {"Q": [q.name], "W": [w.name], "Arena": [arena.name],
         "Rows": [rows.name],
         **({"Bias": [bias.name]} if step else {"Span": [span.name]})},
        {"Out": [out.name]},
        {"topk": int(topk), "block_size": int(block_size)},
    )
    return out


def chunk_paged_attention(q, k_arena, v_arena, rows, span, kv_heads,
                          block_size, sm_scale=1.0, block_len=1, window=0,
                          mask=None, name=None):
    """A prompt chunk's queries ``[C, heads * D]`` over ONE sequence's
    ``[L]`` rows of the paged arenas, grouped-query (``kv_heads`` K/V heads
    a row), under the mask the device makes of ``span`` (the chunk's first
    position and its count of real positions) and ``block_len``: the chunk
    program's form of ``paged_attention``, from the live blocks alone where
    the kernel serves it (kernels/attention.py ``chunk_attention``). With
    ``window`` W a query sees the last W positions, its own among them, and
    nothing older (``chunk_floor``). With ``mask`` (``sparse_index_select``'s
    int8 ``[C, >= L]``) a query sees the rows its mask keeps, which lie
    under the span's horizon, and nothing else."""
    helper = LayerHelper("chunk_paged_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    helper.append_op(
        "chunk_paged_attention",
        {"Q": [q.name], "KArena": [k_arena.name], "VArena": [v_arena.name],
         "Rows": [rows.name], "Span": [span.name],
         **({"Mask": [mask.name]} if mask is not None else {})},
        {"Out": [out.name]},
        {"sm_scale": float(sm_scale), "kv_heads": int(kv_heads),
         "block_size": int(block_size), "block_len": int(block_len),
         # written only where there is one: a program without a window
         # keeps the bytes it had (the compile cache's key)
         **({"window": int(window)} if window else {})},
    )
    return out


def rms_norm(input, epsilon=1e-5, param_attr=None, out_dtype=None,
             name=None):
    """RMSNorm over the last dimension with a learned scale (ones at
    start), computed in float32; ``out_dtype`` names the result's dtype
    (a float32 residual normed into a bfloat16 mixer input)."""
    from paddle_tpu.initializer import ConstantInitializer

    helper = LayerHelper("rms_norm", param_attr=param_attr, name=name)
    scale = helper.create_parameter(
        helper.param_attr, shape=[int(input.shape[-1])], dtype="float32",
        default_initializer=ConstantInitializer(1.0))
    out = helper.create_variable_for_type_inference(out_dtype or input.dtype)
    attrs = {"epsilon": float(epsilon)}
    if out_dtype:
        attrs["out_dtype"] = out_dtype
    helper.append_op("rms_norm", {"X": [input.name], "Scale": [scale.name]},
                     {"Out": [out.name]}, attrs)
    return out


def rotary_embedding(x, positions, theta=10000.0, out_dtype=None, name=None,
                     freqs=None, interleaved=False):
    """Rotary positions (ops/nn.py ``rotary_embedding``): ``x`` ``[...,
    heads, D]`` turned, whole head and rotate-half, by ``positions``
    ``[...]`` (what ``paged_step_feeds`` gives a step, the chunk program's
    position feed) at base ``theta``; float32 inside. ``freqs`` (``D / 2``
    numbers) is a frequency table in ``theta``'s place, ``interleaved``
    pairs lanes ``(2 i, 2 i + 1)``."""
    helper = LayerHelper("rotary_embedding", name=name)
    out = helper.create_variable_for_type_inference(out_dtype or x.dtype)
    attrs = {"theta": float(theta)}
    if out_dtype:
        attrs["out_dtype"] = out_dtype
    # written only where they are not the default: a program that does not
    # use them keeps the bytes, and the compile-cache key, it had
    if freqs is not None:
        attrs["freqs"] = [float(f) for f in freqs]
    if interleaved:
        attrs["interleaved"] = True
    helper.append_op("rotary_embedding",
                     {"X": [x.name], "Positions": [positions.name]},
                     {"Out": [out.name]}, attrs)
    return out


def position_log_scale(x, positions, beta, period, out_dtype=None, name=None):
    """``x`` times ``1 + beta * ln(1 + floor(position / period))`` (ops/nn.py
    ``position_log_scale``): a query scaled by how many ``period``s of
    context lie before it."""
    helper = LayerHelper("position_log_scale", name=name)
    out = helper.create_variable_for_type_inference(out_dtype or x.dtype)
    attrs = {"beta": float(beta), "period": int(period)}
    if out_dtype:
        attrs["out_dtype"] = out_dtype
    helper.append_op("position_log_scale",
                     {"X": [x.name], "Positions": [positions.name]},
                     {"Out": [out.name]}, attrs)
    return out


def _latent_weights(helper, heads, nope, value, latent, param_attrs, dtype):
    """``(w_uk [heads, nope, latent], w_uv [heads, latent, value])``: the
    compressed K/V's up-projection, a head's key part and value part."""
    return (helper.create_parameter(param_attrs["w_uk"],
                                    shape=[heads, nope, latent], dtype=dtype),
            helper.create_parameter(param_attrs["w_uv"],
                                    shape=[heads, latent, value], dtype=dtype))


def paged_latent_attention(q, arena, rows, attn_bias, seqs, length, heads,
                           nope, rope, value, latent, param_attrs,
                           sm_scale=1.0, block_size=None, name=None):
    """A decode step's latent attention (ops/nn.py
    ``paged_latent_attention``): ``q`` ``[S, heads * (nope + rope)]`` over
    the ONE arena of rows ``[c (latent) | k^R (rope) | zeros]``, absorbed;
    ``param_attrs``: ``w_uk`` and ``w_uv``. Returns ``[S, heads *
    value]``."""
    helper = LayerHelper("paged_latent_attention", name=name)
    w_uk, w_uv = _latent_weights(helper, heads, nope, value, latent,
                                 param_attrs, q.dtype)
    out = helper.create_variable_for_type_inference(q.dtype)
    attrs = {"sm_scale": float(sm_scale), "seqs": int(seqs),
             "length": int(length), "rope": int(rope)}
    if block_size:
        attrs["block_size"] = int(block_size)
    helper.append_op(
        "paged_latent_attention",
        {"Q": [q.name], "WUK": [w_uk.name], "WUV": [w_uv.name],
         "Arena": [arena.name], "Rows": [rows.name],
         "Bias": [attn_bias.name]},
        {"Out": [out.name]}, attrs)
    return out


def chunk_latent_attention(q, arena, rows, span, heads, nope, rope, value,
                           latent, param_attrs, sm_scale=1.0,
                           block_size=None, name=None):
    """A prompt chunk's latent attention (ops/nn.py
    ``chunk_latent_attention``): ``q`` ``[C, heads * (nope + rope)]`` over
    ONE sequence's rows of the latent arena under the mask of ``span``,
    expanded (kernels/attention.py ``latent_chunk_expanded``; with
    ``block_size``, the arena's, the ``latent_chunk_attention`` kernel can
    walk the slot's block table). Returns ``[C, heads * value]``."""
    helper = LayerHelper("chunk_latent_attention", name=name)
    w_uk, w_uv = _latent_weights(helper, heads, nope, value, latent,
                                 param_attrs, q.dtype)
    out = helper.create_variable_for_type_inference(q.dtype)
    attrs = {"sm_scale": float(sm_scale), "rope": int(rope)}
    if block_size:
        attrs["block_size"] = int(block_size)
    helper.append_op(
        "chunk_latent_attention",
        {"Q": [q.name], "WUK": [w_uk.name], "WUV": [w_uv.name],
         "Arena": [arena.name], "Rows": [rows.name], "Span": [span.name]},
        {"Out": [out.name]}, attrs)
    return out


def relu2(x, name=None):
    """Squared relu, ``max(x, 0)^2`` (also ``fc(..., act="relu2")``)."""
    helper = LayerHelper("relu2", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("relu2", {"X": [x.name]}, {"Out": [out.name]})
    return out


def moe_routed_experts(input, write_rows, num_rows, router_experts,
                       held_experts, ffn_dim, k, param_attrs, expert_offset=0,
                       score_scale=1.0, normalize=True, norm_epsilon=1e-20,
                       kernel=False, score="sigmoid", group_counts=False,
                       name=None):
    """This chip's share of a routed-experts layer (ops/moe.py
    ``moe_routed_experts``): the router scores ``input`` ``[..., H]``
    against all ``router_experts`` (``score``: ``sigmoid`` scores, or a
    ``softmax`` over all the experts; a selection bias, top ``k``,
    normalised over the k's sum + ``norm_epsilon``, times ``score_scale``) and the ``held_experts`` that live here (ids from
    ``expert_offset``) add their FFNs' part; no capacity, no dropped token.
    ``write_rows`` marks the real tokens (a row ``>= num_rows`` is routed
    nowhere). ``param_attrs``: ``gate`` ``[E_all, H]`` and ``select_bias``
    ``[E_all]`` (float32), ``w_up``, ``w_down`` ``[held, F, H]``
    (``input``'s dtype) and, for gated experts (``silu(gate) * up`` in
    place of ``relu(up)^2``), ``w_gate`` likewise. ``kernel`` lets the
    ``moe_experts`` kernel serve the op (the decode step); elsewhere the op
    takes the grouped product where its rule says so. Returns ``(out
    float32, counts int32 [4])`` and, with ``group_counts``, int32 ``[3]``
    besides: the (token, held expert) pairs, the rows multiplied for them
    and the held experts with a pair."""
    helper = LayerHelper("moe_routed_experts", name=name)
    hidden = int(input.shape[-1])
    gate = helper.create_parameter(
        param_attrs["gate"], shape=[router_experts, hidden], dtype="float32")
    select_bias = helper.create_parameter(
        param_attrs["select_bias"], shape=[router_experts], dtype="float32")
    w_up = helper.create_parameter(
        param_attrs["w_up"], shape=[held_experts, ffn_dim, hidden],
        dtype=input.dtype)
    w_down = helper.create_parameter(
        param_attrs["w_down"], shape=[held_experts, ffn_dim, hidden],
        dtype=input.dtype)
    ins = {"X": [input.name], "GateW": [gate.name],
           "SelectBias": [select_bias.name], "WUp": [w_up.name],
           "WDown": [w_down.name], "WriteRows": [write_rows.name]}
    if "w_gate" in param_attrs:
        ins["WGate"] = [helper.create_parameter(
            param_attrs["w_gate"], shape=[held_experts, ffn_dim, hidden],
            dtype=input.dtype).name]
    out = helper.create_variable_for_type_inference("float32")
    counts = helper.create_variable_for_type_inference("int32")
    outs = {"Out": [out.name], "Counts": [counts.name]}
    pairs = None
    if group_counts:
        pairs = helper.create_variable_for_type_inference("int32")
        outs["GroupCounts"] = [pairs.name]
    helper.append_op(
        "moe_routed_experts", ins, outs,
        {"k": int(k), "score_scale": float(score_scale),
         "normalize": bool(normalize), "norm_epsilon": float(norm_epsilon),
         "expert_offset": int(expert_offset),
         "num_rows": int(num_rows), "kernel": bool(kernel),
         # written only where it is not the default: a program that
         # scores by sigmoid keeps the bytes it had (the compile cache's
         # key: ROADMAP 3.13)
         **({"score": str(score)} if score != "sigmoid" else {}),
         **({"group_counts": True} if group_counts else {})},
    )
    return (out, counts, pairs) if group_counts else (out, counts)


def mamba2_mixer(input, conv_state, ssm_state, write_rows, num_rows, mode,
                 heads, head_dim, groups, state_size, conv_kernel,
                 param_attrs, slot=None, positions=None, chunk_size=128,
                 epsilon=1e-5, out_dtype=None, name=None):
    """The Mamba-2 mixer between its projections (ops/mamba.py): ``input``
    is the input projection's ``z | xBC | dt``, ``[1, C, width]`` of the one
    slot ``slot`` names (``mode="chunk"``; ``positions`` tells a prompt's
    first chunk, which starts from zero states) or ``[S, 1, width]``
    (``mode="step"``). Advances the per-slot ``conv_state``
    ``[S, K - 1, D]`` and ``ssm_state`` ``[S, H, P, N]`` in place (assigned
    back, so the lowering donates them) for the tokens ``write_rows`` marks
    as real, and returns the gated, normed ``y``. ``param_attrs``:
    ``conv_w`` ``[K, D]``, ``conv_b`` ``[D]``, ``dt_bias``, ``a_log``, ``d``
    ``[H]``, ``norm_w`` ``[H * P]``, all float32."""
    from paddle_tpu.layers.tensor import assign

    helper = LayerHelper("mamba2_mixer", name=name)
    d_inner = heads * head_dim
    conv_dim = d_inner + 2 * groups * state_size
    shapes = {"conv_w": [conv_kernel, conv_dim], "conv_b": [conv_dim],
              "dt_bias": [heads], "a_log": [heads], "d": [heads],
              "norm_w": [d_inner]}
    params = {k: helper.create_parameter(param_attrs[k], shape=shape,
                                         dtype="float32")
              for k, shape in shapes.items()}
    ins = {"X": [input.name], "ConvW": [params["conv_w"].name],
           "ConvB": [params["conv_b"].name],
           "DtBias": [params["dt_bias"].name],
           "ALog": [params["a_log"].name], "D": [params["d"].name],
           "NormW": [params["norm_w"].name],
           "ConvState": [conv_state.name], "SsmState": [ssm_state.name],
           "WriteRows": [write_rows.name]}
    if mode == "chunk":
        ins["Slot"] = [slot.name]
        ins["Positions"] = [positions.name]
    out = helper.create_variable_for_type_inference(out_dtype or input.dtype)
    new_conv = helper.create_variable_for_type_inference(conv_state.dtype)
    new_ssm = helper.create_variable_for_type_inference(ssm_state.dtype)
    attrs = {"mode": mode, "heads": int(heads), "head_dim": int(head_dim),
             "groups": int(groups), "state_size": int(state_size),
             "chunk_size": int(chunk_size), "epsilon": float(epsilon),
             "num_rows": int(num_rows)}
    if out_dtype:
        attrs["out_dtype"] = out_dtype
    helper.append_op(
        "mamba2_mixer", ins,
        {"Out": [out.name], "ConvStateOut": [new_conv.name],
         "SsmStateOut": [new_ssm.name]}, attrs)
    assign(new_conv, output=conv_state)
    assign(new_ssm, output=ssm_state)
    return out


def gated_short_conv(input, conv_state, write_rows, num_rows, mode, taps,
                     param_attr, slot=None, positions=None, out_dtype=None,
                     name=None):
    """The gated short convolution between its projections
    (ops/short_conv.py): ``input`` is the input projection's ``B | C | u``,
    ``[1, C, 3 D]`` of the one slot ``slot`` names (``mode="chunk"``;
    ``positions`` tells a prompt's first chunk, whose tail starts from
    zeros) or ``[S, 1, 3 D]`` (``mode="step"``). Advances the per-slot
    ``conv_state`` ``[S, taps - 1, D]`` in place (assigned back, so the
    lowering donates it) for the tokens ``write_rows`` marks as real, and
    returns ``C * conv(B * u)``. ``param_attr``: the convolution's weight
    ``[taps, D]``, float32."""
    from paddle_tpu.layers.tensor import assign

    helper = LayerHelper("gated_short_conv", name=name)
    width = int(input.shape[-1]) // 3
    conv_w = helper.create_parameter(param_attr, shape=[int(taps), width],
                                     dtype="float32")
    ins = {"X": [input.name], "ConvW": [conv_w.name],
           "ConvState": [conv_state.name], "WriteRows": [write_rows.name]}
    if mode == "chunk":
        ins["Slot"] = [slot.name]
        ins["Positions"] = [positions.name]
    out = helper.create_variable_for_type_inference(out_dtype or input.dtype)
    new_conv = helper.create_variable_for_type_inference(conv_state.dtype)
    attrs = {"mode": mode, "num_rows": int(num_rows)}
    if out_dtype:
        attrs["out_dtype"] = out_dtype
    helper.append_op("gated_short_conv", ins,
                     {"Out": [out.name], "ConvStateOut": [new_conv.name]},
                     attrs)
    assign(new_conv, output=conv_state)
    return out


def moe_ffn(input, num_experts, d_ff=None, expert_axis="expert",
            capacity_factor=2.0, capacity=0, activation="gelu",
            param_attr=None, name=None):
    """Top-2 gated mixture-of-experts FFN (expert parallelism on the IR
    path — SURVEY §2.7 new first-class work). `input` [..., H] is routed
    through `num_experts` stacked FFNs; compiled on a mesh whose
    `expert_axis` has size > 1, experts and tokens shard over that axis
    with all_to_all dispatch (ops/moe.py); otherwise the routing runs
    dense. Returns (out, aux_loss) — add aux_loss to the objective for
    load balancing."""
    from paddle_tpu.initializer import ConstantInitializer
    from paddle_tpu.param_attr import ParamAttr

    helper = LayerHelper("moe_ffn", param_attr=param_attr, name=name)
    H = input.shape[-1]
    F = d_ff or 4 * H
    base = helper.param_attr

    def _wattr(suffix):
        # one ParamAttr per weight: sharing a NAMED attr would resolve all
        # three weights to the same variable (create_parameter returns the
        # existing var on a name hit)
        return ParamAttr(
            name=f"{base.name}_{suffix}" if base.name else None,
            initializer=base.initializer,
            regularizer=base.regularizer,
            trainable=base.trainable,
        )

    gate_w = helper.create_parameter(
        _wattr("gate"), shape=[H, num_experts], dtype="float32",
    )
    w1 = helper.create_parameter(_wattr("w1"), shape=[num_experts, H, F],
                                 dtype="float32")
    b1 = helper.create_parameter(
        ParamAttr(initializer=ConstantInitializer(0.0)),
        shape=[num_experts, F], dtype="float32",
    )
    w2 = helper.create_parameter(_wattr("w2"), shape=[num_experts, F, H],
                                 dtype="float32")
    b2 = helper.create_parameter(
        ParamAttr(initializer=ConstantInitializer(0.0)),
        shape=[num_experts, H], dtype="float32",
    )
    out = helper.create_variable_for_type_inference(input.dtype)
    aux = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        "moe_ffn",
        {"X": [input.name], "GateW": [gate_w.name], "W1": [w1.name],
         "B1": [b1.name], "W2": [w2.name], "B2": [b2.name]},
        {"Out": [out.name], "AuxLoss": [aux.name]},
        {"expert_axis": expert_axis, "capacity_factor": capacity_factor,
         "capacity": capacity, "activation": activation},
    )
    return out, aux


def _next_table_id(program):
    """First free PS table id across BOTH registries (host-pull
    `_sparse_tables` and in-graph `_remote_tables`) — one allocation rule
    for every producer (sparse_embedding, distributed_embedding, the
    is_distributed transpiler)."""
    used = {
        t["table_id"]
        for reg in ("_sparse_tables", "_remote_tables")
        for t in getattr(program, reg, {}).values()
    }
    return max(used, default=100) + 1


def sparse_embedding(
    input,
    embedding_dim,
    table_id=None,
    init_range=0.01,
    optimizer="sgd",
    name=None,
):
    """Parameter-server-backed embedding for billion-feature tables
    (reference: distributed_lookup_table / prefetch flow —
    paddle/fluid/operators/distributed/parameter_prefetch.cc; pslib pull in
    fleet_wrapper.h:84). The table never materializes on device: per step
    the PS worker pulls the batch's unique rows (fleet/parameter_server.py
    PSWorker.run), feeds them as `<name>__rows`, and the graph gathers via
    `<name>__idx`; row grads flow back through the gather vjp and are pushed
    to the server. `input` must be an int feed var of ids (any shape)."""
    from paddle_tpu.core.ir import default_main_program
    from paddle_tpu.layers import tensor as tensor_layers

    helper = LayerHelper("sparse_embedding", name=name)
    tname = name or unique_name.generate("sparse_emb")
    program = default_main_program()
    tables = getattr(program, "_sparse_tables", None)
    if tables is None:
        tables = program._sparse_tables = {}
    if table_id is None:
        table_id = _next_table_id(program)
    rows = tensor_layers.data(
        f"{tname}__rows", shape=[-1, embedding_dim],
        dtype="float32", append_batch_size=False,
    )
    rows.stop_gradient = False  # leaf grad target (extra seed in backward)
    idx_shape = [(-1 if d in (-1, None) else d) for d in (input.shape or [-1])]
    idx = tensor_layers.data(
        f"{tname}__idx", shape=idx_shape, dtype="int32",
        append_batch_size=False,
    )
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        "lookup_table_ps",
        {"Rows": [rows.name], "Idx": [idx.name]},
        {"Out": [out.name]},
        {"table_id": table_id},
    )
    tables[tname] = {
        "table_id": table_id,
        "ids": input.name,
        "rows": rows.name,
        "idx": idx.name,
        "dim": embedding_dim,
        "init_range": init_range,
        "optimizer": optimizer,
    }
    return out


def distributed_embedding(
    input,
    size,
    table_name=None,
    table_id=None,
    init_range=0.01,
    optimizer="sgd",
    dtype="float32",
):
    """Embedding whose table lives ONLY on parameter servers, pulled inside
    the compiled step (reference: distributed_lookup_table +
    paddle/fluid/operators/distributed/parameter_prefetch.cc:1). No local
    parameter is created; `size` is [vocab, dim] where vocab is advisory
    (servers grow rows on demand — billion-feature tables never
    materialize). The backward pushes merged row grads to the servers
    (ParameterServerOptimizer wires the push op); fleet.init_worker()
    creates the server tables and activates the lookup context. Use
    `RemoteLookupContext.prefetch` / PSWorker.prefetch for double-buffered
    pulls.

    Id range: in-graph ids ride the XLA int path (int32 under the default
    x64-disabled config), so ids must be < 2^31 — pre-hash larger spaces
    (`id % (2**31 - 1)`, the reference's hash-op recipe) or use
    `sparse_embedding`, whose host-side pull keeps the full uint64 space."""
    from paddle_tpu.core.ir import default_main_program
    from paddle_tpu.utils.enforce import enforce

    enforce(
        dtype == "float32",
        f"distributed_embedding dtype must be float32 (got {dtype}): the "
        "PS wire format and the in-step pull callback are f32",
    )
    helper = LayerHelper("distributed_embedding", name=table_name)
    tname = table_name or unique_name.generate("dist_emb")
    dim = int(size[1])
    program = default_main_program()
    tables = getattr(program, "_remote_tables", None)
    if tables is None:
        tables = program._remote_tables = {}
    if table_id is None:
        table_id = _next_table_id(program)
    out = helper.create_variable_for_type_inference(dtype)
    ids_shape = [d for d in (input.shape or [-1])]
    if len(ids_shape) >= 2 and ids_shape[-1] == 1:
        ids_shape = ids_shape[:-1]
    out.shape = ids_shape + [dim]
    out.stop_gradient = False
    helper.append_op(
        "distributed_lookup_table",
        {"Ids": [input.name]},
        {"Outputs": [out.name]},
        {"table_name": tname, "dim": dim},
    )
    tables[tname] = {
        "table_id": table_id,
        "table_name": tname,  # wire/registration name (entry keys may differ)
        "ids": input.name,
        "out": out.name,
        "dim": dim,
        "init_range": init_range,
        "optimizer": optimizer,
    }
    return out


def sharded_embedding(
    input,
    embedding_dim,
    capacity=65536,
    ep=1,
    name=None,
    init_range=0.01,
    lr=0.1,
    seed=0,
    min_bucket=8,
    vocab_size=None,
):
    """Embedding over the two-tier sharded engine (paddle_tpu/embedding/):
    hot rows live in a device slab row-sharded over the ``ep`` mesh axis,
    the cold tail overflows to host RAM, and the step gathers the slab
    ONCE at the batch's deduplicated unique ids. The TPU-native successor
    to both ``embedding`` (needs a dense [vocab, dim] device table) and
    ``sparse_embedding`` (round-trips every batch's rows host<->device).

    The graph sees only cache-sized tensors: ``<name>__slots`` (unique
    slot indices, bucket-padded) and ``<name>__inv`` (occurrence ->
    unique map), both produced per step by
    ``EmbeddingEngine.prepare_feed``. The slab trains with its OWN
    row-sparse SGD at ``lr`` — the deferred ``sharded_embedding_update``
    pass strips whatever dense optimizer ``minimize`` attached (an Adam
    step on untouched cached rows would drift them, breaking the
    engine's cache-size-invariance contract). ``capacity`` must divide
    evenly by ``ep``; ids span the full u64 space (``vocab_size`` is
    advisory, like the PS tables)."""
    from paddle_tpu.core.ir import default_main_program
    from paddle_tpu.embedding.table import TableConfig
    from paddle_tpu.initializer import ConstantInitializer
    from paddle_tpu.layers import tensor as tensor_layers
    from paddle_tpu.param_attr import ParamAttr

    helper = LayerHelper("sharded_embedding", name=name)
    tname = name or unique_name.generate("sharded_emb")
    cfg = TableConfig(
        tname, embedding_dim, capacity, ep=ep, vocab_size=vocab_size,
        init_range=init_range, lr=lr, seed=seed, min_bucket=min_bucket,
    )
    program = default_main_program()
    tables = getattr(program, "_sharded_tables", None)
    if tables is None:
        tables = program._sharded_tables = {}

    slab = helper.create_parameter(
        ParamAttr(name=cfg.slab_name,
                  initializer=ConstantInitializer(0.0)),
        shape=[cfg.capacity, cfg.dim], dtype="float32",
    )
    slots = tensor_layers.data(
        f"{tname}__slots", shape=[-1], dtype="int32",
        append_batch_size=False,
    )
    ids_shape = [d for d in (input.shape or [-1])]
    if len(ids_shape) >= 2 and ids_shape[-1] == 1:
        ids_shape = ids_shape[:-1]
    idx_shape = [(-1 if d in (-1, None) else d) for d in ids_shape]
    inv = tensor_layers.data(
        f"{tname}__inv", shape=idx_shape, dtype="int32",
        append_batch_size=False,
    )
    out = helper.create_variable_for_type_inference("float32")
    out.shape = idx_shape + [cfg.dim]
    out.stop_gradient = False
    helper.append_op(
        "sharded_embedding_lookup",
        {"Table": [slab.name], "Slots": [slots.name], "Inv": [inv.name]},
        {"Out": [out.name]},
        cfg.to_attrs(),
    )
    program._wants_sharded_embedding_update = True
    tables[tname] = {
        "table_name": tname,
        "ids": input.name,
        "slots": slots.name,
        "inv": inv.name,
        "slab": cfg.slab_name,
        "dim": cfg.dim,
        "capacity": cfg.capacity,
        "ep": cfg.ep,
        "vocab_size": vocab_size,
        "init_range": cfg.init_range,
        "lr": cfg.lr,
        "seed": cfg.seed,
        "min_bucket": cfg.min_bucket,
    }
    return out


def dropout(
    x,
    dropout_prob,
    is_test=False,
    seed=0,
    name=None,
    dropout_implementation="downgrade_in_infer",
):
    """Zero each element of `x` with probability `dropout_prob` (reference:
    fluid.layers.dropout). The mask is saved for the gradient. With `seed`
    0 it follows the program's `random_seed` and the executor's step count,
    so equal (seed, step) give equal masks. Under
    `CompiledProgram.with_parallel` over a mesh whose data axes divide
    dim 0, each shard draws its own rows' bits (ops/common.py keep_mask):
    the masks are a function of (seed, step, op, shard), not those the seed
    gives on one device, and they depend on the data axes' size."""
    helper = LayerHelper("dropout", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    mask = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    helper.append_op(
        "dropout",
        {"X": [x.name]},
        {"Out": [out.name], "Mask": [mask.name]},
        {
            "dropout_prob": dropout_prob,
            "is_test": is_test,
            "seed": seed,
            "dropout_implementation": dropout_implementation,
        },
    )
    return out


# -- activations ------------------------------------------------------------


def _make_act(op_type):
    def act_fn(x, name=None, **attrs):
        return _single_op(op_type, x, attrs, name=name)

    act_fn.__name__ = op_type
    return act_fn


relu = _make_act("relu")
relu6 = _make_act("relu6")
sigmoid = _make_act("sigmoid")
tanh = _make_act("tanh")
leaky_relu = _make_act("leaky_relu")
elu = _make_act("elu")
swish = _make_act("swish")
hard_swish = _make_act("hard_swish")
hard_sigmoid = _make_act("hard_sigmoid")
softplus = _make_act("softplus")
softsign = _make_act("softsign")
sqrt = _make_act("sqrt")
square = _make_act("square")
abs = _make_act("abs")
exp = _make_act("exp")
log = _make_act("log")
sin = _make_act("sin")
cos = _make_act("cos")
erf = _make_act("erf")


def gelu(x, approximate=False, name=None):
    return _single_op("gelu", x, {"approximate": approximate}, name=name)


def pow(x, factor=1.0, name=None):
    return _single_op("pow", x, {"factor": factor}, name=name)


def softmax(input, axis=-1, name=None):
    return _single_op("softmax", input, {"axis": axis}, name=name)


def log_softmax(input, axis=-1, name=None):
    return _single_op("log_softmax", input, {"axis": axis}, name=name)


def prelu(x, mode="all", param_attr=None, name=None):
    from paddle_tpu.initializer import ConstantInitializer

    helper = LayerHelper("prelu", param_attr=param_attr, name=name)
    alpha_shape = [1] if mode == "all" else [x.shape[1]]
    alpha = helper.create_parameter(
        helper.param_attr,
        shape=alpha_shape,
        dtype=x.dtype,
        default_initializer=ConstantInitializer(0.25),
    )
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        "prelu",
        {"X": [x.name], "Alpha": [alpha.name]},
        {"Out": [out.name]},
        {"mode": mode},
    )
    return out


# -- elementwise / math -----------------------------------------------------


def elementwise_op(op_type, x, y, axis=-1, act=None, name=None):
    helper = LayerHelper(op_type, act=act, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        op_type, {"X": [x.name], "Y": [y.name]}, {"Out": [out.name]}, {"axis": axis}
    )
    return helper.append_activation(out)


def elementwise_add(x, y, axis=-1, act=None, name=None):
    return elementwise_op("elementwise_add", x, y, axis, act, name)


def elementwise_sub(x, y, axis=-1, act=None, name=None):
    return elementwise_op("elementwise_sub", x, y, axis, act, name)


def elementwise_mul(x, y, axis=-1, act=None, name=None):
    return elementwise_op("elementwise_mul", x, y, axis, act, name)


def elementwise_div(x, y, axis=-1, act=None, name=None):
    return elementwise_op("elementwise_div", x, y, axis, act, name)


def elementwise_max(x, y, axis=-1, act=None, name=None):
    return elementwise_op("elementwise_max", x, y, axis, act, name)


def elementwise_min(x, y, axis=-1, act=None, name=None):
    return elementwise_op("elementwise_min", x, y, axis, act, name)


def elementwise_pow(x, y, axis=-1, act=None, name=None):
    return elementwise_op("elementwise_pow", x, y, axis, act, name)


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None,
           out_dtype=None):
    helper = LayerHelper("matmul", name=name)
    out = helper.create_variable_for_type_inference(out_dtype or x.dtype)
    attrs = {"transpose_X": transpose_x, "transpose_Y": transpose_y,
             "alpha": alpha}
    if out_dtype:
        attrs["out_dtype"] = out_dtype
    helper.append_op(
        "matmul",
        {"X": [x.name], "Y": [y.name]},
        {"Out": [out.name]},
        attrs,
    )
    return out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper("mul", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        "mul",
        {"X": [x.name], "Y": [y.name]},
        {"Out": [out.name]},
        {"x_num_col_dims": x_num_col_dims, "y_num_col_dims": y_num_col_dims},
    )
    return out


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    helper = LayerHelper("scale", act=act, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        "scale",
        {"X": [x.name]},
        {"Out": [out.name]},
        {"scale": scale, "bias": bias, "bias_after_scale": bias_after_scale},
    )
    return helper.append_activation(out)


def mean(x, name=None):
    return _single_op("mean", x, name=name)


def _make_reduce(op_type):
    def fn(input, dim=None, keep_dim=False, name=None):
        attrs = {
            "dim": dim if dim is not None else [0],
            "keep_dim": keep_dim,
            "reduce_all": dim is None,
        }
        return _single_op(op_type, input, attrs, name=name)

    fn.__name__ = op_type
    return fn


reduce_sum = _make_reduce("reduce_sum")
reduce_mean = _make_reduce("reduce_mean")
reduce_max = _make_reduce("reduce_max")
reduce_min = _make_reduce("reduce_min")
reduce_prod = _make_reduce("reduce_prod")


def clip(x, min, max, name=None):
    return _single_op("clip", x, {"min": min, "max": max}, name=name)


def clip_by_norm(x, max_norm, name=None):
    return _single_op("clip_by_norm", x, {"max_norm": max_norm}, name=name)


def l2_normalize(x, axis=-1, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", name=name)
    sq = square(x)
    ssum = reduce_sum(sq, dim=[axis] if axis is not None else None, keep_dim=True)
    norm = sqrt(elementwise_add(ssum, fill_constant_like(ssum, epsilon)))
    return elementwise_div(x, norm)


def fill_constant_like(x, value):
    from paddle_tpu.layers.tensor import fill_constant

    return fill_constant(shape=[1], dtype=x.dtype, value=value)


# -- losses & metrics -------------------------------------------------------


def cross_entropy(input, label, soft_label=False, ignore_index=-100, name=None):
    helper = LayerHelper("cross_entropy", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "cross_entropy",
        {"X": [input.name], "Label": [label.name]},
        {"Y": [out.name]},
        {"soft_label": soft_label, "ignore_index": ignore_index},
    )
    return out


def softmax_with_cross_entropy(
    logits,
    label,
    soft_label=False,
    ignore_index=-100,
    return_softmax=False,
    axis=-1,
    name=None,
):
    helper = LayerHelper("softmax_with_cross_entropy", name=name)
    softmax_out = helper.create_variable_for_type_inference(logits.dtype)
    loss = helper.create_variable_for_type_inference(logits.dtype)
    helper.append_op(
        "softmax_with_cross_entropy",
        {"Logits": [logits.name], "Label": [label.name]},
        {"Softmax": [softmax_out.name], "Loss": [loss.name]},
        {"soft_label": soft_label, "ignore_index": ignore_index, "axis": axis},
    )
    if return_softmax:
        return loss, softmax_out
    return loss


def sigmoid_cross_entropy_with_logits(
    x, label, ignore_index=-100, normalize=False, name=None
):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        "sigmoid_cross_entropy_with_logits",
        {"X": [x.name], "Label": [label.name]},
        {"Out": [out.name]},
        {"ignore_index": ignore_index, "normalize": normalize},
    )
    return out


def square_error_cost(input, label, name=None):
    helper = LayerHelper("square_error_cost", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "square_error_cost",
        {"X": [input.name], "Y": [label.name]},
        {"Out": [out.name]},
    )
    return out


def mse_loss(input, label, name=None):
    return mean(square_error_cost(input, label), name=name)


def huber_loss(input, label, delta, name=None):
    helper = LayerHelper("huber_loss", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    residual = helper.create_variable_for_type_inference(
        input.dtype, stop_gradient=True
    )
    helper.append_op(
        "huber_loss",
        {"X": [input.name], "Y": [label.name]},
        {"Out": [out.name], "Residual": [residual.name]},
        {"delta": delta},
    )
    return out


def kldiv_loss(x, target, reduction="mean", name=None):
    helper = LayerHelper("kldiv_loss", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        "kldiv_loss",
        {"X": [x.name], "Target": [target.name]},
        {"Loss": [out.name]},
        {"reduction": reduction},
    )
    return out


def topk(input, k, name=None):
    helper = LayerHelper("top_k", name=name)
    values = helper.create_variable_for_type_inference(input.dtype)
    indices = helper.create_variable_for_type_inference("int64", stop_gradient=True)
    helper.append_op(
        "top_k",
        {"X": [input.name]},
        {"Out": [values.name], "Indices": [indices.name]},
        {"k": k},
    )
    return values, indices


def accuracy(input, label, k=1, name=None):
    """reference: python/paddle/fluid/layers/metric_op.py accuracy."""
    helper = LayerHelper("accuracy", name=name)
    values, indices = topk(input, k)
    acc = helper.create_variable_for_type_inference("float32", stop_gradient=True)
    correct = helper.create_variable_for_type_inference("int32", stop_gradient=True)
    total = helper.create_variable_for_type_inference("int32", stop_gradient=True)
    helper.append_op(
        "accuracy",
        {"Out": [values.name], "Indices": [indices.name], "Label": [label.name]},
        {"Accuracy": [acc.name], "Correct": [correct.name], "Total": [total.name]},
    )
    return acc


def auc(input, label, num_thresholds=4095, name=None):
    """Streaming AUC; stats are persistable state vars
    (reference: python/paddle/fluid/layers/metric_op.py auc)."""
    from paddle_tpu.initializer import ConstantInitializer
    from paddle_tpu.param_attr import ParamAttr

    helper = LayerHelper("auc", name=name)
    stat_pos = helper.create_parameter(
        ParamAttr(initializer=ConstantInitializer(0.0), trainable=False),
        shape=[num_thresholds + 1],
        dtype="int64",
    )
    stat_neg = helper.create_parameter(
        ParamAttr(initializer=ConstantInitializer(0.0), trainable=False),
        shape=[num_thresholds + 1],
        dtype="int64",
    )
    auc_out = helper.create_variable_for_type_inference("float32", stop_gradient=True)
    helper.append_op(
        "auc",
        {
            "Predict": [input.name],
            "Label": [label.name],
            "StatPos": [stat_pos.name],
            "StatNeg": [stat_neg.name],
        },
        {
            "AUC": [auc_out.name],
            "StatPosOut": [stat_pos.name],
            "StatNegOut": [stat_neg.name],
        },
        {"num_thresholds": num_thresholds},
    )
    return auc_out, [stat_pos, stat_neg]


def one_hot(input, depth, name=None):
    return _single_op("one_hot", input, {"depth": depth}, out_dtype="float32", name=name)


def argmax(x, axis=-1, name=None):
    return _single_op("arg_max", x, {"axis": axis}, out_dtype="int64", name=name)


def argmin(x, axis=-1, name=None):
    return _single_op("arg_min", x, {"axis": axis}, out_dtype="int64", name=name)


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze2", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    xshape = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    helper.append_op(
        "unsqueeze2",
        {"X": [input.name]},
        {"Out": [out.name], "XShape": [xshape.name]},
        {"axes": axes},
    )
    return out


def squeeze(input, axes=None, name=None):
    helper = LayerHelper("squeeze2", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    xshape = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    helper.append_op(
        "squeeze2",
        {"X": [input.name]},
        {"Out": [out.name], "XShape": [xshape.name]},
        {"axes": axes or []},
    )
    return out
