"""Optimizer update rules as ops.

Mirrors the reference's optimizer op kernels (reference:
paddle/fluid/operators/optimizers/sgd_op.h, momentum_op.h, adam_op.h,
lamb_op.h, lars_momentum_op.cc ...). Updates are pure functions returning
*Out states; the executor's buffer donation makes them in-place at the XLA
level. All moment arithmetic runs in fp32 even for bf16 params.
"""

import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.registry import register_op
from paddle_tpu.ops.common import first, maybe
from paddle_tpu.utils.enforce import EnforceError


def _f32(x):
    return x.astype(jnp.float32)


@register_op("sgd")
def _sgd(ins, attrs):
    p, g, lr = first(ins, "Param"), first(ins, "Grad"), first(ins, "LearningRate")
    out = _f32(p) - _f32(lr) * _f32(g)
    return {"ParamOut": [out.astype(p.dtype)]}


@register_op("sgd_sparse", nondiff_inputs=("Ids",))
def _sgd_sparse(ins, attrs):
    """SelectedRows-analog row update (reference: paddle/fluid/operators/
    optimizers/sgd_op.h sparse branch; selected_rows.h:32): the embedding
    grad never materializes as a [V, D] dense tensor — the looked-up rows'
    cotangent scatter-subtracts straight into the touched parameter rows
    (duplicate ids combine inside the scatter, the segment-sum the
    reference does in SumKernel's SelectedRows branch). Emitted by the
    sparse_weight_update pass replacing lookup_table_grad + sgd."""
    p = first(ins, "Param")
    ids = first(ins, "Ids").reshape(-1).astype(jnp.int32)
    rows = first(ins, "RowGrad")
    lr = _f32(first(ins, "LearningRate")).reshape(())
    d = p.shape[-1]
    rows2 = rows.reshape(-1, d).astype(p.dtype)
    pi = attrs.get("padding_idx", -1)
    if pi is not None and pi >= 0:
        # the forward zeroed padding rows, so their grads must not land
        rows2 = jnp.where((ids == pi)[:, None], 0.0, rows2)
    scaled = -(lr.astype(p.dtype)) * rows2
    return {
        "ParamOut": [p.at[ids].add(scaled)],
    }


@register_op("momentum")
def _momentum(ins, attrs):
    p, g = _f32(first(ins, "Param")), _f32(first(ins, "Grad"))
    v, lr = _f32(first(ins, "Velocity")), _f32(first(ins, "LearningRate"))
    mu = attrs.get("mu", 0.9)
    rd = attrs.get("regularization_coeff", 0.0)
    if rd and attrs.get("regularization_method", "") == "l2_decay":
        g = g + rd * p
    v_out = mu * v + g
    if attrs.get("use_nesterov", False):
        p_out = p - lr * (g + mu * v_out)
    else:
        p_out = p - lr * v_out
    param = first(ins, "Param")
    return {
        "ParamOut": [p_out.astype(param.dtype)],
        "VelocityOut": [v_out],
    }


@register_op("adam")
def _adam(ins, attrs):
    p = _f32(first(ins, "Param"))
    g = _f32(first(ins, "Grad"))
    m1, m2 = _f32(first(ins, "Moment1")), _f32(first(ins, "Moment2"))
    b1p, b2p = _f32(first(ins, "Beta1Pow")), _f32(first(ins, "Beta2Pow"))
    lr = _f32(first(ins, "LearningRate"))
    b1 = float(maybe(ins, "Beta1Tensor", attrs.get("beta1", 0.9)))
    b2 = float(maybe(ins, "Beta2Tensor", attrs.get("beta2", 0.999)))
    eps = attrs.get("epsilon", 1e-8)
    m1n = b1 * m1 + (1 - b1) * g
    m2n = b2 * m2 + (1 - b2) * jnp.square(g)
    lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
    p_out = p - lr_t * m1n / (jnp.sqrt(m2n) + eps)
    param = first(ins, "Param")
    return {
        "ParamOut": [p_out.astype(param.dtype)],
        "Moment1Out": [m1n],
        "Moment2Out": [m2n],
        "Beta1PowOut": [b1p * b1],
        "Beta2PowOut": [b2p * b2],
    }


@register_op("adamw")
def _adamw(ins, attrs):
    """Decoupled weight decay on top of adam."""
    coeff = attrs.get("coeff", 0.01)
    p = _f32(first(ins, "Param"))
    lr = _f32(first(ins, "LearningRate"))
    outs = _adam(ins, attrs)
    decayed = outs["ParamOut"][0].astype(jnp.float32) - lr * coeff * p
    outs["ParamOut"] = [decayed.astype(first(ins, "Param").dtype)]
    return outs


@register_op("adagrad")
def _adagrad(ins, attrs):
    p, g = _f32(first(ins, "Param")), _f32(first(ins, "Grad"))
    moment, lr = _f32(first(ins, "Moment")), _f32(first(ins, "LearningRate"))
    eps = attrs.get("epsilon", 1e-6)
    m_out = moment + jnp.square(g)
    p_out = p - lr * g / (jnp.sqrt(m_out) + eps)
    return {
        "ParamOut": [p_out.astype(first(ins, "Param").dtype)],
        "MomentOut": [m_out],
    }


@register_op("rmsprop")
def _rmsprop(ins, attrs):
    p, g = _f32(first(ins, "Param")), _f32(first(ins, "Grad"))
    ms, lr = _f32(first(ins, "MeanSquare")), _f32(first(ins, "LearningRate"))
    mom = _f32(first(ins, "Moment"))
    rho = attrs.get("decay", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    momentum = attrs.get("momentum", 0.0)
    if attrs.get("centered", False):
        mg = _f32(first(ins, "MeanGrad"))
        mg_out = rho * mg + (1 - rho) * g
        ms_out = rho * ms + (1 - rho) * jnp.square(g)
        denom = jnp.sqrt(ms_out - jnp.square(mg_out) + eps)
    else:
        mg_out = None
        ms_out = rho * ms + (1 - rho) * jnp.square(g)
        denom = jnp.sqrt(ms_out + eps)
    mom_out = momentum * mom + lr * g / denom
    p_out = p - mom_out
    outs = {
        "ParamOut": [p_out.astype(first(ins, "Param").dtype)],
        "MomentOut": [mom_out],
        "MeanSquareOut": [ms_out],
    }
    if mg_out is not None:
        outs["MeanGradOut"] = [mg_out]
    return outs


@register_op("adamax")
def _adamax(ins, attrs):
    p, g = _f32(first(ins, "Param")), _f32(first(ins, "Grad"))
    m, inf_norm = _f32(first(ins, "Moment")), _f32(first(ins, "InfNorm"))
    b1p, lr = _f32(first(ins, "Beta1Pow")), _f32(first(ins, "LearningRate"))
    b1, b2 = attrs.get("beta1", 0.9), attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    m_out = b1 * m + (1 - b1) * g
    inf_out = jnp.maximum(b2 * inf_norm, jnp.abs(g))
    p_out = p - (lr / (1 - b1p)) * m_out / (inf_out + eps)
    return {
        "ParamOut": [p_out.astype(first(ins, "Param").dtype)],
        "MomentOut": [m_out],
        "InfNormOut": [inf_out],
    }


@register_op("adadelta")
def _adadelta(ins, attrs):
    p, g = _f32(first(ins, "Param")), _f32(first(ins, "Grad"))
    avg_sq_grad = _f32(first(ins, "AvgSquaredGrad"))
    avg_sq_upd = _f32(first(ins, "AvgSquaredUpdate"))
    rho, eps = attrs.get("rho", 0.95), attrs.get("epsilon", 1e-6)
    asg_out = rho * avg_sq_grad + (1 - rho) * jnp.square(g)
    update = -jnp.sqrt((avg_sq_upd + eps) / (asg_out + eps)) * g
    asu_out = rho * avg_sq_upd + (1 - rho) * jnp.square(update)
    p_out = p + update
    return {
        "ParamOut": [p_out.astype(first(ins, "Param").dtype)],
        "AvgSquaredGradOut": [asg_out],
        "AvgSquaredUpdateOut": [asu_out],
    }


@register_op("decayed_adagrad")
def _decayed_adagrad(ins, attrs):
    p, g = _f32(first(ins, "Param")), _f32(first(ins, "Grad"))
    moment, lr = _f32(first(ins, "Moment")), _f32(first(ins, "LearningRate"))
    decay, eps = attrs.get("decay", 0.95), attrs.get("epsilon", 1e-6)
    m_out = decay * moment + (1 - decay) * jnp.square(g)
    p_out = p - lr * g / (jnp.sqrt(m_out) + eps)
    return {
        "ParamOut": [p_out.astype(first(ins, "Param").dtype)],
        "MomentOut": [m_out],
    }


@register_op("ftrl")
def _ftrl(ins, attrs):
    p, g = _f32(first(ins, "Param")), _f32(first(ins, "Grad"))
    sq, lin = _f32(first(ins, "SquaredAccumulator")), _f32(
        first(ins, "LinearAccumulator")
    )
    lr = _f32(first(ins, "LearningRate"))
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    power = attrs.get("lr_power", -0.5)
    new_sq = sq + jnp.square(g)
    sigma = (jnp.power(new_sq, -power) - jnp.power(sq, -power)) / lr
    new_lin = lin + g - sigma * p
    x = jnp.clip(new_lin, -l1, l1) - new_lin
    y = jnp.power(new_sq, -power) / lr + 2 * l2
    p_out = x / y
    return {
        "ParamOut": [p_out.astype(first(ins, "Param").dtype)],
        "SquaredAccumOut": [new_sq],
        "LinearAccumOut": [new_lin],
    }


@register_op("lamb")
def _lamb(ins, attrs):
    """reference: paddle/fluid/operators/optimizers/lamb_op.h — layerwise
    adaptive moments, the large-batch BERT optimizer."""
    p = _f32(first(ins, "Param"))
    g = _f32(first(ins, "Grad"))
    m1, m2 = _f32(first(ins, "Moment1")), _f32(first(ins, "Moment2"))
    b1p, b2p = _f32(first(ins, "Beta1Pow")), _f32(first(ins, "Beta2Pow"))
    lr = _f32(first(ins, "LearningRate"))
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-6)
    wd = attrs.get("weight_decay", 0.01)
    m1n = b1 * m1 + (1 - b1) * g
    m2n = b2 * m2 + (1 - b2) * jnp.square(g)
    m1_hat = m1n / (1 - b1p)
    m2_hat = m2n / (1 - b2p)
    r = m1_hat / (jnp.sqrt(m2_hat) + eps) + wd * p
    p_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
    r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
    trust = jnp.where((p_norm > 0) & (r_norm > 0), p_norm / r_norm, 1.0)
    p_out = p - lr * trust * r
    return {
        "ParamOut": [p_out.astype(first(ins, "Param").dtype)],
        "Moment1Out": [m1n],
        "Moment2Out": [m2n],
        "Beta1PowOut": [b1p * b1],
        "Beta2PowOut": [b2p * b2],
    }


@register_op("lars_momentum")
def _lars_momentum(ins, attrs):
    p, g = _f32(first(ins, "Param")), _f32(first(ins, "Grad"))
    v, lr = _f32(first(ins, "Velocity")), _f32(first(ins, "LearningRate"))
    mu = attrs.get("mu", 0.9)
    coeff = attrs.get("lars_coeff", 0.001)
    wd = attrs.get("lars_weight_decay", 0.0005)
    eps = attrs.get("epsilon", 0.0)
    p_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
    g_norm = jnp.sqrt(jnp.sum(jnp.square(g)))
    local_lr = jnp.where(
        (p_norm > 0) & (g_norm > 0),
        lr * coeff * p_norm / (g_norm + wd * p_norm + eps),
        lr,
    )
    v_out = mu * v + local_lr * (g + wd * p)
    p_out = p - v_out
    return {
        "ParamOut": [p_out.astype(first(ins, "Param").dtype)],
        "VelocityOut": [v_out],
    }


@register_op("dpsgd", stateful=True)
def _dpsgd(ins, attrs):
    """Differentially-private SGD (reference: paddle/fluid/operators/
    optimizers/dpsgd_op.cc): clip per-batch grad, add gaussian noise."""
    import jax

    from paddle_tpu.ops.common import rng_key

    p, g = _f32(first(ins, "Param")), _f32(first(ins, "Grad"))
    lr = _f32(first(ins, "LearningRate"))
    clip = attrs.get("clip", 10.0)
    batch_size = attrs.get("batch_size", 16.0)
    sigma = attrs.get("sigma", 1.0)
    g_norm = jnp.sqrt(jnp.sum(jnp.square(g)))
    g = jnp.where(g_norm > clip, g * (clip / g_norm), g)
    noise = sigma * clip * jax.random.normal(rng_key(ins), g.shape)
    p_out = p - lr * (g + noise / batch_size)
    return {"ParamOut": [p_out.astype(first(ins, "Param").dtype)]}


@register_op("check_finite_and_unscale", nondiff_inputs=("Scale",))
def _check_finite_and_unscale(ins, attrs):
    """reference: paddle/fluid/operators/amp/check_finite_and_unscale_op.cc —
    unscale every gradient by 1/Scale and report whether any is non-finite."""
    xs = ins.get("X", [])
    scale = _f32(first(ins, "Scale")).reshape(())
    inv = 1.0 / scale
    found = jnp.zeros((), jnp.bool_)
    outs = []
    for x in xs:
        found = jnp.logical_or(found, jnp.logical_not(jnp.all(jnp.isfinite(x))))
        outs.append((_f32(x) * inv).astype(x.dtype))
    return {"Out": outs, "FoundInfinite": [found.reshape(1)]}


@register_op("update_loss_scaling", nondiff_inputs=("FoundInfinite", "PrevLossScaling", "InGoodSteps", "InBadSteps"))
def _update_loss_scaling(ins, attrs):
    """reference: paddle/fluid/operators/amp/update_loss_scaling_op.cc.
    On overflow: zero the gradients (skipping the update) and after
    decr_every_n_nan_or_inf consecutive overflows halve the scale; after
    incr_every_n_steps clean steps, grow it."""
    xs = ins.get("X", [])
    found = first(ins, "FoundInfinite").reshape(()).astype(jnp.bool_)
    scale = _f32(first(ins, "PrevLossScaling")).reshape(())
    good = first(ins, "InGoodSteps").reshape(()).astype(jnp.int32)
    bad = first(ins, "InBadSteps").reshape(()).astype(jnp.int32)
    incr_every = attrs.get("incr_every_n_steps", 1000)
    decr_every = attrs.get("decr_every_n_nan_or_inf", 2)
    incr_ratio = attrs.get("incr_ratio", 2.0)
    decr_ratio = attrs.get("decr_ratio", 0.5)
    new_bad = jnp.where(found, bad + 1, 0)
    new_good = jnp.where(found, 0, good + 1)
    should_decr = new_bad >= decr_every
    should_incr = new_good >= incr_every
    new_scale = jnp.where(should_decr, scale * decr_ratio, scale)
    new_scale = jnp.where(should_incr, scale * incr_ratio, new_scale)
    new_scale = jnp.maximum(new_scale, 1e-8)
    new_bad = jnp.where(should_decr, 0, new_bad)
    new_good = jnp.where(should_incr, 0, new_good)
    prev = first(ins, "PrevLossScaling")
    outs = [jnp.where(found, jnp.zeros_like(x), x) for x in xs]
    return {
        "Out": outs,
        "LossScaling": [new_scale.reshape(1).astype(prev.dtype)],
        "OutGoodSteps": [new_good.reshape(1)],
        "OutBadSteps": [new_bad.reshape(1)],
    }


@register_op("ema_update")
def _ema_update(ins, attrs):
    """Shadow-parameter EMA step (reference: python/paddle/fluid/
    optimizer.py:3166 ExponentialMovingAverage — its in-graph ema ops)."""
    p, s = first(ins, "Param"), first(ins, "Shadow")
    decay = attrs.get("decay", 0.999)
    return {"ShadowOut": [decay * s + (1.0 - decay) * p.astype(s.dtype)]}


@register_op("model_average_update")
def _model_average_update(ins, attrs):
    """Windowed running parameter sum (reference: python/paddle/fluid/
    optimizer.py:2862 ModelAverage accumulators). The effective window is
    clamp(rate * total_updates, min_window, max_window); once `count`
    reaches it the sum decays geometrically so old snapshots age out — the
    static-shape analog of the reference's sum_1/2/3 window restarts.
    Count stores (window_count, total_updates)."""
    p = first(ins, "Param")
    s, c = first(ins, "Sum"), first(ins, "Count")
    rate = attrs.get("rate", 0.15)
    min_w = attrs.get("min_window", 10000.0)
    max_w = attrs.get("max_window", 10000.0)
    cnt = c.reshape(-1)[0]
    total = c.reshape(-1)[1] if c.size > 1 else cnt
    window = jnp.clip(rate * (total + 1.0), min_w, max_w)
    at_cap = cnt >= window
    new_sum = jnp.where(
        at_cap, s * (window - 1.0) / window, s
    ) + p.astype(s.dtype)
    new_cnt = jnp.minimum(cnt + 1.0, window)
    out = jnp.stack([new_cnt, total + 1.0])
    return {"SumOut": [new_sum], "CountOut": [out]}


@register_op("dgc_momentum")
def _dgc_momentum(ins, attrs):
    """DGC update (reference: paddle/fluid/operators/dgc_op.cc semantics):
    u = mu*u + g; v += u; select |v| above the sparsity quantile; apply the
    selected (sparse) update; clear u,v at selected positions (error
    feedback keeps the rest).

    Two forms:
    * dense (default): one fused per-param op; under GSPMD the gradient
      exchange is compiler-inserted dense traffic (compression semantics
      without wire savings).
    * sparse exchange (CompiledProgram data-parallel + DGC, per-shard
      mode): the block runs per-shard under shard_map, U/V are per-shard
      state with a leading local axis, and the update is a top-k
      (index, value) all_gather over the data axis — 2*k*n floats on the
      wire instead of the dense gradient (reference:
      details/sparse_all_reduce_op_handle.h).
    """
    from paddle_tpu.parallel import env as penv

    p = first(ins, "Param")
    g = first(ins, "Grad").astype(p.dtype)
    u, v = first(ins, "U"), first(ins, "V")
    lr = _f32(first(ins, "LearningRate")).reshape(())
    step = first(ins, "CurrentStep").reshape(())
    mu = attrs.get("mu", 0.9)
    begin = attrs.get("rampup_begin_step", 0.0)
    ramp = max(attrs.get("rampup_step", 1.0), 1.0)
    sparsity = jnp.asarray(attrs.get("sparsity", [0.999]), jnp.float32)
    L = sparsity.shape[0]
    dgc_axis = penv.current_dgc_axis()

    if dgc_axis is None and u.ndim == p.ndim + 1:
        raise EnforceError(
            "dgc accumulators carry per-shard state (leading shard axis) "
            "from a sparse-exchange CompiledProgram run; keep running the "
            "compiled program, or reset the accumulators, before using the "
            "plain Executor"
        )
    if dgc_axis is not None:
        # per-shard sparse exchange: U/V arrive [1, ...] (this shard's
        # slice), Grad is this shard's local-batch gradient
        u = u[0]
        v = v[0]

    u_new = mu * u + g
    contrib = g + mu * u_new if attrs.get("use_nesterov", False) else u_new
    # warmup ramp through the sparsity list; before rampup_begin the update
    # is PLAIN momentum (reference runs the regular momentum op until
    # rampup_begin_step) — u carries velocity, v stays untouched
    idx = jnp.clip(((step - begin) * L / ramp).astype(jnp.int32), 0, L - 1)
    ratio = jnp.where(step < begin, 0.0, jnp.take(sparsity, idx))
    is_dense = ratio <= 0.0

    if dgc_axis is not None:
        from jax import lax

        size = int(np.prod(p.shape))
        # static top-k bound from the FINAL (largest-k) sparsity; the
        # traced ramp ratio masks the tail during warmup
        k_max = max(1, int(round(size * (1.0 - float(min(
            attrs.get("sparsity", [0.999])
        ))))))
        v_acc = (v + contrib).reshape(-1)
        mag = jnp.abs(v_acc)
        _, top_idx = lax.top_k(mag, k_max)                # [k]
        k_dyn = jnp.round(size * (1.0 - ratio)).astype(jnp.int32)
        keep = (jnp.arange(k_max) < jnp.maximum(k_dyn, 1)).astype(v_acc.dtype)
        vals = v_acc[top_idx] * keep
        n = lax.psum(1, dgc_axis)

        def _sparse(_):
            # THE wire: 2*k*n floats instead of `size` — the honest DGC
            # saving
            all_idx = lax.all_gather(top_idx, dgc_axis)       # [n, k]
            all_vals = lax.all_gather(vals, dgc_axis)         # [n, k]
            sparse_update = (
                jnp.zeros((size,), v_acc.dtype)
                .at[all_idx.reshape(-1)]
                .add(all_vals.reshape(-1)) / n
            ).reshape(p.shape)
            sent = jnp.zeros((size,), bool).at[top_idx].set(keep > 0)
            sent = sent.reshape(p.shape)
            return (sparse_update,
                    jnp.where(sent, 0.0, u_new),
                    jnp.where(sent, 0.0, v_acc.reshape(p.shape)))

        def _dense(_):
            return lax.pmean(contrib, dgc_axis), u_new, v

        # phase select around lax.cond, not jnp.where: where() evaluates
        # BOTH sides, so the rampup pmean put a dense all-reduce on the
        # wire during the sparse phase. A schedule that is STATICALLY
        # sparse (rampup_begin <= 0 and every sparsity entry > 0 — the
        # production DGC config) prunes the dense branch entirely: the
        # compiled module carries no dense all-reduce at all; a genuinely
        # dynamic schedule keeps both branches but executes only one.
        statically_sparse = (
            float(begin) <= 0.0
            and min(float(x) for x in attrs.get("sparsity", [0.999])) > 0.0
        )
        if statically_sparse:
            update, u_out, v_out = _sparse(None)
        else:
            update, u_out, v_out = lax.cond(is_dense, _dense, _sparse, None)
        return {
            "ParamOut": [p - lr.astype(p.dtype) * update],
            "UOut": [u_out[None]],
            "VOut": [v_out[None]],
        }

    v_acc = v + contrib
    absv = jnp.abs(v_acc)
    thr = jnp.quantile(absv.reshape(-1).astype(jnp.float32), ratio)
    mask = absv >= thr.astype(absv.dtype)
    update = jnp.where(is_dense, contrib, jnp.where(mask, v_acc, 0.0))
    u_out = jnp.where(is_dense, u_new, jnp.where(mask, 0.0, u_new))
    v_out = jnp.where(is_dense, v, jnp.where(mask, 0.0, v_acc))
    return {
        "ParamOut": [p - lr.astype(p.dtype) * update],
        "UOut": [u_out],
        "VOut": [v_out],
    }
