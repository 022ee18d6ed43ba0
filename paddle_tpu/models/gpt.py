"""GPT-style decoder LM — the hybrid-parallelism flagship.

Composes, in ONE shard_map'd train step over a 4-axis
('data','stage','model','seq') mesh, every parallelism family:

  dp  — batch sharded on 'data', grads psum over it
        (reference analog: AllReduceSSAGraphBuilder, paddle/fluid/framework/
        ir/multi_devices_graph_pass/multi_devices_graph_pass.h:110)
  pp  — decoder blocks stacked and sharded on 'stage', GPipe microbatch
        schedule via parallel.pipeline (reference analog: PipelineOptimizer,
        python/paddle/fluid/optimizer.py:3414)
  tp  — Megatron column/row-parallel attention+FFN on 'model'
        (absent in reference, SURVEY §2.7)
  sp  — sequence shards on 'seq', ring attention via parallel.ring
        (absent in reference, SURVEY §5.7)
  ep  — MoE experts sharded over 'data' (DeepSpeed-MoE style: EP group ==
        DP group), all_to_all token dispatch via parallel.moe
        (absent in reference)

The per-parameter PartitionSpecs drive both shard_map in_specs and the
psum axes for gradient reduction: a parameter's gradient is psum'd over
exactly the mesh axes its spec does NOT shard (its replication group).
"""

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

from paddle_tpu.parallel.ring import ring_attention_local
from paddle_tpu.parallel.moe import moe_ffn_local
from paddle_tpu.parallel.pipeline import pipeline_apply, split_microbatches

AXES = ("data", "stage", "model", "seq")


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 32000
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    ffn_mult: int = 4
    max_seq_len: int = 1024
    num_experts: int = 0          # 0 => dense FFN in every block
    capacity_factor: float = 2.0
    aux_loss_weight: float = 0.01  # MoE load-balance loss weight
    attention: str = "ring"       # 'ring' | 'ulysses' (sp mechanism)

    @staticmethod
    def tiny(**kw):
        d = dict(vocab_size=256, hidden_size=64, num_layers=4, num_heads=4,
                 ffn_mult=2, max_seq_len=128)
        d.update(kw)
        return GPTConfig(**d)


# ---------------------------------------------------------------------------
# parameters


def init_params(rng, cfg):
    """Returns a pytree of np.float32 arrays. Block params are stacked on a
    leading num_layers dim (pipeline shards it over 'stage')."""
    h, l = cfg.hidden_size, cfg.num_layers
    f = cfg.ffn_mult * h
    std = 0.02

    def w(*shape):
        return (rng.standard_normal(shape) * std).astype(np.float32)

    def zeros(*shape):
        return np.zeros(shape, np.float32)

    def ones(*shape):
        return np.ones(shape, np.float32)

    blocks = dict(
        ln1_s=ones(l, h), ln1_b=zeros(l, h),
        wq=w(l, h, h), bq=zeros(l, h),
        wk=w(l, h, h), bk=zeros(l, h),
        wv=w(l, h, h), bv=zeros(l, h),
        wo=w(l, h, h), bo=zeros(l, h),
        ln2_s=ones(l, h), ln2_b=zeros(l, h),
    )
    if cfg.num_experts:
        e = cfg.num_experts
        blocks.update(
            gate=w(l, h, e),
            we1=w(l, e, h, f), be1=zeros(l, e, f),
            we2=w(l, e, f, h), be2=zeros(l, e, h),
        )
    else:
        blocks.update(
            w1=w(l, h, f), b1=zeros(l, f),
            w2=w(l, f, h), b2=zeros(l, h),
        )
    return dict(
        embed=w(cfg.vocab_size, h),
        pos_emb=w(cfg.max_seq_len, h),
        lnf_s=ones(h), lnf_b=zeros(h),
        blocks=blocks,
    )


def param_specs(cfg):
    """PartitionSpecs mirroring init_params: stage on the stacked-layer dim,
    Megatron model-sharding inside blocks, experts on 'data'."""
    blocks = dict(
        ln1_s=P("stage"), ln1_b=P("stage"),
        wq=P("stage", None, "model"), bq=P("stage", "model"),
        wk=P("stage", None, "model"), bk=P("stage", "model"),
        wv=P("stage", None, "model"), bv=P("stage", "model"),
        wo=P("stage", "model", None), bo=P("stage"),
        ln2_s=P("stage"), ln2_b=P("stage"),
    )
    if cfg.num_experts:
        # experts on 'data' (EP group == DP group), each expert's FFN hidden
        # dim Megatron-sharded on 'model' so tp ranks don't duplicate FLOPs
        blocks.update(
            gate=P("stage"),
            we1=P("stage", "data", None, "model"), be1=P("stage", "data", "model"),
            we2=P("stage", "data", "model", None), be2=P("stage", "data"),
        )
    else:
        blocks.update(
            w1=P("stage", None, "model"), b1=P("stage", "model"),
            w2=P("stage", "model", None), b2=P("stage"),
        )
    return dict(
        embed=P(), pos_emb=P(), lnf_s=P(), lnf_b=P(), blocks=blocks,
    )


def grad_psum_axes(spec):
    """Axes a gradient must be summed over = mesh axes the param is
    replicated across."""
    used = set()
    for entry in spec:
        if entry is None:
            continue
        for ax in (entry if isinstance(entry, tuple) else (entry,)):
            used.add(ax)
    return tuple(ax for ax in AXES if ax not in used)


# ---------------------------------------------------------------------------
# model pieces (all run INSIDE shard_map; [mb, s_local, ...] activations)


def _layer_norm(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * scale + bias).astype(x.dtype)


def _attention(p, x, cfg, model_size):
    """Causal self-attention: heads split on 'model', sequence ring on 'seq'."""
    mb, s_loc, h = x.shape
    n_head_loc = cfg.num_heads // model_size
    d = cfg.hidden_size // cfg.num_heads

    def heads(t):  # [mb, s, h_loc] -> [mb, nh_loc, s, d]
        return t.reshape(mb, s_loc, n_head_loc, d).transpose(0, 2, 1, 3)

    q = heads(x @ p["wq"] + p["bq"])
    k = heads(x @ p["wk"] + p["bk"])
    v = heads(x @ p["wv"] + p["bv"])
    if cfg.attention == "ring":
        ctx = ring_attention_local(q, k, v, "seq", causal=True)
    else:
        from paddle_tpu.parallel.ulysses import ulysses_attention_local

        ctx = ulysses_attention_local(q, k, v, "seq", causal=True)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(mb, s_loc, -1)
    out = lax.psum(ctx @ p["wo"], "model") + p["bo"]
    return out


def _ffn(p, x, cfg):
    y = jax.nn.gelu(x @ p["w1"] + p["b1"])
    return lax.psum(y @ p["w2"], "model") + p["b2"]


def _moe_ffn(p, x, cfg):
    mb, s_loc, h = x.shape
    flat = x.reshape(-1, h)

    def expert(ep, xe):
        y = jax.nn.gelu(xe @ ep["w1"] + ep["b1"])
        return lax.psum(y @ ep["w2"], "model") + ep["b2"]

    ep_params = dict(w1=p["we1"], b1=p["be1"], w2=p["we2"], b2=p["be2"])
    y, aux = moe_ffn_local(
        flat, p["gate"], ep_params, expert, "data",
        capacity_factor=cfg.capacity_factor,
    )
    return y.reshape(mb, s_loc, h), aux


def make_block_fn(cfg, model_size):
    """Block over a (h, aux) carry: aux accumulates the MoE load-balance
    loss as the activation traverses the pipeline stages."""

    def block(p, carry):
        x, aux = carry
        a = _attention(p, _layer_norm(x, p["ln1_s"], p["ln1_b"]), cfg, model_size)
        x = x + a
        y = _layer_norm(x, p["ln2_s"], p["ln2_b"])
        if cfg.num_experts:
            y, layer_aux = _moe_ffn(p, y, cfg)
            aux = aux + layer_aux / cfg.num_layers
        else:
            y = _ffn(p, y, cfg)
        return x + y, aux

    return block


# ---------------------------------------------------------------------------
# the hybrid train step


def _local_loss(params, tokens, labels, cfg, mesh_sizes, num_microbatches):
    """INSIDE shard_map: tokens/labels [B_loc, S_loc] on (data, seq)."""
    n_stage = mesh_sizes["stage"]
    s_loc = tokens.shape[1]
    seq_idx = lax.axis_index("seq")
    stage_idx = lax.axis_index("stage")

    emb = params["embed"][tokens]                        # [B_loc, s_loc, H]
    # positions are global: slice the table at this seq shard's offset
    pos = lax.dynamic_slice_in_dim(params["pos_emb"], seq_idx * s_loc, s_loc, 0)
    x = emb + pos[None]

    x_mb = split_microbatches(x, num_microbatches)       # [M, mb, s_loc, H]
    # zero per-microbatch aux accumulator deriving x's device-varying type
    aux_mb = (0.0 * x_mb.astype(jnp.float32)).sum(axis=(1, 2, 3))
    block = make_block_fn(cfg, mesh_sizes["model"])
    outs, aux = pipeline_apply(
        block, params["blocks"], (x_mb, aux_mb), "stage", collect="last"
    )
    hs = outs.reshape(x.shape)                           # valid on last stage

    hs = _layer_norm(hs, params["lnf_s"], params["lnf_b"])
    logits = hs @ params["embed"].T                      # [B_loc, s_loc, V]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    # head/loss only counts on the last stage (collect='last' zeros others)
    ce_sum = jnp.where(stage_idx == n_stage - 1, nll.sum(), 0.0)
    total = lax.psum(ce_sum, ("data", "seq", "stage"))
    n_tokens = (
        tokens.shape[0] * s_loc * mesh_sizes["data"] * mesh_sizes["seq"]
    )
    loss = total / n_tokens
    if cfg.num_experts:
        # load-balance aux loss: mean over microbatches and (data, seq)
        # shards; only the last stage holds the accumulated value
        aux_sum = jnp.where(stage_idx == n_stage - 1, aux.sum(), 0.0)
        aux_total = lax.psum(aux_sum, ("data", "seq", "stage"))
        n_shards = (
            num_microbatches * mesh_sizes["data"] * mesh_sizes["seq"]
        )
        loss = loss + cfg.aux_loss_weight * aux_total / n_shards
    return loss


def build_train_step(cfg, mesh, num_microbatches=2, lr=1e-3, b1=0.9, b2=0.95,
                     eps=1e-8, weight_decay=0.0):
    """Returns (step, init_state). step(state, tokens, labels) -> (state, loss)
    — jitted, params/opt-state donated, every axis of `mesh` exercised."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for ax in AXES:
        assert ax in sizes, f"mesh must name axis {ax!r} (size may be 1)"
    specs = param_specs(cfg)

    def local_fn(params, tokens, labels):
        loss, grads = jax.value_and_grad(_local_loss)(
            params, tokens, labels, cfg=cfg, mesh_sizes=sizes,
            num_microbatches=num_microbatches,
        )
        grads = jax.tree_util.tree_map(
            lambda g, s: lax.psum(g, grad_psum_axes(s)) if grad_psum_axes(s) else g,
            grads,
            specs,
        )
        return loss, grads

    data_spec = P("data", "seq")
    sharded = _shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(specs, data_spec, data_spec),
        out_specs=(P(), specs),
    )

    def step(state, tokens, labels):
        params, m, v, t = state
        loss, grads = sharded(params, tokens, labels)
        t = t + 1
        lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)

        def upd(p, g, m_, v_):
            m_ = b1 * m_ + (1 - b1) * g
            v_ = b2 * v_ + (1 - b2) * g * g
            p = p - lr_t * (m_ / (jnp.sqrt(v_) + eps) + weight_decay * p)
            return p, m_, v_

        flat_p, tree = jax.tree_util.tree_flatten(params)
        flat_g = jax.tree_util.tree_leaves(grads)
        flat_m = jax.tree_util.tree_leaves(m)
        flat_v = jax.tree_util.tree_leaves(v)
        new = [upd(p, g, m_, v_) for p, g, m_, v_ in zip(flat_p, flat_g, flat_m, flat_v)]
        params = jax.tree_util.tree_unflatten(tree, [n[0] for n in new])
        m = jax.tree_util.tree_unflatten(tree, [n[1] for n in new])
        v = jax.tree_util.tree_unflatten(tree, [n[2] for n in new])
        return (params, m, v, t), loss

    from paddle_tpu.core.lowering import jit_compile

    jit_step = jit_compile(step, donate_argnums=(0,))

    def init_state(rng):
        params = init_params(rng, cfg)
        from jax.sharding import NamedSharding

        put = lambda tree: jax.tree_util.tree_map(
            lambda a, s: jax.device_put(jnp.asarray(a), NamedSharding(mesh, s)),
            tree,
            specs,
        )
        params = put(params)
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        return (params, zeros, jax.tree_util.tree_map(jnp.zeros_like, params),
                jnp.zeros((), jnp.int32))

    return jit_step, init_state


def synthetic_batch(rng, batch, seq_len, cfg):
    tokens = rng.randint(0, cfg.vocab_size, size=(batch, seq_len + 1))
    return tokens[:, :-1].astype(np.int32), tokens[:, 1:].astype(np.int32)
