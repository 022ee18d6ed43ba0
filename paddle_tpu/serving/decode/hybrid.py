"""A hybrid state-space / attention / routed-experts decoder as a paged
DecodeModel: the ``nemotron_h`` family (NVIDIA Nemotron-H / Nemotron 3).

``hybrid_override_pattern`` names one mixer a block: ``M`` a Mamba-2 mixer,
``*`` grouped-query attention (no position encoding: the Mamba layers carry
the order), ``E`` routed experts beside one shared expert. Every block is
``x <- x + mixer(RMSNorm(x))``; a final RMSNorm; an untied embedding and
head; no bias but the convolution's. Parameters are ``dtype`` (bfloat16 as
published; float32 for the exact tests), products are accumulated in
float32, and the residual stream, the norms, the router, the SSM state and
the softmax are float32.

Two programs, not four: EVERY prompt, short or long, streams through the
chunk program against its own slot's state (a one-shot prefill would have
to hand a ``[H, P, N]`` state a Mamba layer across the host), and there is
no inject program because nothing re-injects: a model with per-slot
recurrent state is hosted without a prefix cache and without a host tier
(``GenerationEngine.register_model`` refuses either), since a K/V row is a
function of its token prefix alone and a recurrent state is not a thing
the radix index or the tier can key. The decode step takes the contract's
one host feed (``dec_step``: model.py) and expands it on the device like
every model's; its positions go unread.

State of two kinds: per attention layer the paged ``[R, kv_heads * D]`` K
and V arenas (``state_names``), per Mamba layer the per-SLOT convolution
tail and SSM state (``slot_states``), reset by the prompt's first chunk,
advanced by chunks and decode steps for the tokens whose write row is real,
untouched for a slot that does not step.

Expert parallelism seen from one rank: the router scores all
``router_experts``; the ``n_routed_experts`` held here are ids
``expert_rank * n_routed_experts ..``; what the absent experts would add is
left out. The vocabulary may be a slice likewise (``vocab_size`` rows).
"""

import math

from paddle_tpu.serving.decode.model import DecodeModel, _state_var

__all__ = ["build_nemotron_h_model", "MOE_COUNTS"]

#: what the decode step's ``Counts`` hold, in order: the engine adds them
#: to the counters of these names when the step's tokens come back
MOE_COUNTS = ("moe_assignments", "moe_held_assignments",
              "moe_touched_experts")


def _then(block, shape, start, ops, out):
    """Startup ops ``ops`` ((type, attrs), each reading the last one's
    output) from the var ``start`` into the var ``out``, through float32
    temporaries named after ``out``."""
    cur = start
    for i, (op_type, attrs) in enumerate(ops):
        nxt = out if i == len(ops) - 1 else f"{out}.t{i}"
        if nxt != out:
            block.create_var(name=nxt, shape=shape, dtype="float32")
        block.append_op(op_type, {"X": [cur]}, {"Out": [nxt]}, attrs)
        cur = nxt


def _drawn(block, shape, name, low, high):
    """A startup temporary ``name``: a uniform draw in [low, high)."""
    block.create_var(name=name, shape=shape, dtype="float32")
    block.append_op("uniform_random", {}, {"Out": [name]},
                    {"shape": shape, "dtype": "float32", "min": float(low),
                     "max": float(high), "seed": 0})
    return name


def _log_uniform(low, high):
    """``log U(low, high)``: Mamba-2's ``A_log``."""
    from paddle_tpu.initializer import Initializer

    class LogUniform(Initializer):
        def __call__(self, var, block):
            shape = list(var.shape)
            _then(block, shape,
                  _drawn(block, shape, f"{var.name}.draw", low, high),
                  [("log", {})], var.name)

    return LogUniform()


def _dt_bias(dt_min, dt_max, floor):
    """``softplus^-1(dt)`` for ``dt = max(exp U(log dt_min, log dt_max),
    floor)``, as ``dt + log(1 - exp(-dt))``: Mamba-2's ``dt_bias``."""
    from paddle_tpu.initializer import Initializer

    class DtBias(Initializer):
        def __call__(self, var, block):
            shape = list(var.shape)
            dt, tail = f"{var.name}.dt", f"{var.name}.tail"
            for name in (dt, tail):
                block.create_var(name=name, shape=shape, dtype="float32")
            _then(block, shape,
                  _drawn(block, shape, f"{var.name}.draw", math.log(dt_min),
                         math.log(dt_max)),
                  [("exp", {}), ("clip", {"min": float(floor), "max": 1e30})],
                  dt)
            _then(block, shape, dt,
                  [("scale", {"scale": -1.0}), ("exp", {}),
                   ("scale", {"scale": -1.0, "bias": 1.0}), ("log", {})],
                  tail)
            block.append_op("elementwise_add", {"X": [dt], "Y": [tail]},
                            {"Out": [var.name]}, {"axis": -1})

    return DtBias()


def build_nemotron_h_model(
        vocab_size, hidden_size, hybrid_override_pattern, *,
        mamba_num_heads, mamba_head_dim, n_groups, ssm_state_size,
        conv_kernel, chunk_size, num_attention_heads, num_key_value_heads,
        head_dim, n_routed_experts, router_experts, num_experts_per_tok,
        moe_intermediate_size, moe_shared_expert_intermediate_size,
        routed_scaling_factor, norm_topk_prob=True, layer_norm_epsilon=1e-5,
        time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4,
        expert_rank=0, dtype="bfloat16", state_dtype="float32", slots=4,
        max_len=64, block_size=16, num_blocks=None, chunk_tokens=16, eos_id=None, name="nemotron_h",
        version="1"):
    """Build the hybrid decoder as a paged DecodeModel (module docstring).
    The sizes are the published ``config.json``'s keys under their own
    names; ``n_routed_experts`` is how many experts are HELD here and
    ``router_experts`` how many the router scores (the published count);
    ``vocab_size`` the rows of the vocabulary held here. ``state_dtype`` is
    the SSM state's and the convolution tail's (float32 as served; a
    narrower one runs the composite and is what the comparison with the
    reference has to catch)."""
    import paddle_tpu as fluid
    from paddle_tpu.core.ir import Program, program_guard
    from paddle_tpu.initializer import (
        ConstantInitializer, NormalInitializer, UniformInitializer)
    from paddle_tpu.utils import unique_name

    V, H, S, L = int(vocab_size), int(hidden_size), int(slots), int(max_len)
    pattern = str(hybrid_override_pattern)
    if set(pattern) - set("ME*"):
        raise ValueError(f"hybrid_override_pattern {pattern!r}: a block is "
                         "M (Mamba-2), E (experts) or * (attention)")
    NL = len(pattern)
    BS = int(block_size)
    NB = int(num_blocks) if num_blocks else S * -(-L // BS)
    R = NB * BS
    C = int(chunk_tokens)
    if not 2 <= C <= L:
        raise ValueError(f"chunk_tokens must be in [2, {L}], got {C}")
    MH, MP, MG, MN = (int(mamba_num_heads), int(mamba_head_dim),
                      int(n_groups), int(ssm_state_size))
    d_inner = MH * MP
    conv_dim = d_inner + 2 * MG * MN
    in_width = 2 * d_inner + 2 * MG * MN + MH
    NQ, NKV, D = (int(num_attention_heads), int(num_key_value_heads),
                  int(head_dim))
    kv_width = NKV * D
    held, router = int(n_routed_experts), int(router_experts)
    offset = int(expert_rank) * held
    if offset + held > router:
        raise ValueError(f"expert_rank {expert_rank} x {held} held experts "
                         f"passes the router's {router}")
    eps = float(layer_norm_epsilon)
    sm_scale = 1.0 / math.sqrt(D)
    prefix = f"{name}_v{version}"
    std = 0.02
    back = std / math.sqrt(NL)      # rescale_prenorm_residual

    def attr(suffix, init):
        return fluid.ParamAttr(name=f"{prefix}.{suffix}", initializer=init)

    def matrix(suffix, residual=False):
        return attr(suffix, NormalInitializer(0.0, back if residual else std))

    def proj(h, size, suffix, act=None, residual=False, out_dtype=None):
        return fluid.layers.fc(
            h, size, num_flatten_dims=2, act=act, bias_attr=False,
            param_attr=matrix(suffix + ".w", residual), out_dtype=out_dtype)

    def norm(h, suffix):
        return fluid.layers.rms_norm(
            h, epsilon=eps, out_dtype=dtype,
            param_attr=attr(suffix, ConstantInitializer(1.0)))

    m_layers = [i for i, kind in enumerate(pattern) if kind == "M"]
    a_layers = [i for i, kind in enumerate(pattern) if kind == "*"]
    state_names = [(f"{prefix}.kcache{i}", f"{prefix}.vcache{i}")
                   for i in a_layers]
    slot_states = []
    for i in m_layers:
        slot_states.append((f"{prefix}.conv{i}",
                            (S, int(conv_kernel) - 1, conv_dim),
                            state_dtype))
        slot_states.append((f"{prefix}.ssm{i}", (S, MH, MP, MN),
                            state_dtype))
    startup = Program()

    def mamba_attrs(i):
        return {
            "conv_w": attr(f"l{i}.conv_w", UniformInitializer(-0.5, 0.5)),
            "conv_b": attr(f"l{i}.conv_b", UniformInitializer(-0.5, 0.5)),
            "dt_bias": attr(f"l{i}.dt_bias", _dt_bias(
                time_step_min, time_step_max, time_step_floor)),
            "a_log": attr(f"l{i}.a_log", _log_uniform(1.0, 16.0)),
            "d": attr(f"l{i}.d", ConstantInitializer(1.0)),
            "norm_w": attr(f"l{i}.mixer_norm", ConstantInitializer(1.0)),
        }

    def expert_attrs(i):
        return {
            "gate": matrix(f"l{i}.gate"),
            "select_bias": attr(f"l{i}.select_bias",
                                NormalInitializer(0.0, 0.05)),
            "w_up": matrix(f"l{i}.w_up"),
            "w_down": matrix(f"l{i}.w_down", residual=True),
        }

    def stack(program, toks, positions, wrows, mode, attend, slot=None):
        """The 52 blocks over ``toks`` ([S, 1] or [1, C]); ``attend(i, q, k,
        v)`` is the program's own attention over the paged arenas. Returns
        (logits, the expert layers' routing counts)."""
        h = fluid.layers.cast(fluid.layers.embedding(
            toks, size=(V, H), dtype=dtype,
            param_attr=matrix("embed")), "float32")
        counts = []
        for i, kind in enumerate(pattern):
            x = norm(h, f"l{i}.norm")
            if kind == "M":
                conv = _state_var(program, startup, *slot_states[
                    2 * m_layers.index(i)][:2], dtype=state_dtype)
                ssm = _state_var(program, startup, *slot_states[
                    2 * m_layers.index(i) + 1][:2], dtype=state_dtype)
                y = fluid.layers.mamba2_mixer(
                    proj(x, in_width, f"l{i}.in_proj", out_dtype="float32"),
                    conv, ssm, wrows, R, mode, MH, MP, MG, MN,
                    int(conv_kernel), mamba_attrs(i), slot=slot,
                    positions=positions, chunk_size=int(chunk_size),
                    epsilon=eps, out_dtype=dtype)
                out = proj(y, H, f"l{i}.out_proj", residual=True,
                           out_dtype="float32")
            elif kind == "*":
                ctx = attend(i, proj(x, NQ * D, f"l{i}.q"),
                             proj(x, kv_width, f"l{i}.k"),
                             proj(x, kv_width, f"l{i}.v"))
                out = proj(ctx, H, f"l{i}.o", residual=True,
                           out_dtype="float32")
            else:
                routed, n = fluid.layers.moe_routed_experts(
                    x, wrows, R, router, held, int(moe_intermediate_size),
                    int(num_experts_per_tok), expert_attrs(i),
                    expert_offset=offset,
                    score_scale=float(routed_scaling_factor),
                    normalize=bool(norm_topk_prob), kernel=mode == "step")
                counts.append(n)
                shared = proj(
                    proj(x, int(moe_shared_expert_intermediate_size),
                         f"l{i}.shared_up", act="relu2"),
                    H, f"l{i}.shared_down", residual=True,
                    out_dtype="float32")
                out = fluid.layers.elementwise_add(routed, shared)
            h = fluid.layers.elementwise_add(h, out)
        logits = proj(norm(h, "final_norm"), V, "head", out_dtype="float32")
        return logits, counts

    def arenas(program, i):
        kn, vn = state_names[a_layers.index(i)]
        return (_state_var(program, startup, kn, [R, kv_width], dtype=dtype),
                _state_var(program, startup, vn, [R, kv_width], dtype=dtype))

    def write(program, i, wrows, k, v, axis):
        """Scatter the new K/V rows and persist (the lowering donates the
        arenas); attention reads the written views."""
        kc, vc = arenas(program, i)
        nk = fluid.layers.block_scatter_write(
            kc, wrows, fluid.layers.squeeze(k, [axis]))
        nv = fluid.layers.block_scatter_write(
            vc, wrows, fluid.layers.squeeze(v, [axis]))
        fluid.layers.assign(nk, output=kc)
        fluid.layers.assign(nv, output=vc)
        return nk, nv

    # -- decode step: one token per slot at [S, 1] -----------------------
    decode = Program()
    with unique_name.guard(), program_guard(decode, startup):
        # no position encoding: the step's positions go unread
        tok, _pos, bias, rows, wrows = fluid.layers.paged_step_feeds(
            fluid.data(DecodeModel.DEC_STEP,
                       [S, DecodeModel.STEP_TABLE + -(-L // BS)],
                       dtype="int32"),
            fluid.data(DecodeModel.DEC_TOKEN, [S, 1], dtype="int64"), L, BS)

        def attend_step(i, q, k, v):
            nk, nv = write(decode, i, wrows, k, v, 1)
            ctx = fluid.layers.paged_attention(
                fluid.layers.squeeze(q, [1]), nk, nv, rows, bias, S, L,
                sm_scale=sm_scale, block_size=BS, kv_heads=NKV)
            return fluid.layers.unsqueeze(ctx, [1])

        dec_logits, counts = stack(decode, tok, None, wrows, "step",
                                   attend_step)
        next_token = fluid.layers.argmax(dec_logits, axis=-1)
        # what a greedy step hands the host in ONE fetch: the S tokens,
        # then the step's routing counts summed over the expert layers
        host = [fluid.layers.cast(
            fluid.layers.reshape(next_token, [S]), "int32")]
        if counts:
            host.append(fluid.layers.sums(counts) if len(counts) > 1
                        else counts[0])
        token_counts = fluid.layers.concat(host, axis=0)

    # -- chunk prefill: [1, C] of ONE slot's prompt ----------------------
    chunk = Program()
    with unique_name.guard(), program_guard(chunk, startup):
        toks = fluid.data(DecodeModel.CHU_TOKENS, [1, C], dtype="int64")
        pos = fluid.data(DecodeModel.CHU_POSITIONS, [1, C], dtype="int64")
        cbias = fluid.data(DecodeModel.CHU_BIAS, [1, C, L], dtype="float32")
        crows = fluid.data(DecodeModel.CHU_ROWS, [L], dtype="int64")
        cwrows = fluid.data(DecodeModel.CHU_WRITE_ROWS, [C], dtype="int64")
        cslot = fluid.data(DecodeModel.CHU_SLOT, [1], dtype="int64")

        def attend_chunk(i, q, k, v):
            nk, nv = write(chunk, i, cwrows, k, v, 0)
            ctx = fluid.layers.chunk_paged_attention(
                fluid.layers.squeeze(q, [0]), nk, nv, crows, cbias, NKV,
                sm_scale=sm_scale)
            return fluid.layers.unsqueeze(ctx, [0])

        chu_logits, _ = stack(chunk, toks, pos, cwrows, "chunk",
                              attend_chunk, slot=cslot)

    kwargs = dict(
        vocab_size=V, hidden_size=H, hybrid_override_pattern=pattern,
        mamba_num_heads=MH, mamba_head_dim=MP, n_groups=MG,
        ssm_state_size=MN, conv_kernel=conv_kernel, chunk_size=chunk_size,
        num_attention_heads=NQ, num_key_value_heads=NKV, head_dim=D,
        n_routed_experts=held, router_experts=router,
        num_experts_per_tok=num_experts_per_tok,
        moe_intermediate_size=moe_intermediate_size,
        moe_shared_expert_intermediate_size=(
            moe_shared_expert_intermediate_size),
        routed_scaling_factor=routed_scaling_factor,
        norm_topk_prob=norm_topk_prob, layer_norm_epsilon=eps,
        time_step_min=time_step_min, time_step_max=time_step_max,
        time_step_floor=time_step_floor, expert_rank=expert_rank,
        dtype=dtype, state_dtype=state_dtype, slots=S, max_len=L,
        block_size=BS, num_blocks=NB, chunk_tokens=C, eos_id=eos_id, name=name, version=version)
    return DecodeModel(
        decode_program=decode, prefill_program=None, inject_program=None,
        chunk_program=chunk, startup_program=startup,
        slots=S, max_len=L, vocab_size=V, hidden=H, block_size=BS,
        num_blocks=NB, chunk_tokens=C, state_names=state_names,
        kv_width=kv_width, kv_dtype=dtype, slot_states=slot_states,
        logits_fetch=dec_logits.name, token_fetch=next_token.name,
        counts_fetch=token_counts.name if counts else None,
        count_names=MOE_COUNTS if counts else (),
        prefill_logits_fetch=None, chunk_logits_fetch=chu_logits.name,
        prefill_kv_fetches=[], inject_kv_feeds=[],
        eos_id=eos_id, name=name, version=version,
        builder=lambda: build_nemotron_h_model(**kwargs))
