"""Hybrid decoders as paged DecodeModels: per-slot recurrent state beside
paged grouped-query K/V rows, routed experts of which this chip holds a
share, a stack of layers run several times a token, attention layers that
keep different rows or read a chosen subset of them. Eight families, one
set of parts (``_Parts``: the named seeded parameters, projections, norms,
arenas and their write; ``_hybrid_model``: the two programs around a
family's ``stack`` and the DecodeModel).

**``nemotron_h``** (NVIDIA Nemotron-H / Nemotron 3:
``build_nemotron_h_model``).

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

**``lfm2_moe``** (Liquid LFM2 with routed experts, ``build_lfm2_model``).
``layer_types`` names one OPERATOR a layer, ``conv`` a gated short
convolution (``C * conv(B * u)`` over ``conv_L_cache`` taps: its per-slot
state is the tail of the last ``conv_L_cache - 1`` inputs and nothing
else) or ``full_attention`` grouped-query attention with an RMSNorm over
each head of q and k (QK-norm) and rotary positions (whole head,
rotate-half) read from the positions every program already carries; K rows
are stored after both. Every layer is ``x <- x + operator(RMSNorm(x))`` and
then ``x <- x + ffn(RMSNorm(x))``: a dense SwiGLU in the
``num_dense_layers`` leading layers, sigmoid top-k routed SwiGLU experts
(a bias on the choice, the chosen scores normalised over their sum + 1e-6,
no shared expert) in the others. A final RMSNorm and a head TIED to the
embedding; no bias anywhere. The same two programs, the same one-feed
decode step, the same share of the experts.

**``ouro``** (ByteDance Ouro, a looped language model:
``build_ouro_model``). ONE stack of ``num_hidden_layers`` layers, applied
``total_ut_steps`` times to every token with the SAME parameters (made once
by name: a layer's seven matrices and four norms exist once, whatever the
number of passes). A layer is sandwich-normed: ``h <- h + RMSNorm(attn(
RMSNorm(h)))`` then ``h <- h + RMSNorm(ffn(RMSNorm(h)))``, attention
multi-head with rotary positions (whole head, rotate-half) and a SwiGLU
feed-forward, no bias anywhere. After a pass the final RMSNorm; the normed
stream is what enters the next pass, and after the last the untied head.
The K/V rows of layer ``l`` differ from pass to pass, so the paged state is
one arena pair per (pass, layer): ``total_ut_steps x num_hidden_layers``
pairs in ``state_names``, no per-slot state. An exit gate reads every
pass's normed stream (``sigmoid(w . h + b)``); at the published
``early_exit_threshold`` of 1 every token takes every pass, and the gate's
distribution rides to the host as two counts beside the step's tokens
(``LOOP_COUNTS``). A threshold under 1 is refused: leaving early is another
result, and the scheduler steps every slot at one depth.

**``sdar_moe``** (JetLM SDAR with routed experts, a block-diffusion
decoder: ``build_sdar_model``). Every layer is grouped-query attention with
an RMSNorm over each head of q and k and rotary positions (as
``lfm2_moe``'s attention layers) and then routed SwiGLU experts: a SOFTMAX
over all the router's experts, top k, the chosen weights over their sum, no
selection bias, no shared expert, no dense layer. A final RMSNorm and an
untied head; no bias anywhere. What makes it the family is the mask and the
generation rule (``DecodeModel``, "a model that generates by filling
blocks"): a position sees every earlier block of ``block_len`` positions
and the whole of its own, and the decode program is a block pass over ``[S,
block_len]`` positions whose queries ride K/V-head-major into the one
``paged_attention`` op (a K/V head's ``block_len x group`` query rows
together, all under the slot's one bias row), whose experts see ``S x
block_len`` tokens, and whose last op decides one position a slot
(``block_fill_decide``).

**``keye_vl``** (Kwai Keye-VL-2.0's language model: ``build_keye_vl_model``).
``sdar_moe``'s layer (the Qwen3-MoE decoder's: the two share one stack,
``_qk_normed_moe_stack``) under a causal mask, a token a step, and an INDEXER
a layer (DeepSeek-Sparse-Attention's): from the layer's normed input ``x``,
``qI = rot(W_qI x)`` in ``indexer_num_heads`` heads of ``indexer_head_dim``,
ONE index key a token ``kI = rot(LN(W_kI x))`` (LayerNorm with weight and
bias; the whole head rotated, rotate-half) and weights ``w = W_w x heads^-1/2
dim^-1/2``; position t scores ``I(t, s) = sum_j w_j relu(qI_j . kI_s)`` for
``s <= t`` and attends to its ``topk`` positions of largest score alone, a
tie to the lower one, all of them where it sees fewer: exactly
(kernels/sparse.py). The index keys lie in a THIRD paged arena a layer
(``index_names``: ``[R, 128]``, the key's 64 lanes and zeros, under the
layer's block table), written with K and V; a step hands ``paged_attention``
its bias with the positions not kept closed, a chunk hands
``chunk_paged_attention`` the selection's ``[C, L]`` mask. The chunk
program sums its routed layers' counts on the device as ``mistral4``'s does.
The vision tower is not built: image and video tokens are ids like any other
and carry the text's one position.

**``granitemoehybrid``** (IBM Granite 4.0-H, dense: ``build_granite_hybrid_model``).
``layer_types`` names one MIXER a layer, ``mamba`` (``nemotron_h``'s
Mamba-2 mixer with ONE group: every head reads the same B and C) or
``attention`` (grouped-query, no position encoding, scores scaled by
``attention_multiplier`` and not by ``1 / sqrt(head)``); every layer is ``h
<- h + residual_multiplier * mixer(RMSNorm(h))`` and then ``h <- h +
residual_multiplier * mlp(RMSNorm(h))``, a dense SwiGLU of width
``shared_intermediate_size`` in EVERY layer (no routed term: the family's
``num_local_experts`` is 0 here, and another count is refused). The
embedding is scaled by ``embedding_multiplier``, the head is TIED to it and
the logits are divided by ``logits_scaling``; a final RMSNorm; no bias but
the convolution's. No expert is counted: the decode step's one fetch is its
tokens.

**``mistral4``** (Mistral Small 4, the DeepSeek-V2/V3 decoder's keys:
``build_latent_moe_model``). Every layer is LATENT attention and then routed
experts beside one shared expert. Attention: the query through a low-rank
pair (``q_a`` to ``q_lora_rank``, an RMSNorm, ``q_b`` to ``heads x (nope +
rope)``); K and V through ONE compressed vector a token (``kv_a`` to
``kv_lora_rank + rope``: ``c``, RMSNormed, and ``k^R``, the one rotary key
all heads share); head h's key is ``[c . W_UK,h | k^R]`` and its value ``c .
W_UV,h``. What a layer CACHES is the token's ``[c | k^R]`` row alone, in ONE
arena (``state_names`` of 1-tuples), padded with zeros to whole 128-lane
tiles (384 lanes for 256 + 64: an array's minor dimension is tiled by 128 on
the chip whatever its declared width, so a 320-lane row would take the same
bytes). The step attends ABSORBED (``W_UK`` moved onto the query, ``W_UV``
onto the context: all heads read the one row), a chunk EXPANDED
(``kernels/attention.py latent_chunk_attention``, one kernel; its fallback
the same form by XLA's loops, ``latent_chunk_expanded``). The rotation is over the
``rope`` lanes of the query's heads and of ``k^R``, INTERLEAVED pairs ``(2 j,
2 j + 1)`` as published (``rope_interleave``), by a YaRN frequency table
(``yarn_frequencies``: the base's frequencies blended with the base's /
``factor``; sines and cosines unscaled, ``mscale == mscale_all_dim``), and
the whole query is scaled by ``1 + beta ln(1 + floor(p / original_max))``
(``llama_4_scaling_beta``). Scores are scaled by ``(nope + rope)^-1/2 m^2``,
``m = 0.1 mscale_all_dim ln(factor) + 1``. The router is a softmax over all
experts, top k, renormalised; gated SwiGLU experts and a shared expert of
the same width; a final RMSNorm and an untied head; no bias anywhere. The
chunk program sums, on the device, what its routed layers multiplied
(``GROUPED_COUNTS``), and the next step hands the sums to the host in its
one fetch.

**``afmoe``** (Arcee Trinity: ``build_afmoe_model``). ``layer_types`` names
one ATTENTION a layer: ``sliding_attention``, whose query at position i sees
the keys ``j`` with ``0 <= i - j < sliding_window`` and whose q and k are
rotated (whole head, rotate-half), or ``full_attention``, which sees every
``j <= i`` and carries no position encoding. Both are grouped-query with an
RMSNorm over each head of q and k, and both are GATED: ``attn = (softmax(q k
/ sqrt(D)) v * sigmoid(u Wg)) Wo``, the gate as wide as the query and read
from the same normed input ``u``. The sliding layers are a window group of
their own (model.py ``KVGroup``; kvstate.py, "Layer groups"): their arenas
hold a sequence's last ``sliding_window`` rows and what a chunk adds, the
blocks behind them go back to the group's pool, the step reads them through
the group's own table and a bias with a LOWER edge, a chunk under
``chunk_paged_attention``'s ``window``. A layer is sandwich-normed, four
RMSNorms: ``a = x + N2(attn(N1(x)))``, ``x' = a + N4(ffn(N3(a)))``; the
embedding is scaled by ``sqrt(hidden_size)`` (``mup_enabled``). The
feed-forward is a dense SwiGLU in the ``num_dense_layers`` leading layers;
in the others ``shared(h) + route_scale * sum_e w_e expert_e(h)``: sigmoid
scores over all the router's experts in float32, a bias on the choice and
not on the weight, top k, the chosen scores over their sum (``route_norm``;
guarded by 1e-20), gated SwiGLU experts of which this chip holds a share
and a shared expert of the same width. A final RMSNorm and an untied head;
no bias anywhere. The chunk program sums its routed layers' counts on the
device as ``mistral4``'s does (``GROUPED_COUNTS``).
"""

import math

from paddle_tpu.serving.decode.model import (
    DecodeModel, KVGroup, _state_var, window_chunk_blocks,
    window_table_blocks)

__all__ = ["build_nemotron_h_model", "build_lfm2_model", "build_ouro_model",
           "build_sdar_model", "build_keye_vl_model",
           "build_granite_hybrid_model",
           "build_latent_moe_model", "build_afmoe_model", "yarn_frequencies",
           "MOE_COUNTS", "GROUPED_COUNTS", "LOOP_COUNTS"]

#: what the decode step's ``Counts`` hold, in order: the engine adds them
#: to the counters of these names when the step's tokens come back
MOE_COUNTS = ("moe_assignments", "moe_held_assignments",
              "moe_touched_experts", "moe_peak_expert_tokens")

#: what the prompt chunks' routed layers multiplied since the step before:
#: the (token, held expert) pairs their routing made, the rows computed for
#: them, and the held experts with a pair (a layer and launch each); summed
#: on the device by the chunk program (a chunk is a launch and no fetch)
#: and handed over, and zeroed, by the next step
GROUPED_COUNTS = ("moe_grouped_pairs", "moe_grouped_rows",
                  "moe_grouped_experts")

#: a looped stack's: the passes run over the step's stepping tokens, and
#: the sum over them of the pass at which the exit gate expects to leave,
#: in thousandths
LOOP_COUNTS = ("loop_pass_tokens", "loop_exit_pass_milli")


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


class _Parts:
    """What a hybrid family's layers are built from, under the model's
    name: seeded parameters (matrices normal ``std``, those that write into
    the residual ``back``), bias-free projections, RMSNorms, the paged K/V
    arenas of the attention layers ``a_layers`` with their scatter write,
    and the per-slot states. An attention layer is named by its index, or
    by ``(pass, layer)`` where a stack runs several times and every pass
    keeps rows of its own. ``windows`` lists further groups of attention
    layers, ``(name, layers, num_blocks, window)`` each: ``window_groups``
    holds them as model.py's ``KVGroup`` (their arenas have the group's
    ``num_blocks x block_size`` rows), ``window_layers`` each group's
    layers, and ``group_of`` says which group a layer writes to and
    reads."""

    def __init__(self, prefix, dtype, eps, std, back, rows, kv_width,
                 a_layers, slot_states, latent=False, windows=(),
                 block_size=0, index_width=0):
        import paddle_tpu as fluid
        from paddle_tpu.core.ir import Program

        self.fluid = fluid
        self.prefix, self.dtype, self.eps = prefix, dtype, eps
        self.std, self.back = std, back
        self.rows, self.kv_width = rows, kv_width
        self.a_layers = a_layers
        tags = [i if isinstance(i, int) else ".p%d.l%d" % i
                for i in a_layers]
        # a latent cache keeps ONE arena a layer: a token's row is its
        # key and, in its first lanes, its value
        self.state_names = [
            (f"{prefix}.lcache{tag}",) if latent
            else (f"{prefix}.kcache{tag}", f"{prefix}.vcache{tag}")
            for tag in tags]
        # an indexer's keys: a THIRD arena a layer, ``index_width`` lanes a
        # token, under the layer's block table
        self.index_width = int(index_width)
        self.index_names = ([f"{prefix}.icache{tag}" for tag in tags]
                            if index_width else [])
        self.slot_states = slot_states
        self.block_size = int(block_size)
        self.window_layers = [list(layers) for _n, layers, _b, _w in windows]
        self.window_groups = [
            KVGroup(name, [(f"{prefix}.kcache{i}", f"{prefix}.vcache{i}")
                           for i in layers], blocks, window)
            for name, layers, blocks, window in windows]
        self.startup = Program()

    def group_of(self, i):
        """The index in ``window_groups`` of attention layer ``i``'s group,
        or None for a layer of the first group."""
        return next((g for g, layers in enumerate(self.window_layers)
                     if i in layers), None)

    def attr(self, suffix, init):
        return self.fluid.ParamAttr(name=f"{self.prefix}.{suffix}",
                                    initializer=init)

    def matrix(self, suffix, residual=False, std=None):
        from paddle_tpu.initializer import NormalInitializer

        return self.attr(suffix, NormalInitializer(
            0.0, std or (self.back if residual else self.std)))

    def proj(self, h, size, suffix, act=None, residual=False,
             out_dtype=None, std=None):
        return self.fluid.layers.fc(
            h, size, num_flatten_dims=len(h.shape) - 1, act=act,
            bias_attr=False,
            param_attr=self.matrix(suffix + ".w", residual, std),
            out_dtype=out_dtype)

    def norm(self, h, suffix, out_dtype=None):
        from paddle_tpu.initializer import ConstantInitializer

        return self.fluid.layers.rms_norm(
            h, epsilon=self.eps, out_dtype=out_dtype or self.dtype,
            param_attr=self.attr(suffix, ConstantInitializer(1.0)))

    def normed_rotated_heads(self, t, n, positions, suffix, theta):
        """QK-norm (an RMSNorm over a head, weight ``suffix``), then the
        rotation at ``positions`` (``theta`` None: no rotation), over each
        of ``t``'s ``n`` heads."""
        fluid = self.fluid
        lead, d = [int(x) for x in t.shape[:2]], int(t.shape[-1]) // n
        t = fluid.layers.reshape(t, lead + [n, d])
        if theta is None:
            return fluid.layers.reshape(
                self.norm(t, suffix), lead + [n * d])
        t = self.norm(t, suffix, out_dtype="float32")
        return fluid.layers.reshape(fluid.layers.rotary_embedding(
            t, positions, theta=float(theta), out_dtype=self.dtype),
            lead + [n * d])

    def slot_state(self, program, index):
        name, shape, dtype = self.slot_states[index]
        return _state_var(program, self.startup, name, shape, dtype=dtype)

    def arenas(self, program, i):
        g = self.group_of(i)
        if g is None:
            rows, names = self.rows, self.state_names[self.a_layers.index(i)]
        else:
            group = self.window_groups[g]
            names = group.state_names[self.window_layers[g].index(i)]
            rows = group.num_blocks * self.block_size
        return tuple(
            _state_var(program, self.startup, n, [rows, self.kv_width],
                       dtype=self.dtype) for n in names)

    def write_index(self, program, i, wrows, axis, row):
        """``write`` for layer ``i``'s index arena: the tokens' index keys
        ``[.., index_width]`` at the rows K and V go to."""
        fluid = self.fluid
        arena = _state_var(
            program, self.startup, self.index_names[self.a_layers.index(i)],
            [self.rows, self.index_width], dtype=self.dtype)
        written = fluid.layers.block_scatter_write(
            arena, wrows, fluid.layers.squeeze(row, [axis]))
        fluid.layers.assign(written, output=arena)
        return written

    def write(self, program, i, wrows, axis, *rows):
        """Scatter the new rows, one ``[.., kv_width]`` array an arena of
        layer ``i`` (K and V, or a latent cache's one; ``axis``: the one to
        squeeze out of each, or None for ``[rows, kv_width]`` as they are)
        and persist (the lowering donates the arenas); attention reads the
        written views."""
        fluid = self.fluid
        flat = ((lambda t: t) if axis is None
                else (lambda t: fluid.layers.squeeze(t, [axis])))
        arenas = self.arenas(program, i)
        written = [fluid.layers.block_scatter_write(arena, wrows, flat(new))
                   for arena, new in zip(arenas, rows)]
        for view, arena in zip(written, arenas):
            fluid.layers.assign(view, output=arena)
        return written


def _hybrid_model(parts, stack, rebuild, *, vocab, hidden, slots, max_len,
                  block_size, num_blocks, chunk_tokens, kv_heads, sm_scale,
                  eos_id, name, version, count_names=MOE_COUNTS, passes=1,
                  block_len=1, mask_token=None, latent=None, index_topk=0):
    """The hybrid family's two programs around ``stack(program, toks,
    positions, wrows, mode, attend, slot)`` (the layers over ``toks`` ``[S,
    1]`` or ``[1, C]``; ``attend(i, q, k, v)`` is the program's own
    attention over the paged arenas; returns the logits and the int32
    vectors of ``count_names`` that its layers counted, which are summed),
    and their DecodeModel. With ``block_len`` B > 1 the decode program is
    a block pass (``DecodeModel``): ``toks`` ``[S, B]``, a K/V head's B x
    group query rows side by side into ``paged_attention``, and one
    position a slot decided at the end. With ``latent`` (the widths
    ``heads``, ``nope``, ``rope``, ``value``, ``latent`` of a latent
    attention) a layer keeps ONE arena and ``attend(i, q, row, weights)``
    writes the token's ``row`` and attends over the arena as it lies
    (``weights``: the ``w_uk`` and ``w_uv`` parameter attributes). Where
    ``parts`` has window groups, ``attend`` writes and reads layer ``i``
    through ITS group's row map, bias and write rows (model.py, "Layer
    groups"), a chunk under the group's window; the ``wrows`` the stack is
    handed are the first group's, which marks every real token. A layer
    that hands ``attend`` an indexer's ``index`` (the tokens' index queries,
    index keys and weights) keeps the keys in a third arena and attends to
    the ``index_topk`` rows the indexer chooses (kernels/sparse.py): a step
    under a bias with the others closed, a chunk under a mask."""
    import paddle_tpu as fluid
    from paddle_tpu.core.ir import Program, program_guard
    from paddle_tpu.utils import unique_name

    S, L, BS, C = slots, max_len, block_size, chunk_tokens
    startup = parts.startup
    per_slot = -(-L // BS)
    groups = parts.window_groups
    # a window group's table in ``dec_step`` and its chunk row map, blocks
    step_blocks = [window_table_blocks(g.window, BS, per_slot)
                   for g in groups]
    chunk_blocks = [window_chunk_blocks(g.window, C, BS, per_slot)
                    for g in groups]

    # -- decode step: one token per slot at [S, 1], or a block's B --------
    B = int(block_len)
    decode = Program()
    with unique_name.guard(), program_guard(decode, startup):
        packed = fluid.data(
            DecodeModel.DEC_STEP,
            [S, DecodeModel.STEP_TABLE + (B if B > 1 else 0) + per_slot
             + sum(3 + n for n in step_blocks)],
            dtype="int32")
        if B > 1:
            state = fluid.data(DecodeModel.DEC_TOKEN, [S, 2 * B],
                               dtype="int64")
            tok, pos, bias, rows, wrows, held, decided = (
                fluid.layers.paged_block_feeds(packed, state, L, BS, B,
                                               mask_token))
        else:
            tok, pos, bias, rows, wrows = fluid.layers.paged_step_feeds(
                packed,
                fluid.data(DecodeModel.DEC_TOKEN, [S, 1], dtype="int64"),
                L, BS)

        # a window group's (bias, rows, write rows, rows a slot)
        column, step_groups = DecodeModel.STEP_TABLE + per_slot, []
        for n in step_blocks:
            step_groups.append(fluid.layers.paged_window_feeds(
                packed, column, n, BS) + (n * BS,))
            column += 3 + n

        def attend_step(i, q, k, v, index=None):
            g = parts.group_of(i)
            gbias, grows, gwrows, length = (
                (bias, rows, wrows, L) if g is None else step_groups[g])
            nk, nv = parts.write(decode, i, gwrows, 1, k, v)
            if index is not None:
                qi, ki, w = index
                gbias = fluid.layers.sparse_index_select(
                    fluid.layers.squeeze(qi, [1]),
                    fluid.layers.squeeze(w, [1]),
                    parts.write_index(decode, i, gwrows, 1, ki), grows,
                    index_topk, BS, bias=gbias)
            ctx = fluid.layers.paged_attention(
                fluid.layers.squeeze(q, [1]), nk, nv, grows, gbias, S,
                length, sm_scale=sm_scale, block_size=BS, kv_heads=kv_heads)
            return fluid.layers.unsqueeze(ctx, [1])

        def attend_block(i, q, k, v):
            """The block's rows written FIRST, then its B positions as
            further query rows of their K/V head: all see the same rows."""
            width = parts.kv_width
            nk, nv = parts.write(
                decode, i, wrows, None,
                fluid.layers.reshape(k, [S * B, width]),
                fluid.layers.reshape(v, [S * B, width]))
            group = int(q.shape[-1]) // kv_heads       # a K/V head's lanes
            by_head = fluid.layers.transpose(
                fluid.layers.reshape(q, [S, B, kv_heads, group]),
                [0, 2, 1, 3])
            ctx = fluid.layers.paged_attention(
                fluid.layers.reshape(by_head, [S, kv_heads * B * group]),
                nk, nv, rows, bias, S, L, sm_scale=sm_scale, block_size=BS,
                kv_heads=kv_heads)
            return fluid.layers.reshape(fluid.layers.transpose(
                fluid.layers.reshape(ctx, [S, kv_heads, B, group]),
                [0, 2, 1, 3]), [S, B, kv_heads * group])

        def attend_step_latent(i, q, row, weights):
            arena, = parts.write(decode, i, wrows, 1, row)
            ctx = fluid.layers.paged_latent_attention(
                fluid.layers.squeeze(q, [1]), arena, rows, bias, S, L,
                param_attrs=weights, sm_scale=sm_scale, block_size=BS,
                **latent)
            return fluid.layers.unsqueeze(ctx, [1])

        dec_logits, counts = stack(
            decode, tok, pos, wrows, "step",
            attend_step_latent if latent else
            attend_block if B > 1 else attend_step)
        if B > 1:
            # the block after the pass stays on the device for the next
            # launch; the host gets each slot's decided position and token
            next_token, decided_now = fluid.layers.block_fill_decide(
                dec_logits, held, decided, mask_token)
            host = [decided_now]
        else:
            next_token = fluid.layers.argmax(dec_logits, axis=-1)
            # what a greedy step hands the host in ONE fetch: the S
            # tokens, then the step's routing counts summed over the
            # expert layers
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
        # the chunk's first position and its count of real positions: the
        # mask is made of them on the device
        cspan = fluid.data(DecodeModel.CHU_SPAN, [2], dtype="int32")
        crows = fluid.data(DecodeModel.CHU_ROWS, [L], dtype="int64")
        cwrows = fluid.data(DecodeModel.CHU_WRITE_ROWS, [C], dtype="int64")
        # whose rows of the per-slot states the chunk advances
        cslot = (fluid.data(DecodeModel.CHU_SLOT, [1], dtype="int64")
                 if parts.slot_states else None)

        chunk_groups = []
        for g, n in enumerate(chunk_blocks):
            span, grows, gwrows = DecodeModel.chunk_group_feeds(g)
            chunk_groups.append((
                fluid.data(span, [2], dtype="int32"),
                fluid.data(grows, [n * BS], dtype="int64"),
                fluid.data(gwrows, [C], dtype="int64"), groups[g].window))

        def attend_chunk(i, q, k, v, index=None):
            g = parts.group_of(i)
            gspan, grows, gwrows, window = (
                (cspan, crows, cwrows, 0) if g is None else chunk_groups[g])
            nk, nv = parts.write(chunk, i, gwrows, 0, k, v)
            mask = None
            if index is not None:
                qi, ki, w = index
                mask = fluid.layers.sparse_index_select(
                    fluid.layers.squeeze(qi, [0]),
                    fluid.layers.squeeze(w, [0]),
                    parts.write_index(chunk, i, gwrows, 0, ki), grows,
                    index_topk, BS, span=gspan)
            ctx = fluid.layers.chunk_paged_attention(
                fluid.layers.squeeze(q, [0]), nk, nv, grows, gspan, kv_heads,
                BS, sm_scale=sm_scale, block_len=B, window=window, mask=mask)
            return fluid.layers.unsqueeze(ctx, [0])

        def attend_chunk_latent(i, q, row, weights):
            arena, = parts.write(chunk, i, cwrows, 0, row)
            ctx = fluid.layers.chunk_latent_attention(
                fluid.layers.squeeze(q, [0]), arena, crows, cspan,
                param_attrs=weights, sm_scale=sm_scale, block_size=BS,
                **latent)
            return fluid.layers.unsqueeze(ctx, [0])

        chu_logits, _ = stack(
            chunk, toks, pos, cwrows, "chunk",
            attend_chunk_latent if latent else attend_chunk, slot=cslot)

    return DecodeModel(
        decode_program=decode, prefill_program=None, inject_program=None,
        chunk_program=chunk, startup_program=startup,
        slots=S, max_len=L, vocab_size=vocab, hidden=hidden, block_size=BS,
        num_blocks=num_blocks, chunk_tokens=C,
        state_names=parts.state_names, kv_width=parts.kv_width,
        kv_dtype=parts.dtype, slot_states=parts.slot_states,
        logits_fetch=dec_logits.name, token_fetch=next_token.name,
        counts_fetch=token_counts.name if counts or B > 1 else None,
        count_names=count_names if counts else (), passes=passes,
        block_len=B, mask_token=mask_token, window_groups=groups,
        index_names=parts.index_names, index_width=parts.index_width,
        index_topk=index_topk, prefill_logits_fetch=None, chunk_logits_fetch=chu_logits.name,
        prefill_kv_fetches=[], inject_kv_feeds=[],
        eos_id=eos_id, name=name, version=version, builder=rebuild)


def _geometry(slots, max_len, block_size, num_blocks, chunk_tokens):
    """``(S, L, BS, NB, C)`` as whole numbers, the arena sized for every
    slot's full length where ``num_blocks`` is not given."""
    S, L, BS, C = int(slots), int(max_len), int(block_size), int(chunk_tokens)
    if not 2 <= C <= L:
        raise ValueError(f"chunk_tokens must be in [2, {L}], got {C}")
    return S, L, BS, int(num_blocks) if num_blocks else S * -(-L // BS), C


def _held(expert_rank, held, router):
    """The id of the first expert held here."""
    offset = int(expert_rank) * held
    if offset + held > router:
        raise ValueError(f"expert_rank {expert_rank} x {held} held experts "
                         f"passes the router's {router}")
    return offset


def _mamba_attrs(attr, i, dt_min, dt_max, dt_floor):
    """Layer ``i``'s small Mamba-2 parameters as the family draws them:
    ``A_log = log U(1, 16)``, ``dt_bias`` the inverse softplus of a
    log-uniform step, ``D`` and the mixer norm 1, the convolution's weight
    and bias ``U(-0.5, 0.5)`` (conv1d's default at 4 taps)."""
    from paddle_tpu.initializer import ConstantInitializer, UniformInitializer

    return {
        "conv_w": attr(f"l{i}.conv_w", UniformInitializer(-0.5, 0.5)),
        "conv_b": attr(f"l{i}.conv_b", UniformInitializer(-0.5, 0.5)),
        "dt_bias": attr(f"l{i}.dt_bias", _dt_bias(dt_min, dt_max, dt_floor)),
        "a_log": attr(f"l{i}.a_log", _log_uniform(1.0, 16.0)),
        "d": attr(f"l{i}.d", ConstantInitializer(1.0)),
        "norm_w": attr(f"l{i}.mixer_norm", ConstantInitializer(1.0)),
    }


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
    """Build the ``nemotron_h`` decoder as a paged DecodeModel (module
    docstring). The sizes are the published ``config.json``'s keys under
    their own names; ``n_routed_experts`` is how many experts are HELD here
    and ``router_experts`` how many the router scores (the published
    count); ``vocab_size`` the rows of the vocabulary held here.
    ``state_dtype`` is the SSM state's and the convolution tail's (float32
    as served; a narrower one runs the composite and is what the comparison
    with the reference has to catch)."""
    kwargs = dict(locals())
    import paddle_tpu as fluid
    from paddle_tpu.initializer import NormalInitializer

    V, H = int(vocab_size), int(hidden_size)
    pattern = str(hybrid_override_pattern)
    if set(pattern) - set("ME*"):
        raise ValueError(f"hybrid_override_pattern {pattern!r}: a block is "
                         "M (Mamba-2), E (experts) or * (attention)")
    S, L, BS, NB, C = _geometry(slots, max_len, block_size, num_blocks,
                                chunk_tokens)
    R = NB * BS
    MH, MP, MG, MN = (int(mamba_num_heads), int(mamba_head_dim),
                      int(n_groups), int(ssm_state_size))
    d_inner = MH * MP
    conv_dim = d_inner + 2 * MG * MN
    in_width = 2 * d_inner + 2 * MG * MN + MH
    NQ, NKV, D = (int(num_attention_heads), int(num_key_value_heads),
                  int(head_dim))
    held, router = int(n_routed_experts), int(router_experts)
    offset = _held(expert_rank, held, router)
    eps = float(layer_norm_epsilon)
    prefix = f"{name}_v{version}"
    m_layers = [i for i, kind in enumerate(pattern) if kind == "M"]
    slot_states = []
    for i in m_layers:
        slot_states.append((f"{prefix}.conv{i}",
                            (S, int(conv_kernel) - 1, conv_dim),
                            state_dtype))
        slot_states.append((f"{prefix}.ssm{i}", (S, MH, MP, MN),
                            state_dtype))
    # rescale_prenorm_residual: one mixer a block
    parts = _Parts(prefix, dtype, eps, 0.02, 0.02 / math.sqrt(len(pattern)),
                   R, NKV * D,
                   [i for i, kind in enumerate(pattern) if kind == "*"],
                   slot_states)
    attr, matrix, proj, norm = (parts.attr, parts.matrix, parts.proj,
                                parts.norm)

    def mamba_attrs(i):
        return _mamba_attrs(attr, i, time_step_min, time_step_max,
                            time_step_floor)

    def expert_attrs(i):
        return {
            "gate": matrix(f"l{i}.gate"),
            "select_bias": attr(f"l{i}.select_bias",
                                NormalInitializer(0.0, 0.05)),
            "w_up": matrix(f"l{i}.w_up"),
            "w_down": matrix(f"l{i}.w_down", residual=True),
        }

    def stack(program, toks, positions, wrows, mode, attend, slot=None):
        """The 52 blocks over ``toks``; no position encoding: a step's
        positions go unread."""
        h = fluid.layers.cast(fluid.layers.embedding(
            toks, size=(V, H), dtype=dtype,
            param_attr=matrix("embed")), "float32")
        counts = []
        for i, kind in enumerate(pattern):
            x = norm(h, f"l{i}.norm")
            if kind == "M":
                at = 2 * m_layers.index(i)
                conv, ssm = (parts.slot_state(program, at),
                             parts.slot_state(program, at + 1))
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
                             proj(x, NKV * D, f"l{i}.k"),
                             proj(x, NKV * D, f"l{i}.v"))
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

    return _hybrid_model(
        parts, stack, lambda: build_nemotron_h_model(**kwargs), vocab=V,
        hidden=H, slots=S, max_len=L, block_size=BS, num_blocks=NB,
        chunk_tokens=C, kv_heads=NKV, sm_scale=1.0 / math.sqrt(D),
        eos_id=eos_id, name=name, version=version)


def build_lfm2_model(
        vocab_size, hidden_size, layer_types, *, num_attention_heads,
        num_key_value_heads, intermediate_size, num_dense_layers,
        num_experts, router_experts, num_experts_per_tok,
        moe_intermediate_size, conv_L_cache=3, rope_theta=1000000.0,
        routed_scaling_factor=1.0, norm_topk_prob=True, norm_eps=1e-5,
        initializer_range=0.02, expert_rank=0, dtype="bfloat16",
        state_dtype="float32", slots=4,
        max_len=64, block_size=16, num_blocks=None, chunk_tokens=16,
        eos_id=None, name="lfm2", version="1"):
    """Build the ``lfm2_moe`` decoder as a paged DecodeModel (module
    docstring). The sizes are the published ``config.json``'s keys under
    their own names (``rope_theta`` is ``rope_parameters``'s); a head is
    ``hidden_size / num_attention_heads`` wide; ``num_experts`` is how many
    experts are HELD here and ``router_experts`` how many the router scores
    (the published count). ``state_dtype`` is the convolution tails';
    ``initializer_range`` the matrices' standard deviation (a tiny preset
    takes a wider one: at 0.02 a hidden size of 64 leaves the layers
    nothing to say beside the embedding)."""
    kwargs = dict(locals())
    import paddle_tpu as fluid
    from paddle_tpu.initializer import NormalInitializer, UniformInitializer

    V, H = int(vocab_size), int(hidden_size)
    kinds = [str(kind) for kind in layer_types]
    if set(kinds) - {"conv", "full_attention"}:
        raise ValueError(f"layer_types {kinds}: an operator is conv or "
                         "full_attention")
    S, L, BS, NB, C = _geometry(slots, max_len, block_size, num_blocks,
                                chunk_tokens)
    R = NB * BS
    NQ, NKV = int(num_attention_heads), int(num_key_value_heads)
    D = H // NQ
    taps, dense = int(conv_L_cache), int(num_dense_layers)
    held, router = int(num_experts), int(router_experts)
    offset = _held(expert_rank, held, router)
    prefix = f"{name}_v{version}"
    c_layers = [i for i, kind in enumerate(kinds) if kind == "conv"]
    # two sub-layers a layer write into the residual
    std = float(initializer_range)
    parts = _Parts(
        prefix, dtype, float(norm_eps), std,
        std / math.sqrt(2 * len(kinds)), R, NKV * D,
        [i for i, kind in enumerate(kinds) if kind == "full_attention"],
        [(f"{prefix}.conv{i}", (S, taps - 1, H), state_dtype)
         for i in c_layers])
    attr, matrix, proj, norm = (parts.attr, parts.matrix, parts.proj,
                                parts.norm)
    bound = 1.0 / math.sqrt(taps)       # conv1d's default draw

    def expert_attrs(i):
        return {
            "gate": matrix(f"l{i}.gate"),
            "select_bias": attr(f"l{i}.expert_bias",
                                NormalInitializer(0.0, 0.05)),
            "w_gate": matrix(f"l{i}.w1"),
            "w_up": matrix(f"l{i}.w3"),
            "w_down": matrix(f"l{i}.w2", residual=True),
        }

    def heads(t, n, positions, suffix):
        return parts.normed_rotated_heads(t, n, positions, suffix,
                                          rope_theta)

    def stack(program, toks, positions, wrows, mode, attend, slot=None):
        """The 40 layers over ``toks``: operator, then feed-forward."""
        h = fluid.layers.cast(fluid.layers.embedding(
            toks, size=(V, H), dtype=dtype,
            param_attr=matrix("embed")), "float32")
        embed = program.global_block().var(f"{prefix}.embed")
        counts = []
        for i, kind in enumerate(kinds):
            x = norm(h, f"l{i}.operator_norm")
            if kind == "conv":
                y = fluid.layers.gated_short_conv(
                    proj(x, 3 * H, f"l{i}.in_proj", out_dtype="float32"),
                    parts.slot_state(program, c_layers.index(i)), wrows, R,
                    mode, taps,
                    attr(f"l{i}.conv_w", UniformInitializer(-bound, bound)),
                    slot=slot, positions=positions, out_dtype=dtype)
                out = proj(y, H, f"l{i}.out_proj", residual=True,
                           out_dtype="float32")
            else:
                ctx = attend(
                    i,
                    heads(proj(x, NQ * D, f"l{i}.q", out_dtype="float32"),
                          NQ, positions, f"l{i}.q_layernorm"),
                    heads(proj(x, NKV * D, f"l{i}.k", out_dtype="float32"),
                          NKV, positions, f"l{i}.k_layernorm"),
                    proj(x, NKV * D, f"l{i}.v"))
                out = proj(ctx, H, f"l{i}.out_proj", residual=True,
                           out_dtype="float32")
            h = fluid.layers.elementwise_add(h, out)
            x = norm(h, f"l{i}.ffn_norm")
            if i < dense:
                gated = fluid.layers.elementwise_mul(
                    proj(x, int(intermediate_size), f"l{i}.w1", act="silu",
                         out_dtype="float32"),
                    proj(x, int(intermediate_size), f"l{i}.w3",
                         out_dtype="float32"))
                out = proj(fluid.layers.cast(gated, dtype), H, f"l{i}.w2",
                           residual=True, out_dtype="float32")
            else:
                out, n = fluid.layers.moe_routed_experts(
                    x, wrows, R, router, held, int(moe_intermediate_size),
                    int(num_experts_per_tok), expert_attrs(i),
                    expert_offset=offset,
                    score_scale=float(routed_scaling_factor),
                    normalize=bool(norm_topk_prob), norm_epsilon=1e-6,
                    kernel=mode == "step")
                counts.append(n)
            h = fluid.layers.elementwise_add(h, out)
        logits = fluid.layers.matmul(norm(h, "embedding_norm"), embed,
                                     transpose_y=True, out_dtype="float32")
        return logits, counts

    return _hybrid_model(
        parts, stack, lambda: build_lfm2_model(**kwargs), vocab=V, hidden=H,
        slots=S, max_len=L, block_size=BS, num_blocks=NB, chunk_tokens=C,
        kv_heads=NKV, sm_scale=1.0 / math.sqrt(D), eos_id=eos_id, name=name,
        version=version)


def build_ouro_model(
        vocab_size, hidden_size, num_hidden_layers, *, num_attention_heads,
        num_key_value_heads, head_dim, intermediate_size, total_ut_steps=4,
        early_exit_threshold=1.0, rms_norm_eps=1e-6, rope_theta=1000000.0,
        initializer_range=0.02, dtype="bfloat16", slots=4, max_len=64,
        block_size=16, num_blocks=None, chunk_tokens=16, eos_id=None,
        name="ouro", version="1"):
    """Build the ``ouro`` looped decoder as a paged DecodeModel (module
    docstring). The sizes are the published ``config.json``'s keys under
    their own names. ``num_blocks`` may be fewer than ``slots`` sequences
    of ``max_len`` need (a token's rows are ``total_ut_steps`` times a
    plain stack's): the engine then admits a request against its whole
    block chain (engine.py, admission by reservation).
    ``initializer_range`` is the matrices' standard deviation (a tiny
    preset takes a wider one, as ``build_lfm2_model``'s)."""
    kwargs = dict(locals())
    import paddle_tpu as fluid
    from paddle_tpu.initializer import ConstantInitializer
    from paddle_tpu.layer_helper import LayerHelper

    if float(early_exit_threshold) < 1.0:
        raise ValueError(
            f"early_exit_threshold {early_exit_threshold}: under 1 a token "
            "leaves before its last pass, which is another result and "
            "would step slots at different depths; only 1 is served")
    V, H, NL = int(vocab_size), int(hidden_size), int(num_hidden_layers)
    T = int(total_ut_steps)
    if T < 1:
        raise ValueError(f"total_ut_steps must be at least 1, got {T}")
    S, L, BS, NB, C = _geometry(slots, max_len, block_size, num_blocks,
                                chunk_tokens)
    R = NB * BS
    NQ, NKV, D = (int(num_attention_heads), int(num_key_value_heads),
                  int(head_dim))
    F = int(intermediate_size)
    prefix = f"{name}_v{version}"
    # two sub-layers a layer application write into the residual
    std = float(initializer_range)
    parts = _Parts(prefix, dtype, float(rms_norm_eps), std,
                   std / math.sqrt(2 * NL * T), R, NKV * D,
                   [(t, i) for t in range(T) for i in range(NL)], [])
    attr, matrix, proj, norm = (parts.attr, parts.matrix, parts.proj,
                                parts.norm)

    def heads(x, n, suffix, positions):
        """``x`` projected to ``n`` heads and the rotation over each. The
        matrix is stored ``[n * D, H]``, a head's rows together: the
        product that feeds a rotation wants it so, and a stack run more
        than once otherwise keeps a transposed copy of it in HBM from its
        first pass to its last (16.8 MB a layer: PERF.md section 6,
        PR 43)."""
        lead = [int(d) for d in x.shape[:2]]
        w = LayerHelper("fc").create_parameter(
            matrix(suffix + ".w"), shape=[n * D, H], dtype=dtype)
        t = fluid.layers.matmul(x, w, transpose_y=True, out_dtype="float32")
        return fluid.layers.reshape(fluid.layers.rotary_embedding(
            fluid.layers.reshape(t, lead + [n, D]), positions,
            theta=float(rope_theta), out_dtype=dtype), lead + [n * D])

    def layer(h, t, i, positions, attend):
        x = norm(h, f"l{i}.input_layernorm")
        ctx = attend((t, i), heads(x, NQ, f"l{i}.q", positions),
                     heads(x, NKV, f"l{i}.k", positions),
                     proj(x, NKV * D, f"l{i}.v"))
        out = proj(ctx, H, f"l{i}.o", residual=True, out_dtype="float32")
        h = fluid.layers.elementwise_add(
            h, norm(out, f"l{i}.post_attention_layernorm",
                    out_dtype="float32"))
        x = norm(h, f"l{i}.pre_feedforward_layernorm")
        gated = fluid.layers.elementwise_mul(
            proj(x, F, f"l{i}.gate", act="silu", out_dtype="float32"),
            proj(x, F, f"l{i}.up", out_dtype="float32"))
        out = proj(fluid.layers.cast(gated, dtype), H, f"l{i}.down",
                   residual=True, out_dtype="float32")
        return fluid.layers.elementwise_add(
            h, norm(out, f"l{i}.post_feedforward_layernorm",
                    out_dtype="float32"))

    def stack(program, toks, positions, wrows, mode, attend, slot=None):
        """The ``NL`` layers ``T`` times over ``toks``, the final norm
        after every pass; the exit gate's two counts over the tokens whose
        write row is real."""
        h = fluid.layers.cast(fluid.layers.embedding(
            toks, size=(V, H), dtype=dtype,
            param_attr=matrix("embed")), "float32")
        # E[exit pass] = sum over t of P(not left before pass t + 1):
        # 1 + (1 - l_1) + (1 - l_1)(1 - l_2) + ..; the last pass takes
        # what is left, so its own gate decides nothing
        stays, expected = None, []
        for t in range(T):
            for i in range(NL):
                h = layer(h, t, i, positions, attend)
            h = norm(h, "final_norm", out_dtype="float32")
            leave = fluid.layers.sigmoid(fluid.layers.fc(
                h, 1, num_flatten_dims=2, param_attr=matrix("exit_gate.w"),
                bias_attr=attr("exit_gate.b", ConstantInitializer(0.0))))
            stay = fluid.layers.scale(leave, scale=-1.0, bias=1.0)
            stays = stay if stays is None else fluid.layers.elementwise_mul(
                stays, stay)
            if t < T - 1:
                expected.append(stays)
        logits = proj(fluid.layers.cast(h, dtype), V, "head",
                      out_dtype="float32")
        n = int(wrows.shape[0])
        # 1.0 for a token whose write row is real, 0.0 at the sentinel R
        steps = fluid.layers.clip(fluid.layers.scale(
            fluid.layers.cast(wrows, "float32"), scale=-1.0, bias=float(R)),
            0.0, 1.0)
        later = (fluid.layers.sums(expected) if len(expected) > 1
                 else expected[0] if expected
                 else fluid.layers.scale(steps, scale=0.0))
        milli = fluid.layers.scale(fluid.layers.reshape(later, [n]),
                                   scale=1000.0, bias=1000.5)
        # truncation rounds a positive number to nearest
        milli = fluid.layers.cast(fluid.layers.cast(milli, "int32"),
                                  "float32")
        counts = fluid.layers.cast(fluid.layers.concat([
            fluid.layers.scale(fluid.layers.reduce_sum(steps), scale=float(T)),
            fluid.layers.reduce_sum(
                fluid.layers.elementwise_mul(milli, steps))], axis=0),
            "int32")
        return logits, [counts]

    return _hybrid_model(
        parts, stack, lambda: build_ouro_model(**kwargs), vocab=V, hidden=H,
        slots=S, max_len=L, block_size=BS, num_blocks=NB, chunk_tokens=C,
        kv_heads=NKV, sm_scale=1.0 / math.sqrt(D), eos_id=eos_id, name=name,
        version=version, count_names=LOOP_COUNTS, passes=T)


def _qk_normed_moe_stack(parts, V, H, NL, NQ, NKV, D, rope_theta, router,
                         held, offset, ffn, top_k, normalize, indexer=None,
                         grouped_name=None):
    """The stack ``sdar_moe`` and ``keye_vl`` share (the Qwen3-MoE
    decoder's): ``NL`` layers of grouped-query attention with an RMSNorm
    over each head of q and k and rotary positions, then softmax-routed
    SwiGLU experts (top ``top_k`` of ``router``, ``held`` of them here),
    a final RMSNorm and an untied head. ``indexer(i, x, positions)``, where
    a family has one, gives layer ``i``'s index queries, keys and weights
    from the layer's normed input: they ride to ``attend`` as its
    ``index``. With ``grouped_name`` (a state's name) the chunk program
    sums what its routed layers multiplied on the device and the next step
    hands the sums over and zeroes them, as ``mistral4``'s does
    (``GROUPED_COUNTS``)."""
    fluid = parts.fluid
    from paddle_tpu.initializer import ConstantInitializer

    attr, matrix, proj, norm = (parts.attr, parts.matrix, parts.proj,
                                parts.norm)
    dtype, R = parts.dtype, parts.rows

    def heads(t, n, positions, suffix):
        return parts.normed_rotated_heads(t, n, positions, suffix,
                                          rope_theta)

    def stack(program, toks, positions, wrows, mode, attend, slot=None):
        """The ``NL`` layers over ``toks``: attention, then the experts."""
        h = fluid.layers.cast(fluid.layers.embedding(
            toks, size=(V, H), dtype=dtype,
            param_attr=matrix("embed")), "float32")
        counts, pairs = [], []
        for i in range(NL):
            x = norm(h, f"l{i}.input_layernorm")
            ctx = attend(
                i,
                heads(proj(x, NQ * D, f"l{i}.q", out_dtype="float32"),
                      NQ, positions, f"l{i}.q_norm"),
                heads(proj(x, NKV * D, f"l{i}.k", out_dtype="float32"),
                      NKV, positions, f"l{i}.k_norm"),
                proj(x, NKV * D, f"l{i}.v"),
                **({"index": indexer(i, x, positions)} if indexer else {}))
            h = fluid.layers.elementwise_add(h, proj(
                ctx, H, f"l{i}.o", residual=True, out_dtype="float32"))
            out, n, *g = fluid.layers.moe_routed_experts(
                norm(h, f"l{i}.post_attention_layernorm"), wrows, R, router,
                held, ffn, top_k,
                {"gate": matrix(f"l{i}.gate"),
                 # this router's choice is its scores' own
                 "select_bias": attr(f"l{i}.select_bias",
                                     ConstantInitializer(0.0)),
                 "w_gate": matrix(f"l{i}.w1"),
                 "w_up": matrix(f"l{i}.w3"),
                 "w_down": matrix(f"l{i}.w2", residual=True)},
                expert_offset=offset, normalize=normalize,
                kernel=mode == "step", score="softmax",
                **({"group_counts": True} if grouped_name else {}))
            counts.append(n)
            pairs.extend(g)
            h = fluid.layers.elementwise_add(h, out)
        logits = proj(norm(h, "norm"), V, "head", out_dtype="float32")
        if not grouped_name:
            return logits, counts
        total = _state_var(program, parts.startup, grouped_name,
                           [len(GROUPED_COUNTS)], dtype="int32")
        if mode == "chunk":
            fluid.layers.assign(fluid.layers.sums([total] + pairs),
                                output=total)
            return logits, []
        both = fluid.layers.concat([fluid.layers.sums(counts), total],
                                   axis=0)
        fluid.layers.assign(
            fluid.layers.fill_constant([len(GROUPED_COUNTS)], "int32", 0),
            output=total)
        return logits, [both]

    return stack


def build_sdar_model(
        vocab_size, hidden_size, num_hidden_layers, *, num_attention_heads,
        num_key_value_heads, head_dim, num_experts, router_experts,
        num_experts_per_tok, moe_intermediate_size, block_len=4,
        denoising_steps=4, mask_token_id=None, norm_topk_prob=True,
        rms_norm_eps=1e-6, rope_theta=1000000.0, initializer_range=0.02,
        expert_rank=0, dtype="bfloat16", slots=4, max_len=64, block_size=16,
        num_blocks=None, chunk_tokens=16, eos_id=None, name="sdar",
        version="1"):
    """Build the ``sdar_moe`` block-diffusion decoder as a paged
    DecodeModel (module docstring). The sizes are the published
    ``config.json``'s keys under their own names; ``num_experts`` is how
    many experts are HELD here and ``router_experts`` how many the router
    scores (the published count). ``block_len`` positions make a block,
    filled over ``denoising_steps`` passes: only one position a pass is
    served (``denoising_steps == block_len``: with more a pass the host
    could no longer say, without a fetch, which pass of which block a slot
    is in once a first block opens part decided). ``mask_token_id`` is what
    an undecided position holds and what no answer may hold (default: the
    vocabulary's last id). ``initializer_range`` as ``build_lfm2_model``'s."""
    kwargs = dict(locals())
    V, H, NL = int(vocab_size), int(hidden_size), int(num_hidden_layers)
    B = int(block_len)
    if B < 2 or int(denoising_steps) != B:
        raise ValueError(
            f"block_len {block_len} with denoising_steps {denoising_steps}: "
            "a block of at least 2 positions is filled one position a pass")
    mask = V - 1 if mask_token_id is None else int(mask_token_id)
    if not 0 <= mask < V:
        raise ValueError(f"mask_token_id {mask} is not in [0, {V})")
    S, L, BS, NB, C = _geometry(slots, max_len, block_size, num_blocks,
                                chunk_tokens)
    R = NB * BS
    NQ, NKV, D = (int(num_attention_heads), int(num_key_value_heads),
                  int(head_dim))
    held, router = int(num_experts), int(router_experts)
    offset = _held(expert_rank, held, router)
    prefix = f"{name}_v{version}"
    # two sub-layers a layer write into the residual
    std = float(initializer_range)
    parts = _Parts(prefix, dtype, float(rms_norm_eps), std,
                   std / math.sqrt(2 * NL), R, NKV * D, list(range(NL)), [])
    stack = _qk_normed_moe_stack(
        parts, V, H, NL, NQ, NKV, D, rope_theta, router, held, offset,
        int(moe_intermediate_size), int(num_experts_per_tok),
        bool(norm_topk_prob))

    return _hybrid_model(
        parts, stack, lambda: build_sdar_model(**kwargs), vocab=V, hidden=H,
        slots=S, max_len=L, block_size=BS, num_blocks=NB, chunk_tokens=C,
        kv_heads=NKV, sm_scale=1.0 / math.sqrt(D), eos_id=eos_id, name=name,
        version=version, block_len=B, mask_token=mask)


def build_keye_vl_model(
        vocab_size, hidden_size, num_hidden_layers, *, num_attention_heads,
        num_key_value_heads, head_dim, num_experts, router_experts,
        num_experts_per_tok, moe_intermediate_size, sa_config,
        norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=10000000.0,
        initializer_range=0.02, expert_rank=0, dtype="bfloat16", slots=4,
        max_len=64, block_size=16, num_blocks=None, chunk_tokens=16,
        eos_id=None, name="keye_vl", version="1"):
    """Build the ``keye_vl`` language model as a paged DecodeModel (module
    docstring): ``sdar_moe``'s stack a token a step, and an indexer a
    layer. The sizes are the published ``config.json``'s keys under their
    own names; ``sa_config`` is the published group whole
    (``indexer_num_heads``, ``indexer_head_dim``, ``topk``; ONE index key
    head: ``indexer_num_kv_heads`` 1; its chunk sizes tile the source's
    score computation and are not read); ``num_experts`` is how many
    experts are HELD here and ``router_experts`` how many the router
    scores. ``chunk_tokens`` has to be a whole number of blocks."""
    kwargs = dict(locals())
    from paddle_tpu.initializer import ConstantInitializer

    V, H, NL = int(vocab_size), int(hidden_size), int(num_hidden_layers)
    S, L, BS, NB, C = _geometry(slots, max_len, block_size, num_blocks,
                                chunk_tokens)
    R = NB * BS
    NQ, NKV, D = (int(num_attention_heads), int(num_key_value_heads),
                  int(head_dim))
    held, router = int(num_experts), int(router_experts)
    offset = _held(expert_rank, held, router)
    IH, ID, topk = (int(sa_config["indexer_num_heads"]),
                    int(sa_config["indexer_head_dim"]),
                    int(sa_config["topk"]))
    if int(sa_config.get("indexer_num_kv_heads", 1)) != 1:
        raise ValueError("sa_config.indexer_num_kv_heads "
                         f"{sa_config['indexer_num_kv_heads']}: the indexer "
                         "keeps ONE key a token, which all its heads read")
    if topk < 1 or ID % 2:
        raise ValueError(f"sa_config: topk {topk} has to be positive and "
                         f"indexer_head_dim {ID} even (it is rotated)")
    if C % BS:
        raise ValueError(f"chunk_tokens {C} has to be whole blocks of {BS}")
    # a token's key in whole 128-lane tiles, zeros behind it: an array's
    # minor dimension is tiled by 128 on the chip whatever its declared
    # width, so a 64-lane row takes the same bytes
    IW = -(-ID // 128) * 128
    prefix = f"{name}_v{version}"
    std = float(initializer_range)
    parts = _Parts(prefix, dtype, float(rms_norm_eps), std,
                   std / math.sqrt(2 * NL), R, NKV * D, list(range(NL)), [],
                   index_width=IW)
    fluid, proj, attr = parts.fluid, parts.proj, parts.attr

    def rotated(t, lead, n, positions):
        """``t`` ``[.., n * ID]`` rotated over each of its ``n`` heads (the
        whole head, rotate-half), each padded to ``IW`` lanes."""
        t = fluid.layers.rotary_embedding(
            fluid.layers.reshape(t, lead + [n, ID]), positions,
            theta=float(rope_theta), out_dtype=dtype)
        if IW > ID:
            t = fluid.layers.pad(t, [0, 0] * (len(lead) + 1) + [0, IW - ID])
        return fluid.layers.reshape(t, lead + [n * IW])

    def indexer(i, x, positions):
        """Layer ``i``'s ``(qI, kI, w)`` from its normed input ``x``: the
        index queries rotated, the one key LayerNormed (weight and bias)
        and rotated, the weights scaled by ``heads^-1/2 width^-1/2``."""
        lead = [int(d) for d in x.shape[:2]]
        qi = rotated(proj(x, IH * ID, f"l{i}.index_q", out_dtype="float32"),
                     lead, IH, positions)
        ki = fluid.layers.layer_norm(
            proj(x, ID, f"l{i}.index_k", out_dtype="float32"),
            begin_norm_axis=2, epsilon=1e-6,
            param_attr=attr(f"l{i}.index_k_norm", ConstantInitializer(1.0)),
            bias_attr=attr(f"l{i}.index_k_norm_b", ConstantInitializer(0.0)))
        w = fluid.layers.scale(
            proj(x, IH, f"l{i}.index_w", out_dtype="float32"),
            scale=1.0 / math.sqrt(IH * ID))
        return qi, rotated(ki, lead, 1, positions), w

    stack = _qk_normed_moe_stack(
        parts, V, H, NL, NQ, NKV, D, rope_theta, router, held, offset,
        int(moe_intermediate_size), int(num_experts_per_tok),
        bool(norm_topk_prob), indexer=indexer,
        grouped_name=f"{prefix}.grouped_counts")
    return _hybrid_model(
        parts, stack, lambda: build_keye_vl_model(**kwargs), vocab=V,
        hidden=H, slots=S, max_len=L, block_size=BS, num_blocks=NB,
        chunk_tokens=C, kv_heads=NKV, sm_scale=1.0 / math.sqrt(D),
        eos_id=eos_id, name=name, version=version, index_topk=topk,
        count_names=MOE_COUNTS + GROUPED_COUNTS)


def build_granite_hybrid_model(
        vocab_size, hidden_size, layer_types, *, num_attention_heads,
        num_key_value_heads, shared_intermediate_size, mamba_n_heads,
        mamba_d_head, mamba_n_groups, mamba_d_state, mamba_d_conv,
        mamba_chunk_size, embedding_multiplier, attention_multiplier,
        residual_multiplier, logits_scaling, num_local_experts=0,
        rms_norm_eps=1e-5, initializer_range=0.02, qk_initializer_range=None,
        time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4,
        dtype="bfloat16", state_dtype="float32", slots=4, max_len=64,
        block_size=16, num_blocks=None, chunk_tokens=16, eos_id=None,
        name="granite_hybrid", version="1"):
    """Build the dense ``granitemoehybrid`` decoder as a paged DecodeModel
    (module docstring). The sizes are the published ``config.json``'s keys
    under their own names; a head is ``hidden_size / num_attention_heads``
    wide; the Mamba mixer's inner width is ``mamba_n_heads x mamba_d_head``
    (``mamba_expand`` is not read). ``num_blocks`` may be fewer than
    ``slots`` sequences of ``max_len`` need: the engine then admits a
    request against its whole block chain. ``state_dtype`` and
    ``initializer_range`` as ``build_nemotron_h_model``'s and
    ``build_lfm2_model``'s, but the embedding, which is also the head, is
    drawn at ``initializer_range / embedding_multiplier``: the stream the
    first layer reads then has the other families' scale, and a token does
    not simply answer itself through the tied head.
    ``qk_initializer_range`` draws the attention mixers' q and k
    projections apart from the rest: ``attention_multiplier`` is muP's
    ``1 / head`` and not ``1 / sqrt(head)``, which presumes a query and its
    keys CORRELATE as a trained pair does; two independent draws at
    ``initializer_range`` give scores of standard deviation
    ``sqrt(head) x hidden x range^2 x attention_multiplier`` (0.1 at the
    published sizes), a uniform softmax that hands on the mean of V
    whatever K holds. The time-step draws are Mamba-2's defaults (the
    family's ``config.json`` does not carry them)."""
    kwargs = dict(locals())
    import paddle_tpu as fluid
    from paddle_tpu.initializer import NormalInitializer

    if int(num_local_experts):
        raise ValueError(
            f"num_local_experts {num_local_experts}: only the dense member "
            "of the family (no routed term beside the shared MLP) is built")
    V, H = int(vocab_size), int(hidden_size)
    kinds = [str(kind) for kind in layer_types]
    if set(kinds) - {"mamba", "attention"}:
        raise ValueError(f"layer_types {kinds}: a mixer is mamba or "
                         "attention")
    S, L, BS, NB, C = _geometry(slots, max_len, block_size, num_blocks,
                                chunk_tokens)
    R = NB * BS
    MH, MP, MG, MN = (int(mamba_n_heads), int(mamba_d_head),
                      int(mamba_n_groups), int(mamba_d_state))
    taps, F = int(mamba_d_conv), int(shared_intermediate_size)
    d_inner = MH * MP
    conv_dim = d_inner + 2 * MG * MN
    in_width = 2 * d_inner + 2 * MG * MN + MH
    NQ, NKV = int(num_attention_heads), int(num_key_value_heads)
    D = H // NQ
    eps = float(rms_norm_eps)
    embed_by, by = float(embedding_multiplier), float(residual_multiplier)
    prefix = f"{name}_v{version}"
    m_layers = [i for i, kind in enumerate(kinds) if kind == "mamba"]
    slot_states = []
    for i in m_layers:
        slot_states.append((f"{prefix}.conv{i}", (S, taps - 1, conv_dim),
                            state_dtype))
        slot_states.append((f"{prefix}.ssm{i}", (S, MH, MP, MN),
                            state_dtype))
    # one mixer's output projection a layer is drawn narrower, as
    # ``nemotron_h``'s (1 / sqrt(layers)); the MLP's takes the same
    std = float(initializer_range)
    qk_std = float(qk_initializer_range or std)
    parts = _Parts(
        prefix, dtype, eps, std, std / math.sqrt(len(kinds)), R, NKV * D,
        [i for i, kind in enumerate(kinds) if kind == "attention"],
        slot_states)
    attr, matrix, proj, norm = (parts.attr, parts.matrix, parts.proj,
                                parts.norm)

    def stack(program, toks, positions, wrows, mode, attend, slot=None):
        """The layers over ``toks``: mixer, then the dense gated MLP, each
        into the residual times ``residual_multiplier``; no position
        encoding: a step's positions go unread."""
        h = fluid.layers.scale(fluid.layers.cast(fluid.layers.embedding(
            toks, size=(V, H), dtype=dtype,
            param_attr=attr("embed", NormalInitializer(0.0, std / embed_by))),
            "float32"), scale=embed_by)
        embed = program.global_block().var(f"{prefix}.embed")
        for i, kind in enumerate(kinds):
            x = norm(h, f"l{i}.input_layernorm")
            if kind == "mamba":
                at = 2 * m_layers.index(i)
                y = fluid.layers.mamba2_mixer(
                    proj(x, in_width, f"l{i}.in_proj", out_dtype="float32"),
                    parts.slot_state(program, at),
                    parts.slot_state(program, at + 1), wrows, R, mode, MH,
                    MP, MG, MN, taps,
                    _mamba_attrs(attr, i, time_step_min, time_step_max,
                                 time_step_floor),
                    slot=slot, positions=positions,
                    chunk_size=int(mamba_chunk_size), epsilon=eps,
                    out_dtype=dtype)
                out = proj(y, H, f"l{i}.out_proj", residual=True,
                           out_dtype="float32")
            else:
                ctx = attend(i, proj(x, NQ * D, f"l{i}.q", std=qk_std),
                             proj(x, NKV * D, f"l{i}.k", std=qk_std),
                             proj(x, NKV * D, f"l{i}.v"))
                out = proj(ctx, H, f"l{i}.o", residual=True,
                           out_dtype="float32")
            h = fluid.layers.elementwise_add(
                h, fluid.layers.scale(out, scale=by))
            x = norm(h, f"l{i}.post_attention_layernorm")
            # the input projection's two halves, stored apart
            gated = fluid.layers.elementwise_mul(
                proj(x, F, f"l{i}.mlp_gate", act="silu",
                     out_dtype="float32"),
                proj(x, F, f"l{i}.mlp_up", out_dtype="float32"))
            out = proj(fluid.layers.cast(gated, dtype), H, f"l{i}.mlp_down",
                       residual=True, out_dtype="float32")
            h = fluid.layers.elementwise_add(
                h, fluid.layers.scale(out, scale=by))
        logits = fluid.layers.scale(
            fluid.layers.matmul(norm(h, "norm"), embed, transpose_y=True,
                                out_dtype="float32"),
            scale=1.0 / float(logits_scaling))
        return logits, []

    return _hybrid_model(
        parts, stack, lambda: build_granite_hybrid_model(**kwargs), vocab=V,
        hidden=H, slots=S, max_len=L, block_size=BS, num_blocks=NB,
        chunk_tokens=C, kv_heads=NKV, sm_scale=float(attention_multiplier),
        eos_id=eos_id, name=name, version=version, count_names=())


def yarn_frequencies(dim, theta, factor, beta_fast, beta_slow,
                     original_max_position_embeddings):
    """The ``dim / 2`` rotary frequencies of a YaRN-scaled base: ``theta_j
    = theta^(-2 j / dim)`` below the correction range, ``theta_j / factor``
    past it, a linear blend across it. The range is where a frequency
    turns ``beta_fast`` (its low end, floored) to ``beta_slow`` (its high
    end, ceiled) times over the original context:
    ``dim ln(original / (2 pi beta)) / (2 ln theta)``, clipped to the
    table."""
    half = int(dim) // 2

    def turns(beta):
        return (dim * math.log(original_max_position_embeddings
                               / (2 * math.pi * beta))
                / (2 * math.log(theta)))

    low = max(math.floor(turns(beta_fast)), 0)
    high = min(math.ceil(turns(beta_slow)), dim - 1)
    span = max(high - low, 0.001)
    out = []
    for j in range(half):
        base = float(theta) ** (-2.0 * j / dim)
        ramp = min(max((j - low) / span, 0.0), 1.0)
        out.append((1.0 - ramp) * base + ramp * base / float(factor))
    return out


def build_latent_moe_model(
        vocab_size, hidden_size, num_hidden_layers, *, num_attention_heads,
        q_lora_rank, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
        v_head_dim, rope_parameters, n_routed_experts, router_experts,
        num_experts_per_tok, moe_intermediate_size, n_shared_experts=1,
        routed_scaling_factor=1.0, norm_topk_prob=True, rope_interleave=True,
        rms_norm_eps=1e-6, initializer_range=0.02, expert_rank=0,
        dtype="bfloat16", slots=4, max_len=64, block_size=16,
        num_blocks=None, chunk_tokens=16, eos_id=None, name="mistral4",
        version="1"):
    """Build the ``mistral4`` latent-attention decoder as a paged
    DecodeModel (module docstring). The sizes are the published
    ``config.json``'s keys under their own names (``rope_parameters`` the
    whole group: ``rope_theta``, ``factor``, ``beta_fast``, ``beta_slow``,
    ``original_max_position_embeddings``, ``mscale``, ``mscale_all_dim``,
    ``llama_4_scaling_beta``); ``n_routed_experts`` is how many experts are
    HELD here and ``router_experts`` how many the router scores;
    ``vocab_size`` the rows of the vocabulary held here. ``num_blocks`` may
    be fewer than ``slots`` sequences of ``max_len`` need (admission by
    reservation). ``initializer_range`` as ``build_lfm2_model``'s (the
    query passes an RMSNorm before ``q_b`` and the rotary key none, so one
    range gives scores that peak: no range of their own as
    ``build_granite_hybrid_model``'s)."""
    kwargs = dict(locals())
    import paddle_tpu as fluid
    from paddle_tpu.initializer import ConstantInitializer

    rp = dict(rope_parameters)
    if float(rp.get("mscale", 1.0)) != float(rp.get("mscale_all_dim", 1.0)):
        raise ValueError(
            f"rope_parameters mscale {rp.get('mscale')} != mscale_all_dim "
            f"{rp.get('mscale_all_dim')}: the sines and cosines would be "
            "scaled by their ratio, which is not built")
    V, H, NL = int(vocab_size), int(hidden_size), int(num_hidden_layers)
    S, L, BS, NB, C = _geometry(slots, max_len, block_size, num_blocks,
                                chunk_tokens)
    R = NB * BS
    NH, QR, KVR = (int(num_attention_heads), int(q_lora_rank),
                   int(kv_lora_rank))
    DN, DR, DV = (int(qk_nope_head_dim), int(qk_rope_head_dim),
                  int(v_head_dim))
    W = -(-(KVR + DR) // 128) * 128             # an arena row, whole tiles
    F, FS = (int(moe_intermediate_size),
             int(moe_intermediate_size) * int(n_shared_experts))
    held, router = int(n_routed_experts), int(router_experts)
    offset = _held(expert_rank, held, router)
    freqs = yarn_frequencies(
        DR, float(rp["rope_theta"]), float(rp["factor"]),
        float(rp["beta_fast"]), float(rp["beta_slow"]),
        int(rp["original_max_position_embeddings"]))
    m = 0.1 * float(rp.get("mscale_all_dim", 1.0)) * math.log(
        float(rp["factor"])) + 1.0
    sm_scale = m * m / math.sqrt(DN + DR)
    beta = float(rp.get("llama_4_scaling_beta", 0.0))
    period = int(rp["original_max_position_embeddings"])
    prefix = f"{name}_v{version}"
    # two sub-layers a layer write into the residual
    std = float(initializer_range)
    parts = _Parts(prefix, dtype, float(rms_norm_eps), std,
                   std / math.sqrt(2 * NL), R, W, list(range(NL)), [],
                   latent=True)
    attr, matrix, proj, norm = (parts.attr, parts.matrix, parts.proj,
                                parts.norm)
    grouped_name = f"{prefix}.grouped_counts"

    def rotated(t, positions, out_dtype):
        return fluid.layers.rotary_embedding(
            t, positions, freqs=freqs, interleaved=bool(rope_interleave),
            out_dtype=out_dtype)

    def stack(program, toks, positions, wrows, mode, attend, slot=None):
        """The ``NL`` layers over ``toks``: latent attention, then the
        routed experts beside the shared one."""
        h = fluid.layers.cast(fluid.layers.embedding(
            toks, size=(V, H), dtype=dtype,
            param_attr=matrix("embed")), "float32")
        lead = [int(d) for d in toks.shape[:2]]
        counts, pairs = [], []
        # which tokens the router routes: a write row under ``R``, but for a
        # CHUNK its span's real positions (``c - real < 0``): a position
        # whose row a shared block already holds (the last token of a prompt
        # served before: this model has no per-slot state, so the pool
        # shares its full blocks) writes nowhere and is still a token
        routes, under = wrows, R
        if mode == "chunk":
            real = fluid.layers.slice(
                program.global_block().var(DecodeModel.CHU_SPAN), [0], [1],
                [2])
            routes, under = fluid.layers.elementwise_sub(
                fluid.layers.cumsum(fluid.layers.fill_constant(
                    [lead[1]], "int32", 1), exclusive=True), real), 0
        for i in range(NL):
            x = norm(h, f"l{i}.input_layernorm")
            cq = norm(proj(x, QR, f"l{i}.q_a", out_dtype="float32"),
                      f"l{i}.q_a_layernorm")
            q = fluid.layers.position_log_scale(
                fluid.layers.reshape(
                    proj(cq, NH * (DN + DR), f"l{i}.q_b",
                         out_dtype="float32"),
                    lead + [NH, DN + DR]), positions, beta, period)
            qn, qr = fluid.layers.split(q, [DN, DR], dim=-1)
            q = fluid.layers.cast(fluid.layers.reshape(
                fluid.layers.concat(
                    [qn, rotated(qr, positions, "float32")], axis=-1),
                lead + [NH * (DN + DR)]), dtype)
            c, kr = fluid.layers.split(
                proj(x, KVR + DR, f"l{i}.kv_a", out_dtype="float32"),
                [KVR, DR], dim=-1)
            kr = fluid.layers.reshape(
                rotated(fluid.layers.reshape(kr, lead + [1, DR]), positions,
                        dtype), lead + [DR])
            row = fluid.layers.pad(
                fluid.layers.concat(
                    [norm(c, f"l{i}.kv_a_layernorm"), kr], axis=-1),
                [0, 0, 0, 0, 0, W - KVR - DR])
            ctx = attend(i, q, row, {"w_uk": matrix(f"l{i}.kv_b_k"),
                                     "w_uv": matrix(f"l{i}.kv_b_v")})
            h = fluid.layers.elementwise_add(h, proj(
                ctx, H, f"l{i}.o", residual=True, out_dtype="float32"))
            x = norm(h, f"l{i}.post_attention_layernorm")
            routed, n, g = fluid.layers.moe_routed_experts(
                x, routes, under, router, held, F, int(num_experts_per_tok),
                {"gate": matrix(f"l{i}.gate"),
                 # this router's choice is its scores' own
                 "select_bias": attr(f"l{i}.select_bias",
                                     ConstantInitializer(0.0)),
                 "w_gate": matrix(f"l{i}.w1"),
                 "w_up": matrix(f"l{i}.w3"),
                 "w_down": matrix(f"l{i}.w2", residual=True)},
                expert_offset=offset,
                score_scale=float(routed_scaling_factor),
                normalize=bool(norm_topk_prob), kernel=mode == "step",
                score="softmax", group_counts=True)
            counts.append(n)
            pairs.append(g)
            gated = fluid.layers.elementwise_mul(
                proj(x, FS, f"l{i}.shared_gate", act="silu",
                     out_dtype="float32"),
                proj(x, FS, f"l{i}.shared_up", out_dtype="float32"))
            shared = proj(fluid.layers.cast(gated, dtype), H,
                          f"l{i}.shared_down", residual=True,
                          out_dtype="float32")
            h = fluid.layers.elementwise_add(
                h, fluid.layers.elementwise_add(routed, shared))
        logits = proj(norm(h, "norm"), V, "head", out_dtype="float32")
        # what the chunks' routed layers multiplied is summed on the device
        # (a chunk is a launch and no fetch); a step hands the sums over
        # beside its own counts and zeroes them
        total = _state_var(program, parts.startup, grouped_name,
                           [len(GROUPED_COUNTS)], dtype="int32")
        if mode == "chunk":
            fluid.layers.assign(fluid.layers.sums([total] + pairs),
                                output=total)
            return logits, []
        both = fluid.layers.concat([fluid.layers.sums(counts), total],
                                   axis=0)
        fluid.layers.assign(
            fluid.layers.fill_constant([len(GROUPED_COUNTS)], "int32", 0),
            output=total)
        return logits, [both]

    return _hybrid_model(
        parts, stack, lambda: build_latent_moe_model(**kwargs), vocab=V,
        hidden=H, slots=S, max_len=L, block_size=BS, num_blocks=NB,
        chunk_tokens=C, kv_heads=1, sm_scale=sm_scale, eos_id=eos_id,
        name=name, version=version,
        count_names=MOE_COUNTS + GROUPED_COUNTS,
        latent={"heads": NH, "nope": DN, "rope": DR, "value": DV,
                "latent": KVR})


def build_afmoe_model(
        vocab_size, hidden_size, layer_types, *, num_attention_heads,
        num_key_value_heads, head_dim, intermediate_size, num_dense_layers,
        num_experts, router_experts, num_experts_per_tok,
        moe_intermediate_size, sliding_window, num_shared_experts=1,
        route_scale=1.0, route_norm=True, mup_enabled=True,
        rms_norm_eps=1e-5, rope_theta=10000.0, initializer_range=0.02,
        expert_rank=0, dtype="bfloat16", slots=4,
        max_len=64, block_size=16, num_blocks=None, window_num_blocks=None,
        chunk_tokens=16, eos_id=None, name="afmoe", version="1"):
    """Build the ``afmoe`` decoder as a paged DecodeModel (module
    docstring). The sizes are the published ``config.json``'s keys under
    their own names; ``num_experts`` is how many experts are HELD here and
    ``router_experts`` how many the router scores (the published count);
    ``vocab_size`` the rows of the vocabulary held here. ``num_blocks`` is
    the FULL layers' pool and ``window_num_blocks`` the sliding layers'
    (default: every slot's ``max_len``, and what a slot can hold at once
    in its window: nothing can then run out); either may be smaller, and
    the engine admits a request against what it needs in both.
    ``initializer_range`` as ``build_lfm2_model``'s."""
    kwargs = dict(locals())
    import paddle_tpu as fluid
    from paddle_tpu.initializer import NormalInitializer

    V, H = int(vocab_size), int(hidden_size)
    kinds = [str(kind) for kind in layer_types]
    if set(kinds) - {"sliding_attention", "full_attention"}:
        raise ValueError(f"layer_types {kinds}: a layer's attention is "
                         "sliding_attention or full_attention")
    S, L, BS, NB, C = _geometry(slots, max_len, block_size, num_blocks,
                                chunk_tokens)
    R = NB * BS
    NQ, NKV, D = (int(num_attention_heads), int(num_key_value_heads),
                  int(head_dim))
    W, dense = int(sliding_window), int(num_dense_layers)
    F, FS = (int(moe_intermediate_size),
             int(moe_intermediate_size) * int(num_shared_experts))
    held, router = int(num_experts), int(router_experts)
    offset = _held(expert_rank, held, router)
    sliding = [i for i, kind in enumerate(kinds)
               if kind == "sliding_attention"]
    WNB = int(window_num_blocks) if window_num_blocks else (
        S * window_chunk_blocks(W, C, BS, -(-L // BS)))
    prefix = f"{name}_v{version}"
    # two sub-layers a layer write into the residual
    std = float(initializer_range)
    parts = _Parts(
        prefix, dtype, float(rms_norm_eps), std,
        std / math.sqrt(2 * len(kinds)), R, NKV * D,
        [i for i, kind in enumerate(kinds) if kind == "full_attention"], [],
        windows=[("sliding", sliding, WNB, W)] if sliding else [],
        block_size=BS)
    attr, matrix, proj, norm = (parts.attr, parts.matrix, parts.proj,
                                parts.norm)
    grouped_name = f"{prefix}.grouped_counts"

    def swiglu(x, width, gate, up, down):
        gated = fluid.layers.elementwise_mul(
            proj(x, width, gate, act="silu", out_dtype="float32"),
            proj(x, width, up, out_dtype="float32"))
        return proj(fluid.layers.cast(gated, dtype), H, down, residual=True,
                    out_dtype="float32")

    def stack(program, toks, positions, wrows, mode, attend, slot=None):
        """The layers over ``toks``: gated attention, then the
        feed-forward, each between two norms."""
        h = fluid.layers.cast(fluid.layers.embedding(
            toks, size=(V, H), dtype=dtype,
            param_attr=matrix("embed")), "float32")
        if mup_enabled:
            h = fluid.layers.scale(h, scale=math.sqrt(H))
        counts, pairs = [], []
        for i, kind in enumerate(kinds):
            x = norm(h, f"l{i}.input_layernorm")
            theta = rope_theta if kind == "sliding_attention" else None
            ctx = attend(
                i,
                parts.normed_rotated_heads(
                    proj(x, NQ * D, f"l{i}.q", out_dtype="float32"), NQ,
                    positions, f"l{i}.q_norm", theta),
                parts.normed_rotated_heads(
                    proj(x, NKV * D, f"l{i}.k", out_dtype="float32"), NKV,
                    positions, f"l{i}.k_norm", theta),
                proj(x, NKV * D, f"l{i}.v"))
            gated = fluid.layers.elementwise_mul(
                fluid.layers.cast(ctx, "float32"),
                proj(x, NQ * D, f"l{i}.gate_proj", act="sigmoid",
                     out_dtype="float32"))
            out = proj(fluid.layers.cast(gated, dtype), H, f"l{i}.o",
                       residual=True, out_dtype="float32")
            h = fluid.layers.elementwise_add(
                h, norm(out, f"l{i}.post_attention_layernorm",
                        out_dtype="float32"))
            x = norm(h, f"l{i}.pre_mlp_layernorm")
            if i < dense:
                out = swiglu(x, int(intermediate_size), f"l{i}.gate",
                             f"l{i}.up", f"l{i}.down")
            else:
                routed, n, g = fluid.layers.moe_routed_experts(
                    x, wrows, R, router, held, F, int(num_experts_per_tok),
                    {"gate": matrix(f"l{i}.router"),
                     "select_bias": attr(f"l{i}.expert_bias",
                                         NormalInitializer(0.0, 0.05)),
                     "w_gate": matrix(f"l{i}.w1"),
                     "w_up": matrix(f"l{i}.w3"),
                     "w_down": matrix(f"l{i}.w2", residual=True)},
                    expert_offset=offset, score_scale=float(route_scale),
                    normalize=bool(route_norm), kernel=mode == "step",
                    group_counts=True)
                counts.append(n)
                pairs.append(g)
                out = fluid.layers.elementwise_add(routed, swiglu(
                    x, FS, f"l{i}.shared_gate", f"l{i}.shared_up",
                    f"l{i}.shared_down"))
            h = fluid.layers.elementwise_add(
                h, norm(out, f"l{i}.post_mlp_layernorm",
                        out_dtype="float32"))
        logits = proj(norm(h, "norm"), V, "head", out_dtype="float32")
        if not counts:
            return logits, []
        # the chunks' routed counts are summed on the device and handed
        # over, and zeroed, by the next step (``build_latent_moe_model``)
        total = _state_var(program, parts.startup, grouped_name,
                           [len(GROUPED_COUNTS)], dtype="int32")
        if mode == "chunk":
            fluid.layers.assign(fluid.layers.sums([total] + pairs),
                                output=total)
            return logits, []
        both = fluid.layers.concat(
            [fluid.layers.sums(counts) if len(counts) > 1 else counts[0],
             total], axis=0)
        fluid.layers.assign(
            fluid.layers.fill_constant([len(GROUPED_COUNTS)], "int32", 0),
            output=total)
        return logits, [both]

    return _hybrid_model(
        parts, stack, lambda: build_afmoe_model(**kwargs), vocab=V, hidden=H,
        slots=S, max_len=L, block_size=BS, num_blocks=NB, chunk_tokens=C,
        kv_heads=NKV, sm_scale=1.0 / math.sqrt(D), eos_id=eos_id, name=name,
        version=version,
        count_names=(MOE_COUNTS + GROUPED_COUNTS
                     if len(kinds) > dense else ()))
