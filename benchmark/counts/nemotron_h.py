"""The ``nemotron_h`` decode step (``serving/decode/hybrid.py``) by what its
kernels and its stepped tokens REQUIRE. The work follows the traffic (which
experts the tokens chose, how many slots stepped, how long they are), so
every size is a counter's movement over the traced stretch, and each
function gives ALL the calls of that stretch together."""


def expert_calls(touched_experts, held_assignments, hidden, ffn,
                 bytes_per_el):
    """(operations, bytes) of the ``moe_experts`` kernel's calls.
    ``touched_experts`` is the held experts that at least one token of a
    step chose, summed over expert layers and steps: each is read once, its
    up and its down matrix, ``hidden x ffn`` elements each, however many
    tokens it sees. ``held_assignments`` is the (token, held expert) pairs:
    each is an up and a down product of ``2 x hidden x ffn`` operations.
    The tokens, the weights' column and the output are not counted."""
    return (held_assignments * 2 * 2 * hidden * ffn,
            touched_experts * 2 * hidden * ffn * bytes_per_el)


def state_updates(slot_steps, mamba_layers, heads, head_dim, state_size):
    """(operations, bytes) of the ``ssm_update`` kernel's calls.
    ``slot_steps`` is the slots stepped, summed over the steps
    (``serving_active_slot_steps_total``): each updates every Mamba
    layer's state, reading and writing one float32 state of ``heads x
    head_dim x state_size`` a layer (the decay, the outer product's add,
    the product with C and its sum: 5 operations an element). x, B, C, dt
    are not counted, nor the step a slot took in vain after its last
    token."""
    elements = slot_steps * mamba_layers * heads * head_dim * state_size
    return 5 * elements, 2 * 4 * elements


def attention_calls(live_blocks, block_size, kv_heads, query_heads, head_dim,
                    layers, bytes_per_el):
    """(operations, bytes) of the grouped-query ``paged_attention``
    kernel's calls. ``live_blocks`` as ``paged_attention.decode_calls`` has
    them (counted once a step, not once a layer); every attention layer's
    call reads those blocks of its K and of its V arena, ``block_size`` rows
    of ``kv_heads x head_dim`` elements, and does q.k^T and p.v for
    ``query_heads`` heads over their positions."""
    positions = live_blocks * block_size
    return (layers * 2 * 2 * positions * query_heads * head_dim,
            layers * 2 * positions * kv_heads * head_dim * bytes_per_el)


def stepped_tokens(slot_steps, live_blocks, held_assignments, block_size,
                   hidden, vocab, mamba_layers, attention_layers,
                   expert_layers, mamba_heads, mamba_head_dim, groups,
                   state_size, query_heads, kv_heads, head_dim,
                   router_experts, ffn, shared_ffn):
    """(operations, bytes) that the decode steps of a stretch REQUIRE
    through this chip's share: ``slot_steps`` tokens were stepped, each
    through every Mamba layer's two projections and state update, every
    attention layer's four projections, every expert layer's router and
    shared expert, and the head over the held rows of the vocabulary;
    attention's two products run over ``live_blocks`` blocks; the routed
    experts' products are counted by assignment (``held_assignments``: the
    pairs that landed on an expert held here), not by token. Idle slots'
    work is not required and not counted. Bytes: not reckoned, so 0."""
    d_inner = mamba_heads * mamba_head_dim
    in_width = 2 * d_inner + 2 * groups * state_size + mamba_heads
    mamba = 2 * hidden * in_width + 2 * d_inner * hidden \
        + 5 * d_inner * state_size
    q_width, kv_width = query_heads * head_dim, kv_heads * head_dim
    attention = 2 * hidden * (q_width + 2 * kv_width) + 2 * q_width * hidden
    experts = 2 * hidden * router_experts + 2 * 2 * hidden * shared_ffn
    per_token = (mamba_layers * mamba + attention_layers * attention
                 + expert_layers * experts + 2 * hidden * vocab)
    over_positions = (attention_layers * 2 * 2 * live_blocks * block_size
                      * q_width)
    routed = held_assignments * 2 * 2 * hidden * ffn
    return slot_steps * per_token + over_positions + routed, 0
