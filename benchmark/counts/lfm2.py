"""The ``lfm2_moe`` decode step (``serving/decode/hybrid.py
build_lfm2_model``) by what its kernels and its stepped tokens REQUIRE. The
work follows the traffic (which experts the tokens chose, how many slots
stepped, how long they are), so every size is a counter's movement over the
traced stretch, and each function gives ALL the calls of that stretch
together."""


def expert_calls(touched_experts, held_assignments, hidden, ffn,
                 bytes_per_el):
    """(operations, bytes) of the ``moe_experts`` kernel's calls over GATED
    experts. ``touched_experts`` is the held experts that at least one token
    of a step chose, summed over expert layers and steps: each is read once,
    its gate, its up and its down matrix, ``hidden x ffn`` elements each,
    however many tokens it sees. ``held_assignments`` is the (token, held
    expert) pairs: each is three products of ``2 x hidden x ffn``
    operations. The tokens, the weights' column and the output are not
    counted."""
    return (held_assignments * 3 * 2 * hidden * ffn,
            touched_experts * 3 * hidden * ffn * bytes_per_el)


def attention_calls(live_blocks, block_size, kv_heads, query_heads, head_dim,
                    layers, bytes_per_el):
    """(operations, bytes) of the grouped-query ``paged_attention``
    kernel's calls. ``live_blocks`` is the K/V blocks that hold the stepping
    slots' positions up to their cursors, summed over the stretch's steps
    (counted once a step, not once a layer); every attention layer's call
    reads those blocks of its K and of its V arena, ``block_size`` rows of
    ``kv_heads x head_dim`` elements, and does q.k^T and p.v for
    ``query_heads`` heads over their positions. The products the kernel
    makes over its neighbours' zeroed lanes (two heads a lane tile) are not
    required and not counted."""
    positions = live_blocks * block_size
    return (layers * 2 * 2 * positions * query_heads * head_dim,
            layers * 2 * positions * kv_heads * head_dim * bytes_per_el)


def stepped_tokens(slot_steps, live_blocks, held_assignments, block_size,
                   hidden, vocab, conv_layers, attention_layers,
                   dense_layers, expert_layers, taps, query_heads, kv_heads,
                   head_dim, dense_ffn, router_experts, ffn):
    """(operations, bytes) that the decode steps of a stretch REQUIRE
    through this chip's share: ``slot_steps`` tokens were stepped, each
    through every conv layer's two projections, gates and taps, every
    attention layer's four projections, every dense layer's three matrices,
    every expert layer's router and the tied head over the vocabulary;
    attention's two products run over ``live_blocks`` blocks; the routed
    experts' three products are counted by assignment (``held_assignments``:
    the pairs that landed on an expert held here), not by token. Idle
    slots' work is not required and not counted. Bytes: not reckoned, so
    0."""
    conv = 2 * hidden * 3 * hidden + 2 * hidden * hidden \
        + (2 * taps + 2) * hidden
    q_width, kv_width = query_heads * head_dim, kv_heads * head_dim
    attention = 2 * hidden * (q_width + 2 * kv_width) + 2 * q_width * hidden
    per_token = (conv_layers * conv + attention_layers * attention
                 + dense_layers * 3 * 2 * hidden * dense_ffn
                 + expert_layers * 2 * hidden * router_experts
                 + 2 * hidden * vocab)
    over_positions = (attention_layers * 2 * 2 * live_blocks * block_size
                      * q_width)
    routed = held_assignments * 3 * 2 * hidden * ffn
    return slot_steps * per_token + over_positions + routed, 0
