"""The ``sdar_moe`` block pass (``serving/decode/hybrid.py
build_sdar_model``) by what its kernels and its DECIDED tokens require. The
work follows the traffic (how many slots ran a pass, how long they are,
which experts their positions chose), so every size is a counter's movement
over the traced stretch, and each function gives ALL the calls of that
stretch together. The gated experts' kernel is counted by
``lfm2.expert_calls`` (three matrices an expert, whatever the model)."""


def attention_calls(live_blocks, block_size, kv_heads, query_heads, head_dim,
                    block_len, layers, bytes_per_el):
    """(operations, bytes) of the ``paged_attention`` kernel's calls in a
    block pass. ``live_blocks`` is the K/V blocks that hold the stepping
    slots' positions up to the END of their current block, summed over the
    stretch's passes (counted once a pass, not once a layer); every layer's
    call reads those blocks of its K and of its V arena, ``block_size``
    rows of ``kv_heads x head_dim`` elements, ONCE for all the block's
    positions, and does q.k^T and p.v for ``block_len x query_heads`` query
    rows over their positions."""
    positions = live_blocks * block_size
    return (layers * 2 * 2 * positions * block_len * query_heads * head_dim,
            layers * 2 * positions * kv_heads * head_dim * bytes_per_el)


def stepped_tokens(tokens, slot_passes, live_blocks, held_assignments,
                   block_len, block_size, hidden, vocab, layers, query_heads,
                   kv_heads, head_dim, router_experts, ffn):
    """(operations, bytes) that the tokens DECIDED in a stretch required
    through this chip's share, ONE pass of ONE position a token: the four
    attention projections, the router and the head over the vocabulary,
    attention's two products over the mean positions a slot's pass had
    live, and the routed experts' three products by the mean held
    assignments a position of a pass made. ``slot_passes`` slots ran a pass
    in the stretch, each over ``block_len`` positions, reading
    ``live_blocks`` blocks and making ``held_assignments`` (position, held
    expert) pairs in all; of that the ``tokens`` decided required one
    position's share of one pass each. What the other positions of a pass
    and the passes beyond one a token cost (a block of B costs B + 1) is
    NOT required work and not counted: the reading shows what it costs.
    Bytes: not reckoned, so 0."""
    if not slot_passes:
        return 0, 0
    q_width, kv_width = query_heads * head_dim, kv_heads * head_dim
    per_token = layers * (
        2 * hidden * (q_width + 2 * kv_width) + 2 * q_width * hidden
        + 2 * hidden * router_experts) + 2 * hidden * vocab
    positions_a_pass = live_blocks * block_size / slot_passes
    over_positions = layers * 2 * 2 * positions_a_pass * q_width
    assignments = held_assignments / (slot_passes * block_len)
    routed = assignments * 3 * 2 * hidden * ffn
    return tokens * (per_token + over_positions + routed), 0
