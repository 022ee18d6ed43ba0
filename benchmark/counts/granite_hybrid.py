"""The dense ``granitemoehybrid`` model (``serving/decode/hybrid.py
build_granite_hybrid_model``) by what its chunk kernel and its served tokens
REQUIRE. The work follows the traffic (how long the prompts are so far, how
many slots stepped), so every size is a counter's movement over the traced
stretch, and each function gives ALL the calls of that stretch together."""


def chunk_attention_calls(attended_rows, context_rows, chunk_tokens,
                          kv_heads, query_heads, head_dim, layers,
                          bytes_per_el):
    """(operations, bytes) of the ``chunk_attention`` kernel's calls.
    ``attended_rows`` is the (query, row) pairs the chunks' causal masks
    opened (``serving_chunk_attended_rows_total``: query c of a chunk that
    starts at s sees s + c + 1 rows), ``context_rows`` the rows behind the
    chunks and ``chunk_tokens`` their real positions, all summed over the
    chunk launches and counted once a launch, not once a layer. Every
    attention layer's call does q.k^T and p.v over its pairs for
    ``query_heads`` heads of ``head_dim``, and has to read each row a chunk
    can see (those behind it and its own) ONCE from its K and once from its
    V arena, ``kv_heads x head_dim`` elements a row. The queries, the
    output, the block table, the rows a tile of queries reads again and the
    products over a neighbour head's zeroed lanes are not required and not
    counted."""
    ops = layers * 2 * 2 * attended_rows * query_heads * head_dim
    moved = (layers * 2 * (context_rows + chunk_tokens) * kv_heads * head_dim
             * bytes_per_el)
    return ops, moved


def served_tokens(slot_steps, live_blocks, chunk_tokens, attended_rows,
                  block_size, hidden, vocab, mamba_layers, attention_layers,
                  mamba_heads, mamba_head_dim, groups, state_size,
                  query_heads, kv_heads, head_dim, ffn):
    """(operations, bytes) that the decode steps AND the prompt chunks of a
    stretch REQUIRE: ``slot_steps`` tokens were stepped and
    ``chunk_tokens`` prompt positions prefilled, each through every Mamba
    layer's two projections and state update (the recurrence's 5 operations
    an element of state, whichever form evaluates it), every attention
    layer's four projections and every layer's gated MLP of width ``ffn``;
    a stepped token also through the tied head over ``vocab`` rows (a
    prompt's positions need no logits but its last, which is not counted).
    Attention's two products run over ``live_blocks`` blocks for the steps
    and over ``attended_rows`` (query, row) pairs for the chunks. Idle
    slots' work and a chunk's padding are not required and not counted.
    Bytes: not reckoned, so 0."""
    d_inner = mamba_heads * mamba_head_dim
    in_width = 2 * d_inner + 2 * groups * state_size + mamba_heads
    mamba = 2 * hidden * in_width + 2 * d_inner * hidden \
        + 5 * d_inner * state_size
    q_width, kv_width = query_heads * head_dim, kv_heads * head_dim
    attention = 2 * hidden * (q_width + 2 * kv_width) + 2 * q_width * hidden
    per_token = (mamba_layers * mamba + attention_layers * attention
                 + (mamba_layers + attention_layers) * 3 * 2 * hidden * ffn)
    over_rows = attention_layers * 2 * 2 * q_width * (
        live_blocks * block_size + attended_rows)
    return ((slot_steps + chunk_tokens) * per_token
            + slot_steps * 2 * hidden * vocab + over_rows, 0)
