"""The ``afmoe`` model (``serving/decode/hybrid.py build_afmoe_model``:
sliding-window and full attention layers in one stack) by what its kernels
and its served tokens REQUIRE. The work follows the traffic (how long the
sequences are, where their windows stand, how the router routed), so every
size is a counter's movement over the traced stretch, and each function
gives ALL the calls of that stretch together."""


def windowed_chunk_calls(window_pairs, window_rows, kv_heads, query_heads,
                         head_dim, layers, bytes_per_el):
    """(operations, bytes) of the chunk kernel's calls under a window.
    ``window_pairs`` is the (query, row) pairs the window's mask opens, a
    query at position p ``min(window, p + 1)`` of them, and ``window_rows``
    the rows from a chunk's FIRST query's lower edge to its last position
    (``window + chunk - 1`` at most), both summed over the chunk launches
    and counted once a launch, not once a layer. A pair costs q.k^T and p.v
    for ``query_heads`` heads of ``head_dim``; a row has to be read once
    from K and once from V, ``kv_heads x head_dim`` elements each. What the
    kernel reads beyond that (a copy tile's rows under the edge, a tile
    read again by the next tile of queries) is not required and not
    counted; nor are the queries and the output."""
    return (layers * 2 * 2 * window_pairs * query_heads * head_dim,
            layers * 2 * window_rows * kv_heads * head_dim * bytes_per_el)


def step_attention_calls(window_rows, live_blocks, block_size, kv_heads,
                         query_heads, head_dim, window_layers, full_layers,
                         bytes_per_el):
    """(operations, bytes) of the step's ``paged_attention`` calls over
    BOTH groups of layers. ``live_blocks`` is the blocks that hold the
    stepping slots' positions up to their cursors (the full layers read
    them all), ``window_rows`` the rows from a slot's first LIVE block of
    the window group to its cursor (``window + block_size - 1`` at most: the
    kernel cannot read less than a block), both summed over the stretch's
    steps and counted once a step, not once a layer. Every layer's call
    reads its rows of K and of V, ``kv_heads x head_dim`` elements, and
    does q.k^T and p.v for ``query_heads`` heads over them."""
    rows = (full_layers * live_blocks * block_size
            + window_layers * window_rows)
    return (2 * 2 * rows * query_heads * head_dim,
            2 * rows * kv_heads * head_dim * bytes_per_el)


def served_tokens(slot_steps, live_blocks, step_window_rows, chunk_tokens,
                  attended_rows, chunk_window_pairs, step_pairs, chunk_pairs,
                  block_size, hidden, vocab, query_heads, kv_heads, head_dim,
                  window_layers, full_layers, dense_layers, dense_ffn,
                  router_experts, ffn, shared_experts):
    """(operations, bytes) that the decode steps AND the prompt chunks of a
    stretch REQUIRE through this chip's share: ``slot_steps`` tokens were
    stepped and ``chunk_tokens`` prompt positions prefilled, each through
    every layer's attention projections (q, its gate, k, v, the output),
    the dense layers' three matrices, and in the others the router over
    ``router_experts``, the shared expert and its routed pairs
    (``step_pairs`` and ``chunk_pairs``: the (token, held expert) pairs,
    summed over the layers by the program); a stepped token also through
    the head over ``vocab`` rows. Attention's two products run, in the full
    layers, over ``live_blocks`` blocks for the steps and ``attended_rows``
    pairs for the chunks, in the window layers over ``step_window_rows``
    and ``chunk_window_pairs``. Idle slots' work, a chunk's padding and the
    absent experts' share are not required and not counted. Bytes: not
    reckoned, so 0."""
    layers = window_layers + full_layers
    q_width, kv_width = query_heads * head_dim, kv_heads * head_dim
    attention = 2 * hidden * (2 * q_width + 2 * kv_width) + 2 * q_width * hidden
    per_token = (layers * attention + dense_layers * 3 * 2 * hidden * dense_ffn
                 + (layers - dense_layers) * (
                     2 * hidden * router_experts
                     + shared_experts * 3 * 2 * hidden * ffn))
    over_rows = 2 * 2 * q_width * (
        full_layers * (live_blocks * block_size + attended_rows)
        + window_layers * (step_window_rows + chunk_window_pairs))
    routed = (step_pairs + chunk_pairs) * 3 * 2 * hidden * ffn
    return ((slot_steps + chunk_tokens) * per_token
            + slot_steps * 2 * hidden * vocab + over_rows + routed, 0)
