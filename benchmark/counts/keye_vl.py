"""The ``keye_vl`` model (``serving/decode/hybrid.py build_keye_vl_model``:
``sdar_moe``'s stack a token a step with an indexer a layer) by what its
kernels and its served tokens REQUIRE. The work follows the traffic (how
long the sequences are, how the router routed), so every size is a counter's
movement over the traced stretch, and each function gives ALL the calls of
that stretch together. The program counts the indexer's rows a LAYER
(``serving_sparse_rows_selected_*``, ``serving_index_rows_scanned_*``); a
chunk's context rows and tokens are counted once a launch, so ``layers``
multiplies them here."""


def index_score_calls(step_rows, chunk_pairs, context_rows, chunk_tokens,
                      layers, heads, width, bytes_per_el):
    """(operations, bytes) of the ``index_scores`` calls. ``step_rows`` is
    the index keys the stepping slots scored and ``chunk_pairs`` the
    (query, key) pairs the prompt chunks scored, both summed over the
    layers: a pair costs ``heads`` products over ``width`` lanes, a ReLU, a
    weight and a sum. A step has to read each of its rows' keys once
    (``width`` elements), a chunk each of the rows under its last horizon
    once a layer (``context_rows + chunk_tokens`` a launch). The 64 lanes
    of zeros a key is padded with, the queries and the scores written are
    not required and not counted."""
    pairs = step_rows + chunk_pairs
    rows = step_rows + layers * (context_rows + chunk_tokens)
    return (pairs * heads * (2 * width + 3), rows * width * bytes_per_el)


def index_select_calls(step_rows, chunk_pairs):
    """(operations, bytes) of the ``index_select`` calls: every score
    scored (``index_score_calls``'s pairs) has to be read once (4 bytes)
    and its place in the mask written (1 byte), and compared once. The
    counting passes of the bisection are the kernel's way and not
    required."""
    pairs = step_rows + chunk_pairs
    return (pairs, 5 * pairs)


def masked_chunk_calls(selected_pairs, context_rows, chunk_tokens, layers,
                       kv_heads, query_heads, head_dim, bytes_per_el):
    """(operations, bytes) of the masked chunk kernel's calls.
    ``selected_pairs`` is the (query, row) pairs the selection opens, a
    query at position p ``min(topk, p + 1)`` of them, summed over the
    layers: a pair costs q.k^T and p.v for ``query_heads`` heads of
    ``head_dim``. A launch has to read the rows under its last horizon once
    from K and once from V a layer (``kv_heads x head_dim`` elements each;
    a row no query of the chunk kept is counted all the same: which rows
    those are is known only to the mask). The pairs the mask closes, the
    mask's own bytes, the queries and the output are not counted."""
    return (2 * 2 * selected_pairs * query_heads * head_dim,
            layers * 2 * (context_rows + chunk_tokens) * kv_heads * head_dim
            * bytes_per_el)


def sparse_step_calls(selected_rows, kv_heads, query_heads, head_dim,
                      bytes_per_el):
    """(operations, bytes) of the step's ``paged_attention`` calls by what
    the MODEL requires: ``selected_rows`` is the rows the stepping slots'
    queries are let attend to, ``min(topk, length)`` a slot, summed over
    the layers; each has to be read once from K and once from V and costs
    q.k^T and p.v for ``query_heads`` heads. The kernel reads every live
    block and masks (kernels/sparse.py, "Both forms are masks"): the rows
    it reads beyond the selected ones are not required and not counted, so
    this share says how far the mask form is from the row form's bytes."""
    return (2 * 2 * selected_rows * query_heads * head_dim,
            2 * selected_rows * kv_heads * head_dim * bytes_per_el)


def served_tokens(slot_steps, chunk_tokens, step_selected, chunk_selected,
                  step_scanned, chunk_scanned, step_pairs, chunk_pairs,
                  hidden, vocab, layers, query_heads, kv_heads, head_dim,
                  index_heads, index_width, router_experts, ffn):
    """(operations, bytes) that the decode steps AND the prompt chunks of a
    stretch REQUIRE through this chip's share: ``slot_steps`` tokens were
    stepped and ``chunk_tokens`` prompt positions prefilled, each through
    every layer's attention projections (q, k, v, the output), the
    indexer's three (index queries, the one key, the weights) and the
    router over ``router_experts``; the indexer's scores over the pairs it
    scanned (``step_scanned`` + ``chunk_scanned``, summed over the layers),
    attention's two products over the pairs it selected, the routed (token,
    held expert) pairs (``step_pairs`` + ``chunk_pairs``, summed over the
    layers by the program) through an expert's three matrices; a stepped
    token also through the head over ``vocab`` rows. Idle slots' work, a
    chunk's padding, the pairs the mask closes and the absent experts'
    share are not required and not counted. Bytes: not reckoned, so 0."""
    q_width, kv_width = query_heads * head_dim, kv_heads * head_dim
    per_token = layers * (
        2 * hidden * (q_width + 2 * kv_width) + 2 * q_width * hidden
        + 2 * hidden * (index_heads * index_width + index_width + index_heads)
        + 2 * hidden * router_experts)
    scored = (step_scanned + chunk_scanned) * index_heads * (
        2 * index_width + 3)
    attended = 2 * 2 * q_width * (step_selected + chunk_selected)
    routed = (step_pairs + chunk_pairs) * 3 * 2 * hidden * ffn
    return ((slot_steps + chunk_tokens) * per_token
            + slot_steps * 2 * hidden * vocab + scored + attended + routed,
            0)
