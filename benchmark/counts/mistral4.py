"""The ``mistral4`` latent-attention model (``serving/decode/hybrid.py
build_latent_moe_model``) by what its kernels and its served tokens
REQUIRE. The work follows the traffic (how long the sequences are, how the
router routed), so every size is a counter's movement over the traced
stretch, and each function gives ALL the calls of that stretch together."""


def latent_step_calls(live_blocks, block_size, layers, heads, latent, rope,
                      bytes_per_el):
    """(operations, bytes) of the step's latent attention kernel.
    ``live_blocks`` is the blocks that hold the stepping slots' positions
    up to their cursors, summed over the stretch's steps (counted once a
    step, not once a layer). Every layer's call has to read each of their
    rows ONCE for all the heads: a token's ``latent + rope`` elements (the
    compressed K/V and the one rotary key; the lanes a row is padded with
    are not required), and attends absorbed: a score over ``latent + rope``
    lanes and a value of ``latent`` for each of ``heads`` heads. The
    queries, the output, the block table and the bias are not counted; a
    block's positions past the cursor are (the kernel cannot read less
    than a block)."""
    positions = live_blocks * block_size
    ops = layers * positions * heads * 2 * (2 * latent + rope)
    moved = layers * positions * (latent + rope) * bytes_per_el
    return ops, moved


def latent_chunk_calls(attended_rows, context_rows, chunk_tokens, layers,
                       heads, nope, rope, value, latent, bytes_per_el):
    """(operations, bytes) of the prompt chunks' latent attention, which
    runs EXPANDED (kernels/attention.py ``latent_chunk_expanded``).
    ``attended_rows`` is the (query, row) pairs the chunks' causal masks
    opened, ``context_rows`` the rows behind the chunks and
    ``chunk_tokens`` their real positions, summed over the chunk launches
    and counted once a launch, not once a layer. A pair costs a score over
    ``nope + rope`` lanes and a value of ``value`` lanes a head, and every
    row a chunk can see is first up-projected to its heads' keys and values
    (``latent x heads x (nope + value)`` products) and has to be read once,
    ``latent + rope`` elements. (Up-projected once a CHUNK: the loops do it
    once a tile of 512 queries, and what they do beyond the count is theirs
    to answer for.)"""
    rows = context_rows + chunk_tokens
    ops = (attended_rows * heads * 2 * (nope + rope + value)
           + rows * 2 * latent * heads * (nope + value))
    return layers * ops, layers * rows * (latent + rope) * bytes_per_el


def grouped_calls(pairs, touched_experts, hidden, ffn, bytes_per_el):
    """(operations, bytes) of the chunks' grouped expert product.
    ``pairs`` is the (token, held expert) pairs the chunks' routing made
    and ``touched_experts`` the held experts with at least one pair, both
    summed over the launches AND the layers (the program counts them a
    layer). A pair goes through its expert's gate, up and down matrices,
    ``3 x 2 x hidden x ffn`` operations; a touched expert's three matrices
    have to be read once a layer of a launch. The rows an expert's group is
    padded with, the sort, the gather and the scatter are not required and
    not counted."""
    return (pairs * 3 * 2 * hidden * ffn,
            touched_experts * 3 * hidden * ffn * bytes_per_el)


def served_tokens(slot_steps, live_blocks, chunk_tokens, attended_rows,
                  step_pairs, chunk_pairs, block_size, layers, hidden, vocab,
                  heads, q_rank, latent, nope, rope, value, ffn,
                  shared_experts, router_experts):
    """(operations, bytes) that the decode steps AND the prompt chunks of a
    stretch REQUIRE: ``slot_steps`` tokens were stepped and ``chunk_tokens``
    prompt positions prefilled, each through every layer's latent
    attention projections (the query's low-rank pair, the compressed K/V
    and rotary key, the up-projection of its own latent to every head's key
    and value, the output), the router over ``router_experts``, the shared
    expert, and its routed pairs (``step_pairs`` and ``chunk_pairs``: the
    (token, held expert) pairs, summed over the layers by the program); a
    stepped token also through the head over ``vocab`` rows. Attention's
    two products run over ``live_blocks`` blocks for the steps and
    ``attended_rows`` pairs for the chunks, priced in the EXPANDED form
    (``nope + rope`` lanes a score, ``value`` a value), the cheaper one a
    pair: what the work requires, whichever form runs. Idle slots' work, a
    chunk's padding and the absent experts' share are not required and not
    counted. Bytes: not reckoned, so 0."""
    projections = 2 * (hidden * q_rank + q_rank * heads * (nope + rope)
                       + hidden * (latent + rope)
                       + latent * heads * (nope + value)
                       + heads * value * hidden)
    per_token = layers * (projections + 2 * hidden * router_experts
                          + shared_experts * 3 * 2 * hidden * ffn)
    over_rows = layers * 2 * heads * (nope + rope + value) * (
        live_blocks * block_size + attended_rows)
    routed = (step_pairs + chunk_pairs) * 3 * 2 * hidden * ffn
    return ((slot_steps + chunk_tokens) * per_token
            + slot_steps * 2 * hidden * vocab + over_rows + routed, 0)
