"""The ``ouro`` decode step (``serving/decode/hybrid.py build_ouro_model``:
one stack of layers run ``passes`` times a token, K/V rows per (pass,
layer)) by what its attention kernel and its stepped tokens REQUIRE. The
work follows the traffic (how many slots stepped, how long they are), so
every size is a counter's movement over the traced stretch, and each
function gives ALL the calls of that stretch together."""


def attention_calls(live_blocks, block_size, heads, head_dim, layers,
                    passes, bytes_per_el):
    """(operations, bytes) of the ``paged_attention`` kernel's calls, one a
    (pass, layer): ``layers x passes`` calls a step. ``live_blocks`` is the
    K/V blocks that hold the stepping slots' positions up to their cursors,
    summed over the stretch's steps (counted once a step, not once a call);
    every call reads those blocks of its own K and of its own V arena,
    ``block_size`` rows of ``heads x head_dim`` elements, and does q.k^T
    and p.v for ``heads`` query heads, one to a K/V head, over their
    positions. The 15 rows of a 16-row query tile that hold no query head
    (one head a K/V head) are not required and not counted."""
    positions = live_blocks * block_size
    pairs = layers * passes
    return (pairs * 2 * 2 * positions * heads * head_dim,
            pairs * 2 * positions * heads * head_dim * bytes_per_el)


def stepped_tokens(slot_steps, live_blocks, block_size, hidden, vocab,
                   layers, passes, heads, head_dim, ffn):
    """(operations, bytes) that the decode steps of a stretch REQUIRE:
    ``slot_steps`` tokens were stepped, each through ``passes`` applications
    of every layer's four attention projections and three feed-forward
    matrices, the exit gate after each pass and the head over the
    vocabulary once; attention's two products run over ``live_blocks``
    blocks in each of the ``layers x passes`` calls. Idle slots' work is
    not required and not counted. Bytes: not reckoned, so 0."""
    width = heads * head_dim
    layer = 2 * hidden * 3 * width + 2 * width * hidden + 3 * 2 * hidden * ffn
    per_token = passes * (layers * layer + 2 * hidden) + 2 * hidden * vocab
    over_positions = layers * passes * 2 * 2 * live_blocks * block_size * width
    return slot_steps * per_token + over_positions, 0
