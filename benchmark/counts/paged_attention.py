"""The blocked paged-attention kernel of the decode step
(``kernels/attention.py paged_attention``): its work follows the sequences'
lengths, so its sizes are a counter's movement and not a shape."""


def decode_calls(live_blocks, block_size, width, layers, bytes_per_el):
    """(operations, bytes) of ALL the kernel's calls over a stretch of
    decode steps. ``live_blocks`` is the K/V blocks that hold the stepping
    slots' positions up to their cursors, summed over the stretch's steps
    (counted once a step, not once a layer); every layer's call reads those
    blocks of its own K and of its own V arena once, ``block_size`` rows of
    ``width`` elements each, and does q.k^T and p.v over their positions.
    The query, the output, the block table and the bias are not counted, and
    a block's positions past the cursor are: the kernel cannot read less
    than a block."""
    positions = live_blocks * block_size
    ops = layers * 2 * 2 * positions * width
    moved = layers * 2 * positions * width * bytes_per_el
    return ops, moved
