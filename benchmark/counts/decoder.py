"""The repo's servable decoder (``serving/decode/model.py
build_decoder_model``) by the tokens it really steps: ``flops.py
decode_step`` charges a step for every slot at the full length, which a
serving cell's steps are far from."""


def stepped_tokens(slot_steps, live_blocks, block_size, hidden, ffn_dim,
                   layers, vocab):
    """(operations, bytes) that the decode steps of a stretch REQUIRE:
    ``slot_steps`` tokens were stepped (one per active slot and step), each
    through the q, k, v, output and two FFN products of every layer and the
    logits head; attention's two products run over the positions of
    ``live_blocks`` blocks (as ``paged_attention.decode_calls`` has them).
    A step computes all its slots, stepping or not: the idle ones' work is
    not required and not counted. Bytes: not reckoned (a step reads every
    weight once however many slots step, which is no function of these
    sizes), so 0."""
    per_token = layers * (4 * 2 * hidden * hidden
                          + 2 * 2 * hidden * ffn_dim) + 2 * hidden * vocab
    attention = layers * 2 * 2 * live_blocks * block_size * hidden
    return slot_steps * per_token + attention, 0
