"""Operations and bytes a step or a kernel REQUIRES, from shapes alone.

One multiply-add is two operations. Recomputed work does not count: the
compiled BERT step runs each layer's flash forward twice (PERF.md), and is
charged for one. ``train_mfu`` and the roofline shares divide these by
measured time; a PR that claims a gain cannot change them. Each function is
checked against a count worked out by hand at a tiny size in the benchmark's
test file.
"""


def _dense(rows, n_in, n_out):
    return 2 * rows * n_in * n_out


def bert_pretrain_forward(model, batch, seq_len, max_predictions):
    """Forward operations of one BERT pretraining step on ``batch``
    sequences: the matrix multiplications of the encoder, attention's two
    batched products, the MLM head on the ``max_predictions`` gathered
    positions and the pooler + NSP head. Embedding lookups, layer norms,
    softmax and activations are not counted."""
    h, f = model["hidden_size"], model["intermediate_size"]
    layers, vocab = model["num_hidden_layers"], model["vocab_size"]
    tokens = batch * seq_len
    per_layer = (
        4 * _dense(tokens, h, h)            # q, k, v, output projections
        + 2 * 2 * batch * seq_len * seq_len * h   # q.k^T and p.v, all heads
        + 2 * _dense(tokens, h, f)          # the two FFN matmuls
    )
    mlm = (_dense(batch * max_predictions, h, h)
           + _dense(batch * max_predictions, h, vocab))
    nsp = _dense(batch, h, h) + _dense(batch, h, 2)
    return layers * per_layer + mlm + nsp


def bert_pretrain_step(model, settings, traffic):
    """Forward + backward of one step: the backward of a matrix
    multiplication is two of the same size, so three times the forward."""
    return 3 * bert_pretrain_forward(
        model, traffic["batch"], traffic["seq_len"],
        settings["max_predictions_per_seq"])


_RESNET_STAGES = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}


def resnet_forward_per_image(model):
    """Forward operations of one image through ResNet-v1 with bottleneck
    blocks (He et al., table 1): every convolution and the classifier,
    2 * k*k * c_in * c_out * h_out * w_out each; stride 2 sits on the 3x3
    of a stage's first block and on its 1x1 shortcut, as
    ``models/resnet.py`` builds it. Batch norm, relu and pooling are not
    counted."""
    blocks = _RESNET_STAGES[model["depth"]]
    _c, height, width = model["image_shape"]

    def conv(k, c_in, c_out, h, w):
        return 2 * k * k * c_in * c_out * h * w

    h, w = height // 2, width // 2
    total = conv(7, 3, 64, h, w)
    h, w = h // 2, w // 2                      # 3x3 max pool, stride 2
    c_in = 64
    for stage, n in enumerate(blocks):
        mid = 64 * 2 ** stage
        out = 4 * mid
        for b in range(n):
            stride = 2 if (b == 0 and stage > 0) else 1
            total += conv(1, c_in, mid, h, w)
            h2, w2 = h // stride, w // stride
            total += conv(3, mid, mid, h2, w2)
            total += conv(1, mid, out, h2, w2)
            if b == 0:
                total += conv(1, c_in, out, h2, w2)   # projection shortcut
            c_in, h, w = out, h2, w2
    return total + 2 * c_in * model["class_dim"]


def resnet_train_step(model, settings, traffic):
    return 3 * traffic["batch"] * resnet_forward_per_image(model)


def flash_forward(batch_heads, seq_q, seq_k, head_dim, bytes_per_el):
    """One flash-attention forward call on ``[batch_heads, seq, head_dim]``
    operands: q.k^T and p.v; reads q, k, v and writes the output once."""
    ops = 2 * 2 * batch_heads * seq_q * seq_k * head_dim
    moved = bytes_per_el * batch_heads * head_dim * (2 * seq_q + 2 * seq_k)
    return ops, moved


def flash_backward_dkdv(batch_heads, seq_q, seq_k, head_dim, bytes_per_el):
    """The dk/dv kernel: recomputes s = q.k^T, then dv = p^T.do,
    dp = do.v^T and dk = ds^T.q: four products. Reads q, k, v, do; writes
    dk, dv."""
    ops = 4 * 2 * batch_heads * seq_q * seq_k * head_dim
    moved = bytes_per_el * batch_heads * head_dim * (2 * seq_q + 4 * seq_k)
    return ops, moved


def flash_backward_dq(batch_heads, seq_q, seq_k, head_dim, bytes_per_el):
    """The dq kernel: recomputes s, then dp = do.v^T and dq = ds.k: three
    products. Reads q, k, v, do; writes dq."""
    ops = 3 * 2 * batch_heads * seq_q * seq_k * head_dim
    moved = bytes_per_el * batch_heads * head_dim * (3 * seq_q + 2 * seq_k)
    return ops, moved


def decode_step(model):
    """(operations, bytes) of ONE decode step of the paged single-head
    decoder over all ``slots``: per layer the q, k, v, output and two FFN
    products for one token per slot, and attention over ``max_len`` gathered
    positions; then the logits head. Bytes: every weight read once, and per
    layer the K and V rows of every slot's ``max_len`` positions read once
    (the least a gather of the whole context can move), in float32."""
    h, ffn = model["hidden"], model.get("ffn_dim") or 4 * model["hidden"]
    layers, vocab = model["num_layers"], model["vocab_size"]
    slots, length = model["slots"], model["max_len"]
    per_layer_ops = (4 * _dense(slots, h, h) + 2 * _dense(slots, h, ffn)
                     + 2 * 2 * slots * length * h)
    ops = layers * per_layer_ops + _dense(slots, h, vocab)
    weights = layers * (4 * h * h + 2 * h * ffn) + h * vocab
    kv = layers * 2 * slots * length * h
    return ops, 4 * (weights + kv)
