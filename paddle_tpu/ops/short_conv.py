"""The gated short convolution (the ``lfm2`` family's operator) between its
two projections, as one IR op.

``gated_short_conv`` takes the input projection's output (``B | C | u``,
three times the hidden size), advances the per-slot convolution tail
(``ConvState`` ``[S, K - 1, D]``: the inputs ``B * u`` of the last ``K - 1``
tokens, oldest first) and gives ``C * conv(B * u)`` that the output
projection takes: a causal depthwise convolution of ``K`` taps (``ConvW``
``[K, D]``, the last on the current token), no bias, no activation, no
other state. The tail's chunk and step code is the Mamba-2 mixer's own
(kernels/mamba.py ``conv_tail_chunk`` / ``conv_tail_step``). Two modes, as
``mamba2_mixer``'s: ``"chunk"`` (``X`` ``[1, C, 3 D]`` of the ONE slot
``Slot`` names; ``Positions`` ``[1, C]`` tells a prompt's first chunk, whose
tail starts from zeros) and ``"step"`` (``X`` ``[S, 1, 3 D]``). ``WriteRows``
marks the real tokens: one whose row is ``>= num_rows`` moves no tail.
"""

from paddle_tpu.core.registry import register_op
from paddle_tpu.ops.common import first


@register_op("gated_short_conv",
             nondiff_inputs=("Slot", "Positions", "WriteRows"))
def _gated_short_conv(ins, attrs):
    from paddle_tpu.kernels import mamba

    x, conv = first(ins, "X"), first(ins, "ConvState")
    mask = first(ins, "WriteRows").reshape(-1) < attrs["num_rows"]
    out_dtype = attrs.get("out_dtype") or x.dtype
    if attrs["mode"] == "chunk":
        y, conv = mamba.short_conv_chunk(
            x[0], first(ins, "ConvW"), conv, first(ins, "Slot")[0], mask,
            first(ins, "Positions")[0, 0] == 0, out_dtype)
        y = y[None]
    else:
        y, conv = mamba.short_conv_step(x[:, 0], first(ins, "ConvW"), conv,
                                        mask, out_dtype)
        y = y[:, None]
    return {"Out": [y], "ConvStateOut": [conv]}
