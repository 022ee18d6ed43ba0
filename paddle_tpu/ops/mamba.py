"""The Mamba-2 mixer between its two projections, as one IR op.

``mamba2_mixer`` takes the input projection's output (``z | xBC | dt``) and
the mixer's small parameters, advances the per-slot states (``ConvState``
``[S, K - 1, D]``, ``SsmState`` ``[S, H, P, N]``) and gives the gated,
normed ``y`` that the output projection takes (kernels/mamba.py has the
mathematics). Two modes, by ``mode``:

* ``"chunk"``: ``X`` is ``[1, C, width]``, a prompt chunk of the ONE slot
  ``Slot`` ``[1]`` names; ``Positions`` ``[1, C]`` says whether the chunk
  opens the prompt (first position 0: the slot's states start from zero).
* ``"step"``: ``X`` is ``[S, 1, width]``, one token of every slot.

In both, ``WriteRows`` marks the real tokens: a token whose row is ``>=
num_rows`` writes no K/V row anywhere (a slot that does not step, a chunk's
padding) and moves no state here. The decode step's state update may be
served by the ``ssm_update`` kernel, a prompt chunk's scan by ``ssm_scan``.
"""

from paddle_tpu.core.registry import OpDef, OpRegistry
from paddle_tpu.ops.common import first

_PARAMS = (("ConvW", "conv_w"), ("ConvB", "conv_b"), ("DtBias", "dt_bias"),
           ("ALog", "a_log"), ("D", "d"), ("NormW", "norm_w"))


def _mixer(ins, attrs, kernel):
    from paddle_tpu.kernels import mamba

    x = first(ins, "X")
    params = {key: first(ins, slot) for slot, key in _PARAMS}
    conv, ssm = first(ins, "ConvState"), first(ins, "SsmState")
    mask = first(ins, "WriteRows").reshape(-1) < attrs["num_rows"]
    sizes = dict(heads=attrs["heads"], head_dim=attrs["head_dim"],
                 groups=attrs["groups"], n_state=attrs["state_size"],
                 eps=attrs.get("epsilon", 1e-5),
                 out_dtype=attrs.get("out_dtype") or x.dtype)
    if attrs["mode"] == "chunk":
        y, conv, ssm = mamba.mixer_chunk(
            x[0], params, conv, ssm, first(ins, "Slot")[0], mask,
            first(ins, "Positions")[0, 0] == 0,
            chunk=attrs.get("chunk_size", 128), kernel=kernel, **sizes)
        y = y[None]
    else:
        y, conv, ssm = mamba.mixer_step(x[:, 0], params, conv, ssm, mask,
                                        kernel=kernel, **sizes)
        y = y[:, None]
    return {"Out": [y], "ConvStateOut": [conv], "SsmStateOut": [ssm]}


def _mixer_reference(ins, attrs):
    return _mixer(ins, attrs, None)


def _mixer_pallas(ins, attrs):
    from paddle_tpu import kernels

    sel = kernels.selected(
        "ssm_update" if attrs["mode"] == "step" else "ssm_scan")
    return _mixer(ins, attrs, None if sel is None else sel.interpret)


OpRegistry.register(OpDef(
    "mamba2_mixer", _mixer_reference, pallas=_mixer_pallas,
    nondiff_inputs=("Slot", "Positions", "WriteRows")))
