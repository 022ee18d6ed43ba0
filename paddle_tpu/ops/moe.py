"""Mixture-of-Experts FFN as a first-class IR op.

Expert parallelism on the Program/Executor surface (SURVEY §2.7 names it
new first-class work the 2020 reference lacks; the closest reference analog
is distributed sparse lookup, not expert routing). The op computes top-2
gated expert FFNs over stacked [E, ...] expert weights:

- with an active mesh (CompiledProgram.with_parallel) whose `expert_axis`
  has size > 1: tokens and experts are sharded over that axis inside a
  shard_map; tokens travel to their expert's device via one lax.all_to_all
  each way over ICI (parallel/moe.py moe_ffn_local);
- otherwise: the same routing math runs dense on one device, so a plain
  Executor run is the numerical reference for the sharded one.

The load-balance aux loss rides as a second output for the caller to add
to the objective.
"""

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map

from paddle_tpu.core.registry import OpDef, OpRegistry, register_op
from paddle_tpu.ops.common import first, maybe, vma_names
from paddle_tpu.utils.enforce import EnforceError

_ACTS = {
    "gelu": jax.nn.gelu,
    "relu": jax.nn.relu,
    "silu": jax.nn.silu,
}


def _expert_ffn(act_fn):
    def fn(params, buf):
        """params: (w1 [H,F], b1 [F], w2 [F,H], b2 [H]); buf [C, H]."""
        w1, b1, w2, b2 = params
        h = act_fn(buf @ w1 + b1)
        return h @ w2 + b2

    return fn


@register_op("moe_ffn")
def _moe_ffn(ins, attrs):
    x = first(ins, "X")           # [..., H] (any leading dims = tokens)
    gate_w = first(ins, "GateW")  # [H, E]
    w1 = first(ins, "W1")         # [E, H, F]
    b1 = first(ins, "B1")         # [E, F]
    w2 = first(ins, "W2")         # [E, F, H]
    b2 = first(ins, "B2")         # [E, H]
    axis = attrs.get("expert_axis", "expert")
    cf = attrs.get("capacity_factor", 2.0)
    capacity = attrs.get("capacity", 0)
    act_fn = _ACTS[attrs.get("activation", "gelu")]
    E = gate_w.shape[1]

    orig_shape = x.shape
    xt = x.reshape(-1, x.shape[-1])
    T = xt.shape[0]
    expert_fn = _expert_ffn(act_fn)

    from paddle_tpu.parallel import env as penv

    mesh = penv.current_mesh()
    n = 1
    if mesh is not None and axis in mesh.axis_names:
        n = dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis, 1)
    if n > 1 and vma_names(xt):
        raise EnforceError(
            "moe_ffn cannot run inside an already-manual region (e.g. a "
            "pipeline_stack body); place the MoE layer on the outer program"
        )

    if n > 1:
        if E % n:
            raise EnforceError(
                f"num_experts {E} must divide expert axis '{axis}' size {n}"
            )
        if T % n:
            raise EnforceError(
                f"expert axis '{axis}' size {n} must divide the token "
                f"count {T}"
            )
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.parallel.moe import moe_ffn_local

        # per-source-device capacity, ceil so the TOTAL per-expert buffer
        # (n * cap_local) is never below the dense path's explicit
        # capacity — dense vs sharded drop behavior matches when the
        # capacity is generous
        cap_local = -(-capacity // n) if capacity else None

        def local(xs, gw, p1, p2, p3, p4):
            y, aux = moe_ffn_local(
                xs, gw, (p1, p2, p3, p4), expert_fn, axis,
                capacity_factor=cf, capacity=cap_local, global_aux=True,
            )
            return y, aux

        y, aux = _shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axis, None), P(), P(axis), P(axis), P(axis), P(axis)),
            out_specs=(P(axis, None), P()),
        )(xt, gate_w, w1, b1, w2, b2)
    else:
        from paddle_tpu.parallel.moe import top2_gating

        cap = capacity or max(int(cf * T * 2 / E), 4)
        logits = xt @ gate_w
        dispatch, combine, aux = top2_gating(logits, cap)
        buf = jnp.einsum("tec,th->ech", dispatch.astype(xt.dtype), xt)
        out = jax.vmap(expert_fn)((w1, b1, w2, b2), buf)
        y = jnp.einsum("tec,ech->th", combine.astype(xt.dtype), out)

    return {
        "Out": [y.reshape(orig_shape).astype(x.dtype)],
        "AuxLoss": [aux.astype(jnp.float32)],
    }


def _moe_routed_experts_reference(ins, attrs):
    return _moe_routed_experts(ins, attrs, None)


def _moe_routed_experts_pallas(ins, attrs):
    """The ``moe_experts`` kernel serves the op where the builder asks for
    it (``kernel``: the decode step); elsewhere (a prompt chunk) the
    ``moe_grouped`` kernel where the rows it multiplies are few beside the
    dense product's (kernels/moe.py ``takes_grouped``), else the
    composite."""
    from paddle_tpu import kernels

    if attrs.get("kernel"):
        sel = kernels.selected("moe_experts")
        return _moe_routed_experts(
            ins, attrs, None if sel is None else sel.interpret)
    sel = kernels.selected("moe_grouped")
    return _moe_routed_experts(
        ins, attrs, None, None if sel is None else sel.interpret)


def _moe_routed_experts(ins, attrs, kernel, grouped=None):
    """The held experts' part of a routed-experts layer (kernels/moe.py):
    ``X`` ``[T, H]``, the router ``GateW`` ``[E_all, H]`` (attribute
    ``score``: ``sigmoid``, or ``softmax`` over all of them) and its
    selection bias ``SelectBias`` ``[E_all]`` over ALL the experts, ``WUp``,
    ``WDown`` and, for gated experts, ``WGate`` ``[held, F, H]`` of the
    experts ``expert_offset .. expert_offset + held - 1`` that live here
    (with ``WGate`` an expert is ``silu(gate) * up``, without it
    ``relu(up)^2``), and ``WriteRows`` ``[T]``: a
    token whose row is ``>= num_rows`` (one that writes nowhere: a slot
    that does not step, a chunk's padding) is routed nowhere and counted
    nowhere. ``Out`` float32 ``[T, H]``; ``Counts`` int32 ``[4]``
    (assignments, held assignments, held experts touched, the busiest held
    expert's tokens); with ``group_counts``, ``GroupCounts`` int32 ``[3]``:
    the (token, held expert) pairs, the rows multiplied for them (each
    held expert's pairs in whole row tiles where the rule takes the grouped
    product, every token for every held expert where it does not) and the
    held experts with a pair.
    ``kernel`` is the step kernel's interpret flag, ``grouped`` the grouped
    kernel's (None: the composite)."""
    from paddle_tpu.kernels import moe

    x = first(ins, "X")
    lead = x.shape[:-1]
    xt = x.reshape(-1, x.shape[-1])
    w_up, w_down = first(ins, "WUp"), first(ins, "WDown")
    w_gate = maybe(ins, "WGate")
    gate_w = first(ins, "GateW")
    held, offset = w_up.shape[0], attrs.get("expert_offset", 0)
    mask = first(ins, "WriteRows").reshape(-1) < attrs["num_rows"]
    idx, w = moe.route(xt, gate_w, first(ins, "SelectBias"),
                       attrs["k"], attrs.get("score_scale", 1.0),
                       attrs.get("normalize", True),
                       attrs.get("norm_epsilon", 1e-20),
                       attrs.get("score", "sigmoid"))
    c = moe.held_weights(idx, w, mask, offset, held)
    by_pairs = not attrs.get("kernel") and moe.takes_grouped(
        xt.shape[0], attrs["k"], held, gate_w.shape[0])
    if kernel is not None:
        out = moe.moe_experts(xt, c, w_up, w_down, w_gate, interpret=kernel)
    elif grouped is not None and by_pairs:
        out = moe.moe_grouped(xt, idx, w, mask, offset, w_up, w_down, w_gate,
                              interpret=grouped)
    else:
        out = moe.experts_composite(xt, c, w_up, w_down, w_gate)
    outs = {"Out": [out.reshape(lead + (x.shape[-1],))],
            "Counts": [moe.routing_counts(idx, c, mask)]}
    if attrs.get("group_counts"):
        pairs = moe.grouped_counts(idx, mask, offset, held)
        outs["GroupCounts"] = [pairs if by_pairs else pairs.at[1].set(
            jnp.sum(mask.astype(jnp.int32)) * held)]
    return outs


OpRegistry.register(OpDef(
    "moe_routed_experts", _moe_routed_experts_reference,
    pallas=_moe_routed_experts_pallas, nondiff_inputs=("WriteRows",)))
