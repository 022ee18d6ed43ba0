"""Static roofline cost model: pre-compile step-time / MFU prediction.

The container has no TPU, so the only trustworthy performance signal is a
static one (ROADMAP grounding note) — and the Fluid-style whole-program
IR makes it tractable the same way PR 9 made sharding and peak HBM
statically decidable. This pass walks the op plan once, assigns every op

  * FLOPs        — per-op rules (matmul family 2*M*N*K, convs
                   2*out*kernel, elementwise ~numel, reductions
                   in-out, optimizers k*param; pure-transcendental work
                   like tanh counts under `transcendentals`, NOT flops,
                   matching XLA's HloCostAnalysis so the COST_EVIDENCE
                   drift gate can compare like with like)
  * HBM bytes    — operand + result shard bytes through the SAME
                   resolver analysis/memory.py prices peaks with
                   (memory.var_bytes), so the two analyzers cannot
                   silently disagree on what a tensor weighs
  * wire bytes   — collectives from analysis/sharding.py's resharding
                   report (grad-sync / weight-gather laws included),
                   priced per mesh axis

and folds them through a mesh-aware machine model: per-chip peak FLOP/s
and HBM bandwidth plus a two-level latency–bandwidth collective model
where every mesh axis is tagged ``ici`` or ``dcn``
(``CostModel.for_mesh``; tags thread from
``CompiledProgram.with_parallel(axis_tags=...)`` /
``DistributedStrategy.mesh_axis_tags``). The report carries predicted
step seconds, MFU, an arithmetic-intensity-vs-ridge classification per
op, and a per-axis collective budget section.

``hierarchical_collective_diagnostics`` is the linter ROADMAP item 4
asked for: an all-reduce whose participation spans a ``dcn``-tagged axis
together with an ``ici``-tagged axis should be the two-level form —
reduce-scatter over ICI, all-reduce of the shard over DCN, all-gather
over ICI — cutting DCN bytes by the ICI degree. ``pipeline_bubble_report``
prices ``pipeline_stack`` ops with the GPipe bubble fraction
(s-1)/(m+s-1) so the 1F1B PR lands against an existing gate.

Control-flow-aware like the memory walk: sub-block ops (while/cond)
count their body ONCE at the parent op (iteration counts are dynamic;
XLA's cost analysis makes the same call), ``pipeline_stack`` multiplies
its layer body by the stacked layer count, and
``recompute_segment_grad`` prices the policy-dependent replay from its
serialized segment — full recomputes everything (max FLOPs, min bytes),
save_all replays nothing (min FLOPs, max bytes), the exact ordering
tests/test_cost_analysis.py pins against remat_hbm_delta.
"""

from paddle_tpu.analysis.memory import var_bytes
from paddle_tpu.analysis.shapes import infer_shapes, is_sym
from paddle_tpu.analysis.verify import Diagnostic
from paddle_tpu.utils.enforce import EnforceError

__all__ = [
    "MachineModel", "MACHINES", "CostModel", "OpCost", "CostReport",
    "analyze_cost", "hierarchical_collective_diagnostics",
    "pipeline_bubble_report", "default_axis_tags",
]


class MachineModel:
    """Nominal per-chip peaks + two-level link model. The numbers are
    catalog peaks, not measured — the roofline's job is RANKING
    programs and catching order-of-magnitude regressions pre-compile;
    absolute wall-clock calibration is on-chip work (ROADMAP item 1)."""

    __slots__ = ("name", "peak_flops", "hbm_bw", "link_bw", "link_lat")

    def __init__(self, name, peak_flops, hbm_bw, ici_bw, ici_lat,
                 dcn_bw, dcn_lat):
        self.name = name
        self.peak_flops = float(peak_flops)   # FLOP/s per chip (bf16)
        self.hbm_bw = float(hbm_bw)           # bytes/s per chip
        self.link_bw = {"ici": float(ici_bw), "dcn": float(dcn_bw)}
        self.link_lat = {"ici": float(ici_lat), "dcn": float(dcn_lat)}

    @property
    def ridge(self):
        """Arithmetic intensity (FLOPs/byte) where compute and HBM time
        balance — ops below it are memory-bound."""
        return self.peak_flops / self.hbm_bw

    def to_json(self):
        return {
            "name": self.name, "peak_flops": self.peak_flops,
            "hbm_bw": self.hbm_bw, "ridge_flops_per_byte": self.ridge,
            "link_bw": dict(self.link_bw), "link_lat": dict(self.link_lat),
        }


#: machine catalog — peak bf16 FLOP/s and HBM BW per chip are the
#: published ones; ICI is the per-chip injection
#: bandwidth of one ring direction-pair, DCN a 100 Gb/s NIC share.
MACHINES = {
    "tpu-v4-8": MachineModel("tpu-v4-8", 275e12, 1.2e12,
                             9e10, 1e-6, 12.5e9, 1e-5),
    "tpu-v5e-8": MachineModel("tpu-v5e-8", 197e12, 8.1e11,
                              4.5e10, 1e-6, 12.5e9, 1e-5),
    "tpu-v5p-8": MachineModel("tpu-v5p-8", 459e12, 2.765e12,
                              9e10, 1e-6, 12.5e9, 1e-5),
    "tpu-v6e-8": MachineModel("tpu-v6e-8", 918e12, 1.64e12,
                              9e10, 1e-6, 12.5e9, 1e-5),
    # the CPU lint rig: keeps ratios finite in tests; never a perf claim
    "cpu-host": MachineModel("cpu-host", 5e11, 5e10,
                             1e10, 1e-6, 1e9, 1e-5),
}

DEFAULT_MACHINE = "tpu-v4-8"


def default_axis_tags(mesh):
    """axis -> 'ici' | 'dcn'. Without explicit tags, an axis NAMED for the
    slow tier ('dcn', 'dcn_*', '*_dcn', 'pod') is DCN and everything else
    is ICI — make_mesh's documented 2-D convention (outer axis = DCN) is
    only honored when the caller says so by name or by axis_tags, because
    most 2-D meshes here are single-slice (data, model)."""
    tags = {}
    for ax in mesh.axis_names:
        low = str(ax).lower()
        dcn = (low == "dcn" or low == "pod" or low.startswith("dcn_")
               or low.endswith("_dcn"))
        tags[ax] = "dcn" if dcn else "ici"
    return tags


#: ring-collective traffic factors: fraction of the payload each chip
#: puts on the wire for an n-chip ring (reduce-scatter + all-gather
#: decomposition of all-reduce = 2(n-1)/n)
_KIND_FACTOR = {
    "all-reduce": lambda n: 2.0 * (n - 1) / n,
    "all-gather": lambda n: (n - 1) / n,
    "reduce-scatter": lambda n: (n - 1) / n,
    "all-to-all": lambda n: (n - 1) / n,
}


class CostModel:
    """A MachineModel bound to a mesh: axis sizes + ici/dcn tags."""

    __slots__ = ("machine", "axis_sizes", "axis_tags")

    def __init__(self, machine, axis_sizes=None, axis_tags=None):
        if isinstance(machine, str):
            if machine not in MACHINES:
                raise EnforceError(
                    f"unknown machine model '{machine}'; have "
                    f"{sorted(MACHINES)}"
                )
            machine = MACHINES[machine]
        self.machine = machine
        self.axis_sizes = dict(axis_sizes or {})
        self.axis_tags = dict(axis_tags or {})

    @classmethod
    def for_mesh(cls, mesh, machine=DEFAULT_MACHINE, axis_tags=None):
        """Bind `machine` to `mesh`. ``axis_tags`` maps axis name ->
        'ici'|'dcn' (partial maps OK — unnamed axes fall back to
        `default_axis_tags`); an unknown axis or tag raises rather than
        silently disarming the DCN linter."""
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        tags = default_axis_tags(mesh)
        for ax, tag in (axis_tags or {}).items():
            if ax not in sizes:
                raise EnforceError(
                    f"axis_tags: '{ax}' is not a mesh axis "
                    f"(have {sorted(sizes)})"
                )
            if tag not in ("ici", "dcn"):
                raise EnforceError(
                    f"axis_tags[{ax!r}] = {tag!r}: tag must be 'ici' or "
                    f"'dcn'"
                )
            tags[ax] = tag
        return cls(machine, sizes, tags)

    @classmethod
    def single_device(cls, machine=DEFAULT_MACHINE):
        return cls(machine)

    def tag(self, axis):
        return self.axis_tags.get(axis, "ici")

    def collective_seconds(self, kind, bytes_, axes):
        """Two-level latency–bandwidth time for one collective: the axes
        run in sequence (hierarchical decomposition), each paying its
        tier's latency + ring traffic over its tier's bandwidth."""
        if not bytes_:
            return 0.0
        factor = _KIND_FACTOR.get(kind, _KIND_FACTOR["all-gather"])
        total = 0.0
        for ax in axes:
            n = self.axis_sizes.get(ax, 1)
            if n <= 1:
                continue
            tag = self.tag(ax)
            total += self.machine.link_lat[tag] + \
                factor(n) * bytes_ / self.machine.link_bw[tag]
        return total

    def to_json(self):
        return {
            "machine": self.machine.to_json(),
            "axis_sizes": dict(self.axis_sizes),
            "axis_tags": dict(self.axis_tags),
        }


class OpCost:
    __slots__ = ("op_type", "op_index", "block_idx", "flops",
                 "transcendentals", "hbm_bytes", "known", "seconds",
                 "bound", "intensity")

    def __init__(self, op_type, op_index, block_idx, flops,
                 transcendentals, hbm_bytes, known):
        self.op_type = op_type
        self.op_index = op_index
        self.block_idx = block_idx
        self.flops = int(flops)
        self.transcendentals = int(transcendentals)
        self.hbm_bytes = int(hbm_bytes)
        self.known = known
        self.seconds = 0.0
        self.bound = None        # 'compute' | 'memory'
        self.intensity = 0.0     # flops / hbm_bytes

    def to_json(self):
        return {
            "op_type": self.op_type, "op_index": self.op_index,
            "block": self.block_idx, "flops": self.flops,
            "transcendentals": self.transcendentals,
            "hbm_bytes": self.hbm_bytes, "known": self.known,
            "seconds": self.seconds, "bound": self.bound,
            "intensity": round(self.intensity, 4),
        }


class CostReport:
    """Everything the roofline decided, machine-readable."""

    def __init__(self, cost_model):
        self.cost_model = cost_model
        self.ops = []                 # OpCost, program order
        self.collectives = []         # priced dicts (kind/var/axes/...)
        self.unknown_ops = set()      # op types served by the default rule
        self.total_flops = 0
        self.total_transcendentals = 0
        self.total_hbm_bytes = 0
        self.compute_seconds = 0.0
        self.memory_seconds = 0.0
        self.roofline_seconds = 0.0   # sum of per-op max(compute, memory)
        self.collective_seconds = 0.0
        self.pipeline = []            # pipeline_bubble_report entries
        self.diagnostics = []

    @property
    def step_seconds(self):
        return self.roofline_seconds + self.collective_seconds

    @property
    def mfu(self):
        peak = self.cost_model.machine.peak_flops
        if not self.step_seconds or not peak:
            return 0.0
        return self.total_flops / (self.step_seconds * peak)

    def per_axis(self):
        """axis -> {tag, size, collectives, wire_bytes, seconds}: the
        collective budget section (wire_bytes are ON-WIRE bytes, i.e.
        payload x ring factor, per chip)."""
        out = {}
        for ax, n in sorted(self.cost_model.axis_sizes.items()):
            out[ax] = {"tag": self.cost_model.tag(ax), "size": n,
                       "collectives": 0, "wire_bytes": 0, "seconds": 0.0}
        for c in self.collectives:
            for ax, wire in c["wire_bytes_by_axis"].items():
                ent = out.setdefault(
                    ax, {"tag": self.cost_model.tag(ax),
                         "size": self.cost_model.axis_sizes.get(ax, 1),
                         "collectives": 0, "wire_bytes": 0, "seconds": 0.0})
                ent["collectives"] += 1
                ent["wire_bytes"] += wire
                ent["seconds"] += c["seconds_by_axis"][ax]
        for ent in out.values():
            ent["wire_bytes"] = int(ent["wire_bytes"])
        return out

    def bound_counts(self):
        out = {"compute": 0, "memory": 0}
        for c in self.ops:
            if c.bound:
                out[c.bound] += 1
        return out

    def to_json(self, ops_limit=64):
        return {
            "model": self.cost_model.to_json(),
            "total_flops": self.total_flops,
            "total_transcendentals": self.total_transcendentals,
            "total_hbm_bytes": self.total_hbm_bytes,
            "compute_seconds": self.compute_seconds,
            "memory_seconds": self.memory_seconds,
            "roofline_seconds": self.roofline_seconds,
            "collective_seconds": self.collective_seconds,
            "step_seconds": self.step_seconds,
            "mfu": round(self.mfu, 6),
            "bound_counts": self.bound_counts(),
            "per_axis": self.per_axis(),
            "collectives": self.collectives[:ops_limit],
            "unknown_ops": sorted(self.unknown_ops),
            "pipeline": self.pipeline,
            "ops": [c.to_json() for c in sorted(
                self.ops, key=lambda c: -c.seconds)[:ops_limit]],
        }


# ---------------------------------------------------------------------------
# per-op FLOP rules
# ---------------------------------------------------------------------------
#
# Each rule returns (flops, transcendentals) for ONE op given numel/shape
# helpers. flops follows XLA's HloCostAnalysis conventions (fused
# multiply-add = 2, reduce = in - out, pure transcendentals = 0 flops) so
# the COST_EVIDENCE drift gate compares the same quantity XLA reports.


def _numel(shape):
    if shape is None:
        return None
    n = 1
    for d in shape:
        if is_sym(d):
            return None
        n *= max(int(d), 1)
    return n


class _Ctx:
    """Shape access for one op inside the walk."""

    __slots__ = ("op", "shape_of")

    def __init__(self, op, shape_of):
        self.op = op
        self.shape_of = shape_of

    def in_shape(self, slot, i=0):
        names = self.op.inputs.get(slot) or ()
        return self.shape_of(names[i]) if len(names) > i else None

    def out_shape(self, slot="Out", i=0):
        names = self.op.outputs.get(slot) or ()
        return self.shape_of(names[i]) if len(names) > i else None

    def out_numel(self, slot="Out"):
        for s in (self.out_shape(slot),
                  self._first_out_shape()):
            n = _numel(s)
            if n is not None:
                return n
        return 0

    def _first_out_shape(self):
        for names in self.op.outputs.values():
            if names:
                return self.shape_of(names[0])
        return None

    def in_numel(self, slot="X", i=0):
        return _numel(self.in_shape(slot, i)) or 0

    def all_out_numel(self):
        total = 0
        for names in self.op.outputs.values():
            for n in names:
                total += _numel(self.shape_of(n)) or 0
        return total

    def all_in_numel(self):
        total = 0
        for names in self.op.inputs.values():
            for n in names:
                total += _numel(self.shape_of(n)) or 0
        return total


def _matmul_flops(ctx):
    """2 * out_numel * K for mul/matmul/matmul_v2 (transpose-aware)."""
    op = ctx.op
    xshape = ctx.in_shape("X")
    out = ctx.out_numel()
    if xshape is None:
        return 2 * out, 0
    if op.type == "mul":
        xnc = op.attrs.get("x_num_col_dims", 1)
        k = _numel(xshape[xnc:])
    else:
        tx = op.attrs.get("transpose_X", op.attrs.get("trans_x", False))
        k = xshape[-2] if tx else xshape[-1]
        k = None if is_sym(k) else int(k)
    if k is None:
        return 2 * out, 0
    return 2 * out * k, 0


def _fwd_out_numel(ctx, slots):
    """Forward-output numel seen from inside a grad op (Out@GRAD input)."""
    for slot in slots:
        n = _numel(ctx.in_shape(slot))
        if n:
            return n
    return None


def _grad_outputs(ctx):
    return sum(1 for names in ctx.op.outputs.values() if names) or 1


def _matmul_grad_flops(ctx):
    """dX = dOut @ Y^T and dY = X^T @ dOut — each costs exactly the
    forward matmul's 2*M*N*K, so total = forward x (#grads produced)."""
    op = ctx.op
    out = _fwd_out_numel(ctx, ("Out@GRAD", "Out"))
    xshape = ctx.in_shape("X")
    if out is None or xshape is None:
        f, t = _matmul_flops(ctx)
        return 2 * f, t
    if op.type == "mul_grad":
        xnc = op.attrs.get("x_num_col_dims", 1)
        k = _numel(xshape[xnc:])
    else:
        tx = op.attrs.get("transpose_X", op.attrs.get("trans_x", False))
        k = xshape[-2] if tx else xshape[-1]
        k = None if is_sym(k) else int(k)
    if k is None:
        f, t = _matmul_flops(ctx)
        return 2 * f, t
    return 2 * out * k * _grad_outputs(ctx), 0


def _conv_flops(ctx):
    op = ctx.op
    wshape = ctx.in_shape("Filter")
    out = ctx.out_numel("Output") or ctx.out_numel()
    if wshape is None or len(wshape) < 4:
        return 2 * out, 0
    kernel = _numel(wshape[1:]) or 1   # C_in/groups * KH * KW
    return 2 * out * kernel, 0


def _conv_grad_flops(ctx):
    """dInput and dFilter each cost the forward conv; scale by the
    number of grads actually produced."""
    op = ctx.op
    wshape = ctx.in_shape("Filter")
    out = _fwd_out_numel(ctx, ("Output@GRAD", "Output"))
    if wshape is None or out is None or len(wshape) < 4:
        f, t = _conv_flops(ctx)
        return 2 * f, t
    kernel = _numel(wshape[1:]) or 1
    return 2 * out * kernel * _grad_outputs(ctx), 0


def _pool_flops(ctx):
    op = ctx.op
    ks = op.attrs.get("ksize") or op.attrs.get("pool_size") or (1, 1)
    k = 1
    for d in ks:
        k *= max(int(d), 1)
    return ctx.out_numel() * k, 0


def _reduce_flops(ctx):
    return max(ctx.in_numel() - ctx.out_numel(), 0), 0


def _ew(mult, trans=0):
    def rule(ctx):
        n = ctx.out_numel()
        return mult * n, trans * n
    return rule


def _ew_in(mult, trans=0):
    def rule(ctx):
        n = ctx.in_numel() or ctx.out_numel()
        return mult * n, trans * n
    return rule


def _zero(ctx):
    return 0, 0


def _sum_flops(ctx):
    ins = sum(len(v) for v in ctx.op.inputs.values())
    return max(ins - 1, 0) * ctx.out_numel(), 0


def _lookup_flops(ctx):
    # gather is data movement; the grad is a scatter-ADD over the rows
    return 0, 0


def _lookup_grad_flops(ctx):
    return ctx.all_out_numel(), 0


def _optimizer(mult, trans=0):
    def rule(ctx):
        n = ctx.in_numel("Param") or ctx.out_numel("ParamOut") \
            or ctx.all_out_numel()
        return mult * n, trans * n
    return rule


def _sdpa_flops(ctx):
    """scaled_dot_product_attention: QK^T + PV = 4 * numel(Q) * S flops,
    softmax exp under transcendentals (one per score entry ~ numel(Q))."""
    q = ctx.in_shape("Q")
    if q is None or len(q) < 2:
        return 0, 0
    nq = _numel(q) or 0
    s = q[-2]
    s = 0 if is_sym(s) else int(s)
    return 4 * nq * s, nq


def _moe_ffn_flops(ctx):
    """moe_ffn (dense path): gate matmul + dispatch/combine einsums over
    the [E, cap, H] capacity buffer + the two expert matmuls, with the
    capacity defaulted exactly as ops/moe.py computes it
    (``capacity or max(int(cf * T * 2 / E), 4)``). Gating softmax and
    the expert activation go under transcendentals."""
    x = ctx.in_shape("X")
    gw = ctx.in_shape("GateW")
    w1 = ctx.in_shape("W1")
    fallback = 2 * (ctx.out_numel() or 0)
    if x is None or gw is None or len(gw) < 2 or w1 is None:
        return fallback, 0
    h, e, f = gw[0], gw[1], w1[-1]
    t = _numel(x)
    if any(is_sym(d) for d in (h, e, f)) or not t or not h:
        return fallback, 0
    h, e, f = int(h), int(e), int(f)
    t //= h
    cap = int(ctx.op.attrs.get("capacity", 0) or 0)
    if not cap:
        cf = float(ctx.op.attrs.get("capacity_factor", 2.0) or 2.0)
        cap = max(int(cf * t * 2 / e), 4)
    gate = 2 * t * h * e
    route = 4 * t * e * cap * h      # dispatch + combine dot-generals
    expert = 4 * e * cap * h * f     # the two FFN matmuls per expert
    return gate + route + expert, t * e + e * cap * f


#: op type -> rule. A type absent here is priced by the default
#: elementwise rule AND recorded in CostReport.unknown_ops — the
#: property test pins unknown_ops == [] on every examples/ program.
_FLOP_RULES = {
    # matmul family
    "mul": _matmul_flops, "matmul": _matmul_flops,
    "matmul_v2": _matmul_flops,
    "mul_grad": _matmul_grad_flops, "matmul_grad": _matmul_grad_flops,
    "matmul_v2_grad": _matmul_grad_flops,
    "conv2d": _conv_flops, "depthwise_conv2d": _conv_flops,
    "conv2d_grad": _conv_grad_flops,
    "depthwise_conv2d_grad": _conv_grad_flops,
    "scaled_dot_product_attention": _sdpa_flops,
    "scaled_dot_product_attention_grad":
        lambda ctx: tuple(2 * v for v in _sdpa_flops(ctx)),
    "moe_ffn": _moe_ffn_flops,
    "moe_ffn_grad": lambda ctx: tuple(2 * v for v in _moe_ffn_flops(ctx)),
    # layout / copies / bookkeeping: bytes, no flops
    "reshape2": _zero, "reshape": _zero, "reshape2_grad": _zero,
    "reshape_grad": _zero, "transpose2": _zero, "transpose": _zero,
    "transpose2_grad": _zero, "transpose_grad": _zero,
    "unsqueeze2": _zero, "squeeze2": _zero, "unsqueeze2_grad": _zero,
    "squeeze2_grad": _zero, "cast": _zero, "cast_grad": _zero,
    "assign": _zero,
    "assign_value": _zero, "fill_constant": _zero, "shape": _zero,
    "fill_constant_batch_size_like": _zero, "fill_zeros_like": _zero,
    "concat": _zero, "concat_grad": _zero, "split": _zero,
    "slice": _zero, "slice_grad": _zero, "stack": _zero,
    "stack_grad": _zero, "expand": _zero, "expand_grad": _zero,
    "gather": _zero, "batched_gather": _zero,
    "gather_grad": _lookup_grad_flops,
    "batched_gather_grad": _lookup_grad_flops,
    "feed": _zero, "fetch": _zero, "read_from_array": _zero,
    "write_to_array": _zero, "increment": _ew(1), "one_hot": _zero,
    "one_hot_v2": _zero, "range": _zero, "uniform_random": _zero,
    "gaussian_random": _zero, "truncated_gaussian_random": _zero,
    "sampling_id": _zero, "top_k": _zero, "arg_max": _zero,
    "sequence_mask": _ew(1), "tile": _zero, "where_index": _zero,
    # embedding lookups (gather; grad is a row scatter-add)
    "lookup_table": _lookup_flops, "lookup_table_v2": _lookup_flops,
    "lookup_table_grad": _lookup_grad_flops,
    "lookup_table_v2_grad": _lookup_grad_flops,
    "sharded_embedding_lookup": _lookup_flops,
    "sharded_embedding_lookup_grad": _lookup_grad_flops,
    # elementwise arithmetic: 1 flop per output element
    "elementwise_add": _ew(1), "elementwise_sub": _ew(1),
    "elementwise_mul": _ew(1), "elementwise_div": _ew(1),
    "elementwise_max": _ew(1), "elementwise_min": _ew(1),
    "elementwise_pow": _ew(0, 1), "scale": _ew(1), "clip": _ew(2),
    "clip_by_norm": _ew(3), "square": _ew(1), "abs": _ew(1),
    "sign": _ew(1), "sqrt": _ew(1), "rsqrt": _ew(0, 1), "pow": _ew(0, 1),
    "elementwise_add_grad": _ew_in(1), "elementwise_sub_grad": _ew_in(1),
    "elementwise_mul_grad": _ew_in(2), "elementwise_div_grad": _ew_in(3),
    "elementwise_max_grad": _ew_in(1), "elementwise_min_grad": _ew_in(1),
    "scale_grad": _ew_in(1), "square_grad": _ew_in(2),
    "sqrt_grad": _ew_in(2), "abs_grad": _ew_in(1), "clip_grad": _ew_in(1),
    # comparisons / logic (XLA prices compares as flops)
    "greater_than": _ew(1), "less_than": _ew(1), "equal": _ew(1),
    "not_equal": _ew(1), "greater_equal": _ew(1), "less_equal": _ew(1),
    "logical_and": _ew(1), "logical_or": _ew(1), "logical_not": _ew(1),
    "isfinite": _ew(1), "accuracy": _ew_in(2), "where": _ew(1),
    "where_grad": _ew_in(1),
    # activations: transcendental part under `transcendentals`
    "relu": _ew(1), "relu_grad": _ew_in(1), "leaky_relu": _ew(2),
    "leaky_relu_grad": _ew_in(2), "sigmoid": _ew(2, 1),
    "sigmoid_grad": _ew_in(2), "tanh": _ew(0, 1), "tanh_grad": _ew_in(2),
    "gelu": _ew(3, 1), "gelu_grad": _ew_in(5, 1),
    "exp": _ew(0, 1), "log": _ew(0, 1),
    "softmax": _ew(2, 1), "softmax_grad": _ew_in(3),
    "log_softmax": _ew(2, 1), "log_softmax_grad": _ew_in(3),
    "dropout": _ew(1), "dropout_grad": _ew_in(1),
    # reductions
    "reduce_sum": _reduce_flops, "reduce_mean": _reduce_flops,
    "reduce_max": _reduce_flops, "reduce_min": _reduce_flops,
    "reduce_prod": _reduce_flops, "mean": _reduce_flops,
    "reduce_sum_grad": _zero, "reduce_mean_grad": _ew(1),
    "reduce_max_grad": _ew(1), "mean_grad": _ew(1),
    "sum": _sum_flops, "sum_grad": _zero,
    # norms
    "layer_norm": _ew_in(7, 1), "layer_norm_grad": _ew_in(12),
    "batch_norm": _ew_in(5, 1), "batch_norm_grad": _ew_in(9),
    # losses
    "square_error_cost": _ew(2), "square_error_cost_grad": _ew_in(2),
    "cross_entropy": _ew(1, 1), "cross_entropy_grad": _ew_in(2),
    "cross_entropy2": _ew(1, 1), "cross_entropy2_grad": _ew_in(2),
    "softmax_with_cross_entropy": _ew_in(3, 1),
    "softmax_with_cross_entropy_grad": _ew_in(3),
    "sigmoid_cross_entropy_with_logits": _ew(3, 1),
    "sigmoid_cross_entropy_with_logits_grad": _ew_in(3),
    "smooth_l1_loss": _ew(3), "smooth_l1_loss_grad": _ew_in(3),
    # pooling
    "pool2d": _pool_flops, "pool2d_grad": _pool_flops,
    # optimizers: k flops per parameter element
    "sgd": _optimizer(2), "sgd_sparse": _optimizer(2),
    "momentum": _optimizer(5), "dgc_momentum": _optimizer(6),
    "adam": _optimizer(12, 1), "adamw": _optimizer(14, 1),
    "adagrad": _optimizer(5, 1), "rmsprop": _optimizer(8, 1),
    "lamb": _optimizer(16, 1), "lars_momentum": _optimizer(8, 1),
    "ftrl": _optimizer(8, 1),
    # fused dedup-grad + SGD row scatter: segment-sum of OutGrad plus the
    # -lr*rowgrad update over the touched rows — ~2 flops per grad element
    "sharded_embedding_sgd":
        lambda ctx: (2 * (ctx.in_numel("OutGrad") or 0), 0),
    # collectives / parallel plumbing: wire cost is priced from the
    # sharding report's events, not here
    "c_allreduce_sum": _zero, "c_allgather": _zero, "c_broadcast": _zero,
    "c_reducescatter": _zero, "c_sync_calc_stream": _zero,
    "c_sync_comm_stream": _zero, "send": _zero, "recv": _zero,
    # misc framework state
    "beam_search": _ew(4), "beam_search_decode": _zero,
    "linear_lr_warmup": _ew(2), "learning_rate_decay": _ew(2),
    "check_finite_and_unscale": _ew_in(2),
    "update_loss_scaling": _ew_in(2),
}


def _default_rule(ctx):
    """Unknown op: price one flop per output element (the elementwise
    assumption) and record the type — coverage gates pin this set empty
    on the example programs."""
    return ctx.all_out_numel(), 0


# ---------------------------------------------------------------------------
# the analysis
# ---------------------------------------------------------------------------


def _spec_divisor(spec, axis_sizes):
    d = 1
    for e in spec or ():
        for ax in e or ():
            d *= axis_sizes.get(ax, 1)
    return d


def analyze_cost(program, *, machine=DEFAULT_MACHINE, cost_model=None,
                 mesh=None, axis_tags=None, feed_shapes=None,
                 feed_dtypes=None, fetch_names=(), shape_report=None,
                 sharding_report=None, spec_layout=None, param_rules=None,
                 param_specs=None, input_specs=None, num_stages=None):
    """Roofline cost pass over one step of ``program``.

    With a ``mesh`` (or a precomputed ``sharding_report``) every op is
    priced PER DEVICE — flops and bytes divided by its value's shard
    divisor — and the sharding report's predicted collectives are priced
    through the two-level link model. Placement kwargs mirror
    ``CompiledProgram.with_parallel`` so the report describes the compile
    the caller will actually pay. Returns a CostReport."""
    if shape_report is None:
        shape_report = infer_shapes(program, feed_shapes=feed_shapes,
                                    feed_dtypes=feed_dtypes)
    if sharding_report is None and mesh is not None:
        from paddle_tpu.analysis.sharding import analyze_sharding

        sharding_report = analyze_sharding(
            program, mesh, spec_layout=spec_layout,
            param_rules=param_rules, param_specs=param_specs,
            input_specs=input_specs, feed_shapes=feed_shapes,
            shape_report=shape_report,
        )
    if cost_model is None:
        if mesh is not None:
            cost_model = CostModel.for_mesh(mesh, machine=machine,
                                            axis_tags=axis_tags)
        elif sharding_report is not None:
            cost_model = CostModel.for_mesh(
                sharding_report.mesh, machine=machine, axis_tags=axis_tags)
        else:
            cost_model = CostModel.single_device(machine)
    report = CostReport(cost_model)

    value_specs = {}
    axis_sizes = dict(cost_model.axis_sizes)
    if sharding_report is not None:
        value_specs = dict(sharding_report.value_specs)
        value_specs.update(sharding_report.param_specs)

    def shape_of(name):
        info = shape_report.get(name)
        if info is not None and info.shape is not None and not any(
                is_sym(d) for d in info.shape):
            return info.shape
        # declared-metadata fallback, same contract as memory._bytes_of
        v = program.global_block()._find_var_recursive(name)
        if v is not None:
            decl = (feed_shapes or {}).get(name, v.shape)
            if decl is not None and all(
                    d is not None and d >= 0 for d in decl):
                return tuple(int(d) for d in decl)
        return info.shape if info is not None else None

    def bytes_of(name, blk):
        return var_bytes(name, shape_report, value_specs, axis_sizes,
                         blk, feed_shapes)

    def spec_of(name):
        """Spec lookup that resolves grad vars through their forward
        base: the sharding walk never visits grad ops, but GSPMD shards
        a cotangent exactly like its primal."""
        s = value_specs.get(name)
        if s is None and name.endswith("@GRAD"):
            s = value_specs.get(name[: -len("@GRAD")])
        return s

    def op_divisor(op):
        """Per-device work divisor. Matmul family (forward AND grad):
        every one of its 2*M*N*K products is split by whichever mesh axes
        shard M, N, or K — out-spec divisor x contraction divisor, with
        the grad reading the FORWARD geometry (dX and dY reuse the same
        M/N/K sharding). Everything else: the shard divisor of the
        largest-sharded output (grad vars resolve through their
        primal)."""
        mm = op.type in ("mul", "matmul", "matmul_v2", "mul_grad",
                         "matmul_grad", "matmul_v2_grad")
        d = 1
        if mm:
            # forward output spec: Out for the fwd op, Out/Out@GRAD
            # input for the grad op (same value)
            out_name = None
            if op.type.endswith("_grad"):
                for slot in ("Out", "Out@GRAD"):
                    names = op.inputs.get(slot) or ()
                    if names:
                        out_name = names[0]
                        break
            else:
                names = op.outputs.get("Out") or ()
                out_name = names[0] if names else None
            if out_name:
                d *= _spec_divisor(spec_of(out_name), axis_sizes)
            for slot in ("X", "Y"):
                names = op.inputs.get(slot) or ()
                if names:
                    spec = spec_of(names[0])
                    shp = shape_of(names[0])
                    if spec and shp and len(spec) == len(shp):
                        # contraction dim: last of X (un-transposed),
                        # first matrix dim of Y — trailing entry approx
                        cd = spec[-1] if slot == "X" else spec[-2] \
                            if len(spec) >= 2 else None
                        for ax in cd or ():
                            d *= axis_sizes.get(ax, 1)
        else:
            for names in op.outputs.values():
                for n in names:
                    d = max(d, _spec_divisor(spec_of(n), axis_sizes))
        return max(d, 1)

    def segment_flops(op, saved):
        """Replay cost of a recompute_segment_grad's serialized segment:
        (grad_flops, recompute_flops, trans). Ops whose outputs are all
        in `saved` (+ boundary outs) skip the replay."""
        segment = op.attrs.get("__segment__") or ()
        outs = set(op.attrs.get("__out_names__") or ())
        saved = set(saved) | outs
        grad_f = grad_t = rec_f = rec_t = 0

        class _SegOp:
            __slots__ = ("type", "inputs", "outputs", "attrs")

            def __init__(self, t, i, o, a):
                self.type, self.inputs, self.outputs, self.attrs = t, i, o, a

        for (t, ins, outs_d, attrs) in segment:
            seg_op = _SegOp(t, ins, outs_d, attrs)
            f, tr = _FLOP_RULES.get(t, _default_rule)(_Ctx(seg_op, shape_of))
            grad_f += 2 * f          # vjp of the segment ~ 2x forward
            grad_t += 2 * tr
            produced = [n for ns in outs_d.values() for n in ns]
            if any(n not in saved for n in produced):
                rec_f += f
                rec_t += tr
        return grad_f, rec_f, grad_t + rec_t

    block = program.global_block()
    from paddle_tpu.analysis.usedef import sub_block_indices

    def op_cost(op, op_index, blk, scale=1):
        t = op.type
        rule = _FLOP_RULES.get(t)
        known = rule is not None
        ctx = _Ctx(op, shape_of)
        if t == "recompute_segment_grad":
            saved = (op.attrs.get("__segment_saved_names__") or {}).get(
                op.attrs.get("__remat_policy__", "full"), ())
            grad_f, rec_f, trans = segment_flops(op, saved)
            flops = grad_f + rec_f
            known = True
            # HBM: operands/results + the policy-pinned saved values the
            # replay reads back (recomputed values are flops, not bytes —
            # the SAME accounting memory.remat_extra prices peaks with,
            # which is what keeps the two analyzers ordering policies
            # identically: more saved = fewer flops, more bytes)
            hbm = sum(bytes_of(n, blk) or 0
                      for n in set(op.input_names()) | set(op.output_names()))
            hbm += sum(bytes_of(n, blk) or 0 for n in saved)
        else:
            flops, trans = (rule or _default_rule)(ctx)
            if not known:
                report.unknown_ops.add(t)
            hbm = sum(bytes_of(n, blk) or 0
                      for n in set(op.input_names()) | set(op.output_names()))
        div = op_divisor(op)
        cost = OpCost(t, op_index, blk.idx, scale * flops // div,
                      scale * trans // div, scale * hbm, known)
        return cost

    def walk(blk, scale=1, _path=frozenset()):
        for op_index, op in enumerate(blk.ops):
            if op.type in ("feed", "fetch"):
                continue
            subs = list(sub_block_indices(op))
            if op.type in ("pipeline_stack", "pipeline_stack_grad"):
                # the layer body runs once per stacked layer (the grad
                # replays it plus the vjp: ~2x); with a 'stage' mesh axis
                # each device owns L/s of the layers
                stacked = op.inputs.get("StackedParams") or ()
                layers = 0
                if stacked:
                    s0 = shape_of(stacked[0])
                    if s0 and not is_sym(s0[0]):
                        layers = int(s0[0])
                stage_axis = op.attrs.get("stage_axis", "stage")
                stages = axis_sizes.get(stage_axis, 1)
                body_scale = scale * max(layers, 1) // max(stages, 1)
                if op.type == "pipeline_stack_grad":
                    body_scale *= 2
                for bi in subs:
                    if bi not in _path and bi < len(program.blocks):
                        walk(program.block(bi), max(body_scale, 1),
                             _path | {blk.idx})
                continue
            cost = op_cost(op, op_index, blk, scale)
            report.ops.append(cost)
            for bi in subs:
                if bi not in _path and bi < len(program.blocks):
                    # while/cond bodies count once (iteration counts are
                    # dynamic; XLA's cost analysis makes the same call)
                    walk(program.block(bi), scale, _path | {blk.idx})

    walk(block)

    # -- collectives from the sharding report ---------------------------
    if sharding_report is not None:
        batch_axis = "data" if "data" in axis_sizes else (
            sharding_report.mesh.axis_names[0]
            if sharding_report.mesh.axis_names else None)
        for e in sharding_report.events:
            if not e.bytes:
                continue
            axes = [ax for ax in (getattr(e, "axes", None) or ())
                    if axis_sizes.get(ax, 1) > 1]
            if not axes and batch_axis and \
                    axis_sizes.get(batch_axis, 1) > 1:
                # events with no recorded participation (explicit
                # collectives with unresolvable ring bindings) default to
                # the batch axis
                axes = [batch_axis]
            if not axes:
                continue
            secs = cost_model.collective_seconds(e.kind, e.bytes, axes)
            factor = _KIND_FACTOR.get(e.kind, _KIND_FACTOR["all-gather"])
            by_axis_bytes, by_axis_secs = {}, {}
            for ax in axes:
                n = cost_model.axis_sizes.get(ax, 1)
                if n <= 1:
                    continue
                tag = cost_model.tag(ax)
                by_axis_bytes[ax] = int(factor(n) * e.bytes)
                by_axis_secs[ax] = cost_model.machine.link_lat[tag] + \
                    factor(n) * e.bytes / cost_model.machine.link_bw[tag]
            report.collectives.append({
                "kind": e.kind, "cause": e.cause, "var": e.var,
                "bytes": e.bytes, "axes": sorted(axes),
                "tags": {ax: cost_model.tag(ax) for ax in axes},
                "seconds": secs,
                "wire_bytes_by_axis": by_axis_bytes,
                "seconds_by_axis": by_axis_secs,
            })
            report.collective_seconds += secs

    # -- fold through the machine model ---------------------------------
    m = cost_model.machine
    for c in report.ops:
        comp = c.flops / m.peak_flops
        memt = c.hbm_bytes / m.hbm_bw
        c.seconds = max(comp, memt)
        c.intensity = c.flops / c.hbm_bytes if c.hbm_bytes else float(
            "inf") if c.flops else 0.0
        c.bound = "compute" if (c.hbm_bytes == 0 or c.intensity >= m.ridge) \
            else "memory"
        report.total_flops += c.flops
        report.total_transcendentals += c.transcendentals
        report.total_hbm_bytes += c.hbm_bytes
        report.compute_seconds += comp
        report.memory_seconds += memt
        report.roofline_seconds += c.seconds

    report.pipeline = pipeline_bubble_report(
        program, shape_report=shape_report, axis_sizes=axis_sizes,
        num_stages=num_stages,
    )
    if report.unknown_ops:
        report.diagnostics.append(Diagnostic(
            "warning", "unknown-op-cost",
            f"{len(report.unknown_ops)} op type(s) priced by the default "
            f"elementwise rule: {sorted(report.unknown_ops)[:8]} — add "
            f"FLOP rules in analysis/cost.py",
        ))
    return report


# ---------------------------------------------------------------------------
# linters over the report
# ---------------------------------------------------------------------------


def hierarchical_collective_diagnostics(report):
    """Flag all-reduces whose participation spans a ``dcn``-tagged axis
    together with ``ici``-tagged axes: the naive single-level form puts
    the FULL payload on DCN; the two-level form (reduce-scatter over ICI,
    all-reduce of the 1/n_ici shard over DCN, all-gather over ICI) cuts
    DCN bytes by the ICI degree. Returns error Diagnostics with the
    predicted saving."""
    cm = report.cost_model
    diags = []
    for c in report.collectives:
        if c["kind"] != "all-reduce":
            continue
        dcn_axes = [ax for ax in c["axes"] if cm.tag(ax) == "dcn"
                    and cm.axis_sizes.get(ax, 1) > 1]
        ici = 1
        for ax in c["axes"]:
            if cm.tag(ax) == "ici":
                ici *= cm.axis_sizes.get(ax, 1)
        if not dcn_axes or ici <= 1:
            continue
        saved = int(c["bytes"] * (1 - 1.0 / ici))
        diags.append(Diagnostic(
            "error", "dcn-allreduce-not-hierarchical",
            f"predicted all-reduce of '{c['var']}' ({c['bytes']} bytes, "
            f"cause={c['cause']}) crosses DCN axis "
            f"{'/'.join(dcn_axes)} at full payload — use the two-level "
            f"form (reduce-scatter over ICI, all-reduce the 1/{ici} "
            f"shard over DCN, all-gather over ICI) and save {saved} "
            f"DCN bytes per step",
            var=c["var"],
        ))
    return diags


def check_cost_budgets(report, *, step_ms=0, collective_kb=0,
                       min_mfu=0.0):
    """Budget gates over a CostReport: predicted step time, per-axis
    on-wire collective bytes, and a minimum-MFU floor (the static half of
    the >=50% MFU north star). Zero disables a gate."""
    diags = []
    if step_ms and report.step_seconds * 1e3 > step_ms:
        diags.append(Diagnostic(
            "error", "step-time-over-budget",
            f"predicted step time {report.step_seconds * 1e3:.3f} ms "
            f"exceeds the {step_ms} ms budget (compute "
            f"{report.compute_seconds * 1e3:.3f} ms, memory "
            f"{report.memory_seconds * 1e3:.3f} ms, collectives "
            f"{report.collective_seconds * 1e3:.3f} ms)",
        ))
    if collective_kb:
        for ax, ent in report.per_axis().items():
            if ent["wire_bytes"] > collective_kb * 1024:
                diags.append(Diagnostic(
                    "error", "axis-collective-over-budget",
                    f"axis '{ax}' ({ent['tag']}) carries "
                    f"{ent['wire_bytes']} on-wire bytes per step "
                    f"(> budget {collective_kb} KB) across "
                    f"{ent['collectives']} collective(s)",
                ))
    if min_mfu and report.total_flops and report.mfu < min_mfu:
        diags.append(Diagnostic(
            "error", "mfu-under-floor",
            f"predicted MFU {report.mfu:.4f} is below the {min_mfu} "
            f"floor on {report.cost_model.machine.name}",
        ))
    return diags


# ---------------------------------------------------------------------------
# pipeline bubble estimation
# ---------------------------------------------------------------------------


def pipeline_bubble_report(program, *, shape_report=None, axis_sizes=None,
                           num_stages=None, feed_shapes=None):
    """GPipe bubble fractions for every ``pipeline_stack`` op: with s
    stages and m microbatches, (s-1)/(m+s-1) of each device's time is
    spent idle at the schedule's edges — the number the 1F1B PR must
    beat. Stages resolve from the mesh's stage-axis size (``axis_sizes``)
    or the ``num_stages`` override; a stage-less (scan fallback) run has
    no bubble."""
    if shape_report is None:
        shape_report = infer_shapes(program, feed_shapes=feed_shapes)
    axis_sizes = axis_sizes or {}
    out = []
    for blk in program.blocks:
        for op_index, op in enumerate(blk.ops):
            if op.type != "pipeline_stack":
                continue
            m = int(op.attrs.get("num_microbatches", 1) or 1)
            stage_axis = op.attrs.get("stage_axis", "stage")
            s = int(num_stages or axis_sizes.get(stage_axis, 1) or 1)
            stacked = op.inputs.get("StackedParams") or ()
            layers = None
            if stacked:
                info = shape_report.get(stacked[0])
                if info is not None and info.shape and \
                        not is_sym(info.shape[0]):
                    layers = int(info.shape[0])
            # schedule-aware (PipelinedStack(schedule=...)); the default
            # is gpipe's (s-1)/(m+s-1), tests/test_cost_analysis.py::
            # test_pipeline_bubble_gpipe_fraction
            kind = op.attrs.get("schedule") or "gpipe"
            if kind != "gpipe" and s > 1:
                from paddle_tpu.parallel.pipeline_runtime.schedule import (
                    predicted_bubble,
                )

                bubble = predicted_bubble(
                    kind, s, m, op.attrs.get("interleave") or 2)
            else:
                bubble = (s - 1) / (m + s - 1) if s > 1 else 0.0
            out.append({
                "op_index": op_index, "block": blk.idx,
                "stage_axis": stage_axis, "stages": s,
                "num_microbatches": m, "layers": layers,
                "schedule": kind,
                "bubble_fraction": round(bubble, 6),
            })
    return out
