"""Static PartitionSpec propagation + pre-compile collective-cost linter.

Everything PR 7 proved by compiling tiny-BERT on an 8-device mesh and
grepping the optimized HLO (utils/hlo.py weight_shaped_collectives /
collective_byte_report) is *statically decidable on the IR*: the GSPMD
contract is deterministic enough that "which edges force a collective,
and how many bytes does it move" follows from (program, mesh, parameter
layout) alone. This pass walks the program once — no XLA in the loop —
and emits a **resharding report**:

  * seeds: parameters from the canonical SpecLayout registry (or a
    param_rules pattern table / exact param_specs — the same three
    placement sources CompiledProgram.with_parallel accepts), feeds from
    the mesh batch axis;
  * propagation: specs pushed through matmul/elementwise/transpose/
    reduce/lookup ops with GSPMD-style transfer rules; a sharded
    contraction met on both sides predicts the Megatron epilogue
    all-reduce, met on one side predicts an operand all-gather;
  * parameter-level laws: every trainable parameter pays a grad-sync
    all-reduce over the data axis (bytes = its SHARD, which is why
    sharding the layout shrinks the wire); a parameter left REPLICATED
    in a tensor-sharded program pays a full weight-sized all-gather to
    reconcile its shard-computed update — the exact failure
    tests/test_hlo.py::test_tp_mesh_no_weight_sized_collectives pinned
    and PR 7's registry closed.

``collective_budget_diagnostics`` turns the report into a linter with a
configurable byte budget (tools/lint_program.py ``collectives
--budget-kb``); ``weight_sized_events`` is the static twin of
utils/hlo.py ``weight_shaped_collectives``. tests/test_hlo.py::
test_static_sharding_analysis_predicts_the_live_collectives holds the
predictions against the lowered HLO on the registry and Megatron arms.
"""


from paddle_tpu.analysis.shapes import infer_shapes, is_sym
from paddle_tpu.analysis.verify import Diagnostic
from paddle_tpu.core.dtypes import dtype_size

__all__ = [
    "ReshardEvent", "ShardingReport", "analyze_sharding",
    "collective_budget_diagnostics", "weight_param_shapes",
    "weight_sized_events",
]


class ReshardEvent:
    """One predicted collective: what moves, why, and how many bytes per
    device it materializes (the same accounting as utils/hlo.py
    collective_byte_report: the largest value the collective touches)."""

    __slots__ = ("kind", "cause", "var", "op_type", "op_index", "block_idx",
                 "bytes", "shape", "spec", "axes")

    def __init__(self, kind, cause, var, bytes_, shape, spec=None,
                 op_type=None, op_index=None, block_idx=None, axes=()):
        self.kind = kind          # all-reduce | all-gather | all-to-all
        self.cause = cause
        self.var = var
        self.bytes = bytes_       # None when a symbolic dim survived
        self.shape = tuple(shape) if shape is not None else None
        self.spec = spec
        self.op_type = op_type
        self.op_index = op_index
        self.block_idx = block_idx
        # mesh axes the collective's ring spans — what the cost model
        # (analysis/cost.py) prices through the ici/dcn link tiers.
        self.axes = tuple(axes or ())

    def to_json(self):
        return {
            "kind": self.kind, "cause": self.cause, "var": self.var,
            "bytes": self.bytes,
            "shape": list(self.shape) if self.shape else None,
            "spec": self.spec, "op_type": self.op_type,
            "op_index": self.op_index,
        }

    def __repr__(self):
        return (f"ReshardEvent({self.kind}, {self.cause}, var={self.var}, "
                f"bytes={self.bytes}, shape={self.shape})")


class ShardingReport:
    """events + the resolved per-var specs the propagation settled on."""

    def __init__(self, mesh):
        self.mesh = mesh
        self.tensor_sharded = False  # any param sharded over a tp/fsdp axis
        self.events = []
        self.param_specs = {}     # persistable name -> spec tuple
        self.value_specs = {}     # activation name -> spec tuple
        self.diagnostics = []

    def max_bytes(self):
        return max((e.bytes for e in self.events if e.bytes), default=0)

    def total_bytes(self):
        return sum(e.bytes for e in self.events if e.bytes)

    def by_kind(self):
        out = {}
        for e in self.events:
            ent = out.setdefault(
                e.kind, {"count": 0, "total_bytes": 0, "max_bytes": 0}
            )
            ent["count"] += 1
            if e.bytes:
                ent["total_bytes"] += e.bytes
                ent["max_bytes"] = max(ent["max_bytes"], e.bytes)
        return out

    def to_json(self):
        return {
            "events": [e.to_json() for e in self.events],
            "by_kind": self.by_kind(),
            "max_bytes": self.max_bytes(),
            "total_bytes": self.total_bytes(),
            "param_specs": {
                n: _spec_str(s) for n, s in sorted(self.param_specs.items())
            },
        }


# ---------------------------------------------------------------------------
# spec plumbing — a spec here is a tuple over dims; each entry is None or a
# tuple of mesh axis names (the normalized form of a PartitionSpec)
# ---------------------------------------------------------------------------


def _norm_spec(spec, rank):
    """PartitionSpec/tuple -> normalized tuple of length `rank`."""
    entries = tuple(spec) if spec is not None else ()
    out = []
    for i in range(rank):
        e = entries[i] if i < len(entries) else None
        if e is None:
            out.append(None)
        elif isinstance(e, tuple):
            out.append(tuple(e))
        else:
            out.append((str(e),))
    return tuple(out)


def _spec_str(spec):
    if spec is None or all(e is None for e in spec):
        return "replicated"
    return str(tuple(
        (e[0] if len(e) == 1 else e) if e is not None else None
        for e in spec
    ))


def _is_replicated(spec):
    return spec is None or all(e is None for e in spec)


def _spec_axes(spec):
    axes = set()
    for e in spec or ():
        if e:
            axes.update(e)
    return axes


def _divisor(spec, axis_sizes):
    d = 1
    for e in spec or ():
        for ax in e or ():
            d *= axis_sizes.get(ax, 1)
    return d


def _shard_bytes(shape, spec, axis_sizes, dtype):
    """Per-device bytes of `shape` under `spec` (None on symbolic dims)."""
    if shape is None:
        return None
    n = 1
    for d in shape:
        if is_sym(d):
            return None
        n *= max(int(d), 1)
    n *= dtype_size(dtype)
    return n // max(_divisor(spec, axis_sizes), 1)


def _full_bytes(shape, dtype):
    return _shard_bytes(shape, None, {}, dtype)


# ---------------------------------------------------------------------------
# parameter placement resolution (mirrors CompiledProgram.with_parallel)
# ---------------------------------------------------------------------------


def _resolve_param_specs(program, mesh, spec_layout, param_rules,
                         param_specs, names_shapes):
    """name -> normalized spec tuple for the program's persistable state,
    through the same three placement sources the compiler accepts."""
    names = [n for n, _s in names_shapes]
    shapes = [s for _n, s in names_shapes]
    if spec_layout is not None:
        shardings = spec_layout.derive_shardings(
            program, names, shapes, mesh, overrides=param_specs,
        )
        return {
            n: _norm_spec(shardings[n].spec, len(s))
            for n, s in names_shapes
        }
    if param_rules is not None or param_specs:
        from paddle_tpu.parallel.sharding import derive_shardings

        shardings = derive_shardings(
            names, shapes, mesh, rules=param_rules, overrides=param_specs,
        )
        return {
            n: _norm_spec(shardings[n].spec, len(s))
            for n, s in names_shapes
        }
    return {n: _norm_spec(None, len(s)) for n, s in names_shapes}


def _persistable_state(program, shape_report):
    """(name, concrete shape) for every persistable var the program reads
    or writes — the static analog of the step's scope inputs + outputs."""
    touched = set()
    for block in program.blocks:
        for op in block.ops:
            touched.update(op.input_names())
            touched.update(op.output_names())
    out = []
    for v in program.global_block().vars.values():
        if not v.persistable or v.name not in touched:
            continue
        info = shape_report.get(v.name)
        shape = info.shape if info is not None else None
        if shape is None or any(is_sym(d) for d in shape):
            shape = tuple(d for d in (v.shape or ()) if d is not None)
        if shape is None:
            continue
        out.append((v.name, tuple(int(d) for d in shape)))
    return out


# ---------------------------------------------------------------------------
# the analysis
# ---------------------------------------------------------------------------


def analyze_sharding(program, mesh, *, spec_layout=None, param_rules=None,
                     param_specs=None, input_specs=None, feed_shapes=None,
                     feed_names=(), shape_report=None, batch_axis=None):
    """Whole-program static resharding analysis. Returns a ShardingReport.

    Placement arguments mirror ``CompiledProgram.with_parallel`` — pass the
    same registry/rules/overrides the compile would use and the report
    describes the collectives THAT compile will pay."""
    from paddle_tpu.parallel.sharding import check_spec
    from paddle_tpu.parallel.spec_layout import TENSOR_AXIS_NAMES

    if shape_report is None:
        shape_report = infer_shapes(program, feed_shapes=feed_shapes)
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if batch_axis is None:
        batch_axis = "data" if "data" in axis_sizes else mesh.axis_names[0]
    report = ShardingReport(mesh)

    # -- parameter placement -------------------------------------------
    names_shapes = _persistable_state(program, shape_report)
    report.param_specs = _resolve_param_specs(
        program, mesh, spec_layout, param_rules, param_specs, names_shapes,
    )
    param_shapes = dict(names_shapes)

    def dtype_of(name):
        info = shape_report.get(name)
        return info.dtype if info is not None else "float32"

    def shape_of(name):
        info = shape_report.get(name)
        return info.shape if info is not None else None

    # -- feed placement -------------------------------------------------
    env = dict(report.param_specs)
    input_specs = input_specs or {}
    feed_names = set(feed_names)
    data_axes = set()   # every mesh axis the feeds are sharded over —
    # the ring the grad-sync all-reduce spans (multi-axis under dp×dcn)
    for block in program.blocks:
        for v in block.vars.values():
            if v.is_data or v.name in feed_names:
                shape = shape_of(v.name)
                rank = len(shape) if shape is not None else 1
                spec = input_specs.get(v.name)
                if spec is None:
                    from jax.sharding import PartitionSpec as P

                    spec = P(batch_axis)
                if shape is not None and \
                        not any(is_sym(d) for d in shape):
                    spec = check_spec(tuple(shape), spec, mesh)
                env[v.name] = _norm_spec(spec, rank)
                data_axes.update(
                    ax for ax in _spec_axes(env[v.name])
                    if axis_sizes.get(ax, 1) > 1
                )

    # -- propagation + per-edge events ----------------------------------
    def emit(kind, cause, var, bytes_, shape, spec=None, op=None,
             op_index=None, block=None, axes=()):
        report.events.append(ReshardEvent(
            kind, cause, var, bytes_, shape, spec=spec,
            op_type=op.type if op is not None else None,
            op_index=op_index,
            block_idx=block.idx if block is not None else None,
            axes=tuple(sorted(set(axes or ()))),
        ))

    def get_spec(name):
        spec = env.get(name)
        if spec is not None:
            return spec
        shape = shape_of(name)
        return _norm_spec(None, len(shape) if shape else 0)

    def walk(block, _path=frozenset()):
        from paddle_tpu.analysis.usedef import sub_block_indices

        for op_index, op in enumerate(block.ops):
            if op.type in ("feed", "fetch"):
                continue
            _transfer(op, op_index, block)
            for idx in sub_block_indices(op):
                if idx in _path or idx >= program.num_blocks() or \
                        idx == block.idx:
                    continue
                walk(program.block(idx), _path | {block.idx})

    def _matmul_like(op, op_index, block, x_name, y_name, out_name,
                     x_contract_dim, y_contract_dim, out_spec_fn):
        xs, ys = get_spec(x_name), get_spec(y_name)
        cx = xs[x_contract_dim] if x_contract_dim < len(xs) else None
        cy = ys[y_contract_dim] if y_contract_dim < len(ys) else None
        out_shape = shape_of(out_name)
        out_spec = out_spec_fn(xs, ys)
        if cx is not None or cy is not None:
            # a sharded contraction dim — on either side — makes the
            # matmul a shard-local partial sum: GSPMD slices a replicated
            # other side for free (dynamic-slice is local) and pays ONE
            # all-reduce of the output, the Megatron epilogue. It does
            # NOT gather the sharded operand; weight gathers only come
            # from the replicated-update law below.
            emit("all-reduce", "matmul-partial-sum", out_name,
                 _shard_bytes(out_shape, out_spec, axis_sizes,
                              dtype_of(out_name)),
                 out_shape, _spec_str(out_spec), op, op_index, block,
                 axes=tuple(cx or ()) + tuple(cy or ()))
        env[out_name] = out_spec

    def _transfer(op, op_index, block):
        t = op.type
        outs = [n for ns in op.outputs.values() for n in ns]
        if t in ("mul",):
            xn, yn = (op.inputs.get("X") or [None])[0], \
                (op.inputs.get("Y") or [None])[0]
            on = (op.outputs.get("Out") or [None])[0]
            if None in (xn, yn, on):
                return
            xnc = op.attrs.get("x_num_col_dims", 1)
            ync = op.attrs.get("y_num_col_dims", 1)
            xshape, yshape = shape_of(xn), shape_of(yn)
            if xshape is None or yshape is None:
                return

            def out_spec(xs, ys):
                return tuple(xs[:xnc]) + tuple(ys[ync:])

            _matmul_like(op, op_index, block, xn, yn, on,
                         min(xnc, len(xshape) - 1), 0, out_spec)
        elif t in ("matmul", "matmul_v2"):
            xn, yn = (op.inputs.get("X") or [None])[0], \
                (op.inputs.get("Y") or [None])[0]
            on = (op.outputs.get("Out") or [None])[0]
            if None in (xn, yn, on):
                return
            xshape, yshape = shape_of(xn), shape_of(yn)
            if xshape is None or yshape is None or len(xshape) < 2 \
                    or len(yshape) < 2:
                return
            tx = op.attrs.get("transpose_X", op.attrs.get("trans_x", False))
            ty = op.attrs.get("transpose_Y", op.attrs.get("trans_y", False))
            xc = len(xshape) - (2 if tx else 1)
            yc = len(yshape) - (1 if ty else 2)

            def out_spec(xs, ys):
                xrow = xs[len(xshape) - (1 if tx else 2)] \
                    if len(xshape) >= 2 else None
                ycol = ys[len(yshape) - (2 if ty else 1)] \
                    if len(yshape) >= 2 else None
                out_shape = shape_of(on)
                rank = len(out_shape) if out_shape else 2
                batch = tuple(xs[:max(rank - 2, 0)])
                return tuple(batch) + (xrow, ycol)

            _matmul_like(op, op_index, block, xn, yn, on, xc, yc, out_spec)
        elif t in ("lookup_table", "lookup_table_v2"):
            wn = (op.inputs.get("W") or [None])[0]
            on = (op.outputs.get("Out") or [None])[0]
            if wn is None or on is None:
                return
            wspec = get_spec(wn)
            out_shape = shape_of(on)
            rank = len(out_shape) if out_shape else 2
            ids_spec = get_spec((op.inputs.get("Ids") or [""])[0])
            out_spec = tuple(ids_spec[: rank - 1]) + (
                wspec[-1] if wspec else None,)
            if wspec and wspec[0] is not None:
                # vocab-sharded table: GSPMD's gather strategy is a
                # masked shard-local take + all-reduce of the result
                emit("all-reduce", "sharded-vocab-lookup", on,
                     _shard_bytes(out_shape, out_spec, axis_sizes,
                                  dtype_of(on)),
                     out_shape, _spec_str(out_spec), op, op_index, block,
                     axes=tuple(wspec[0] or ()))
            env[on] = _norm_spec(out_spec, rank)
        elif t in ("reduce_sum", "reduce_mean", "mean",
                   "softmax_with_cross_entropy", "cross_entropy"):
            xn = (op.inputs.get("X") or op.inputs.get("Logits")
                  or [None])[0]
            if xn is None:
                return
            xs = get_spec(xn)
            for on in outs:
                oshape = shape_of(on)
                rank = len(oshape) if oshape is not None else 0
                # keep leading dims' placement where ranks line up
                env[on] = _norm_spec(tuple(xs[:rank]), rank)
        elif t == "c_allreduce_sum" or t.startswith("c_allreduce"):
            xn = (op.inputs.get("X") or [None])[0]
            if xn is None:
                return
            emit("all-reduce", "explicit-collective", xn,
                 _shard_bytes(shape_of(xn), get_spec(xn), axis_sizes,
                              dtype_of(xn)),
                 shape_of(xn), _spec_str(get_spec(xn)), op, op_index,
                 block)
            for on in outs:
                env[on] = get_spec(xn)
        elif t in ("transpose2", "transpose"):
            xn = (op.inputs.get("X") or [None])[0]
            on = (op.outputs.get("Out") or [None])[0]
            perm = op.attrs.get("axis")
            if None in (xn, on) or perm is None:
                return
            xs = get_spec(xn)
            if len(perm) == len(xs):
                env[on] = tuple(xs[p] for p in perm)
        elif t in ("cast", "scale", "dropout", "relu", "gelu", "tanh",
                   "sigmoid", "assign", "softmax", "log_softmax",
                   "layer_norm", "elementwise_add", "elementwise_sub",
                   "elementwise_mul", "elementwise_div"):
            xn = (op.inputs.get("X") or [None])[0]
            if xn is None:
                return
            xs = get_spec(xn)
            for on in outs:
                oshape = shape_of(on)
                if oshape is not None and len(oshape) == len(xs):
                    env[on] = xs
        elif t == "batched_gather":
            xn = (op.inputs.get("X") or [None])[0]
            idxn = (op.inputs.get("Index") or [None])[0]
            on = (op.outputs.get("Out") or [None])[0]
            if None in (xn, on):
                return
            xs = get_spec(xn)
            idxs = get_spec(idxn) if idxn else ()
            oshape = shape_of(on)
            rank = len(oshape) if oshape is not None else len(xs)
            # batch dim keeps its placement; the gathered dim follows the
            # index; trailing dims follow the source
            spec = (xs[0] if xs else None,)
            spec += tuple(idxs[1:2]) if len(idxs) > 1 else (None,)
            spec += tuple(xs[2:rank])
            env[on] = _norm_spec(spec, rank)
        elif t in ("reshape2", "reshape"):
            xn = (op.inputs.get("X") or [None])[0]
            on = (op.outputs.get("Out") or [None])[0]
            if None in (xn, on):
                return
            xs = get_spec(xn)
            oshape, xshape = shape_of(on), shape_of(xn)
            if oshape is not None and xshape is not None and \
                    len(oshape) == len(xshape):
                env[on] = xs
            elif oshape is not None and xshape is not None and \
                    len(xshape) and len(oshape) and \
                    xshape[0] == oshape[0]:
                # leading dim preserved: keep its placement, drop the rest
                env[on] = _norm_spec((xs[0],), len(oshape)) \
                    if xs else _norm_spec(None, len(oshape))
        # everything else: outputs default to replicated (optimistic — an
        # unknown op never predicts a phantom collective)

    walk(program.global_block())
    report.value_specs = {
        n: s for n, s in env.items() if n not in report.param_specs
    }

    # -- parameter-level laws -------------------------------------------
    has_backward = any(
        op.type.endswith("_grad") or op.attrs.get("op_role", 0) in (1, 2)
        for b in program.blocks for op in b.ops
    )
    written = set()
    read = set()
    for b in program.blocks:
        for op in b.ops:
            written.update(op.output_names())
            read.update(op.input_names())

    tensor_sharded = any(
        _spec_axes(s) & set(TENSOR_AXIS_NAMES)
        for s in report.param_specs.values()
    )
    report.tensor_sharded = tensor_sharded
    data_size = axis_sizes.get(batch_axis, 1)

    # trainable parameters ONLY: optimizer slots (moments, beta pows) and
    # scheduler counters are read+written persistables too, but their
    # updates are computed locally from the already-synced grad — emitting
    # events for them would predict phantom wire (3x for Adam)
    trainable = {p.name for p in program.all_parameters()}
    for name, shape in names_shapes:
        spec = report.param_specs.get(name)
        if name not in trainable or name not in written or not has_backward:
            continue
        dt = dtype_of(name)
        if data_size > 1 and name in read:
            sync_axes = set(data_axes or {batch_axis})
            zero_axes = _spec_axes(spec) & sync_axes
            if zero_axes:
                # ZeRO layout: the parameter is sharded over (some of) the
                # feed-sharded axes, so GSPMD lowers its grad sync as a
                # reduce-scatter over those axes plus an all-reduce of the
                # 1/n shard over the REST — the two-level hierarchy the
                # dcn linter asks for when the rest is the dcn tier. The
                # decomposed events carry their own axes, so the
                # hierarchical diagnostic (which prices all-reduces whose
                # span mixes dcn with >1 ici device) stays quiet.
                rs_spec = tuple(
                    (tuple(a for a in (e or ()) if a not in zero_axes)
                     or None)
                    for e in (spec or ())
                ) or None
                emit("reduce-scatter", "grad-sync", name,
                     _shard_bytes(shape, rs_spec, axis_sizes, dt), shape,
                     _spec_str(spec), axes=sorted(zero_axes))
                rest = sync_axes - zero_axes
                if rest:
                    emit("all-reduce", "grad-sync", name,
                         _shard_bytes(shape, spec, axis_sizes, dt), shape,
                         _spec_str(spec), axes=sorted(rest))
            else:
                # gradient synchronization over the data axes: bytes = the
                # parameter's SHARD (this is why layout sharding shrinks
                # wire); the ring spans EVERY axis the feeds shard over
                # (dp×dcn runs sync across both tiers — what the
                # hierarchical linter prices)
                emit("all-reduce", "grad-sync", name,
                     _shard_bytes(shape, spec, axis_sizes, dt), shape,
                     _spec_str(spec), axes=data_axes or {batch_axis})
        if tensor_sharded and _is_replicated(spec) and len(shape) >= 1:
            # replicated parameter in a tensor-sharded program: its update
            # is computed shard-local (the activations feeding its grad
            # are sharded), then GSPMD all-gathers the FULL result to
            # honor the replicated out-pin — the weight-sized collective
            # class PR 7 eliminated for registry layouts
            emit("all-gather", "replicated-param-update", name,
                 _full_bytes(shape, dt), shape, "replicated",
                 axes=[ax for ax in axis_sizes
                       if ax in TENSOR_AXIS_NAMES
                       and axis_sizes.get(ax, 1) > 1])

    report.events.sort(key=lambda e: -(e.bytes or 0))
    return report


# ---------------------------------------------------------------------------
# linters over the report
# ---------------------------------------------------------------------------


def collective_budget_diagnostics(report, budget_bytes):
    """Error diagnostics for every predicted collective moving more than
    `budget_bytes` per device — the pre-compile wire-volume gate."""
    diags = []
    for e in report.events:
        if e.bytes is not None and e.bytes > budget_bytes:
            diags.append(Diagnostic(
                "error", "collective-over-budget",
                f"predicted {e.kind} of '{e.var}' moves {e.bytes} bytes "
                f"(> budget {budget_bytes}): cause={e.cause}, "
                f"shape={list(e.shape) if e.shape else '?'}, "
                f"spec={e.spec}",
                op_index=e.op_index, op_type=e.op_type, var=e.var,
            ))
    return diags


def weight_param_shapes(program):
    """THE definition of the 'weight-sized' shape set: rank>=2 trainable
    parameters. Shared by the compiler's spec_layout auto-gate, the CLI
    linter, and the evidence generator so they cannot silently diverge
    on what counts as a weight."""
    return [tuple(p.shape) for p in program.all_parameters()
            if p.shape and len(p.shape) >= 2]


def weight_sized_events(report, param_shapes):
    """Predicted collectives that move a FULL (unsharded) rank>=2 weight —
    the static twin of utils/hlo.py weight_shaped_collectives. A correct
    layout predicts none; the count gates flipping spec_layout on by
    default (compiler.py)."""
    shapes = {tuple(s) for s in param_shapes if len(tuple(s)) >= 2}
    out = []
    for e in report.events:
        if e.shape is None or len(e.shape) < 2:
            continue
        if tuple(e.shape) in shapes and e.cause == "replicated-param-update":
            out.append(e)
        elif tuple(e.shape) in shapes and e.cause == "grad-sync" and \
                e.spec == "replicated" and report.tensor_sharded:
            # in a TENSOR-SHARDED program the grad all-reduce of a weight
            # the layout left replicated moves the full weight — avoidable
            # weight-sized wire volume (plain-DP programs are exempt:
            # full-grad sync is the contract there, not a layout bug)
            out.append(e)
    return out
