"""Liveness-driven peak-HBM estimation + donation-safety checking.

The reference sizes memory by running its memory-optimize pass over an
SSA graph (paddle/fluid/framework/ir/memory_optimize_pass/); on TPU the
binding question is different — "does this program FIT per device, and
what does donation buy" — and it is answerable statically: shapes from
analysis/shapes.py, per-device shard sizes from analysis/sharding.py,
liveness from analysis/usedef.py, donation from the lowering plan.

``estimate_peak_hbm`` walks the global block in execution order and
reports the peak of

    persistent state (params + optimizer slots, SHARDED sizes)
  + live intermediates at the worst program point (feeds included;
    a var is live from its producer to its last reader or fetch)
  + the no-donation penalty (without aliasing, every written persistable
    transiently exists twice: old buffer + new value)

``check_donation_safety`` is the hard-error gate ahead of lowering
(core/lowering.py runs it on every donated plan): a donated buffer is
consumed by the step, so a plan that fetches it, aliases it twice, or
reads it after its in-place update is wrong BEFORE any tracing happens:

  * donated-var-fetched      — the fetch would return a dead buffer
  * donated-var-aliased-twice— duplicate donation / donated AND readonly
  * donated-not-written      — destroyed without a write-back value
  * read-after-donate        — a forward/backward op reads the var after
                               an optimizer op rewrote it in place: the
                               read observes the updated value, silently
                               changing the step's math
"""

from paddle_tpu.analysis.shapes import infer_shapes, is_sym
from paddle_tpu.analysis.usedef import UseDefMap
from paddle_tpu.analysis.verify import Diagnostic
from paddle_tpu.core.dtypes import dtype_size

__all__ = ["MemoryReport", "estimate_peak_hbm", "check_donation_safety",
           "check_hbm_budget", "remat_hbm_delta"]

_OP_ROLE_BACKWARD = 1
_OP_ROLE_OPTIMIZE = 2


class MemoryReport:
    def __init__(self):
        self.persistent_bytes = 0
        self.peak_intermediate_bytes = 0
        self.peak_op_index = None
        self.peak_op_type = None
        self.no_donation_extra_bytes = 0
        self.donate = True
        self.unknown_vars = []
        self.diagnostics = []
        self.timeline = []  # (op_index, op_type, live_intermediate_bytes)

    @property
    def peak_total_bytes(self):
        extra = 0 if self.donate else self.no_donation_extra_bytes
        return (self.persistent_bytes + self.peak_intermediate_bytes
                + extra)

    def to_json(self):
        return {
            "peak_total_bytes": self.peak_total_bytes,
            "persistent_bytes": self.persistent_bytes,
            "peak_intermediate_bytes": self.peak_intermediate_bytes,
            "peak_op_index": self.peak_op_index,
            "peak_op_type": self.peak_op_type,
            "donate": self.donate,
            "no_donation_extra_bytes": self.no_donation_extra_bytes,
            "unknown_vars": sorted(self.unknown_vars)[:32],
        }


def var_bytes(name, shape_report, value_specs, axis_sizes, block=None,
              feed_shapes=None):
    """Per-device byte size of `name` (None when unresolvable): inferred
    shape, declared-metadata fallback, shard divisors from `value_specs`.
    Public so analysis/cost.py prices HBM traffic with the SAME resolver
    that prices peaks here — the agreement test_cost_analysis.py pins."""
    return _bytes_of(name, shape_report, value_specs, axis_sizes,
                     block=block, feed_shapes=feed_shapes)


def _bytes_of(name, shape_report, value_specs, axis_sizes, block=None,
              feed_shapes=None):
    info = shape_report.get(name)
    shape = info.shape if info is not None else None
    dtype = info.dtype if info is not None else None
    if (shape is None or any(is_sym(d) for d in shape)) and block is not None:
        # ops without a propagation rule never pull their operands into
        # the report — fall back to the declared metadata + feed binding
        v = block._find_var_recursive(name)
        if v is not None:
            decl = (feed_shapes or {}).get(name, v.shape)
            if decl is not None and all(
                    d is not None and d >= 0 for d in decl):
                shape = tuple(int(d) for d in decl)
                dtype = dtype or v.dtype
    if shape is None:
        return None
    n = 1
    for d in shape:
        if is_sym(d):
            return None
        n *= max(int(d), 1)
    n *= dtype_size(dtype)
    spec = value_specs.get(name) if value_specs else None
    if spec:
        for entry in spec:
            for ax in entry or ():
                n //= max(axis_sizes.get(ax, 1), 1)
    return n


def estimate_peak_hbm(program, *, feed_shapes=None, fetch_names=(),
                      donate=True, shape_report=None,
                      sharding_report=None, kernel_path=None):
    """Static per-device peak-HBM upper bound for one step of `program`.

    ``sharding_report`` (analysis/sharding.py) supplies per-var specs and
    the mesh; without it every buffer is counted full-size (single
    device). Returns a MemoryReport; ``unknown_vars`` lists names whose
    size could not be resolved (symbolic dims with no feed binding) —
    they are excluded from the totals, so bind the feeds for tight
    numbers.

    ``kernel_path`` models the Pallas kernel registry
    (paddle_tpu/kernels/): a fused attention op's COMPOSITE fallback
    materializes dense intermediates (the paged [S, L, H] gather views)
    that the kernel keeps in VMEM. False counts those composite
    internals; True counts none; None (default) consults the live
    registry selection for this process — so the estimate tracks what
    the lowering will actually emit. Remat policies are accounted
    regardless: a ``recompute_segment_grad`` op's
    ``__segment_saved_names__[policy]`` vars stay live from the end of
    its forward segment to the grad op (the span the default
    save-nothing policy frees)."""
    if shape_report is None:
        shape_report = infer_shapes(program, feed_shapes=feed_shapes)
    value_specs = {}
    axis_sizes = {}
    if sharding_report is not None:
        value_specs = dict(sharding_report.value_specs)
        value_specs.update(sharding_report.param_specs)
        axis_sizes = dict(zip(sharding_report.mesh.axis_names,
                              sharding_report.mesh.devices.shape))
    report = MemoryReport()
    report.donate = donate
    block = program.global_block()
    usedef = UseDefMap(block, fetch_names=fetch_names)

    touched = set()
    for op in block.ops:
        touched |= usedef.reads_of(op) | usedef.writes_of(op)

    # a var's size/persistability never changes mid-walk, and the
    # liveness passes revisit the same names once per op — memoize both
    # or the walk is O(ops x live-set) recursive var lookups. Keyed by
    # block too: sub-block-local names can shadow parent names.
    pmemo = {}
    memo = {}

    def persistable(name, blk=block):
        key = (blk.idx, name)
        if key not in pmemo:
            v = blk._find_var_recursive(name)
            pmemo[key] = v is not None and v.persistable
        return pmemo[key]

    def bytes_of(name, blk=block):
        key = (blk.idx, name)
        if key not in memo:
            memo[key] = _bytes_of(name, shape_report, value_specs,
                                  axis_sizes, blk, feed_shapes)
        return memo[key]

    unknown = set()
    for name in sorted(touched):
        if not persistable(name):
            continue
        b = bytes_of(name)
        if b is None:
            unknown.add(name)
        else:
            report.persistent_bytes += b

    # the no-donation penalty: every written persistable transiently
    # holds old + new buffers (no aliasing to update in place)
    written_persistable = set()
    for op in block.ops:
        for n in usedef.writes_of(op):
            if persistable(n):
                written_persistable.add(n)
    for name in written_persistable:
        b = bytes_of(name)
        if b is not None:
            report.no_donation_extra_bytes += b

    # liveness walk over intermediates (feeds + activations + grads):
    # live-after sets computed backward, scanned forward for the peak.
    # Control-flow-aware: UseDefMap already extends parent-var live
    # ranges across sub-block reads; the body's PRIVATE per-iteration
    # buffers are counted by folding each sub-block's own internal peak
    # into the parent op's program point.
    from paddle_tpu.analysis.usedef import sub_block_indices

    def live_bytes(blk, names):
        total = 0
        for n in names:
            b = bytes_of(n, blk)
            if b is None:
                unknown.add(n)
            else:
                total += b
        return total

    sub_peaks = {}

    def fused_internal(op):
        """Composite-fallback internals of a kernel-registry fused op
        (zero when the kernel serves it — its workset stays in VMEM)."""
        if op.type not in ("cached_attention", "paged_attention"):
            return 0
        use_kernel = kernel_path
        if use_kernel is None:
            from paddle_tpu import kernels as _k

            use_kernel = _k.selected_for(op.type, op.attrs) is not None
        if use_kernel:
            return 0
        from paddle_tpu.kernels import fallback_internal_bytes

        def shape_of(slot):
            names = op.inputs.get(slot)
            if not names:
                return None
            info = shape_report.get(names[0])
            if info is None or info.shape is None or any(
                    is_sym(d) for d in info.shape):
                return None
            return info.shape

        q = shape_of("Q")
        itemsize = 4
        if q is not None:
            info = shape_report.get(op.inputs["Q"][0])
            if info is not None and info.dtype:
                itemsize = dtype_size(info.dtype)
        return fallback_internal_bytes(op.type, op.attrs, shape_of,
                                       itemsize)

    def remat_extra(blk):
        """Per-op-point bytes the chosen remat policy pins across
        fwd->bwd: the saved values already count INSIDE the forward
        segment (normal liveness); this adds the segment-end -> grad-op
        span the save-nothing policy would free. Names resolve through
        the same feed-bound, shard-aware ``bytes_of`` as everything
        else."""
        extra = [0] * len(blk.ops)
        for gi, op in enumerate(blk.ops):
            if op.type != "recompute_segment_grad":
                continue
            names = (op.attrs.get("__segment_saved_names__") or {}).get(
                op.attrs.get("__remat_policy__", "full"), ())
            saved = sum(bytes_of(n, blk) or 0 for n in names)
            if not saved:
                continue
            outs = set(op.attrs.get("__out_names__") or ())
            fi = None
            for j in range(gi - 1, -1, -1):
                if outs & set(blk.ops[j].output_names()):
                    fi = j
                    break
            for j in range((fi if fi is not None else 0), gi):
                extra[j] += saved
        return extra

    def pipeline_extra(blk):
        """Per-op-point activation-stash bytes of each pipeline_stack's
        compiled schedule (pipeline_runtime/schedule.py liveness walk),
        live across the fwd op -> its grad op, the span the microbatch
        residuals survive — priced pre-compile exactly like remat. A
        stage-less run (no mesh / stage axis 1) has no schedule and no
        stash beyond normal liveness."""
        extra = [0] * len(blk.ops)
        for fi, op in enumerate(blk.ops):
            if op.type != "pipeline_stack":
                continue
            stage_axis = op.attrs.get("stage_axis", "stage")
            s = int(axis_sizes.get(stage_axis, 1) or 1)
            if s <= 1:
                continue
            m = int(op.attrs.get("num_microbatches", 1) or 1)
            xn = (op.inputs.get("X") or [None])[0]
            stacked = op.inputs.get("StackedParams") or ()
            if xn is None or not stacked:
                continue
            xb = bytes_of(xn, blk)
            info = shape_report.get(stacked[0])
            if xb is None or info is None or not info.shape or \
                    is_sym(info.shape[0]):
                continue
            layers = int(info.shape[0])
            from paddle_tpu.parallel.pipeline_runtime.memory import (
                schedule_stash_bytes,
            )
            from paddle_tpu.parallel.pipeline_runtime.schedule import (
                compile_schedule,
            )

            try:
                sched = compile_schedule(
                    op.attrs.get("schedule") or "gpipe", s, m,
                    op.attrs.get("interleave"))
            except ValueError:
                continue
            stash = schedule_stash_bytes(sched, xb // max(m, 1), layers)
            # span: fwd op to its grad op (the residual lifetime); the
            # grad op reads the fwd op's output-grads
            outs = set(op.output_names())
            gi = None
            for j in range(len(blk.ops) - 1, fi, -1):
                if blk.ops[j].type == "pipeline_stack_grad" and \
                        outs & {n.replace("@GRAD", "")
                                for n in blk.ops[j].input_names()}:
                    gi = j
                    break
            for j in range(fi, (gi if gi is not None else fi) + 1):
                extra[j] += stash
        return extra

    def block_peak(blk, fetches, top=False):
        ud = usedef if top else UseDefMap(blk)
        live_after = [set() for _ in blk.ops]
        needed = set(fetches)
        for i in range(len(blk.ops) - 1, -1, -1):
            live_after[i] = {n for n in needed if not persistable(n, blk)}
            op = blk.ops[i]
            needed -= ud.writes_of(op)
            needed |= ud.reads_of(op)
        # entry point: feeds + anything read before first written
        entry_live = {n for n in needed if not persistable(n, blk)
                      and blk._find_var_recursive(n) is not None}
        peak = live_bytes(blk, entry_live)
        if top:
            report.peak_op_index, report.peak_op_type = -1, "<entry>"
            report.timeline.append((-1, "<entry>", peak))
        extra = remat_extra(blk)
        pextra = pipeline_extra(blk)
        for i, op in enumerate(blk.ops):
            if op.type in ("feed", "fetch"):
                continue
            b = live_bytes(blk, live_after[i]) + extra[i] + pextra[i]
            b += fused_internal(op)
            for bi in sub_block_indices(op):
                if bi not in sub_peaks:
                    sub_peaks[bi] = block_peak(program.block(bi), ())
                b += sub_peaks[bi]
            if top:
                report.timeline.append((i, op.type, b))
            if b > peak:
                peak = b
                if top:
                    report.peak_op_index, report.peak_op_type = i, op.type
        return peak

    report.peak_intermediate_bytes = block_peak(block, fetch_names,
                                                top=True)
    report.unknown_vars = sorted(unknown)
    if unknown:
        report.diagnostics.append(Diagnostic(
            "warning", "unresolved-size",
            f"{len(unknown)} vars have symbolic/unknown sizes and are "
            f"excluded from the peak estimate (bind feed shapes): "
            f"{sorted(unknown)[:5]}",
        ))
    return report


# ---------------------------------------------------------------------------
# donation safety — the pre-lowering hard-error gate
# ---------------------------------------------------------------------------


def remat_hbm_delta(program_plain, program_remat, *, feed_shapes=None,
                    fetch_names=()):
    """Pre-compile peak-HBM delta of a remat decision: the same model
    built WITHOUT checkpoints vs WITH (RecomputeOptimizer + an IR-keyed
    policy, kernels/remat.py). Both sides are pure static analysis —
    this is the number an operator reads BEFORE paying a compile to
    decide whether a long-sequence config trades HBM for recompute."""
    plain = estimate_peak_hbm(program_plain, feed_shapes=feed_shapes,
                              fetch_names=fetch_names)
    remat = estimate_peak_hbm(program_remat, feed_shapes=feed_shapes,
                              fetch_names=fetch_names)
    policies = sorted({
        op.attrs.get("__remat_policy__")
        for op in program_remat.global_block().ops
        if op.type == "recompute_segment_grad"
        and op.attrs.get("__remat_policy__")
    })
    return {
        "plain_peak_bytes": plain.peak_total_bytes,
        "remat_peak_bytes": remat.peak_total_bytes,
        "plain_intermediate_bytes": plain.peak_intermediate_bytes,
        "remat_intermediate_bytes": remat.peak_intermediate_bytes,
        "saved_bytes": plain.peak_total_bytes - remat.peak_total_bytes,
        "ratio": (plain.peak_total_bytes
                  / float(max(remat.peak_total_bytes, 1))),
        "policies": policies,
    }


def check_hbm_budget(report, budget_bytes, label=""):
    """Gate a MemoryReport against a per-device HBM budget BEFORE any
    compile. Returns error Diagnostics (empty = fits). The continuous-
    batching decode engine sizes its pre-allocated KV arenas with this:
    the arenas are persistable program state, so an oversized
    ``slots x max_len`` grid shows up in ``persistent_bytes`` and fails
    here with sizing advice instead of OOMing inside XLA."""
    budget_bytes = int(budget_bytes)
    if budget_bytes <= 0 or report.peak_total_bytes <= budget_bytes:
        return []
    what = f" for '{label}'" if label else ""
    return [Diagnostic(
        "error", "hbm-over-budget",
        f"estimated peak HBM{what} is "
        f"{report.peak_total_bytes / 2**20:.1f} MiB "
        f"(persistent {report.persistent_bytes / 2**20:.1f} MiB + "
        f"intermediates {report.peak_intermediate_bytes / 2**20:.1f} MiB "
        f"at op #{report.peak_op_index} <{report.peak_op_type}>), over "
        f"the {budget_bytes / 2**20:.1f} MiB budget — shrink the KV "
        f"arena (fewer slots / shorter max_len), drop layers, or raise "
        f"the budget",
    )]


def check_donation_safety(program, donated, readonly=(), fetch_names=(),
                          block=None):
    """Validate a lowering plan's donation set against the program.
    Returns error Diagnostics (empty = safe). Control-flow-aware: reads
    inside a while body count at the while op's position."""
    block = block if block is not None else program.global_block()
    usedef = UseDefMap(block)
    diags = []
    donated = list(donated)
    donated_set = set(donated)
    fetch_set = set(fetch_names)

    seen = set()
    for d in donated:
        if d in seen:
            diags.append(Diagnostic(
                "error", "donated-var-aliased-twice",
                f"'{d}' appears twice in the donation list — one buffer "
                f"cannot back two in-place updates", var=d,
            ))
        seen.add(d)
    for d in donated_set & set(readonly):
        diags.append(Diagnostic(
            "error", "donated-var-aliased-twice",
            f"'{d}' is both donated and passed read-only — the read-only "
            f"argument would observe a consumed buffer", var=d,
        ))
    for d in donated_set & fetch_set:
        diags.append(Diagnostic(
            "error", "donated-var-fetched",
            f"'{d}' is donated AND fetched — the fetch would return a "
            f"dead buffer (exclude it from donation or from the fetch "
            f"list)", var=d,
        ))

    written = set()
    for op in block.ops:
        written |= usedef.writes_of(op)
    for d in donated:
        if d not in written:
            diags.append(Diagnostic(
                "error", "donated-not-written",
                f"'{d}' is donated but no op writes it — its buffer is "
                f"destroyed with no replacement value to write back",
                var=d,
            ))

    # read-after-donate: an optimizer-role op rewrote the donated buffer
    # in place; a later NON-optimizer op still reads the name and silently
    # observes the updated value (e.g. a loss/metric computed from
    # already-stepped weights). Scoped to TRAINABLE state — Parameters
    # and their optimizer slots — because scheduler counters and similar
    # plain persistables are legitimately written early and read later
    # (linear_lr_warmup increments @LR_DECAY_COUNTER@ then reads it).
    from paddle_tpu.core.ir import Parameter
    from paddle_tpu.parallel.sharding import _slot_parent

    param_names = {
        v.name for v in program.global_block().vars.values()
        if isinstance(v, Parameter)
    }

    def is_trainable_state(name):
        return name in param_names or \
            _slot_parent(name, param_names) is not None

    updated_at = {}  # name -> first optimizer write index
    for i, op in enumerate(block.ops):
        if op.attrs.get("op_role", 0) != _OP_ROLE_OPTIMIZE:
            continue
        for n in usedef.writes_of(op):
            if n in donated_set and n not in updated_at and \
                    is_trainable_state(n):
                updated_at[n] = i
    for i, op in enumerate(block.ops):
        if op.attrs.get("op_role", 0) == _OP_ROLE_OPTIMIZE:
            continue
        for n in usedef.reads_of(op):
            at = updated_at.get(n)
            if at is not None and i > at:
                diags.append(Diagnostic(
                    "error", "read-after-donate",
                    f"op '{op.type}' reads donated '{n}' after the "
                    f"optimizer update at op #{at} rewrote its buffer in "
                    f"place — the read observes the stepped value",
                    block_idx=block.idx, op_index=i, op_type=op.type,
                    var=n,
                    callstack=op.attrs.get("op_callstack"),
                ))
    return diags
