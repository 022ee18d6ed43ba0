"""Program verifier: structured well-formedness diagnostics over a Program.

Every training feature here is a program rewrite (backward, AMP, fusion,
sharding, pruning), so a buggy rewrite corrupts every downstream consumer
silently — the verifier makes rewrites checkable instead of hoped-correct.
``verify_program`` walks the IR and returns structured ``Diagnostic``s;
``PassManager(verify_each_pass=True)`` (passes.py) runs it after every pass
and names the pass that broke an invariant; tools/lint_program.py is the
stand-alone CLI over serialized programs.

Checked invariants:
  * use-before-def   — an op reads a var no earlier op produced and that is
                       neither persistable, a declared feed (is_data), nor an
                       explicit feed name. Control-flow sub-blocks are walked
                       with the defined-set at their op's position (while
                       bodies may read loop-carried state defined outside).
  * dangling-var     — an op names a var not declared in the block chain.
  * dtype/rank       — declared var metadata violates the op's registered
                       static signature (analysis/signatures.py).
  * unknown-op       — op type with no registered lowering (and no
                       synthesizable ``*_grad`` base, see core/backward.py).
  * shadowed-var     — a sub-block declares a var name an ancestor also
                       declares (legal but almost always a rewrite bug).
  * sub-blocks       — sub_block attrs must reference existing blocks;
                       unreachable non-root blocks are reported.
  * sharding         — optional (pass ``mesh=``): partition specs must name
                       mesh axes that exist and divide the var dims; skipped
                       optimizer-slot spec inheritance is surfaced
                       (parallel/sharding.py).

Severity is "error" for invariants whose violation breaks execution and
"warning" for suspicious-but-runnable shapes. A verifier must never flag a
well-formed program: anything uncertain is a warning or unchecked.
"""

from paddle_tpu.analysis.signatures import get_signature
from paddle_tpu.analysis.usedef import sub_block_indices

__all__ = ["Diagnostic", "verify_program", "verify_shardings"]

#: op types executed structurally by the interpreter, not via the registry
_STRUCTURAL_OPS = frozenset({"while", "conditional_block", "feed", "fetch"})

#: sub-blocks whose reads resolve through op-private state the IR doesn't
#: express (StaticRNN memories; pipeline_stack binds its stage body's
#: inputs — h_in, per-stage params — from the stacked tensors at run
#: time) — use-before-def is not decidable there
_OPAQUE_SUB_BLOCK_OPS = frozenset(
    {"recurrent", "recurrent_grad", "pipeline_stack", "pipeline_stack_grad"}
)


class Diagnostic:
    """One verifier finding, with op attribution for error surfacing."""

    def __init__(self, severity, code, message, block_idx=None, op_index=None,
                 op_type=None, var=None, callstack=None, pass_name=None):
        self.severity = severity  # "error" | "warning"
        self.code = code
        self.message = message
        self.block_idx = block_idx
        self.op_index = op_index
        self.op_type = op_type
        self.var = var
        self.callstack = callstack
        self.pass_name = pass_name  # filled in by PassManager

    def key(self):
        """Identity for de-duplicating diagnostics across verifier runs
        (PassManager.verify_each_pass compares post-pass findings against
        the pre-pass set). Content-based on purpose — including op_index
        would make every pre-existing finding look new whenever a pass
        merely removes ops above it and shifts positions."""
        return (self.severity, self.code, self.block_idx, self.op_type,
                self.var, self.message)

    def __repr__(self):
        return f"Diagnostic({self.severity}, {self.code}, {self.message!r})"

    def __str__(self):
        loc = []
        if self.pass_name:
            loc.append(f"after pass '{self.pass_name}'")
        if self.block_idx is not None:
            loc.append(f"block {self.block_idx}")
        if self.op_index is not None:
            loc.append(f"op #{self.op_index}")
        if self.op_type:
            loc.append(f"<{self.op_type}>")
        head = f"[{self.severity}] {self.code}: {self.message}"
        if loc:
            head += f"  ({', '.join(loc)})"
        if self.callstack:
            head += "\n  [user callstack]\n" + "".join(
                "  " + line for line in self.callstack
            )
        return head


def _diag(diags, severity, code, message, block=None, op_index=None, op=None,
          var=None):
    diags.append(Diagnostic(
        severity, code, message,
        block_idx=block.idx if block is not None else None,
        op_index=op_index,
        op_type=op.type if op is not None else None,
        var=var,
        callstack=op.attrs.get("op_callstack") if op is not None else None,
    ))


def _op_resolvable(op_type):
    from paddle_tpu.core.registry import OpRegistry

    if op_type in _STRUCTURAL_OPS or OpRegistry.has(op_type):
        return True
    if op_type.endswith("_grad"):
        # core/backward.py synthesizes grad defs from the base lowering
        return OpRegistry.has(op_type[: -len("_grad")])
    return False


def _declared_dtype(block, name):
    v = block._find_var_recursive(name)
    return None if v is None or v.dtype is None else str(v.dtype)


def _check_signature(block, op, op_index, diags):
    sig = get_signature(op.type)
    if sig is None:
        return
    for group in sig.same_dtype:
        seen = {}
        for slot in group:
            for n in op.inputs.get(slot, []) + op.outputs.get(slot, []):
                dt = _declared_dtype(block, n)
                if dt is not None:
                    seen.setdefault(dt, n)
        if len(seen) > 1:
            pairs = ", ".join(f"{n}:{dt}" for dt, n in sorted(seen.items()))
            _diag(diags, "error", "dtype-mismatch",
                  f"op '{op.type}' requires one dtype across slots "
                  f"{'/'.join(group)}, got {pairs}",
                  block=block, op_index=op_index, op=op,
                  var=next(iter(seen.values())))
    for slot, want in sig.ranks.items():
        want_set = want if isinstance(want, tuple) else (want,)
        for n in op.inputs.get(slot, []) + op.outputs.get(slot, []):
            v = block._find_var_recursive(n)
            if v is None or v.shape is None:
                continue
            if len(v.shape) not in want_set:
                _diag(diags, "error", "rank-mismatch",
                      f"op '{op.type}' slot {slot} expects rank "
                      f"{'/'.join(map(str, want_set))}, var '{n}' has shape "
                      f"{list(v.shape)}",
                      block=block, op_index=op_index, op=op, var=n)
    for slot, family in sig.dtype_family.items():
        for n in op.inputs.get(slot, []) + op.outputs.get(slot, []):
            dt = _declared_dtype(block, n)
            # bfloat16 is a float dtype whose name does not start so
            if dt is not None and not dt.startswith(family) and not (
                    family == "float" and dt == "bfloat16"):
                _diag(diags, "error", "dtype-mismatch",
                      f"op '{op.type}' slot {slot} expects a {family} dtype, "
                      f"var '{n}' is {dt}",
                      block=block, op_index=op_index, op=op, var=n)


def _ancestor_declares(block, name):
    b = block.parent_block
    while b is not None:
        if name in b.vars:
            return b
        b = b.parent_block
    return None


def _walk_block(program, block, defined, feed_names, diags,
                check_defs=True, _path=frozenset()):
    """Verify one block's ops in order; `defined` is the set of names known
    to hold values when the block starts executing (mutated as ops produce).
    Sub-blocks are walked at their control-flow op's position with a COPY of
    the defined-set — their writes escape only through the op's own outputs
    (loop carry re-writes already-defined names). `_path` carries the block
    indices on the current recursion path so a cyclic sub_block reference
    becomes a diagnostic, not a RecursionError."""
    for name in block.vars:
        if block.idx != 0 and _ancestor_declares(block, name) is not None:
            _diag(diags, "warning", "shadowed-var",
                  f"sub-block {block.idx} declares '{name}' which an "
                  f"enclosing block also declares", block=block, var=name)
    for op_index, op in enumerate(block.ops):
        if op.type in ("feed", "fetch"):
            continue
        if not _op_resolvable(op.type):
            _diag(diags, "error", "unknown-op",
                  f"op type '{op.type}' has no registered lowering",
                  block=block, op_index=op_index, op=op)
        for name in op.input_names():
            v = block._find_var_recursive(name)
            if v is None:
                _diag(diags, "error", "dangling-input",
                      f"op '{op.type}' reads '{name}' which is not declared "
                      f"in block {block.idx} or its ancestors",
                      block=block, op_index=op_index, op=op, var=name)
                continue
            if (
                check_defs
                and name not in defined
                and not v.persistable
                and not v.is_data
                and name not in feed_names
            ):
                _diag(diags, "error", "use-before-def",
                      f"op '{op.type}' reads '{name}' before any op produces "
                      f"it (not persistable, not a feed)",
                      block=block, op_index=op_index, op=op, var=name)
        for idx in sub_block_indices(op):
            if idx >= program.num_blocks():
                _diag(diags, "error", "bad-sub-block",
                      f"op '{op.type}' references sub-block {idx} but the "
                      f"program has {program.num_blocks()} blocks",
                      block=block, op_index=op_index, op=op)
                continue
            if idx == block.idx or idx in _path:
                _diag(diags, "error", "bad-sub-block",
                      f"op '{op.type}' references sub-block {idx} which is "
                      f"already on the enclosing block path — cyclic "
                      f"control flow",
                      block=block, op_index=op_index, op=op)
                continue
            _walk_block(
                program, program.block(idx), set(defined), feed_names, diags,
                check_defs=check_defs
                and op.type not in _OPAQUE_SUB_BLOCK_OPS,
                _path=_path | {block.idx},
            )
        for name in op.output_names():
            if block._find_var_recursive(name) is None:
                _diag(diags, "error", "dangling-output",
                      f"op '{op.type}' writes '{name}' which is not declared "
                      f"in block {block.idx} or its ancestors",
                      block=block, op_index=op_index, op=op, var=name)
            defined.add(name)
        _check_signature(block, op, op_index, diags)


def _check_block_graph(program, diags):
    reachable = {0}
    for b in program.blocks:
        for op in b.ops:
            for idx in sub_block_indices(op):
                if idx < program.num_blocks():
                    reachable.add(idx)
    for b in program.blocks:
        if b.idx not in reachable:
            _diag(diags, "warning", "orphaned-sub-block",
                  f"block {b.idx} is not referenced by any control-flow op",
                  block=b)


def verify_program(program, feed_names=(), fetch_names=(), scope=None,
                   mesh=None, sharding_rules=None, sharding_overrides=None):
    """Run all verifier checks over `program`; returns a list of Diagnostics
    (errors first). `feed_names` supplements vars marked is_data as the
    block-0 inputs assumed present; `scope`/`mesh` unlock the optional
    scope-presence and sharding-spec checks."""
    diags = []
    feed_names = set(feed_names)
    _check_block_graph(program, diags)
    # parent chains must strictly decrease (blocks are created parent-first)
    # — var lookup walks them unboundedly, so a cyclic chain would hang
    # every later check; report and stop at the structural level instead
    chain_ok = True
    for b in program.blocks:
        want_ok = b.parent_idx < 0 if b.idx == 0 else \
            0 <= b.parent_idx < b.idx
        if not want_ok:
            _diag(diags, "error", "bad-block-parent",
                  f"block {b.idx} has parent_idx {b.parent_idx} — parent "
                  f"indices must be earlier blocks (cycle-free chain)",
                  block=b)
            chain_ok = False
    if not chain_ok:
        diags.sort(key=lambda d: 0 if d.severity == "error" else 1)
        return diags
    defined = set(feed_names)
    _walk_block(program, program.global_block(), defined, feed_names, diags)

    # fetches must exist somewhere in the program
    declared = {n for b in program.blocks for n in b.vars}
    for name in fetch_names:
        if name not in declared:
            _diag(diags, "error", "dangling-fetch",
                  f"fetch target '{name}' is not declared in the program",
                  block=program.global_block(), var=name)

    if mesh is not None:
        gblock = program.global_block()
        names, shapes = [], []
        for v in gblock.vars.values():
            if v.persistable and v.shape is not None:
                names.append(v.name)
                shapes.append(tuple(v.shape))
        diags.extend(verify_shardings(
            names, shapes, mesh,
            rules=sharding_rules, overrides=sharding_overrides,
        ))
    diags.sort(key=lambda d: 0 if d.severity == "error" else 1)
    return diags


def verify_shardings(names, shapes, mesh, rules=None, overrides=None):
    """Check partition-spec consistency for `names`/`shapes` against `mesh`
    (parallel/sharding.py semantics). Explicit overrides that cannot apply
    are errors (the user asked for that layout); rule-derived specs that
    fall back to replicated are warnings; optimizer-slot inheritance that is
    skipped because the suffix is not a known accumulator is surfaced so the
    silent-layout-change failure mode (ADVICE r5 low) is visible."""
    from paddle_tpu.parallel.sharding import (
        MEGATRON_RULES,
        _prefix_parent,
        _slot_parent,
        known_slot_suffixes,
        match_spec,
    )

    diags = []
    rules = rules if rules is not None else MEGATRON_RULES
    overrides = overrides or {}
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    name_set = set(names)

    def spec_problems(name, shape, spec):
        problems = []
        if spec is None or not tuple(spec):
            return problems
        if len(spec) > len(shape):
            problems.append(
                f"spec {tuple(spec)} has more dims than var '{name}' "
                f"(shape {list(shape)})"
            )
            return problems
        for dim, axes in zip(shape, tuple(spec)):
            if axes is None:
                continue
            axes = axes if isinstance(axes, tuple) else (axes,)
            total = 1
            for ax in axes:
                if ax not in sizes:
                    problems.append(
                        f"spec {tuple(spec)} names mesh axis '{ax}' but the "
                        f"mesh has axes {sorted(sizes)}"
                    )
                    return problems
                total *= sizes[ax]
            if dim is not None and dim > 0 and dim % total != 0:
                problems.append(
                    f"axis group {axes} of size {total} does not divide "
                    f"dim {dim} of var '{name}'"
                )
        return problems

    for name, shape in zip(names, shapes):
        explicit = name in overrides
        spec = overrides.get(name)
        if spec is None:
            spec = match_spec(name, rules)
        for problem in spec_problems(name, tuple(shape), spec):
            diags.append(Diagnostic(
                "error" if explicit else "warning",
                "bad-sharding-spec",
                problem + ("" if explicit
                           else " — falling back to replicated"),
                var=name,
            ))
        # surface skipped optimizer-slot inheritance: the name prefix-extends
        # another var's name, but the suffix is not a known accumulator, so
        # derive_shardings will NOT inherit the parent's (possibly sharded)
        # spec — silent replication of what looks like an optimizer slot
        if not explicit and spec is not None and not tuple(spec):
            parent = _prefix_parent(name, name_set)
            if parent is not None and _slot_parent(name, name_set) is None:
                pspec = overrides.get(parent)
                if pspec is None:
                    pspec = match_spec(parent, rules)
                if pspec is not None and tuple(pspec):
                    diags.append(Diagnostic(
                        "warning", "sharding-slot-skipped",
                        f"'{name}' extends '{parent}' but its suffix is not "
                        f"a known optimizer-slot name "
                        f"({'/'.join(sorted(known_slot_suffixes()))}) — it "
                        f"will NOT inherit the parent's spec {tuple(pspec)}",
                        var=name,
                    ))
    return diags
