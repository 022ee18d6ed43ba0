"""Lower framework programs to HLO text for chip-independent perf assertions.

The reference proves kernel choices with a micro-bench runner
(reference: paddle/fluid/operators/benchmark/op_tester.cc:1); on TPU the
compiler is the schedule, so the equivalent evidence is the compiled
computation itself: lower the REAL train step to StableHLO / optimized HLO
and assert structural properties — no O(S^2) HBM buffers on the flash path,
bf16 on every MXU dot under AMP, the expected collectives under dp/tp
meshes. tests/test_hlo.py runs these as regression gates; this module is the
shared lowering plumbing.

StableHLO (pre-XLA-optimization) is the right layer for dtype and shape
discipline: it reflects what the framework emitted. Optimized HLO reflects
backend choices — on the CPU test rig XLA rewrites bf16 dots to f32
(hardware has no bf16 units), so dtype assertions there would be
meaningless; buffer-shape and collective assertions remain valid.
"""

import re

import numpy as np

import jax


def _sds_of(value):
    arr = np.asarray(value) if not hasattr(value, "shape") else value
    return jax.ShapeDtypeStruct(tuple(arr.shape), np.asarray(value).dtype if not hasattr(value, "dtype") else value.dtype)


def lower_program_step(program, feed, fetch_list, scope=None, donate=True,
                       sharding=None):
    """Lower the Executor's whole-block step for `program` WITHOUT running it.

    `feed` maps name -> array (shape/dtype only). The scope must hold
    initialized persistables (run the startup program first). Returns the
    jax ``Lowered``: ``.as_text()`` is StableHLO, ``.compile().as_text()``
    the backend-optimized HLO. ``sharding`` goes on every argument: a
    ``SingleDeviceSharding`` on a device of a DESCRIBED topology
    (``jax.experimental.topologies``) makes ``.compile()`` the TPU
    compiler's own, chip-free (tests/test_kernels_tpu_aot.py).

    Routes through the shared lowering (core/lowering.py, cache bypassed:
    evidence must come from a fresh trace of the CURRENT program), so the
    lowered computation — plan, verifier pass, donation — is exactly what
    the Executor would run.
    """
    from paddle_tpu.core import lowering
    from paddle_tpu.core.scope import global_scope
    from paddle_tpu.passes import (
        apply_deferred_sharded_embedding_rewrite,
        apply_deferred_sparse_rewrite,
    )

    scope = scope or global_scope()
    apply_deferred_sparse_rewrite(program)
    apply_deferred_sharded_embedding_rewrite(program)
    feed_names = sorted(feed)
    fetch_names = [f if isinstance(f, str) else f.name for f in fetch_list]
    feed_sds = tuple(_sds_of(feed[n]) for n in feed_names)
    feed_sig = tuple(
        (n, tuple(s.shape), str(s.dtype))
        for n, s in zip(feed_names, feed_sds)
    )
    entry, _src = lowering.lower_step(
        program, scope, feed_sig, fetch_names,
        donate=donate, use_cache=False, persist=False, label="hlo",
    )
    donated_sds = tuple(_sds_of(scope.find_var(n)) for n in entry.donated)
    readonly_sds = tuple(_sds_of(scope.find_var(n)) for n in entry.readonly)
    key = jax.random.PRNGKey(0)
    args = (feed_sds, donated_sds, readonly_sds, _sds_of(key))
    if sharding is not None:
        args = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=sharding), args)
    return entry.lower(*args)


def lower_parallel_step(exe, compiled_program, feed, fetch_list, scope):
    """Lower a CompiledProgram (mesh) step. Runs ONE real step first so the
    CompiledProgram builds its cache entry (shardings, donation plan) through
    the production path, then re-lowers that exact jitted step with abstract
    args. Returns (Lowered, mesh)."""
    from paddle_tpu.parallel.env import mesh_context

    exe.run(compiled_program, feed=feed, fetch_list=fetch_list, scope=scope)
    entries = list(compiled_program._cache.values())
    assert len(entries) == 1, "expected exactly one cache entry"
    entry = entries[0]
    compiled = entry.fn
    feed_names = sorted(feed)
    feed_sds = tuple(_sds_of(feed[n]) for n in feed_names)
    donated_sds = tuple(_sds_of(scope.find_var(n)) for n in entry.donated)
    readonly_sds = tuple(_sds_of(scope.find_var(n)) for n in entry.readonly)
    key = jax.random.PRNGKey(0)
    with mesh_context(compiled_program._mesh):
        lowered = compiled.lower(feed_sds, donated_sds, readonly_sds, key)
    return lowered, compiled_program._mesh


_PALLAS_NAME = re.compile(r'op_name="[^"]*?(\w+)\)*/pallas_call"')
_FIRST_SHAPE = re.compile(r"\b\w+\[[\d,]*\]")


def pallas_custom_calls(hlo_text):
    """Census of the Pallas (Mosaic) kernels in OPTIMIZED HLO text:
    ``{kernel name: {"count": n, "result": first result shape}}``, the
    name being the ``pallas_call(name=...)`` each kernel in this repo
    sets. Read from the executable that ran, this is what tells "the
    flash kernel served forward and backward" apart from "a composite
    ran while the registry said tpu" (chip_smoke.py)."""
    out = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _PALLAS_NAME.search(line)
        name = m.group(1) if m else "unnamed"
        shape = _FIRST_SHAPE.search(line.split("custom-call(")[0])
        rec = out.setdefault(
            name, {"count": 0, "result": shape.group(0) if shape else None})
        rec["count"] += 1
    return out


# ---------------------------------------------------------------------------
# shared step builders — tests/test_hlo.py's gates lower through these
# ---------------------------------------------------------------------------


def bert_train_step_text(flash, seq_len=512, layers=2, batch=4,
                         vocab=30522, max_pred=77, hidden=768, heads=12,
                         device=None):
    """StableHLO text of a BERT train step (bf16 AMP, gathered MLM head),
    or, with ``device`` (one of a described topology's: see
    ``lower_program_step``), the OPTIMIZED HLO of that step compiled for it.

    ``flash=True`` lowers under the kernel registry's INTERPRET mode
    (paddle_tpu/kernels/): on this CPU rig the registry's default
    resolution is the composite fallback, but the flash HLO evidence
    must assert on the KERNEL's emitted computation (no S^2 buffers,
    bf16 dots inside the blocks) — interpret mode traces the Pallas body
    into the StableHLO exactly as the TPU path schedules it."""
    import paddle_tpu as fluid
    from paddle_tpu import kernels
    from paddle_tpu.models import bert

    cfg = bert.BertConfig(
        vocab_size=vocab, hidden_size=hidden, num_hidden_layers=layers,
        num_attention_heads=heads, max_position_embeddings=seq_len,
        use_flash_attention=flash,
        attention_probs_dropout_prob=0.0 if flash else 0.1,
    )
    main, startup, feeds, fetches = bert.build_bert_pretrain(
        cfg, seq_len=seq_len, lr=1e-4, use_amp=True,
        max_predictions_per_seq=max_pred,
    )
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    mode = kernels.scoped_mode("interpret" if flash else "off")
    with fluid.scope_guard(scope), mode:
        exe.run(startup)
        data = bert.synthetic_batch(
            np.random.RandomState(0), batch, seq_len, cfg,
            max_predictions_per_seq=max_pred,
        )
        lowered = lower_program_step(
            main, data, [fetches[0]], scope=scope,
            sharding=device and jax.sharding.SingleDeviceSharding(device),
        )
        return (lowered.as_text() if device is None
                else lowered.compile().as_text())


def tiny_bert_parallel_text(mesh_shape, axis_names, param_rules=None,
                            spec_layout=None, seq_len=16, batch=8,
                            max_pred=None, with_param_shapes=False):
    """Optimized (post-GSPMD) HLO of a tiny-BERT step over a virtual mesh.

    ``spec_layout`` routes parameter placement through the canonical
    registry (parallel/spec_layout.py). The weight-sized-collective gates
    use seq_len=24: at the default 16, a flattened activation
    [B_local*S, H] = [64, 64] collides with the qkv weight shape and a
    hidden-sharded residual gather would read as a weight move.
    ``with_param_shapes=True`` also returns the set of full (unsharded)
    rank>=2 parameter shapes, for weight_shaped_collectives()."""
    import paddle_tpu as fluid
    from paddle_tpu.models import bert
    from paddle_tpu.parallel.env import make_mesh

    cfg = bert.BertConfig.tiny()
    cfg.hidden_dropout_prob = 0.0
    cfg.attention_probs_dropout_prob = 0.0
    build_kwargs = {}
    if max_pred is not None:
        build_kwargs["max_predictions_per_seq"] = max_pred
    main, startup, feeds, fetches = bert.build_bert_pretrain(
        cfg, seq_len=seq_len, lr=1e-3, **build_kwargs
    )
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        mesh = make_mesh(shape=mesh_shape, axis_names=axis_names)
        prog = fluid.CompiledProgram(main).with_parallel(
            mesh=mesh, loss_name=fetches[0].name, param_rules=param_rules,
            spec_layout=spec_layout,
        )
        data = bert.synthetic_batch(
            np.random.RandomState(0), batch, seq_len, cfg,
            **({"max_predictions_per_seq": max_pred}
               if max_pred is not None else {})
        )
        lowered, _ = lower_parallel_step(
            exe, prog, data, [fetches[0]], scope
        )
        txt = lowered.compile().as_text()
    if with_param_shapes:
        shapes = {
            tuple(p.shape) for p in main.all_parameters()
            if p.shape and len(p.shape) >= 2
        }
        return txt, shapes
    return txt


def resnet_train_step_text(depth=50, class_dim=1000, image_shape=None,
                           batch=2, use_amp=True):
    """StableHLO text of a ResNet train step."""
    import paddle_tpu as fluid
    from paddle_tpu.models import resnet

    kwargs = {}
    if image_shape is not None:
        kwargs["image_shape"] = image_shape
    main, startup, feeds, fetches = resnet.build_resnet_train(
        depth=depth, class_dim=class_dim, lr=0.1, use_amp=use_amp, **kwargs
    )
    shape = image_shape or (3, 224, 224)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        feed = {
            "img": np.zeros((batch,) + tuple(shape), "float32"),
            "label": np.zeros((batch, 1), "int64"),
        }
        return lower_program_step(
            main, feed, [fetches[0]], scope=scope
        ).as_text()


def adam_mlp_step_lowered(hidden=16, batch=4):
    """Small-but-real Adam train step (two fc layers) lowered through the
    production path — the donation/aliasing and fused-optimizer gates
    (tests/test_hlo.py) read THIS computation. Returns (lowered, donated_names, program).
    """
    import paddle_tpu as fluid
    from paddle_tpu.core.executor import plan_step
    from paddle_tpu.core.ir import Program, program_guard

    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", shape=[-1, 8])
        y = fluid.data("y", shape=[-1, 1])
        h = fluid.layers.fc(x, size=hidden, act="relu")
        pred = fluid.layers.fc(h, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        feed = {"x": np.zeros((batch, 8), "float32"),
                "y": np.zeros((batch, 1), "float32")}
        donated, _ro, _w, _ops = plan_step(
            main.global_block(), sorted(feed), [loss.name], scope, True
        )
        lowered = lower_program_step(main, feed, [loss], scope=scope)
    return lowered, donated, main


def conv_dtype_census(stablehlo_text):
    """Result dtypes of every convolution in StableHLO text."""
    counts = {}
    for m in re.finditer(
        r"stablehlo\.convolution.*?->\s*tensor<[^>]*x([a-z0-9]+)>",
        stablehlo_text,
    ):
        counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    return counts


# ---------------------------------------------------------------------------
# text analysis
# ---------------------------------------------------------------------------

_STABLEHLO_TENSOR = re.compile(r"tensor<([0-9]+(?:x[0-9]+)*)x([a-z0-9]+)>")
_OPT_HLO_SHAPE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def stablehlo_tensors(text):
    """All ranked tensor types in StableHLO text as (dims tuple, dtype)."""
    out = []
    for m in _STABLEHLO_TENSOR.finditer(text):
        dims = tuple(int(d) for d in m.group(1).split("x"))
        out.append((dims, m.group(2)))
    return out


def opt_hlo_shapes(text):
    """All shaped values in optimized HLO text as (dims tuple, dtype)."""
    out = []
    for m in _OPT_HLO_SHAPE.finditer(text):
        if not m.group(2):
            continue
        dims = tuple(int(d) for d in m.group(2).split(","))
        out.append((dims, m.group(1)))
    return out


def tensors_with_trailing(tensors, trailing):
    """Tensors whose shape ends with the given dims (e.g. (S, S))."""
    t = tuple(trailing)
    return [x for x in tensors if x[0][-len(t):] == t]


def tensors_containing_dims(tensors, dims):
    """Tensors whose shape contains ALL the given dim sizes (any order)."""
    need = list(dims)
    out = []
    for shape, dt in tensors:
        pool = list(shape)
        ok = True
        for d in need:
            if d in pool:
                pool.remove(d)
            else:
                ok = False
                break
        if ok:
            out.append((shape, dt))
    return out


_OP_SIGNATURE = re.compile(r":\s*\((tensor<[^)]*)\)\s*->")
_WINDOW_DIMS = re.compile(r"(?:offset_dims|update_window_dims) = \[[0-9]")
_MOVER = re.compile(r"stablehlo\.(gather|scatter|sort)\b")


def element_granular_movers(stablehlo_text, hidden, sort_elements):
    """(kind, operand dims) of every ``stablehlo.gather`` /
    ``stablehlo.scatter`` that moves ONE element per index (no offset /
    update-window dimension) on an operand that carries the ``hidden``
    size, and of every ``stablehlo.sort`` over ``sort_elements`` elements
    or more. A row gather of X [B, S, H] has ``offset_dims = [2]`` and its
    transpose ``update_window_dims = [2]``; a full-shape index (one index
    per OUTPUT element) has neither, and B*P*H single fetches and
    single-element adds follow (tests/test_hlo.py)."""
    out = []
    lines = stablehlo_text.splitlines()
    for i, line in enumerate(lines):
        m = _MOVER.search(line)
        if not m:
            continue
        typed = line
        if "({" in line:    # scatter, sort: the types close the region
            typed = next(ln for ln in lines[i:]
                         if ln.lstrip().startswith("})"))
        operand = stablehlo_tensors(
            _OP_SIGNATURE.search(typed).group(1))[0][0]
        if m.group(1) == "sort":
            if int(np.prod(operand)) >= sort_elements:
                out.append(("sort", operand))
        elif hidden in operand and not _WINDOW_DIMS.search(line):
            out.append((m.group(1), operand))
    return out


def stablehlo_dots(text):
    """(lhs, rhs, out) tensor types for every dot_general in StableHLO."""
    dots = []
    pat = re.compile(
        r"stablehlo\.dot_general.*?:\s*\(tensor<([^>]+)>,\s*tensor<([^>]+)>\)"
        r"\s*->\s*tensor<([^>]+)>"
    )
    for m in pat.finditer(text):
        dots.append((m.group(1), m.group(2), m.group(3)))
    return dots


_ALIASING_ATTR = re.compile(
    r"%arg(\d+):[^,)]*tf\.aliasing_output\s*=\s*(\d+)"
)


def stablehlo_donated_args(text):
    """(arg_index, aliased_output_index) for every entry argument carrying
    a ``tf.aliasing_output`` attr — the donation/aliasing evidence: each
    pair is a buffer XLA may update IN PLACE (parameter and optimizer-slot
    state stepping without a second copy). Chip-independent: the attr is
    framework-emitted, present in StableHLO before any backend choice."""
    pairs = _ALIASING_ATTR.findall(text)
    return sorted({(int(a), int(o)) for a, o in pairs})


_UNFUSED_ADAM_OP = re.compile(
    r"=\s*(?:f32|bf16|f16)\[\d[0-9,]*\]\S*\s+"
    r"(sqrt|rsqrt|divide|power)\("
)


def unfused_adam_chain_ops(opt_text):
    """Top-level (non-fused) sqrt/rsqrt/divide/power instructions on
    non-scalar float buffers in the optimized HLO ENTRY computation.

    A fused adam update keeps the whole m/v/sqrt/divide/param-delta chain
    inside one loop fusion per parameter; any of these ops surviving at
    ENTRY level means the chain broke into separate kernels (extra HBM
    round-trips per parameter per step). Returns the offending lines so
    the failure names the instruction."""
    entry = opt_text.split("ENTRY ", 1)
    if len(entry) < 2:
        return []
    out = []
    for line in entry[1].splitlines():
        s = line.strip()
        if s.startswith("%") and " fusion(" not in s and \
                _UNFUSED_ADAM_OP.search(s):
            out.append(s[:160])
    return out


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_LAYOUT = re.compile(r"\]\{[^{}]*\}")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = (\(.*?\)|[a-z0-9]+\[[0-9,]*\]) ([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
#: instructions that compute nothing an element
_NOT_ARITHMETIC = frozenset((
    "parameter", "constant", "broadcast", "bitcast", "copy", "reshape",
    "transpose", "tuple", "get-tuple-element", "convolution", "fusion",
    "iota", "slice", "dynamic-slice", "pad", "concatenate",
))


def fusion_census(opt_text):
    """Every ``fusion`` instruction of OPTIMIZED HLO text with what its
    computation holds, fusions nested in it included: a list of
    ``{"name", "kind", "outputs": [(dims, dtype)], "body": [(opcode,
    dims, dtype, op_name)]}``, ``body`` holding the instructions that
    produce ONE array. On the TPU a matrix product is a ``convolution``
    and ``kind=kOutput`` means the fusion is built AROUND it: what else it
    holds rides on the product's operands (a prologue) or on its result
    (an epilogue), and the MXU waits for it (PERF.md section 6, PR 49)."""
    comps, current = {}, None
    for line in opt_text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            current = comps.setdefault(m.group(1), []) if m else None
        elif current is not None:
            current.append(_LAYOUT.sub("]", line))

    def body_of(name):
        out = []
        for line in comps.get(name, ()):
            m = _INSTRUCTION.match(line)
            if not m:
                continue
            if m.group(3) == "fusion":
                out.extend(body_of(_CALLS.search(line).group(1)))
                continue
            shapes = opt_hlo_shapes(m.group(2))
            if len(shapes) == 1 and not m.group(2).startswith("("):
                op_name = _OP_NAME.search(line)
                out.append((m.group(3),) + shapes[0]
                           + (op_name.group(1) if op_name else "",))
        return out

    found = []
    for lines in comps.values():
        for line in lines:
            m = _INSTRUCTION.match(line)
            if not m or m.group(3) != "fusion":
                continue
            found.append({
                "name": m.group(1),
                "kind": re.search(r"kind=(\w+)", line).group(1),
                "outputs": opt_hlo_shapes(m.group(2)),
                "body": body_of(_CALLS.search(line).group(1)),
            })
    return found


def erfc_expansions(opt_text):
    """Instructions of OPTIMIZED HLO text that jax traced under ``erfc``
    (``op_name=".../erfc"``): XLA has no such instruction and writes ~60
    float32 operations an element in its place."""
    return [line.strip()[:160] for line in opt_text.splitlines()
            if re.search(r'op_name="[^"]*/erfc"', line)]


def activation_epilogues(opt_text, width):
    """What rides on the matrix products that make or read an activation
    of ``width`` features: for every fusion that holds a ``convolution``
    and outputs ``bf16[..., width]`` or ``f32[width]`` (BERT's FFN1 forward
    product and FFN2's data-gradient product with the bias gradient),
    ``{"name", "kind", "erf": how many, "float32_ops": the float32
    instructions of the activation's shape that compute something}``."""
    found = []
    for fusion in fusion_census(opt_text):
        body = fusion["body"]
        outputs = fusion["outputs"]
        if not any(op == "convolution" for op, *_ in body) or not any(
                (dtype == "bf16" and len(dims) > 1 and dims[-1] == width)
                or (dtype, dims) == ("f32", (width,))
                for dims, dtype in outputs):
            continue
        found.append({
            "name": fusion["name"], "kind": fusion["kind"],
            "erf": sum(op == "erf" for op, *_ in body),
            "float32_ops": sum(
                dtype == "f32" and len(dims) > 1 and dims[-1] == width
                and op not in _NOT_ARITHMETIC
                for op, dims, dtype, _name in body),
        })
    return found


def count_collectives(opt_text):
    """Collective-op counts in optimized HLO (post-SPMD-partitioning)."""
    return {
        "all-reduce": len(re.findall(r"\ball-reduce(?:-start)?\(", opt_text)),
        "all-gather": len(re.findall(r"\ball-gather(?:-start)?\(", opt_text)),
        "reduce-scatter": len(re.findall(r"\breduce-scatter\(", opt_text)),
        "all-to-all": len(re.findall(r"\ball-to-all\(", opt_text)),
        "collective-permute": len(
            re.findall(r"\bcollective-permute(?:-start)?\(", opt_text)
        ),
    }


# ---------------------------------------------------------------------------
# collective byte accounting ('collective bytes stay activation-sized',
# tests/test_hlo.py::test_tp_registry_collective_bytes_activation_sized) —
# what each collective MOVES, not just that it exists
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVE_KIND = re.compile(
    r"=\s*(?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\])\S*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)


def collective_lines(opt_text):
    """(kind, line) for every collective INSTRUCTION in optimized HLO —
    matched on the instruction's own op, so a line merely naming
    %all-reduce.N as an operand does not count."""
    out = []
    for line in opt_text.splitlines():
        m = _COLLECTIVE_KIND.search(line)
        if m:
            out.append((m.group(1), line.strip()))
    return out


def _shape_bytes(shape, dtype):
    n = 1
    for d in shape:
        n *= d
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_byte_report(opt_text):
    """Per-collective byte accounting: op kind x the LARGEST shaped value
    on the instruction line (result for gathers, operand for scatters —
    the upper bound on what the collective materializes per device).
    Returns {kind: {"count", "total_bytes", "max_bytes"}} plus
    "max_bytes"/"total_bytes" overall; shapes are the per-device HLO
    shapes of the partitioned module."""
    report = {}
    overall_max = 0
    overall_total = 0
    for kind, line in collective_lines(opt_text):
        line_max = 0
        for shape, dt in opt_hlo_shapes(line):
            line_max = max(line_max, _shape_bytes(shape, dt))
        ent = report.setdefault(
            kind, {"count": 0, "total_bytes": 0, "max_bytes": 0}
        )
        ent["count"] += 1
        ent["total_bytes"] += line_max
        ent["max_bytes"] = max(ent["max_bytes"], line_max)
        overall_max = max(overall_max, line_max)
        overall_total += line_max
    return {
        "by_kind": report,
        "max_bytes": overall_max,
        "total_bytes": overall_total,
    }


def weight_shaped_collectives(opt_text, param_shapes):
    """Collective instructions moving a FULL (unsharded) weight: any
    rank>=2 shape on a collective line that equals a parameter's full
    shape. With a correct sharding layout the list is empty — parameter
    math stays shard-local and only activations ride the wire. Callers
    must pick lowering geometry where activation shapes cannot collide
    with parameter shapes (see tiny_bert_parallel_text)."""
    shapes = {tuple(s) for s in param_shapes if len(tuple(s)) >= 2}
    offenders = []
    for kind, line in collective_lines(opt_text):
        for shape, dt in opt_hlo_shapes(line):
            if len(shape) >= 2 and tuple(shape) in shapes:
                offenders.append((kind, tuple(shape), line[:160]))
                break
    return offenders
