"""Static sharding & memory analyzer (ISSUE 9): shape/dtype/PartitionSpec
propagation, the pre-compile collective-cost linter, and the liveness
peak-HBM + donation-safety checker.

Property contract: the analyzers must be SILENT on every well-formed
example/model program, agree with runtime-observed shapes/dtypes and live
byte counts, and each hard-error class must fire on a synthetic positive
control with op/var attribution — before any lowering happens.
"""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu.analysis.memory import (
    check_donation_safety,
    estimate_peak_hbm,
)
from paddle_tpu.analysis.shapes import infer_shapes
from paddle_tpu.analysis.sharding import (
    analyze_sharding,
    collective_budget_diagnostics,
    weight_sized_events,
)
from paddle_tpu.analysis.signatures import get_signature
from paddle_tpu.parallel.env import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _discover_examples():
    """Mirror of tools/lint_program.py _discover_examples (that module is
    importlib-loaded per-test, too late for parametrize): every
    examples/*.py defining build_programs() — filesystem-derived so a new
    example enters these gates automatically."""
    names = []
    for fn in sorted(os.listdir(os.path.join(REPO, "examples"))):
        path = os.path.join(REPO, "examples", fn)
        if fn.endswith(".py"):
            with open(path) as f:
                if "def build_programs" in f.read():
                    names.append(fn[:-3])
    return tuple(names)


EXAMPLES = _discover_examples()

#: examples whose programs run with plain synthetic feeds (wide_deep needs
#: the embedding engine's prepare_feed slot resolution)
RUNNABLE_EXAMPLES = tuple(n for n in EXAMPLES if n != "wide_deep")


def _build_example(name):
    spec = importlib.util.spec_from_file_location(
        f"sa_example_{name}", os.path.join(REPO, "examples", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    built = mod.build_programs()
    fetch = built[3]
    return built[0], built[1], built[2], [
        f if isinstance(f, str) else f.name for f in fetch
    ]


def _synthetic_feeds(program, feed_names, batch=4):
    """Zeros-valued feeds from declared metadata (always-legal ids)."""
    block = program.global_block()
    out = {}
    for name in feed_names:
        v = block._find_var_recursive(name)
        shape = tuple(batch if d is None or d < 0 else int(d)
                      for d in (v.shape or (1,)))
        dt = str(v.dtype or "float32")
        if "int" in dt:
            out[name] = np.zeros(shape, dt)
        else:
            out[name] = np.random.RandomState(0).randn(*shape).astype(dt)
    return out


# ---------------------------------------------------------------------------
# shapes: silence on well-formed programs + runtime agreement
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("example", EXAMPLES)
def test_shapes_silent_on_examples(example):
    main, startup, _feed, _fetch = _build_example(example)
    for prog in (main, startup):
        rep = infer_shapes(prog)
        assert rep.errors() == [], [str(d) for d in rep.errors()[:3]]
        assert [d for d in rep.diagnostics
                if d.code == "amp-fp32-matmul"] == []


@pytest.mark.parametrize("example", RUNNABLE_EXAMPLES)
def test_static_shapes_agree_with_runtime(example):
    """Property test: static shape/dtype inference matches the
    runtime-observed fetch arrays on every example program."""
    main, startup, feed_names, fetch_names = _build_example(example)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        feeds = _synthetic_feeds(main, feed_names)
        outs = exe.run(main, feed=feeds, fetch_list=fetch_names)
    rep = infer_shapes(
        main, feed_shapes={k: v.shape for k, v in feeds.items()}
    )
    assert rep.errors() == []
    for name, val in zip(fetch_names, outs):
        info = rep.get(name)
        assert info is not None, f"no static info for fetch '{name}'"
        assert info.shape is not None
        got = tuple(np.asarray(val).shape)
        assert len(info.shape) == len(got), (name, info.shape, got)
        for s, g in zip(info.shape, got):
            if isinstance(s, int):
                assert s == g, (name, info.shape, got)
        # dtype family must agree (x64-disabled jax narrows int64->int32)
        want = (info.dtype or "").rstrip("0123456789")
        have = str(np.asarray(val).dtype).rstrip("0123456789")
        assert want == have, (name, info.dtype, np.asarray(val).dtype)


def test_shapes_bert_amp_clean_and_symbolic_dims():
    from paddle_tpu.models import bert

    cfg = bert.BertConfig.tiny()
    main, _s, _f, _t = bert.build_bert_pretrain(
        cfg, seq_len=16, lr=1e-3, use_amp=True
    )
    rep = infer_shapes(main)
    assert rep.amp_mode
    assert rep.errors() == []
    assert [d for d in rep.diagnostics
            if d.code == "amp-fp32-matmul"] == []
    # the unfed batch dim survives as a named unknown, not a guess
    x = fluid.Program()
    with fluid.program_guard(x, fluid.Program()):
        inp = fluid.data("inp", shape=[-1, 8])
        h = fluid.layers.fc(inp, size=4)
    info = infer_shapes(x).get(h.name)
    assert info.shape[1] == 4
    assert isinstance(info.shape[0], str)  # symbolic


def test_shape_mismatch_positive_control_names_op_and_var():
    main = fluid.Program()
    b = main.global_block()
    b.create_var(name="x", shape=[4, 8], dtype="float32", is_data=True)
    b.create_var(name="w", shape=[9, 3], dtype="float32", persistable=True)
    b.create_var(name="out", shape=[4, 3], dtype="float32")
    b.append_op("matmul", {"X": ["x"], "Y": ["w"]}, {"Out": ["out"]})
    errs = infer_shapes(main).errors()
    assert any(d.code == "shape-mismatch" and d.op_type == "matmul"
               and d.var == "w" for d in errs)


def test_amp_fp32_matmul_positive_control():
    main = fluid.Program()
    b = main.global_block()
    b.create_var(name="a", shape=[4, 8], dtype="float32", is_data=True)
    b.create_var(name="a16", shape=[4, 8], dtype="bfloat16")
    b.create_var(name="w", shape=[8, 3], dtype="float32", persistable=True)
    b.create_var(name="o", shape=[4, 3], dtype="float32")
    b.append_op("cast", {"X": ["a"]}, {"Out": ["a16"]},
                {"out_dtype": "bfloat16"})
    b.append_op("matmul", {"X": ["a"], "Y": ["w"]}, {"Out": ["o"]})
    diags = infer_shapes(main).diagnostics
    hits = [d for d in diags if d.code == "amp-fp32-matmul"]
    assert hits and hits[0].op_type == "matmul"


# ---------------------------------------------------------------------------
# signatures audit: zero unknown-signature ops across the example set
# ---------------------------------------------------------------------------


def test_example_programs_have_full_signature_coverage():
    """Every op type the examples/ build_programs() graphs emit resolves a
    static signature (grad ops resolve through their base op), so the
    verifier and the shape pass see the whole surface."""
    structural = {"feed", "fetch", "while", "conditional_block"}
    missing = set()
    for example in EXAMPLES:
        main, startup, _f, _t = _build_example(example)
        for prog in (main, startup):
            for block in prog.blocks:
                for op in block.ops:
                    t = op.type
                    if t in structural:
                        continue
                    base = t[:-5] if t.endswith("_grad") else t
                    if get_signature(base) is None:
                        missing.add(t)
    assert missing == set(), (
        f"ops without a static signature: {sorted(missing)} — add them to "
        f"analysis/signatures.py (empty OpSignature() marks 'audited, "
        f"nothing checkable')"
    )


# ---------------------------------------------------------------------------
# sharding: the pre-compile collective-cost linter
# ---------------------------------------------------------------------------


def _tiny_tp_program(hidden=64):
    """Two-fc net with transformer-style naming, small enough to analyze
    in milliseconds but shaped like the real placement problem."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[-1, hidden])
        h = fluid.layers.fc(x, size=hidden, act="relu", name="enc.ffn1")
        y = fluid.layers.fc(h, size=hidden, name="enc.ffn2")
        loss = fluid.layers.mean(y)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def test_sharding_pure_dp_mesh_predicts_no_weight_updates_gathers():
    main, _s, _loss = _tiny_tp_program()
    mesh = make_mesh((8,), ("data",))
    rep = analyze_sharding(main, mesh, feed_shapes={"x": (16, 64)})
    assert [e for e in rep.events
            if e.cause == "replicated-param-update"] == []
    # grad-sync all-reduces ARE predicted on a dp mesh
    assert any(e.cause == "grad-sync" for e in rep.events)


def test_sharding_grad_sync_is_per_trainable_param_only():
    """Adam: moments/beta pows are read+written persistables too, but
    their updates are local once the grad is synced — one predicted
    all-reduce per PARAMETER, no phantom events for optimizer slots."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[-1, 8])
        y = fluid.data("y", shape=[-1, 1])
        p = fluid.layers.fc(x, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(p, y))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    rep = analyze_sharding(main, make_mesh((8,), ("data",)),
                           feed_shapes={"x": (16, 8), "y": (16, 1)})
    synced = {e.var for e in rep.events if e.cause == "grad-sync"}
    assert synced == {p.name for p in main.all_parameters()}, synced


def test_sharding_replicated_param_in_tp_program_is_flagged():
    """The PR-7 failure class, statically: a layout that tensor-shards one
    weight but leaves another replicated predicts a full weight-sized
    all-gather for the replicated one."""
    from jax.sharding import PartitionSpec as P

    main, _s, _loss = _tiny_tp_program()
    mesh = make_mesh((2, 4), ("data", "model"))
    w_names = sorted(
        p.name for p in main.all_parameters() if len(p.shape) == 2
    )
    # shard the first weight by hand, leave the second replicated
    rep = analyze_sharding(
        main, mesh,
        param_specs={w_names[0]: P(None, "model")},
        feed_shapes={"x": (16, 64)},
    )
    param_shapes = [tuple(p.shape) for p in main.all_parameters()
                    if len(p.shape or ()) >= 2]
    ws = weight_sized_events(rep, param_shapes)
    offenders = {e.var for e in ws if e.cause == "replicated-param-update"}
    assert w_names[1] in offenders
    assert w_names[0] not in offenders
    # and the registry layout clears it
    from paddle_tpu.parallel.spec_layout import SpecLayout

    rep2 = analyze_sharding(main, mesh, spec_layout=SpecLayout(),
                            feed_shapes={"x": (16, 64)})
    assert weight_sized_events(rep2, param_shapes) == []


def test_collective_budget_linter_positive_control():
    from jax.sharding import PartitionSpec as P

    main, _s, _loss = _tiny_tp_program()
    mesh = make_mesh((2, 4), ("data", "model"))
    w_names = sorted(
        p.name for p in main.all_parameters() if len(p.shape) == 2
    )
    rep = analyze_sharding(
        main, mesh, param_specs={w_names[0]: P(None, "model")},
        feed_shapes={"x": (16, 64)},
    )
    # full 64x64 f32 weight = 16 KiB; a 8 KiB budget must fire and the
    # diagnostic must name the variable
    diags = collective_budget_diagnostics(rep, 8 * 1024)
    assert diags
    assert any(d.var == w_names[1] for d in diags)
    assert all(d.code == "collective-over-budget" for d in diags)
    # a generous budget passes
    assert collective_budget_diagnostics(rep, 1024 * 1024) == []


def test_sharding_matmul_partial_sum_predicted():
    """A tensor-sharded contraction predicts the Megatron epilogue
    all-reduce with activation-sized bytes, not a weight gather."""
    from jax.sharding import PartitionSpec as P

    main, _s, _loss = _tiny_tp_program()
    mesh = make_mesh((2, 4), ("data", "model"))
    w = sorted(p.name for p in main.all_parameters()
               if len(p.shape) == 2)
    rep = analyze_sharding(
        main, mesh,
        param_specs={w[0]: P(None, "model"), w[1]: P("model", None)},
        feed_shapes={"x": (16, 64)},
    )
    partials = [e for e in rep.events if e.cause == "matmul-partial-sum"]
    assert partials, [e.cause for e in rep.events[:10]]
    # activation-sized: [16, 64] f32 sharded over data -> 2 KiB
    assert all(e.bytes <= 16 * 64 * 4 for e in partials)


# ---------------------------------------------------------------------------
# memory: peak-HBM accuracy + donation safety
# ---------------------------------------------------------------------------


def _runtime_peak_reference(main, feeds, fetch_names, scope):
    """The 'true' per-device live-bytes upper bound: run the block per-op
    with concrete arrays, record every produced buffer's ACTUAL nbytes,
    then replay the same liveness walk over actual sizes."""
    from paddle_tpu.analysis.usedef import UseDefMap
    from paddle_tpu.core.executor import _interpret_block

    block = main.global_block()
    env = {k: jax.numpy.asarray(v) for k, v in feeds.items()}
    for name in block.vars:
        v = scope.find_var(name)
        if v is not None and name not in env:
            env[name] = v
    _interpret_block(block, env, jax.random.PRNGKey(0))
    sizes = {}
    for n, v in env.items():
        try:
            sizes[n] = np.asarray(v).nbytes
        except Exception:
            pass

    usedef = UseDefMap(block, fetch_names=fetch_names)

    def persistable(n):
        v = block._find_var_recursive(n)
        return v is not None and v.persistable

    touched = set()
    for op in block.ops:
        touched |= usedef.reads_of(op) | usedef.writes_of(op)
    persistent = sum(sizes.get(n, 0) for n in touched if persistable(n))

    needed = set(fetch_names)
    live_after = [set() for _ in block.ops]
    for i in range(len(block.ops) - 1, -1, -1):
        live_after[i] = {n for n in needed if not persistable(n)}
        needed -= usedef.writes_of(block.ops[i])
        needed |= usedef.reads_of(block.ops[i])
    entry = {n for n in needed if not persistable(n) and n in sizes}
    peak = sum(sizes.get(n, 0) for n in entry)
    for live in live_after:
        peak = max(peak, sum(sizes.get(n, 0) for n in live))
    return persistent + peak


@pytest.mark.parametrize(
    "example", ["fit_a_line", "recognize_digits", "recommender_system"]
)
def test_peak_hbm_estimate_within_25pct_of_runtime(example):
    main, startup, feed_names, fetch_names = _build_example(example)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        feeds = _synthetic_feeds(main, feed_names)
        ref = _runtime_peak_reference(main, feeds, fetch_names, scope)
    rep = estimate_peak_hbm(
        main, feed_shapes={k: v.shape for k, v in feeds.items()},
        fetch_names=fetch_names, donate=True,
    )
    est = rep.peak_total_bytes
    assert ref > 0 and est > 0
    assert abs(est - ref) / ref <= 0.25, (
        f"{example}: static {est} vs runtime {ref} "
        f"({abs(est - ref) / ref:.1%} off); unknown={rep.unknown_vars[:5]}"
    )
    # donation strictly shrinks the estimate (in-place updates alias)
    rep_off = estimate_peak_hbm(
        main, feed_shapes={k: v.shape for k, v in feeds.items()},
        fetch_names=fetch_names, donate=False,
    )
    assert rep_off.peak_total_bytes > est


def test_memory_counts_sub_block_intermediates():
    """A while body's private per-iteration buffers are live while the
    while op runs — the peak at that program point must include the
    body's own internal worst point, not just parent-block vars."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[-1, 8], dtype="float32")
        big = fluid.layers.fc(x, size=256)
        i = fluid.layers.fill_constant([1], "float32", 0.0)
        limit = fluid.layers.fill_constant([1], "float32", 3.0)
        s = fluid.layers.fill_constant([1], "float32", 0.0)
        cond = fluid.layers.less_than(i, limit)
        with fluid.layers.While(cond):
            t = fluid.layers.elementwise_add(big, big)  # body-local [B,256]
            ns = fluid.layers.elementwise_add(s, fluid.layers.reduce_sum(t))
            fluid.layers.assign(ns, s)
            ni = fluid.layers.increment(i, value=1.0, in_place=False)
            fluid.layers.assign(ni, i)
            fluid.layers.less_than(i, limit, cond=cond)
    rep = estimate_peak_hbm(main, feed_shapes={"x": (64, 8)},
                            fetch_names=[s.name])
    body_buf = 64 * 256 * 4  # t lives only inside the body
    while_points = [b for _i, t_, b in rep.timeline if t_ == "while"]
    assert while_points and max(while_points) >= body_buf, rep.timeline


def _adam_mlp():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[-1, 8])
        y = fluid.data("y", shape=[-1, 1])
        h = fluid.layers.fc(x, size=16, act="relu")
        pred = fluid.layers.fc(h, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    return main, startup, loss


def test_donation_safety_clean_on_adam_step():
    """All 20 donated inputs of the r06 adam step (params + both moments +
    beta pows) verify clean."""
    from paddle_tpu.core.executor import plan_step

    main, startup, loss = _adam_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        donated, readonly, _w, _ops = plan_step(
            main.global_block(), ["x", "y"], [loss.name], scope, True
        )
    assert len(donated) == 20
    assert check_donation_safety(main, donated, readonly,
                                 [loss.name]) == []


def test_read_after_donate_rejected_before_lowering():
    """A program reading a parameter AFTER its optimizer update is
    rejected by lower_step with op/var-attributed diagnostics before any
    tracing (the donation-safety gate is always on)."""
    main, startup, loss = _adam_mlp()
    b = main.global_block()
    late = b.create_var(name="late_read", shape=[1], dtype="float32")
    param = main.all_parameters()[0].name
    b.append_op("mean", {"X": [param]}, {"Out": [late.name]},
                {"op_role": 0})
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        with pytest.raises(fluid.EnforceError) as ei:
            exe.run(main,
                    feed={"x": np.zeros((4, 8), "float32"),
                          "y": np.zeros((4, 1), "float32")},
                    fetch_list=[loss.name])
    msg = str(ei.value)
    assert "read-after-donate" in msg
    assert param in msg


def test_donated_fetched_and_aliased_twice_are_hard_errors():
    main, _startup, loss = _adam_mlp()
    params = [p.name for p in main.all_parameters()]
    donated = params + [params[0]]          # aliased twice
    diags = check_donation_safety(main, donated, [], [loss.name, params[1]])
    codes = {d.code for d in diags}
    assert "donated-var-aliased-twice" in codes
    assert "donated-var-fetched" in codes
    fetched = [d for d in diags if d.code == "donated-var-fetched"]
    assert fetched[0].var == params[1]
    # donated-but-never-written is caught too
    ghost = check_donation_safety(main, ["never_written_var"], [], [])
    assert any(d.code == "donated-not-written" for d in ghost)


# ---------------------------------------------------------------------------
# opt-in diagnostic stages in core/lowering.py
# ---------------------------------------------------------------------------


def test_static_diagnostics_stage_rejects_shape_mismatch():
    from paddle_tpu.utils.flags import flags

    main = fluid.Program()
    b = main.global_block()
    b.create_var(name="x", shape=[4, 8], dtype="float32", is_data=True)
    b.create_var(name="w", shape=[9, 3], dtype="float32", persistable=True)
    b.create_var(name="out", shape=[4, 3], dtype="float32")
    b.append_op("matmul", {"X": ["x"], "Y": ["w"]}, {"Out": ["out"]})
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    old = flags.static_diagnostics
    flags.static_diagnostics = "shapes"
    try:
        with fluid.scope_guard(scope):
            scope.set("w", np.zeros((9, 3), "float32"))
            with pytest.raises(fluid.EnforceError) as ei:
                exe.run(main, feed={"x": np.zeros((4, 8), "float32")},
                        fetch_list=["out"])
        assert "shape-mismatch" in str(ei.value)
    finally:
        flags.static_diagnostics = old


def test_static_diagnostics_off_by_default():
    from paddle_tpu.utils.flags import flags

    assert flags.static_diagnostics == ""


# ---------------------------------------------------------------------------
# spec_layout auto-default (ROADMAP item 1 remaining)
# ---------------------------------------------------------------------------


def test_spec_layout_defaults_on_for_tp_mesh_when_analyzer_clean():
    from paddle_tpu.models import bert

    cfg = bert.BertConfig.tiny()
    cfg.hidden_dropout_prob = 0.0
    cfg.attention_probs_dropout_prob = 0.0
    main, startup, feeds, fetches = bert.build_bert_pretrain(
        cfg, seq_len=16, lr=1e-3
    )
    mesh = make_mesh((2, 4), ("data", "model"))
    prog = fluid.CompiledProgram(main).with_parallel(
        mesh=mesh, loss_name=fetches[0].name
    )
    layout = prog._resolve_spec_layout({})
    assert layout is not None, (
        "registry should default ON: the analyzer predicts zero "
        "weight-sized collectives for tiny-BERT under the registry"
    )
    # explicit False wins
    prog_off = fluid.CompiledProgram(main).with_parallel(
        mesh=mesh, loss_name=fetches[0].name, spec_layout=False
    )
    assert prog_off._resolve_spec_layout({}) is None
    # param_rules present -> auto stays out of the way
    from paddle_tpu.parallel.sharding import MEGATRON_RULES

    prog_rules = fluid.CompiledProgram(main).with_parallel(
        mesh=mesh, loss_name=fetches[0].name, param_rules=MEGATRON_RULES
    )
    assert prog_rules._resolve_spec_layout({}) is None


def test_spec_layout_auto_off_on_pure_dp_mesh():
    main, _s, loss = _tiny_tp_program()
    prog = fluid.CompiledProgram(main).with_parallel(
        mesh=make_mesh((8,), ("data",)), loss_name=loss.name
    )
    assert prog._resolve_spec_layout({}) is None


# ---------------------------------------------------------------------------
# lint CLI: subcommands, exit codes, JSON
# ---------------------------------------------------------------------------


def _load_lint_main():
    spec = importlib.util.spec_from_file_location(
        "lint_program_r09", os.path.join(REPO, "tools", "lint_program.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _save_desc(program, path, feed_names=(), fetch_names=()):
    desc = json.loads(program.to_bytes().decode("utf-8"))
    desc["feed_var_names"] = list(feed_names)
    desc["fetch_var_names"] = list(fetch_names)
    with open(path, "w") as f:
        json.dump(desc, f)


def test_lint_examples_discovery_matches():
    """The filesystem-derived example list here and in lint_program.py
    are mirrors — they must agree, and must see every example."""
    lint = _load_lint_main()
    assert lint.EXAMPLES == EXAMPLES
    assert set(EXAMPLES) >= {"fit_a_line", "wide_deep"}


def test_lint_subcommand_exit_codes_and_json(tmp_path, capsys):
    lint = _load_lint_main()
    main, _startup, loss = _adam_mlp()
    good = tmp_path / "good.json"
    _save_desc(main, good, ["x", "y"], [loss.name])

    # clean program: every subcommand exits 0
    assert lint.main(["shapes", str(good)]) == 0
    assert lint.main(["memory", str(good)]) == 0
    assert lint.main(
        ["sharding", str(good), "--mesh", "8x1:data,model"]
    ) == 0
    assert lint.main(
        ["collectives", str(good), "--mesh", "8x1:data,model",
         "--budget-kb", "64"]
    ) == 0
    capsys.readouterr()

    # shape defect -> exit 1 with machine-readable findings
    bad_prog = fluid.Program()
    b = bad_prog.global_block()
    b.create_var(name="x", shape=[4, 8], dtype="float32", is_data=True)
    b.create_var(name="w", shape=[9, 3], dtype="float32", persistable=True)
    b.create_var(name="o", shape=[4, 3], dtype="float32")
    b.append_op("matmul", {"X": ["x"], "Y": ["w"]}, {"Out": ["o"]})
    bad = tmp_path / "bad.json"
    _save_desc(bad_prog, bad, ["x"], ["o"])
    assert lint.main(["shapes", str(bad), "--json"]) == 1
    line = capsys.readouterr().out.strip().splitlines()[-1]
    payload = json.loads(line)
    assert payload["pass"] == "shapes" and payload["errors"] >= 1
    assert any(d["code"] == "shape-mismatch"
               for d in payload["diagnostics"])

    # internal error (unreadable file) -> exit 2
    assert lint.main(["shapes", str(tmp_path / "missing.json")]) == 2

    # legacy no-subcommand mode still verifies (back-compat contract)
    assert lint.main([str(good)]) == 0


def test_lint_memory_read_after_donate_exit_code(tmp_path, capsys):
    lint = _load_lint_main()
    main, _startup, loss = _adam_mlp()
    b = main.global_block()
    late = b.create_var(name="late", shape=[1], dtype="float32")
    param = main.all_parameters()[0].name
    b.append_op("mean", {"X": [param]}, {"Out": [late.name]},
                {"op_role": 0})
    bad = tmp_path / "rad.json"
    _save_desc(main, bad, ["x", "y"], [loss.name])
    assert lint.main(["memory", str(bad), "--json"]) == 1
    line = capsys.readouterr().out.strip().splitlines()[-1]
    payload = json.loads(line)
    assert any(d["code"] == "read-after-donate" and d["var"] == param
               for d in payload["diagnostics"])


def test_lint_collectives_budget_exit_code(tmp_path, capsys):
    """Over-budget prediction -> exit 1; the finding names the var."""
    lint = _load_lint_main()
    main, _s, loss = _tiny_tp_program()
    # registry shards both weights -> stay under budget; replicated
    # placement (no --spec-layout) pays full grad-sync all-reduces that
    # blow a 1 KB budget
    p = tmp_path / "tp.json"
    _save_desc(main, p, ["x"], [loss.name])
    assert lint.main(
        ["collectives", str(p), "--mesh", "2x4:data,model",
         "--budget-kb", "1", "--json"]
    ) == 1
    line = capsys.readouterr().out.strip().splitlines()[-1]
    payload = json.loads(line)
    assert any(d["code"] == "collective-over-budget"
               for d in payload["diagnostics"])


@pytest.mark.slow
def test_lint_smoke_subprocess():
    """The fast-tier CI gate end to end: all examples lint clean."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint_program.py"),
         "smoke"],
        capture_output=True, text=True, env=env, timeout=540,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-1000:]
    assert "all examples clean" in proc.stdout


def test_smoke_gate_in_process():
    """The same gate without the subprocess cost (fast tier)."""
    lint = _load_lint_main()
    assert lint.main(["smoke"]) == 0
