"""Static roofline cost model (ISSUE 16): pre-compile step-time / MFU /
bubble prediction and the hierarchical-collective linter.

Property contract: analysis/cost.py must assign a FLOP/byte cost to
EVERY op of every example program (unknown_ops empty — a new op entering
the op set without a cost rule fails here), its FLOP totals must agree
with XLA's own ``cost_analysis()`` within a committed tolerance, its
policy-dependent recompute pricing must reorder programs the same way
the static peak-HBM analyzer does, and each linter class must fire on a
synthetic positive control — all before any compile happens.
"""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.analysis.cost import (
    MACHINES,
    CostModel,
    analyze_cost,
    check_cost_budgets,
    hierarchical_collective_diagnostics,
    pipeline_bubble_report,
)
from paddle_tpu.analysis.memory import estimate_peak_hbm, remat_hbm_delta
from paddle_tpu.core.ir import Program, program_guard
from paddle_tpu.parallel.env import make_mesh
from paddle_tpu.parallel.spec_layout import SpecLayout

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: static-vs-XLA total-FLOP ratio bound for the property test. Measured
#: spread on the example set is 1.02-1.09 (XLA folds/pads transcendental
#: and reduce work the analytic rules count differently); 1.25 leaves
#: headroom without letting a broken rule (2x = one missed grad) pass.
XLA_FLOPS_TOLERANCE = 1.25


def _discover_examples():
    names = []
    for fn in sorted(os.listdir(os.path.join(REPO, "examples"))):
        path = os.path.join(REPO, "examples", fn)
        if fn.endswith(".py"):
            with open(path) as f:
                if "def build_programs" in f.read():
                    names.append(fn[:-3])
    return tuple(names)


EXAMPLES = _discover_examples()
RUNNABLE_EXAMPLES = tuple(n for n in EXAMPLES if n != "wide_deep")


def _build_example(name):
    from paddle_tpu.passes import (
        apply_deferred_sharded_embedding_rewrite,
        apply_deferred_sparse_rewrite,
    )

    spec = importlib.util.spec_from_file_location(
        f"ca_example_{name}", os.path.join(REPO, "examples", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    main, startup, feed_names, fetch = mod.build_programs()[:4]
    apply_deferred_sparse_rewrite(main)
    apply_deferred_sharded_embedding_rewrite(main)
    return main, startup, list(feed_names), [
        f if isinstance(f, str) else f.name for f in fetch
    ]


def _synthetic_feeds(program, feed_names, batch=16):
    rng = np.random.RandomState(0)
    block = program.global_block()
    out = {}
    for name in feed_names:
        v = block._find_var_recursive(name)
        shape = tuple(batch if d is None or d < 0 else int(d)
                      for d in v.shape)
        dt = str(getattr(v, "dtype", "float32") or "float32")
        if "int" in dt:
            out[name] = np.zeros(shape, dtype=dt)
        else:
            out[name] = rng.uniform(0.0, 1.0, shape).astype(dt)
    return out


# ---------------------------------------------------------------------------
# op coverage: every example op must have a cost rule
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("example", EXAMPLES)
def test_cost_coverage_examples(example):
    main, _startup, feed_names, fetch_names = _build_example(example)
    feed = _synthetic_feeds(main, feed_names)
    rep = analyze_cost(
        main, feed_shapes={k: v.shape for k, v in feed.items()},
        fetch_names=fetch_names,
    )
    assert sorted(rep.unknown_ops) == [], (
        f"{example}: ops without a cost rule — add them to "
        f"analysis/cost.py _FLOP_RULES")
    assert rep.total_flops > 0
    assert rep.step_seconds > 0
    assert 0 < rep.mfu <= 1.0


def test_cost_coverage_bert_and_gpt():
    """The model zoo's structured programs: tiny-BERT pretrain and the
    pipeline_stack GPT — full coverage including the fused/stacked ops."""
    from paddle_tpu.models import bert, gpt_ir

    cfg = bert.BertConfig.tiny()
    main, _s, _f, fetches = bert.build_bert_pretrain(
        cfg, seq_len=24, lr=1e-3, max_predictions_per_seq=20)
    data = bert.synthetic_batch(np.random.RandomState(0), 8, 24, cfg,
                                max_predictions_per_seq=20)
    rep = analyze_cost(
        main, feed_shapes={k: np.asarray(v).shape for k, v in data.items()},
        fetch_names=[fetches[0].name])
    assert sorted(rep.unknown_ops) == []

    gmain, _gs, _gf, gloss, _stack = gpt_ir.build_gpt_ir(
        gpt_ir.GPTIRConfig(), seq_len=16, num_microbatches=4)
    grep = analyze_cost(
        gmain, feed_shapes={"tokens": (8, 16), "labels": (8, 16)},
        fetch_names=[gloss.name], num_stages=4)
    assert sorted(grep.unknown_ops) == []
    assert grep.total_flops > 0


# ---------------------------------------------------------------------------
# FLOPs agree with XLA's cost model
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("example", RUNNABLE_EXAMPLES)
def test_cost_flops_match_xla(example):
    """The analytic FLOP totals must track what XLA's own
    ``compile().cost_analysis()`` reports for the same lowered step."""
    from paddle_tpu.utils import hlo

    main, startup, feed_names, fetch_names = _build_example(example)
    feed = _synthetic_feeds(main, feed_names)
    rep = analyze_cost(
        main, feed_shapes={k: v.shape for k, v in feed.items()},
        fetch_names=fetch_names,
    )
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        lowered = hlo.lower_program_step(main, feed, fetch_names,
                                         scope=scope)
    ca = lowered.compile().cost_analysis()
    xla = int(ca.get("flops", 0))
    assert xla > 0
    ratio = max(rep.total_flops, xla) / max(min(rep.total_flops, xla), 1)
    assert ratio <= XLA_FLOPS_TOLERANCE, (
        f"{example}: static {rep.total_flops} vs XLA {xla} "
        f"(ratio {ratio:.4f} > {XLA_FLOPS_TOLERANCE})")


# ---------------------------------------------------------------------------
# remat policies: cost.py and memory.py must agree on the trade
# ---------------------------------------------------------------------------


def _remat_program(policy):
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", shape=[-1, 128])
        y = fluid.data("y", shape=[-1, 1])
        h = x
        ckpts = []
        for _ in range(6):
            h = fluid.layers.fc(h, size=128, act="relu")
            ckpts.append(h)
        pred = fluid.layers.fc(h, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        opt = fluid.optimizer.SGD(learning_rate=0.1)
        if policy:
            opt = fluid.optimizer.RecomputeOptimizer(opt, policy=policy)
            opt._set_checkpoints(ckpts)
        opt.minimize(loss)
    return main, loss


def test_remat_policy_cost_memory_agreement():
    """The policy spectrum must price identically in both analyzers:
    recompute FLOPs full >= dots >= save_all, predicted HBM the inverse
    (full <= dots <= save_all) — and the cost model's byte ordering must
    match the ordering of memory.py's static peak (the two share the
    var-byte resolver, so divergence means a pricing bug, not a shape
    disagreement)."""
    fs = {"x": (512, 128), "y": (512, 1)}
    flops, cost_hbm, peak = {}, {}, {}
    for policy in (None, "full", "dots", "save_all"):
        main, loss = _remat_program(policy)
        rep = analyze_cost(main, feed_shapes=fs, fetch_names=[loss.name])
        assert sorted(rep.unknown_ops) == []
        mem = estimate_peak_hbm(main, feed_shapes=fs,
                                fetch_names=[loss.name])
        flops[policy] = rep.total_flops
        cost_hbm[policy] = rep.total_hbm_bytes
        peak[policy] = mem.peak_total_bytes
    # FLOPs: more recompute = more replay work
    assert flops["full"] > flops["dots"] >= flops["save_all"]
    # every remat policy replays at least the plain backward's work
    assert flops["save_all"] > flops[None]
    # bytes: more saved = more traffic/residency — SAME ordering in both
    assert cost_hbm["full"] < cost_hbm["dots"] < cost_hbm["save_all"]
    assert peak["full"] < peak["dots"] < peak["save_all"]
    # save_all is the no-remat control for peak residency
    assert peak["save_all"] == peak[None]
    # and the pre-compile delta tool reports a real saving for 'full'
    plain, _ = _remat_program(None)
    remat, _ = _remat_program("full")
    delta = remat_hbm_delta(plain, remat, feed_shapes=fs)
    assert delta["saved_bytes"] > 0
    assert delta["policies"] == ["full"]


# ---------------------------------------------------------------------------
# machine model + collective model unit properties
# ---------------------------------------------------------------------------


def test_cost_model_for_mesh_validates():
    from paddle_tpu.utils.enforce import EnforceError

    mesh = make_mesh((2, 4), ("data", "model"))
    cm = CostModel.for_mesh(mesh, machine="tpu-v4-8")
    assert cm.axis_sizes == {"data": 2, "model": 4}
    assert cm.tag("data") == "ici" and cm.tag("model") == "ici"
    with pytest.raises(EnforceError):
        CostModel.for_mesh(mesh, machine="tpu-v4-8",
                           axis_tags={"bogus": "ici"})
    with pytest.raises(EnforceError):
        CostModel.for_mesh(mesh, machine="tpu-v4-8",
                           axis_tags={"data": "wat"})
    with pytest.raises(EnforceError):
        analyze_cost(Program(), machine="not-a-machine")


def test_collective_seconds_two_level():
    """The latency-bandwidth law: a dcn-tagged axis pays dcn latency and
    bandwidth; an all-reduce moves 2(n-1)/n of the payload per axis."""
    mesh = make_mesh((2, 4), ("dcn", "data"))
    cm = CostModel.for_mesh(mesh, machine="tpu-v4-8",
                            axis_tags={"dcn": "dcn", "data": "ici"})
    m = cm.machine
    nbytes = 1 << 20
    got = cm.collective_seconds("all-reduce", nbytes, ("dcn", "data"))
    want = (m.link_lat["dcn"]
            + (2 * (2 - 1) / 2) * nbytes / m.link_bw["dcn"]
            + m.link_lat["ici"]
            + (2 * (4 - 1) / 4) * nbytes / m.link_bw["ici"])
    assert got == pytest.approx(want, rel=1e-12)
    # ici-only all-gather: (n-1)/n, single latency term
    got = cm.collective_seconds("all-gather", nbytes, ("data",))
    assert got == pytest.approx(
        m.link_lat["ici"] + (3 / 4) * nbytes / m.link_bw["ici"],
        rel=1e-12)


def test_machine_table_sane():
    for name, m in MACHINES.items():
        assert m.peak_flops > 0 and m.hbm_bw > 0
        assert m.ridge == pytest.approx(m.peak_flops / m.hbm_bw)
        assert m.link_bw["dcn"] < m.link_bw["ici"], name


# ---------------------------------------------------------------------------
# hierarchical-collective linter: positive + negative controls
# ---------------------------------------------------------------------------


def _mnist_cost_report(axes, axis_tags, input_axes):
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.models import mnist

    main, _startup, feeds, fetches = mnist.build_mnist_train()
    feed_names = [f if isinstance(f, str) else f.name for f in feeds]
    fetch_names = [f if isinstance(f, str) else f.name for f in fetches]
    feed = _synthetic_feeds(main, feed_names)
    return analyze_cost(
        main, mesh=make_mesh((2, 4), axes), axis_tags=axis_tags,
        input_specs={n: P(input_axes) for n in feed_names},
        feed_shapes={k: v.shape for k, v in feed.items()},
        fetch_names=fetch_names,
    )


def test_dcn_allreduce_linter_fires():
    """Positive control: batch split over a dcn-tagged outer axis means
    every grad-sync all-reduce crosses DCN at full payload — the linter
    MUST flag each with the two-level saving."""
    rep = _mnist_cost_report(("dcn", "data"),
                             {"dcn": "dcn", "data": "ici"},
                             ("dcn", "data"))
    diags = hierarchical_collective_diagnostics(rep)
    assert diags, "linter did not fire on the dcn positive control"
    assert all(d.code == "dcn-allreduce-not-hierarchical" for d in diags)
    assert all(d.severity == "error" and d.var for d in diags)
    assert any("save" in d.message for d in diags)


def test_dcn_allreduce_linter_silent_on_ici():
    """Negative control: the same program and mesh, all axes ici —
    hierarchical decomposition buys nothing, the linter stays silent."""
    rep = _mnist_cost_report(("outer", "data"),
                             {"outer": "ici", "data": "ici"},
                             ("outer", "data"))
    assert rep.collectives, "control lost its grad-sync collectives"
    assert hierarchical_collective_diagnostics(rep) == []


def test_cost_budget_gates():
    main, _startup, feed_names, fetch_names = _build_example("fit_a_line")
    feed = _synthetic_feeds(main, feed_names)
    rep = analyze_cost(
        main, feed_shapes={k: v.shape for k, v in feed.items()},
        fetch_names=fetch_names)
    assert check_cost_budgets(rep) == []  # zeros disable every gate
    tight = check_cost_budgets(rep, step_ms=1e-9, min_mfu=1.0)
    codes = {d.code for d in tight}
    assert codes == {"step-time-over-budget", "mfu-under-floor"}


# ---------------------------------------------------------------------------
# pipeline bubble prediction
# ---------------------------------------------------------------------------


def test_pipeline_bubble_gpipe_fraction():
    from paddle_tpu.models import gpt_ir

    gmain, _gs, _gf, _gloss, _stack = gpt_ir.build_gpt_ir(
        gpt_ir.GPTIRConfig(), seq_len=16, num_microbatches=4)
    shapes = {"tokens": (8, 16), "labels": (8, 16)}
    bub = pipeline_bubble_report(gmain, feed_shapes=shapes, num_stages=4)
    assert len(bub) == 1
    ent = bub[0]
    assert ent["schedule"] == "gpipe"
    assert ent["stages"] == 4 and ent["num_microbatches"] == 4
    assert ent["bubble_fraction"] == pytest.approx(3 / 7, abs=1e-6)
    # degenerate stacks cost no bubble
    solo = pipeline_bubble_report(gmain, feed_shapes=shapes, num_stages=1)
    assert solo[0]["bubble_fraction"] == 0.0


# ---------------------------------------------------------------------------
# CLI: lint_program cost subcommand + help/usage contract
# ---------------------------------------------------------------------------


def _lint(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint_program.py"),
         *argv],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)


def test_cli_top_level_help():
    r = _lint("--help")
    assert r.returncode == 0
    for sub in ("verify", "shapes", "sharding", "collectives", "memory",
                "cost", "smoke"):
        assert sub in r.stdout, f"--help does not mention '{sub}'"


@pytest.mark.parametrize("sub,flags", [
    ("cost", ("--machine", "--tag", "--budget-step-ms",
              "--budget-collective-kb", "--min-mfu", "--batch-spec",
              "--json")),
    ("sharding", ("--mesh", "--json")),
    ("memory", ("--json",)),
])
def test_cli_subcommand_help_lists_flags(sub, flags):
    r = _lint(sub, "--help")
    assert r.returncode == 0
    for flag in flags:
        assert flag in r.stdout, f"'{sub} --help' missing {flag}"


def test_cli_cost_bad_machine_exits_2():
    r = _lint("cost", "--builtin", "mnist", "--machine", "tpu-v999")
    assert r.returncode == 2, r.stdout + r.stderr
    assert "tpu-v999" in (r.stdout + r.stderr)


def test_cli_cost_clean_and_control():
    r = _lint("cost", "--builtin", "mnist", "--json")
    assert r.returncode == 0, r.stdout + r.stderr
    rep = json.loads(r.stdout.strip().splitlines()[0])
    assert rep["step_seconds"] > 0
    assert rep["unknown_ops"] == []
    # the dcn positive control must exit with findings
    r = _lint("cost", "--builtin", "mnist", "--mesh", "2x4:dcn,data",
              "--tag", "dcn=dcn", "--batch-spec", "dcn,data", "--json")
    assert r.returncode == 1, r.stdout + r.stderr
    rep = json.loads(r.stdout.strip().splitlines()[0])
    assert any(d["code"] == "dcn-allreduce-not-hierarchical"
               for d in rep["diagnostics"])


# ---------------------------------------------------------------------------
# lowering-stage wiring: FLAGS_static_diagnostics=cost
# ---------------------------------------------------------------------------


def test_cost_stage_in_lowering():
    from paddle_tpu.utils.flags import flags

    main, startup, feed_names, fetch_names = _build_example("fit_a_line")
    feed = _synthetic_feeds(main, feed_names, batch=4)
    old = flags.static_diagnostics
    flags.static_diagnostics = "cost"
    try:
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            out = exe.run(main, feed=feed, fetch_list=fetch_names)
        assert np.all(np.isfinite(np.asarray(out[0])))
    finally:
        flags.static_diagnostics = old


def test_static_flops_track_xla_on_the_tp_mesh_arm():
    """The mesh arm of the FLOP agreement (the single-device arms are
    test_cost_flops_match_xla): tiny-BERT on a (2, 4) data x model mesh
    under the SpecLayout registry. analyze_cost prices every op and its
    total is within 2x of XLA's per-device count for the partitioned
    step (measured ~1.35: GSPMD pads the per-device graph with halo and
    select flops the static model ignores)."""
    from paddle_tpu.models import bert
    from paddle_tpu.utils import hlo

    cfg = bert.BertConfig.tiny()
    cfg.hidden_dropout_prob = cfg.attention_probs_dropout_prob = 0.0
    main, startup, _feeds, fetches = bert.build_bert_pretrain(
        cfg, seq_len=24, lr=1e-3, max_predictions_per_seq=20)
    data = bert.synthetic_batch(np.random.RandomState(0), 8, 24, cfg,
                                max_predictions_per_seq=20)
    mesh = make_mesh((2, 4), ("data", "model"))
    rep = analyze_cost(
        main, mesh=mesh, spec_layout=SpecLayout(),
        feed_shapes={k: np.asarray(v).shape for k, v in data.items()},
        fetch_names=[fetches[0].name])
    assert sorted(rep.unknown_ops) == []
    assert rep.collectives and rep.collective_seconds > 0
    prog = fluid.CompiledProgram(main).with_parallel(
        mesh=mesh, loss_name=fetches[0].name, spec_layout=SpecLayout())
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        lowered, _ = hlo.lower_parallel_step(
            exe, prog, data, [fetches[0]], scope)
    xla = int(lowered.compile().cost_analysis().get("flops", 0))
    assert xla > 0
    ratio = max(rep.total_flops, xla) / min(rep.total_flops, xla)
    assert ratio <= 2.0, (rep.total_flops, xla, ratio)
