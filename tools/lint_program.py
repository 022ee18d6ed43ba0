#!/usr/bin/env python
"""Lint serialized programs (train or inference) with the static analyzers.

Subcommands (CI contract: exit 0 = clean, 1 = lint findings, 2 = internal
error; ``--json`` emits one machine-readable report line per program):

  verify       program verifier (use-before-def, dangling vars, dtype/rank
               violations, unknown ops) — the default when no subcommand
               is given, so pre-PR-9 invocations keep working
  shapes       whole-program symbolic shape/dtype inference
               (analysis/shapes.py): shape mismatches + the AMP
               fp32-matmul lint
  sharding     static PartitionSpec propagation (analysis/sharding.py):
               findings are predicted WEIGHT-SIZED collectives — a
               parameter the layout leaves replicated in a tensor-sharded
               program pays a full weight gather per step
  collectives  the same propagation as a byte-budget linter:
               ``--budget-kb N`` fails on any predicted collective moving
               more than N KB per device
  memory       liveness-driven peak-HBM estimate + the donation-safety
               hard errors (read-after-donate, donated-var-fetched,
               donated-var-aliased-twice)
  cost         roofline cost model (analysis/cost.py): predicted step
               seconds / MFU / per-op compute-vs-memory-bound
               classification on ``--machine`` (tpu-v4-8 default), the
               per-axis collective budget, the hierarchical-collective
               (dcn-allreduce) linter when ``--tag AXIS=dcn`` declares a
               slow axis, and ``--budget-step-ms`` /
               ``--budget-collective-kb`` / ``--min-mfu`` gates
  smoke        the fast-tier CI gate: shapes+sharding+donation over every
               examples/ build_programs() graph

Accepts raw ``Program.to_bytes()`` JSON files or saved inference
``__model__`` descs (embedded feed/fetch names ride along), and
``--builtin mnist|mnist_conv|transformer`` for freshly-built models.

Usage:
  python tools/lint_program.py path/to/__model__ [path2 ...]
  python tools/lint_program.py shapes model.json --feed-shape x=32,13
  python tools/lint_program.py sharding --builtin transformer \\
      --mesh 2x4:data,model --spec-layout --json
  python tools/lint_program.py collectives model.json --mesh 2x4:data,model \\
      --budget-kb 192
  python tools/lint_program.py cost --builtin transformer \\
      --mesh 2x4:dcn,data --tag dcn=dcn --machine tpu-v4-8 --json
  python tools/lint_program.py smoke
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BUILTINS = ("mnist", "mnist_conv", "transformer")
SUBCOMMANDS = ("verify", "shapes", "sharding", "collectives", "memory",
               "cost", "smoke")

EXIT_CLEAN, EXIT_FINDINGS, EXIT_INTERNAL = 0, 1, 2

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

def _discover_examples():
    """Every examples/*.py defining build_programs() — the contract
    examples/README.md documents. Derived from the filesystem (not a
    hand-list) so a new example enters the smoke gates — and the mirrors
    in tests/test_static_analysis.py — without a list to forget."""
    names = []
    for fn in sorted(os.listdir(os.path.join(REPO, "examples"))):
        path = os.path.join(REPO, "examples", fn)
        if fn.endswith(".py"):
            with open(path) as f:
                if "def build_programs" in f.read():
                    names.append(fn[:-3])
    return tuple(names)


EXAMPLES = _discover_examples()


def _ensure_virtual_devices(n):
    """The sharding/collectives subcommands need an n-device mesh; on the
    CPU lint rig that means forcing virtual host devices BEFORE jax
    initializes."""
    flags_env = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags_env:
        os.environ["XLA_FLAGS"] = (
            flags_env + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _mesh_arg_devices(argv):
    """Pre-parse --mesh so the virtual-device env is set before jax loads."""
    for i, a in enumerate(argv):
        spec = None
        if a == "--mesh" and i + 1 < len(argv):
            spec = argv[i + 1]
        elif a.startswith("--mesh="):
            spec = a.split("=", 1)[1]
        if spec:
            try:
                shape = _parse_mesh(spec)[0]
                n = 1
                for d in shape:
                    n *= d
                return max(n, 1)
            except Exception:
                return None
    return None


def _usage_error(msg):
    """Bad invocation — exit EXIT_INTERNAL (2), never EXIT_FINDINGS (1):
    CI gates on 1 meaning 'the program has lint findings', and a malformed
    command line must not read as that."""
    print(msg, file=sys.stderr)
    raise SystemExit(EXIT_INTERNAL)


def _parse_mesh(spec):
    """'2x4:data,model' -> ((2, 4), ('data', 'model'))."""
    shape_s, _, axes_s = spec.partition(":")
    try:
        shape = tuple(int(d) for d in shape_s.lower().split("x"))
    except ValueError:
        shape, axes = (), ()
    else:
        axes = tuple(a for a in axes_s.split(",") if a)
    if not axes or len(axes) != len(shape):
        _usage_error(
            f"bad --mesh '{spec}': want SHAPE:AXES like 2x4:data,model"
        )
    return shape, axes


def _parse_feed_shapes(entries):
    """['x=32,13', 'y=32,1'] -> {'x': (32, 13), 'y': (32, 1)}."""
    out = {}
    for e in entries or []:
        name, _, dims = e.partition("=")
        if not dims:
            _usage_error(f"bad --feed-shape '{e}': want name=2,8")
        out[name] = tuple(int(d) for d in dims.replace("x", ",").split(","))
    return out


def _load_program(path):
    """Load a serialized program; returns (program, feed_names, fetch_names).
    Handles both Program.to_bytes() output and save_inference_model's
    __model__ desc (feed/fetch names embedded)."""
    from paddle_tpu.core.ir import Program

    with open(path, "rb") as f:
        data = f.read()
    desc = json.loads(data.decode("utf-8"))
    program = Program.from_bytes(data)
    return (program, desc.get("feed_var_names", []),
            desc.get("fetch_var_names", []))


def _build_builtin(name):
    """Build a known model's train program in-process (no training, no
    execution) — lints the graph builders themselves."""
    import paddle_tpu as fluid

    if name in ("mnist", "mnist_conv"):
        from paddle_tpu.models import mnist

        main, startup, feeds, fetches = mnist.build_mnist_train(
            use_conv=(name == "mnist_conv")
        )
    elif name == "transformer":
        from paddle_tpu.models import transformer as tfm

        main, startup, feeds, fetches = tfm.build_wmt_train(
            tfm.TransformerConfig.tiny(), src_len=8, tgt_len=8,
            optimizer=fluid.optimizer.Adam(1e-3),
        )
    else:
        _usage_error(f"unknown --builtin '{name}'; have {BUILTINS}")
    feed_names = [f if isinstance(f, str) else f.name for f in feeds]
    fetch_names = [f if isinstance(f, str) else f.name for f in fetches]
    return main, feed_names, fetch_names


def _iter_programs(args, feed, fetch):
    for path in args.programs:
        program, ffeed, ffetch = _load_program(path)
        yield os.path.basename(path), program, ffeed or feed, \
            ffetch or fetch
    for name in getattr(args, "builtin", None) or []:
        program, bfeed, bfetch = _build_builtin(name)
        yield f"builtin:{name}", program, bfeed, bfetch


def _diag_json(d):
    return {
        "severity": d.severity, "code": d.code, "message": d.message,
        "block": d.block_idx, "op_index": d.op_index, "op_type": d.op_type,
        "var": d.var,
    }


def _report(label, pass_name, diags, extra=None, as_json=False,
            warnings_as_errors=False, out=sys.stdout):
    """Shared finding formatter; returns the number of gating findings."""
    errors = [d for d in diags if d.severity == "error"]
    gating = diags if warnings_as_errors else errors
    if as_json:
        payload = {
            "program": label,
            "pass": pass_name,
            "errors": len(errors),
            "warnings": len(diags) - len(errors),
            "diagnostics": [_diag_json(d) for d in diags],
        }
        payload.update(extra or {})
        out.write(json.dumps(payload) + "\n")
    else:
        for d in diags:
            out.write(f"{label}: {d}\n")
        for k, v in (extra or {}).items():
            if k != "events":
                out.write(f"{label}: {k} = {v}\n")
        out.write(
            f"{label}: [{pass_name}] {len(errors)} error(s), "
            f"{len(diags) - len(errors)} warning(s)\n"
        )
    return len(gating)


# ---------------------------------------------------------------------------
# subcommand bodies
# ---------------------------------------------------------------------------


def lint(program, feed_names, fetch_names, label, as_json=False,
         warnings_as_errors=False, out=sys.stdout):
    """Verify one program; returns the number of gating findings.
    (Kept under this name: tests and older CI hooks call it directly.)"""
    from paddle_tpu.analysis.verify import verify_program

    diags = verify_program(
        program, feed_names=feed_names, fetch_names=fetch_names
    )
    errors = [d for d in diags if d.severity == "error"]
    gating = diags if warnings_as_errors else errors
    if as_json:
        out.write(json.dumps({
            "program": label,
            "errors": len(errors),
            "warnings": len(diags) - len(errors),
            "diagnostics": [_diag_json(d) for d in diags],
        }) + "\n")
    else:
        for d in diags:
            out.write(f"{label}: {d}\n")
        out.write(
            f"{label}: {len(errors)} error(s), "
            f"{len(diags) - len(errors)} warning(s)\n"
        )
    return len(gating)


def _cmd_shapes(args):
    from paddle_tpu.analysis.shapes import infer_shapes

    feed_shapes = _parse_feed_shapes(args.feed_shape)
    failures = 0
    for label, program, _feed, _fetch in _iter_programs(args, [], []):
        rep = infer_shapes(program, feed_shapes=feed_shapes)
        failures += _report(
            label, "shapes", rep.diagnostics,
            extra={"unresolved_ops": sorted(rep.unresolved),
                   "amp_mode": rep.amp_mode},
            as_json=args.as_json,
            warnings_as_errors=args.warnings_as_errors,
        )
    return failures


def _make_mesh(args):
    shape, axes = _parse_mesh(args.mesh)
    from paddle_tpu.parallel.env import make_mesh

    return make_mesh(shape=shape, axis_names=axes)


def _sharding_report(args, program, feed_shapes):
    from paddle_tpu.analysis.sharding import analyze_sharding

    layout = None
    if args.spec_layout:
        from paddle_tpu.parallel.spec_layout import SpecLayout

        layout = SpecLayout()
    return analyze_sharding(
        program, _make_mesh(args), spec_layout=layout,
        feed_shapes=feed_shapes,
    )


def _cmd_sharding(args):
    from paddle_tpu.analysis.sharding import (
        weight_param_shapes,
        weight_sized_events,
    )
    from paddle_tpu.analysis.verify import Diagnostic

    feed_shapes = _parse_feed_shapes(args.feed_shape)
    failures = 0
    for label, program, _feed, _fetch in _iter_programs(args, [], []):
        rep = _sharding_report(args, program, feed_shapes)
        diags = list(rep.diagnostics)
        for e in weight_sized_events(rep, weight_param_shapes(program)):
            diags.append(Diagnostic(
                "error", "weight-sized-collective",
                f"predicted {e.kind} of FULL weight '{e.var}' "
                f"({list(e.shape)}, {e.bytes} bytes): {e.cause} — shard "
                f"this parameter (spec_layout registry or an override) "
                f"or every step pays a weight-sized gather",
                op_type=e.op_type, op_index=e.op_index, var=e.var,
            ))
        failures += _report(
            label, "sharding", diags,
            extra={"max_bytes": rep.max_bytes(),
                   "total_bytes": rep.total_bytes(),
                   "by_kind": rep.by_kind(),
                   "events": [e.to_json() for e in rep.events[:64]]},
            as_json=args.as_json,
            warnings_as_errors=args.warnings_as_errors,
        )
    return failures


def _cmd_collectives(args):
    from paddle_tpu.analysis.sharding import collective_budget_diagnostics

    feed_shapes = _parse_feed_shapes(args.feed_shape)
    budget = args.budget_kb * 1024
    failures = 0
    for label, program, _feed, _fetch in _iter_programs(args, [], []):
        rep = _sharding_report(args, program, feed_shapes)
        diags = list(rep.diagnostics)
        diags += collective_budget_diagnostics(rep, budget)
        failures += _report(
            label, "collectives", diags,
            extra={"budget_bytes": budget, "max_bytes": rep.max_bytes(),
                   "by_kind": rep.by_kind(),
                   "events": [e.to_json() for e in rep.events[:64]]},
            as_json=args.as_json,
            warnings_as_errors=args.warnings_as_errors,
        )
    return failures


def _parse_axis_tags(entries):
    """['dcn=dcn', 'data=ici'] -> {'dcn': 'dcn', 'data': 'ici'}."""
    out = {}
    for e in entries or []:
        ax, _, tag = e.partition("=")
        if not ax or tag not in ("ici", "dcn"):
            _usage_error(f"bad --tag '{e}': want AXIS=ici|dcn")
        out[ax] = tag
    return out


def _cmd_cost(args):
    from paddle_tpu.analysis.cost import (
        MACHINES,
        analyze_cost,
        check_cost_budgets,
        hierarchical_collective_diagnostics,
    )

    if args.machine not in MACHINES:
        _usage_error(
            f"unknown --machine '{args.machine}'; have {sorted(MACHINES)}"
        )
    axis_tags = _parse_axis_tags(args.tag)
    feed_shapes = _parse_feed_shapes(args.feed_shape)
    mesh = _make_mesh(args) if args.mesh else None
    layout = None
    if getattr(args, "spec_layout", False):
        from paddle_tpu.parallel.spec_layout import SpecLayout

        layout = SpecLayout()
    batch_axes = tuple(a for a in (args.batch_spec or "").split(",") if a)
    failures = 0
    for label, program, feed, fetch in _iter_programs(args, [], []):
        input_specs = None
        if batch_axes:
            from jax.sharding import PartitionSpec as P

            input_specs = {n: P(batch_axes) for n in feed}
        rep = analyze_cost(
            program, machine=args.machine, mesh=mesh,
            axis_tags=axis_tags or None, spec_layout=layout,
            input_specs=input_specs,
            feed_shapes=feed_shapes, fetch_names=fetch,
        )
        diags = list(rep.diagnostics)
        diags += hierarchical_collective_diagnostics(rep)
        diags += check_cost_budgets(
            rep, step_ms=args.budget_step_ms,
            collective_kb=args.budget_collective_kb, min_mfu=args.min_mfu,
        )
        j = rep.to_json(ops_limit=16)
        # show the 1F1B headroom next to each committed GPipe bubble —
        # the number the pipeline runtime must beat (what-if only; the
        # committed entry stays the program's own schedule). m > s has no
        # contention-free interleaved window, so no what-if there.
        from paddle_tpu.parallel.pipeline_runtime.schedule import (
            predicted_bubble,
        )

        pipeline = []
        for ent in j["pipeline"]:
            ent = dict(ent)
            s, m = ent["stages"], ent["num_microbatches"]
            ent["bubble_1f1b_whatif"] = (
                round(predicted_bubble("1f1b", s, m, 2), 6)
                if s > 1 and m <= s else None
            )
            pipeline.append(ent)
        j["pipeline"] = pipeline
        failures += _report(
            label, "cost", diags,
            extra={"machine": args.machine,
                   "step_seconds": j["step_seconds"],
                   "mfu": j["mfu"],
                   "total_flops": j["total_flops"],
                   "total_hbm_bytes": j["total_hbm_bytes"],
                   "bound_counts": j["bound_counts"],
                   "per_axis": j["per_axis"],
                   "unknown_ops": j["unknown_ops"],
                   "pipeline": j["pipeline"],
                   "events": j["collectives"]},
            as_json=args.as_json,
            warnings_as_errors=args.warnings_as_errors,
        )
    return failures


def _static_donation_plan(program, feed_names, fetch_names):
    """plan_step's donation classification without a scope: persistable
    vars written by live ops and not fetched are donated, the rest of the
    persistable reads are read-only."""
    block = program.global_block()
    from paddle_tpu.analysis.usedef import UseDefMap

    usedef = UseDefMap(block)
    read, written = set(), set()
    for op in block.ops:
        read |= usedef.reads_of(op)
        written |= usedef.writes_of(op)

    def persistable(n):
        v = block._find_var_recursive(n)
        return v is not None and v.persistable

    fetches = set(fetch_names)
    donated = sorted(n for n in written
                     if persistable(n) and n not in fetches)
    readonly = sorted(n for n in read
                      if persistable(n) and n not in set(donated))
    return donated, readonly


def _cmd_memory(args):
    from paddle_tpu.analysis.memory import (
        check_donation_safety,
        estimate_peak_hbm,
    )

    feed_shapes = _parse_feed_shapes(args.feed_shape)
    failures = 0
    for label, program, feed, fetch in _iter_programs(args, [], []):
        donated, readonly = _static_donation_plan(program, feed, fetch)
        diags = check_donation_safety(program, donated, readonly, fetch)
        donate = not args.no_donate
        rep = estimate_peak_hbm(
            program, feed_shapes=feed_shapes, fetch_names=fetch,
            donate=donate,
        )
        diags = diags + rep.diagnostics
        failures += _report(
            label, "memory", diags,
            extra={"peak": rep.to_json(), "donated": len(donated)},
            as_json=args.as_json,
            warnings_as_errors=args.warnings_as_errors,
        )
    return failures


def _build_example(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        f"lint_example_{name}", os.path.join(REPO, "examples", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    built = mod.build_programs()
    main, startup, feed_names = built[0], built[1], built[2]
    fetch_names = [f if isinstance(f, str) else f.name for f in built[3]]
    return main, startup, feed_names, fetch_names


def _cmd_smoke(args):
    """Fast-tier CI gate: every examples/ program is clean under shapes +
    sharding (8-way dp mesh) + donation safety."""
    import builtins

    as_json = bool(getattr(args, "as_json", False))
    findings = []

    def print(*a, **kw):  # noqa: A001 - JSON mode keeps stdout machine-only
        msg = " ".join(str(x) for x in a)
        if msg.startswith("SMOKE FAIL"):
            findings.append(msg)
        kw.setdefault("file", sys.stderr if as_json else sys.stdout)
        builtins.print(*a, **kw)

    from paddle_tpu.analysis.memory import check_donation_safety
    from paddle_tpu.analysis.shapes import infer_shapes
    from paddle_tpu.analysis.sharding import analyze_sharding
    from paddle_tpu.parallel.env import make_mesh
    from paddle_tpu.passes import (
        apply_deferred_sharded_embedding_rewrite,
        apply_deferred_sparse_rewrite,
    )

    failures = 0
    mesh = make_mesh(shape=(8,), axis_names=("data",))
    for name in EXAMPLES:
        main, startup, feed_names, fetch_names = _build_example(name)
        apply_deferred_sparse_rewrite(main)
        apply_deferred_sharded_embedding_rewrite(main)
        before = failures
        for tag, program in ((f"{name}:main", main),
                             (f"{name}:startup", startup)):
            rep = infer_shapes(program)
            errs = rep.errors()
            if errs:
                failures += 1
                print(f"SMOKE FAIL {tag}: shape errors: "
                      f"{[str(d)[:120] for d in errs[:3]]}")
        srep = analyze_sharding(main, mesh)
        # weight-sized linting needs a tensor-sharded placement, which no
        # example uses — that class is held by tests/test_hlo.py (registry
        # and megatron-control arms, static against live). What IS checkable on
        # this pure-dp mesh is the grad-sync law: events only for
        # trainable parameters, never optimizer slots/scheduler counters
        # (a phantom event here inflates every downstream byte budget)
        trainable = {p.name for p in main.all_parameters()}
        phantom = sorted({e.var for e in srep.events
                          if e.cause == "grad-sync"} - trainable)
        if phantom:
            failures += 1
            print(f"SMOKE FAIL {name}: grad-sync predicted for "
                  f"non-parameter state: {phantom[:3]}")
        donated, readonly = _static_donation_plan(
            main, feed_names, fetch_names
        )
        ddiags = check_donation_safety(main, donated, readonly,
                                       fetch_names)
        if ddiags:
            failures += 1
            print(f"SMOKE FAIL {name}: donation safety: "
                  f"{[d.code for d in ddiags[:3]]}")
        if failures == before:
            print(f"smoke: {name} clean "
                  f"(donated={len(donated)}, events={len(srep.events)})")

    if not failures:
        print("smoke: all examples clean")
    if as_json:
        builtins.print(json.dumps({
            "program": "smoke", "pass": not failures,
            "examples": list(EXAMPLES), "failures": findings,
        }))
    return failures


# ---------------------------------------------------------------------------
# CLI plumbing
# ---------------------------------------------------------------------------


def _add_common(ap, with_mesh=False, mesh_required=True):
    ap.add_argument("programs", nargs="*", help="serialized program files")
    ap.add_argument("--builtin", action="append", default=[],
                    choices=BUILTINS,
                    help="lint a freshly-built known model program")
    ap.add_argument("--feed-shape", action="append", default=[],
                    metavar="NAME=D0,D1",
                    help="bind a feed's symbolic dims (repeatable)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="one JSON report line per program")
    ap.add_argument("--warnings-as-errors", action="store_true")
    if with_mesh:
        ap.add_argument("--mesh", required=mesh_required,
                        default=None, metavar="SHAPE:AXES",
                        help="virtual mesh, e.g. 2x4:data,model"
                        + ("" if mesh_required
                           else " (omit for single-device)"))
        ap.add_argument("--spec-layout", action="store_true",
                        help="place parameters through the canonical "
                        "SpecLayout registry (parallel/spec_layout.py)")


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] in ("-h", "--help"):
        # top-level help must describe the SUBCOMMAND surface, not fall
        # through to the legacy verify parser (which knows nothing of the
        # other passes — the help/usage drift fixed in round 16)
        print(__doc__)
        return EXIT_CLEAN
    sub = argv[0] if argv and argv[0] in SUBCOMMANDS else None
    if sub in ("sharding", "collectives", "cost"):
        n = _mesh_arg_devices(argv)
        if n:
            _ensure_virtual_devices(n)
    if sub == "smoke":
        _ensure_virtual_devices(8)

    try:
        if sub is None:
            return _legacy_main(argv)
        body = argv[1:]
        if sub == "verify":
            return _legacy_main(body)
        ap = argparse.ArgumentParser(
            prog=f"lint_program.py {sub}",
            description=f"static '{sub}' lint over serialized programs",
        )
        if sub == "smoke":
            ap.add_argument("--json", action="store_true", dest="as_json",
                            help="one JSON summary line on stdout "
                            "(progress goes to stderr)")
            return (EXIT_FINDINGS if _cmd_smoke(ap.parse_args(body))
                    else EXIT_CLEAN)
        _add_common(ap, with_mesh=sub in ("sharding", "collectives", "cost"),
                    mesh_required=sub != "cost")
        if sub == "collectives":
            ap.add_argument("--budget-kb", type=int, required=True,
                            help="per-collective byte budget in KB")
        if sub == "memory":
            ap.add_argument("--no-donate", action="store_true",
                            help="estimate without buffer donation")
        if sub == "cost":
            ap.add_argument("--machine", default="tpu-v4-8",
                            metavar="NAME",
                            help="machine model (analysis/cost.py "
                            "MACHINES); unknown names exit 2")
            ap.add_argument("--tag", action="append", default=[],
                            metavar="AXIS=ici|dcn",
                            help="tag a mesh axis's link tier "
                            "(repeatable); a 'dcn' tag arms the "
                            "hierarchical-allreduce linter")
            ap.add_argument("--budget-step-ms", type=float, default=0.0,
                            help="fail if predicted step time exceeds "
                            "this many ms (0 disables)")
            ap.add_argument("--budget-collective-kb", type=int, default=0,
                            help="fail if any mesh axis carries more "
                            "on-wire KB per step (0 disables)")
            ap.add_argument("--min-mfu", type=float, default=0.0,
                            help="fail if predicted MFU is below this "
                            "floor (0 disables)")
            ap.add_argument("--batch-spec", default="",
                            metavar="AXIS[,AXIS]",
                            help="shard every feed's batch dim over "
                            "these mesh axes (naive dp over dcn,ici — "
                            "the layout the hierarchical linter flags)")
        args = ap.parse_args(body)
        if not args.programs and not args.builtin:
            ap.error("nothing to lint: pass program files and/or --builtin")
        body_fn = {
            "shapes": _cmd_shapes,
            "sharding": _cmd_sharding,
            "collectives": _cmd_collectives,
            "memory": _cmd_memory,
            "cost": _cmd_cost,
        }[sub]
        return EXIT_FINDINGS if body_fn(args) else EXIT_CLEAN
    except SystemExit:
        raise
    except Exception:
        import traceback

        traceback.print_exc()
        return EXIT_INTERNAL


def _legacy_main(argv):
    ap = argparse.ArgumentParser(
        description="Lint serialized programs with the IR verifier"
    )
    ap.add_argument("programs", nargs="*", help="serialized program files")
    ap.add_argument("--builtin", action="append", default=[],
                    choices=BUILTINS,
                    help="lint a freshly-built known model program")
    ap.add_argument("--feed", default="",
                    help="comma-separated feed names (files without "
                    "embedded feed names)")
    ap.add_argument("--fetch", default="",
                    help="comma-separated fetch names")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="one JSON report line per program")
    ap.add_argument("--warnings-as-errors", action="store_true")
    args = ap.parse_args(argv)
    if not args.programs and not args.builtin:
        ap.error("nothing to lint: pass program files and/or --builtin")

    feed = [n for n in args.feed.split(",") if n]
    fetch = [n for n in args.fetch.split(",") if n]

    failures = 0
    for path in args.programs:
        program, ffeed, ffetch = _load_program(path)
        failures += lint(
            program, ffeed or feed, ffetch or fetch, os.path.basename(path),
            as_json=args.as_json, warnings_as_errors=args.warnings_as_errors,
        )
    for name in args.builtin:
        program, bfeed, bfetch = _build_builtin(name)
        failures += lint(
            program, bfeed, bfetch, f"builtin:{name}",
            as_json=args.as_json, warnings_as_errors=args.warnings_as_errors,
        )
    return EXIT_FINDINGS if failures else EXIT_CLEAN


if __name__ == "__main__":
    sys.exit(main())
