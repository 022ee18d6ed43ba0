#!/usr/bin/env python
"""COST_EVIDENCE_r16 generator: static roofline predictions vs XLA.

Round 16's claim is that step time, MFU, and collective cost are
*pre-compile* quantities: analysis/cost.py walks the op plan — no XLA in
the loop — and assigns every op FLOPs, HBM bytes, and wire bytes, folded
through a mesh-aware machine model. This tool makes that falsifiable the
r09 way. For each evidence arm it records

  static:  the analyzer's prediction — total FLOPs, predicted step
           seconds, MFU, roofline bound-class counts, per-axis
           collective budget, op coverage (unknown_ops MUST be empty)
  live:    the same program actually lowered and compiled, with
           ``jax.jit(...).lower().compile().cost_analysis()`` FLOPs
           (per-device partitioned numbers on the mesh arm)
  match:   the static/XLA FLOP ratio against a committed per-arm
           tolerance

plus two static-only control arms: ``dcn_linter_control`` (a mesh with a
declared 'dcn' axis where the hierarchical-collective linter MUST fire)
and ``pipeline_bubble`` (a pipeline_stack program whose GPipe bubble
fraction is predicted). tests/test_cost_analysis.py::
test_cost_evidence_r16_committed re-derives the static half
byte-for-byte and ``--smoke`` does the same in tier-1, so the committed
numbers cannot drift silently.

Usage: python tools/cost_report.py [--out COST_EVIDENCE_r16.json]
       python tools/cost_report.py --smoke   # static half vs committed
       (full run ~2 min on the CPU rig; --smoke is seconds)
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

MACHINE = "tpu-v4-8"
EXAMPLE_BATCH = 16
BERT_GEOMETRY = {"batch": 8, "seq_len": 24, "max_pred": 20}
# committed static/XLA FLOP-ratio bounds (symmetric max/min ratio).
# Single-device arms calibrate ~1.02-1.09 (the slack is XLA folding
# transcendental-heavy ops); the SPMD arm ~1.35 (GSPMD rewrites pad the
# per-device graph with halo/select flops the static model ignores).
TOLERANCES = {"fit_a_line": 1.25, "recognize_digits": 1.25,
              "tp_bert": 2.0}
EVIDENCE = "COST_EVIDENCE_r16.json"


def _load_example(name):
    """examples/<name>.py train program with deferred rewrites applied —
    identical to the static_report.py loader."""
    import importlib.util

    from paddle_tpu.passes import (
        apply_deferred_sharded_embedding_rewrite,
        apply_deferred_sparse_rewrite,
    )

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        f"cr_example_{name}", os.path.join(repo, "examples", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    main, startup, feed_names, fetches = mod.build_programs()[:4]
    apply_deferred_sparse_rewrite(main)
    apply_deferred_sharded_embedding_rewrite(main)
    fetch_names = [f if isinstance(f, str) else f.name for f in fetches]
    return main, startup, list(feed_names), fetch_names


def _synthetic_feed(main, feed_names, batch):
    """name -> ndarray with the symbolic batch dim bound, dtypes from the
    feed vars (int feeds get zeros — always a valid class/token id)."""
    import numpy as np

    rng = np.random.RandomState(0)
    block = main.global_block()
    feed = {}
    for fname in feed_names:
        v = block._find_var_recursive(fname)
        shape = tuple(batch if d is None or d < 0 else int(d)
                      for d in v.shape)
        dt = str(getattr(v, "dtype", "float32") or "float32")
        if "int" in dt:
            feed[fname] = np.zeros(shape, dtype=dt)
        else:
            feed[fname] = rng.uniform(0.0, 1.0, shape).astype(dt)
    return feed


def _static_summary(rep):
    return {
        "machine": rep.cost_model.machine.name,
        "ops": len(rep.ops),
        "unknown_ops": sorted(rep.unknown_ops),
        "total_flops": rep.total_flops,
        "total_transcendentals": rep.total_transcendentals,
        "total_hbm_bytes": rep.total_hbm_bytes,
        "step_seconds": round(rep.step_seconds, 12),
        "mfu": round(rep.mfu, 6),
        "bound_counts": rep.bound_counts(),
        "collective_seconds": round(rep.collective_seconds, 12),
        "per_axis": rep.per_axis(),
    }


def _bert_arm_inputs():
    import numpy as np

    from paddle_tpu.models import bert

    cfg = bert.BertConfig.tiny()
    cfg.hidden_dropout_prob = 0.0
    cfg.attention_probs_dropout_prob = 0.0
    main, startup, feeds, fetches = bert.build_bert_pretrain(
        cfg, seq_len=BERT_GEOMETRY["seq_len"], lr=1e-3,
        max_predictions_per_seq=BERT_GEOMETRY["max_pred"],
    )
    data = bert.synthetic_batch(
        np.random.RandomState(0), BERT_GEOMETRY["batch"],
        BERT_GEOMETRY["seq_len"], cfg,
        max_predictions_per_seq=BERT_GEOMETRY["max_pred"],
    )
    return main, startup, data, fetches


def static_sections():
    """arm -> static prediction (the half --smoke and the evidence test
    recompute byte-for-byte; NO lowering happens here)."""
    from paddle_tpu.analysis.cost import (
        analyze_cost,
        hierarchical_collective_diagnostics,
        pipeline_bubble_report,
    )
    from paddle_tpu.parallel.env import make_mesh
    from paddle_tpu.parallel.spec_layout import SpecLayout

    out = {}

    for name in ("fit_a_line", "recognize_digits"):
        main, _startup, feed_names, fetch_names = _load_example(name)
        feed = _synthetic_feed(main, feed_names, EXAMPLE_BATCH)
        rep = analyze_cost(
            main, machine=MACHINE,
            feed_shapes={k: v.shape for k, v in feed.items()},
            fetch_names=fetch_names,
        )
        out[name] = _static_summary(rep)

    main, _startup, data, fetches = _bert_arm_inputs()
    mesh = make_mesh((2, 4), ("data", "model"))
    rep = analyze_cost(
        main, machine=MACHINE, mesh=mesh, spec_layout=SpecLayout(),
        feed_shapes={k: v.shape for k, v in data.items()},
        fetch_names=[fetches[0].name],
    )
    sec = _static_summary(rep)
    sec["mesh"] = {"shape": [2, 4], "axes": ["data", "model"]}
    out["tp_bert"] = sec

    # positive control: a 'dcn'-tagged outer data axis with the batch
    # split over (dcn, data) — every grad-sync all-reduce then spans DCN
    # at full payload and the hierarchical linter MUST fire.
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.models import mnist

    cmain, _cstartup, cfeeds, cfetches = mnist.build_mnist_train()
    cfeed_names = [f if isinstance(f, str) else f.name for f in cfeeds]
    cfetch_names = [f if isinstance(f, str) else f.name for f in cfetches]
    cmesh = make_mesh((2, 4), ("dcn", "data"))
    cfeed = _synthetic_feed(cmain, cfeed_names, EXAMPLE_BATCH)
    crep = analyze_cost(
        cmain, machine=MACHINE, mesh=cmesh,
        axis_tags={"dcn": "dcn", "data": "ici"},
        input_specs={n: P(("dcn", "data")) for n in cfeed_names},
        feed_shapes={k: v.shape for k, v in cfeed.items()},
        fetch_names=cfetch_names,
    )
    diags = hierarchical_collective_diagnostics(crep)
    out["dcn_linter_control"] = {
        "mesh": {"shape": [2, 4], "axes": ["dcn", "data"]},
        "axis_tags": {"dcn": "dcn", "data": "ici"},
        "collectives": len(crep.collectives),
        "dcn_all_reduces": sum(
            1 for c in crep.collectives if c["kind"] == "all-reduce"
            and "dcn" in c["tags"]),
        "linter_fired": len(diags),
        "codes": sorted({d.code for d in diags}),
        "flagged_vars": sorted(d.var for d in diags),
        "dcn_bytes_saved": sum(
            int(c["bytes"] * (1 - 1.0 / 4)) for c in crep.collectives
            if c["kind"] == "all-reduce" and "dcn" in c["tags"]),
    }

    # bubble arm: ONE pipeline_stack op, 4 layers as 4 stages over 4
    # microbatches -> GPipe bubble (s-1)/(m+s-1) = 3/7.
    from paddle_tpu.models import gpt_ir

    gcfg = gpt_ir.GPTIRConfig()
    gmain, _gs, _gf, gloss, _stack = gpt_ir.build_gpt_ir(
        gcfg, seq_len=16, num_microbatches=4)
    gshapes = {"tokens": (8, 16), "labels": (8, 16)}
    grep = analyze_cost(
        gmain, machine=MACHINE, feed_shapes=gshapes,
        fetch_names=[gloss.name], num_stages=4,
    )
    bub = pipeline_bubble_report(gmain, feed_shapes=gshapes, num_stages=4)
    out["pipeline_bubble"] = {
        "unknown_ops": sorted(grep.unknown_ops),
        "total_flops": grep.total_flops,
        "pipeline": bub,
    }
    return out


def live_sections():
    """arm -> XLA ground truth: lower + compile each runnable arm and
    read cost_analysis() FLOPs (per-device on the mesh arm)."""
    import paddle_tpu as fluid
    from paddle_tpu.parallel.env import make_mesh
    from paddle_tpu.parallel.spec_layout import SpecLayout
    from paddle_tpu.utils import hlo

    def _xla_flops(lowered):
        ca = lowered.compile().cost_analysis()
        return int(ca.get("flops", 0)), int(ca.get("transcendentals", 0))

    out = {}
    for name in ("fit_a_line", "recognize_digits"):
        main, startup, feed_names, fetch_names = _load_example(name)
        feed = _synthetic_feed(main, feed_names, EXAMPLE_BATCH)
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(scope):
            exe.run(startup)
            lowered = hlo.lower_program_step(
                main, feed, fetch_names, scope=scope)
        flops, trans = _xla_flops(lowered)
        out[name] = {"xla_flops": flops, "xla_transcendentals": trans}

    main, startup, data, fetches = _bert_arm_inputs()
    mesh = make_mesh((2, 4), ("data", "model"))
    prog = fluid.CompiledProgram(main).with_parallel(
        mesh=mesh, loss_name=fetches[0].name, spec_layout=SpecLayout())
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        lowered, _ = hlo.lower_parallel_step(
            exe, prog, data, [fetches[0]], scope)
    flops, trans = _xla_flops(lowered)
    out["tp_bert"] = {"xla_flops": flops, "xla_transcendentals": trans,
                      "note": "per-device partitioned flops (SPMD)"}
    return out


def match_sections(static, live):
    out = {}
    for tag, tol in TOLERANCES.items():
        pred = static[tag]["total_flops"]
        got = live[tag]["xla_flops"]
        ratio = max(pred, got) / max(min(pred, got), 1)
        out[tag] = {
            "static_flops": pred,
            "xla_flops": got,
            "flops_ratio": round(ratio, 4),
            "tolerance": tol,
            "verdict": "pass" if ratio <= tol else "fail",
        }
    return out


def build_report(with_live=True):
    static = static_sections()
    report = {
        "machine": MACHINE,
        "example_batch": EXAMPLE_BATCH,
        "bert_geometry": BERT_GEOMETRY,
        "tolerances": TOLERANCES,
        "arms": {tag: {"static": sec} for tag, sec in static.items()},
    }
    if with_live:
        live = live_sections()
        match = match_sections(static, live)
        for tag in live:
            report["arms"][tag]["live"] = live[tag]
            report["arms"][tag]["match"] = match[tag]
    return report


def smoke():
    """Recompute the static half and compare byte-for-byte against the
    committed evidence; verify control invariants. Exit 1 on drift."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, EVIDENCE)
    with open(path) as f:
        committed = json.load(f)
    fresh = static_sections()
    failures = []
    for tag, sec in fresh.items():
        old = committed["arms"].get(tag, {}).get("static")
        if json.dumps(old, sort_keys=True) != json.dumps(
                sec, sort_keys=True):
            failures.append(f"static drift on arm '{tag}'")
    if not committed["arms"]["dcn_linter_control"]["static"][
            "linter_fired"]:
        failures.append("dcn linter control did not fire")
    for tag, m in ((t, committed["arms"][t].get("match"))
                   for t in TOLERANCES):
        if not m or m["verdict"] != "pass":
            failures.append(f"match verdict not 'pass' on arm '{tag}'")
    bub = committed["arms"]["pipeline_bubble"]["static"]["pipeline"]
    if not bub or not bub[0]["bubble_fraction"] > 0:
        failures.append("no positive pipeline bubble prediction")
    for msg in failures:
        print("FAIL:", msg)
    if not failures:
        print(f"smoke OK: {len(fresh)} arms, static half matches "
              f"{EVIDENCE}")
    return 1 if failures else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="also write the JSON report to this path")
    ap.add_argument("--static-only", action="store_true",
                    help="skip the XLA compile half (seconds)")
    ap.add_argument("--smoke", action="store_true",
                    help="recompute the static half and diff it against "
                    "the committed evidence file; exit 1 on drift")
    args = ap.parse_args()
    if args.smoke:
        sys.exit(smoke())
    report = build_report(with_live=not args.static_only)
    text = json.dumps(report, indent=1)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
