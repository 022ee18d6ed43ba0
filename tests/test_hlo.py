"""HLO assertion suite — chip-independent performance evidence.

Compiles the REAL model train steps and asserts structural properties of the
emitted computation, so perf regressions fail tests even without TPU
hardware (the reference's analog is op_tester.cc micro-bench evidence,
reference: paddle/fluid/operators/benchmark/op_tester.cc:1):

  * flash path: no O(S^2) buffer anywhere in the step — forward AND backward
    (the generic-vjp grad op must differentiate the Pallas lowering; a
    regression to the unfused reference path re-materializes [B,H,S,S])
  * AMP: every MXU dot takes bf16 operands (f32 accumulation allowed);
    the MLM head never materializes an [*, S, V] logits tensor
  * ResNet-50 under AMP: every convolution runs on bf16
  * dp mesh: gradient all-reduces present, no all-to-all
  * tp mesh: no collective moves a full weight matrix (collectives ride on
    activations)
  * transpose budget on the optimized step (layout-pessimization canary)

Dtype/shape checks read StableHLO (what the framework emitted); collective
checks read optimized HLO (post-GSPMD). See paddle_tpu/utils/hlo.py.
"""

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu.utils import hlo

S = 512  # long enough that S x S is unambiguous against model dims
VOCAB = 30522
P_PRED = 77
HIDDEN = 768   # bert_train_step_text's defaults
BATCH = 4


def _lower_bert(flash):
    # shared with tools/hlo_report.py so the committed evidence is
    # generated by the exact computation these gates assert on
    return hlo.bert_train_step_text(
        flash, seq_len=S, vocab=VOCAB, max_pred=P_PRED
    )


@pytest.fixture(scope="module")
def bert_flash_stablehlo():
    return _lower_bert(flash=True)


def test_flash_train_step_no_s2_buffers(bert_flash_stablehlo):
    """The whole train step — fwd, bwd, optimizer — must never materialize
    an [S, S]-shaped tensor when flash attention is on. Catches both an
    unfused forward AND a grad op differentiating the unfused path."""
    tensors = hlo.stablehlo_tensors(bert_flash_stablehlo)
    s2 = hlo.tensors_with_trailing(tensors, (S, S))
    assert not s2, f"S^2 buffers on the flash path: {set(s2)}"


def test_unfused_path_detector_fires():
    """Positive control: the unfused path DOES materialize [B,H,S,S] — if
    this stops firing, the S^2 assertions above prove nothing."""
    txt = _lower_bert(flash=False)
    tensors = hlo.stablehlo_tensors(txt)
    s2 = hlo.tensors_with_trailing(tensors, (S, S))
    assert s2, "detector lost the unfused S^2 buffers"


def test_masked_head_no_s_by_vocab(bert_flash_stablehlo):
    """The MLM head must project only gathered masked positions: a tensor
    carrying both S and VOCAB dims means the full [*, S, V] logits came
    back (4 GB at bench shapes, PROFILE.md item 1)."""
    tensors = hlo.stablehlo_tensors(bert_flash_stablehlo)
    sxv = hlo.tensors_containing_dims(tensors, (S, VOCAB))
    assert not sxv, f"[S, V]-sized tensors present: {set(sxv)}"


def test_masked_gather_moves_rows(bert_flash_stablehlo):
    """The masked-position gather and its gradient move ROWS of H: no
    gather / scatter of the step fetches (adds) one element per index on a
    tensor that carries the hidden size, and nothing sorts B*P*H element
    indices. On a v5e the element form was 88.6 ms of BERT-base's 404 ms
    step (PERF.md, PR 26)."""
    found = hlo.element_granular_movers(
        bert_flash_stablehlo, hidden=HIDDEN,
        sort_elements=BATCH * P_PRED * HIDDEN,
    )
    assert not found, f"element-at-a-time movers over H: {found}"


@pytest.mark.parametrize("mode, views", [("interpret", False),
                                         ("off", True)])
def test_paged_decode_step_never_writes_the_gathered_arena(mode, views):
    """The decode step lowered with the paged kernel selected holds no
    value of ``[S * L, H]`` or ``[S, L, H]``: a slot's live blocks are
    read in place, block by block. The composite ("off": the positive
    control, so the detector is known to fire) gathers both views in
    every layer. On a v5e those gathers were ~100 of a 104 ms step
    (PERF.md, PR 29)."""
    from paddle_tpu import kernels
    from paddle_tpu.serving.decode import build_decoder_model

    S, L, H = 4, 96, 24
    # 30 blocks of 8 rows: the arena itself is [240, H], so [S * L, H]
    # can only be a gathered view
    m = build_decoder_model(vocab_size=40, hidden=H, num_layers=2, slots=S,
                            max_len=L, block_size=8, num_blocks=30,
                            name=f"hlo_{mode}", version="1")
    feed = {n: np.zeros(shape, dtype)
            for n, shape, dtype in m.decode_feed_sig()}
    scope = fluid.Scope()
    with fluid.scope_guard(scope), kernels.scoped_mode(mode):
        fluid.Executor(fluid.CPUPlace()).run(m.startup_program)
        txt = hlo.lower_program_step(
            m.decode_program, feed, [m.logits_fetch], scope=scope).as_text()
    tensors = hlo.stablehlo_tensors(txt)
    dense = (hlo.tensors_with_trailing(tensors, (S * L, H))
             + hlo.tensors_with_trailing(tensors, (S, L, H)))
    assert bool(dense) == views, (mode, set(dense))


def test_element_mover_detector_fires():
    """Positive control: an index broadcast to the output's shape DOES
    lower to the element gather and the element scatter-add (and a sort
    that long is seen) — if this stops firing, the gate above proves
    nothing."""
    import jax.numpy as jnp

    def broadcast_index_form(x, idx):
        full = jnp.broadcast_to(idx[:, :, None], idx.shape + x.shape[2:])
        picked = jnp.take_along_axis(x, full, axis=1)
        return jnp.sum(jnp.sort(picked.reshape(-1)))

    txt = jax.jit(jax.grad(broadcast_index_form)).lower(
        jnp.zeros((BATCH, S, HIDDEN)), jnp.zeros((BATCH, P_PRED), "int32")
    ).as_text()
    kinds = {k for k, _ in hlo.element_granular_movers(
        txt, hidden=HIDDEN, sort_elements=BATCH * P_PRED * HIDDEN)}
    assert kinds == {"gather", "scatter", "sort"}, kinds


def test_amp_all_dots_bf16(bert_flash_stablehlo):
    """Under bf16 AMP every dot_general — encoder matmuls, the flash kernel
    blocks, the vocab projection — must take bf16 operands. f32 OUTPUT is
    fine (accumulation); f32 INPUT means a matmul fell off the MXU fast
    path (e.g. an op missing from the AMP white list)."""
    dots = hlo.stablehlo_dots(bert_flash_stablehlo)
    assert len(dots) > 30, f"dot extraction broke (found {len(dots)})"
    f32_in = [d for d in dots if not (
        d[0].endswith("bf16") and d[1].endswith("bf16")
    )]
    assert not f32_in, f"dots with non-bf16 operands: {f32_in[:5]}"


def test_resnet50_amp_convs_bf16():
    """Every convolution in the ResNet-50 train step must run on bf16 under
    AMP — one f32 conv is an MXU-rate regression."""
    txt = hlo.resnet_train_step_text(depth=50, use_amp=True)
    census = hlo.conv_dtype_census(txt)
    total = sum(census.values())
    assert total > 100, f"conv extraction broke (census {census})"
    assert set(census) == {"bf16"}, f"non-bf16 convolutions: {census}"


def test_transpose_budget(bert_flash_stablehlo):
    """Layout canary: transposes in the emitted step. The attention
    head-split/merge costs 8 per layer fwd (+bwd mirrors); a jump past the
    budget means a new layout pessimization crept into a lowering."""
    n = bert_flash_stablehlo.count("stablehlo.transpose")
    assert n <= TRANSPOSE_BUDGET, (
        f"{n} transposes > budget {TRANSPOSE_BUDGET} — a lowering started "
        "moving data it didn't before"
    )


# calibrated on the current step (see test output on change): 2-layer flash
# BERT emits well under this; the budget allows headroom for benign drift
# while catching systematic per-layer regressions
TRANSPOSE_BUDGET = 80


# ---------------------------------------------------------------------------
# mesh collectives (8-virtual-device CPU mesh, post-GSPMD optimized HLO)
# ---------------------------------------------------------------------------


def _tiny_bert_parallel(mesh_shape, axis_names, param_rules=None):
    return hlo.tiny_bert_parallel_text(mesh_shape, axis_names, param_rules)


def test_dp_mesh_collectives():
    """Pure DP: gradient all-reduces must appear; all-to-all means GSPMD
    chose a resharding the model never asked for."""
    assert jax.device_count() >= 8
    txt = _tiny_bert_parallel((8,), ("data",))
    c = hlo.count_collectives(txt)
    assert c["all-reduce"] >= 1, f"no gradient all-reduce in DP step: {c}"
    assert c["all-to-all"] == 0, f"unexpected all-to-all in DP step: {c}"


def test_dp_mesh_masked_gather_stays_local():
    """The masked-position gather has its batching dimension on the
    sharded axis, so GSPMD partitions it and its scatter-add gradient with
    no collective: nothing of [B, S, H] or [B, P, H] size (per shard or
    global, flattened or not) rides a collective of the dp step."""
    assert jax.device_count() >= 8
    b, s, p, h = 16, 24, 5, 64          # tiny BERT: hidden 64
    txt = hlo.tiny_bert_parallel_text(
        (8,), ("data",), seq_len=s, batch=b, max_pred=p)
    assert hlo.count_collectives(txt)["all-reduce"] >= 1
    activations = set()
    for rows in (b, b // 8):
        for n in (s, p):
            activations |= {(rows, n, h), (rows * n, h)}
    # the helper matches any given full shape on a collective's line
    moved = hlo.weight_shaped_collectives(txt, activations)
    assert not moved, f"activation-sized collectives in the DP step: {moved}"


@pytest.fixture(scope="module")
def tp_registry_lowering():
    """dp2 x tp4 tiny-BERT step with parameter placement from the
    canonical SpecLayout registry (parallel/spec_layout.py), plus the
    full rank>=2 parameter-shape set. seq_len=24 keeps activation shapes
    disjoint from every parameter shape (at 16, [B_local*S, H] == the
    qkv weight shape and the scan could not tell them apart)."""
    from paddle_tpu.parallel.spec_layout import SpecLayout

    return hlo.tiny_bert_parallel_text(
        (2, 4), ("data", "model"), spec_layout=SpecLayout(),
        seq_len=24, max_pred=20, with_param_shapes=True,
    )


def test_tp_mesh_no_weight_sized_collectives(tp_registry_lowering):
    """TP via the SpecLayout registry: collectives must move activations,
    not weights. A collective whose operand is a FULL parameter shape
    means GSPMD is gathering params — before the registry, every
    parameter the MEGATRON_RULES table left replicated (pos/type
    embeddings, pooler, the MLM head) paid a full weight-sized all-gather
    per step to reconcile its shard-computed update (the PR-4-era
    tolerated failure this test pinned)."""
    txt, param_shapes = tp_registry_lowering
    c = hlo.count_collectives(txt)
    assert jax.device_count() >= 8
    assert sum(c.values()) >= 1, f"no collectives in dp2xtp4 step: {c}"
    assert len(param_shapes) >= 5, "parameter-shape extraction broke"
    offenders = hlo.weight_shaped_collectives(txt, param_shapes)
    assert not offenders, (
        "weight-sized collective operands:\n" +
        "\n".join(f"  {k} {s}: {l}" for k, s, l in offenders[:8])
    )


def test_weight_shaped_detector_fires():
    """Positive control for the scan itself: a synthetic collective line
    carrying a full weight shape must be flagged (if this stops firing,
    the empty-offender asserts above prove nothing)."""
    fake = (
        "  %all-gather.9 = f32[128,64]{1,0} all-gather(f32[32,64]{1,0} "
        "%fusion.1), channel_id=7, dimensions={0}\n"
    )
    hits = hlo.weight_shaped_collectives(fake, {(128, 64)})
    assert len(hits) == 1 and hits[0][0] == "all-gather"
    # and a line that merely NAMES a collective as an operand is ignored
    ref = "  %tuple.1 = (f32[128,64]{1,0}) tuple(f32[128,64]{1,0} %all-gather.9)\n"
    assert hlo.weight_shaped_collectives(ref, {(128, 64)}) == []


def test_tp_registry_collective_bytes_activation_sized(
    tp_registry_lowering,
):
    """Byte accounting on the same step: the single largest value any
    collective materializes must be activation-class (within 2x of the
    flattened residual activation), nowhere near the largest full
    parameter — the quantitative form of the shape scan above, recorded
    in HLO_EVIDENCE_r07.json by tools/hlo_report.py."""
    txt, param_shapes = tp_registry_lowering
    report = hlo.collective_byte_report(txt)
    assert report["by_kind"], "no collectives parsed"
    # activation bound: the largest activation in the step — the full
    # masked-MLM logits [masked_local, V] (tiny model, fat vocab head:
    # bigger than the [B, S, H] residual) — with 2x headroom for fused
    # epilogues. Weight math staying shard-local is proven exactly by
    # the shape scan; this bounds the wire volume quantitatively.
    act_bytes = max(8 * 24 * 64, (20 * 8 // 2) * 1024) * 4
    assert report["max_bytes"] <= 2 * act_bytes, report


def test_dp_fsdp_tp_registry_no_weight_sized_collectives():
    """The full dp x fsdp x tp factorization (2x2x2) through the
    registry: parameters and optimizer state ZeRO-sliced over fsdp and
    tensor-sharded over tp, still zero full-weight collectives and
    byte-bounded wire traffic."""
    from paddle_tpu.parallel.spec_layout import SpecLayout

    assert jax.device_count() >= 8
    txt, param_shapes = hlo.tiny_bert_parallel_text(
        (2, 2, 2), ("data", "fsdp", "model"), spec_layout=SpecLayout(),
        seq_len=24, max_pred=20, with_param_shapes=True,
    )
    c = hlo.count_collectives(txt)
    assert sum(c.values()) >= 1, f"no collectives in dp2xfsdp2xtp2: {c}"
    offenders = hlo.weight_shaped_collectives(txt, param_shapes)
    assert not offenders, (
        "weight-sized collective operands:\n" +
        "\n".join(f"  {k} {s}: {l}" for k, s, l in offenders[:8])
    )
    report = hlo.collective_byte_report(txt)
    act_bytes = max(8 * 24 * 64, (20 * 8 // 2) * 1024) * 4
    assert report["max_bytes"] <= 2 * act_bytes, report


def test_hlo_evidence_r07_committed(tp_registry_lowering):
    """The committed HLO_EVIDENCE_r07.json (tools/hlo_report.py) must
    claim exactly what this suite proves live: zero weight-shaped
    collectives on the registry steps, a firing detector on the
    MEGATRON_RULES control, and byte accounting present — so the
    evidence cannot drift from the asserted computation."""
    import json
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "HLO_EVIDENCE_r07.json",
    )
    with open(path) as f:
        sec = json.load(f)["spec_layout_r07"]
    assert sec["weight_shaped_collectives_tp_registry"] == 0
    assert sec["weight_shaped_collectives_dp_fsdp_tp_registry"] == 0
    assert sec["weight_shaped_collectives_megatron_control"] > 0, (
        "the positive control stopped firing — the zero-claims above "
        "prove nothing"
    )
    for tag in ("tp_registry", "dp_fsdp_tp_registry"):
        rep = sec[f"collective_bytes_{tag}"]
        assert rep["by_kind"] and rep["max_bytes"] > 0
        assert rep["max_bytes"] < sec["param_full_bytes"]["largest"] * 2
    # the committed zero must match THIS process's lowering of the same
    # builder at the same geometry
    txt, shapes = tp_registry_lowering
    assert len(hlo.weight_shaped_collectives(txt, shapes)) == \
        sec["weight_shaped_collectives_tp_registry"]


def test_static_evidence_r09_committed(tp_registry_lowering):
    """STATIC_EVIDENCE_r09.json (tools/static_report.py) claims the static
    sharding analyzer predicts the live collective story: zero
    weight-sized collectives on the registry arms, every one of the
    control's live weight gathers predicted with bytes within 2x, and the
    budget linter separating the two. The live half is recomputed HERE
    (the static half is re-derived by tools/lint_program.py smoke), so
    neither side of the committed comparison can drift silently."""
    import json
    import os
    from collections import Counter

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "STATIC_EVIDENCE_r09.json",
    )
    with open(path) as f:
        ev = json.load(f)
    arms = ev["arms"]
    # committed static claims: registry clean + under budget, control
    # fires both detectors
    for tag in ("tp_registry", "dp_fsdp_tp_registry"):
        assert arms[tag]["static"]["weight_sized_count"] == 0
        assert arms[tag]["static"]["budget_verdict"] == "pass"
        assert arms[tag]["live"]["weight_shaped_count"] == 0
    control = arms["megatron_control"]
    assert control["static"]["weight_sized_count"] > 0
    assert control["static"]["budget_verdict"] == "fail"
    assert control["live"]["weight_shaped_count"] > 0, (
        "the positive control stopped firing — the zero-claims above "
        "prove nothing"
    )
    # every live weight-shaped collective matched a static prediction,
    # bytes within the 2x acceptance bound
    for tag, arm in arms.items():
        assert arm["match"]["live_collectives_unmatched"] == 0, arm["match"]
        assert arm["match"]["max_byte_ratio"] <= 2.0, arm["match"]

    # live recompute, registry arm: the fixture's lowering of the same
    # builder at the same geometry must still show ZERO weight-shaped
    # collectives (matches the committed live claim)
    txt, shapes = tp_registry_lowering
    assert len(hlo.weight_shaped_collectives(txt, shapes)) == \
        arms["tp_registry"]["live"]["weight_shaped_count"]

    # live recompute, control arm: offender shapes/counts must equal the
    # committed live section AND be covered by the committed static
    # predictions within 2x bytes
    from paddle_tpu.parallel.sharding import MEGATRON_RULES

    ctl_txt, ctl_shapes = hlo.tiny_bert_parallel_text(
        (2, 4), ("data", "model"), param_rules=MEGATRON_RULES,
        seq_len=24, max_pred=20, with_param_shapes=True,
    )
    offenders = hlo.weight_shaped_collectives(ctl_txt, ctl_shapes)
    assert len(offenders) == control["live"]["weight_shaped_count"]
    live_counts = Counter(
        (k, "x".join(map(str, s))) for k, s, _l in offenders
    )
    committed_counts = {
        (e["kind"], e["shape"]): e["count"]
        for e in control["live"]["weight_shaped"]
    }
    assert dict(live_counts) == committed_counts
    static_preds = control["static"]["weight_sized"]
    for (_kind, shape_s), _n in live_counts.items():
        shape = tuple(int(d) for d in shape_s.split("x"))
        nbytes = 4
        for d in shape:
            nbytes *= d
        preds = [e for e in static_preds
                 if tuple(e["shape"]) == shape and e["bytes"]]
        assert preds, f"live {shape} gather has no static prediction"
        ratio = max(preds[0]["bytes"], nbytes) / min(preds[0]["bytes"],
                                                    nbytes)
        assert ratio <= 2.0, (shape, preds[0]["bytes"], nbytes)


def test_flash_long_context_no_s2():
    """Long-context story: at S=2048 (16x the bench S) the flash train
    step still materializes nothing S^2-shaped — the memory property that
    makes long sequences fit at all."""
    S_long = 2048
    txt = hlo.bert_train_step_text(
        True, seq_len=S_long, layers=1, batch=2, vocab=1024,
        max_pred=64, hidden=256, heads=4,
    )
    tensors = hlo.stablehlo_tensors(txt)
    s2 = hlo.tensors_with_trailing(tensors, (S_long, S_long))
    assert not s2, f"S^2 buffers at S={S_long}: {set(s2)}"


@pytest.mark.slow
def test_full_bert_base_12_layer_properties():
    """The REAL flagship at full depth: 12-layer BERT-base lowers with
    every per-layer property intact (the default-suite 2-layer tests
    prove the per-layer math; this proves nothing depth-dependent breaks)."""
    txt = hlo.bert_train_step_text(
        True, seq_len=S, layers=12, vocab=VOCAB, max_pred=P_PRED
    )
    tensors = hlo.stablehlo_tensors(txt)
    assert not hlo.tensors_with_trailing(tensors, (S, S))
    assert not hlo.tensors_containing_dims(tensors, (S, VOCAB))
    dots = hlo.stablehlo_dots(txt)
    assert len(dots) > 100, f"dot extraction broke (found {len(dots)})"
    bad = [d for d in dots if not (
        d[0].endswith("bf16") and d[1].endswith("bf16")
    )]
    assert not bad, bad[:5]


def test_resnet_dp_mesh_collectives():
    """ResNet under a pure-DP mesh: gradient all-reduces present, no
    all-to-all — the conv-net analog of the BERT dp check."""
    from paddle_tpu.models import resnet
    from paddle_tpu.parallel.env import make_mesh

    assert jax.device_count() >= 8
    main, startup, feeds, fetches = resnet.build_resnet_train(
        depth=18, class_dim=10, image_shape=(3, 32, 32), lr=0.1
    )
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        mesh = make_mesh(shape=(8,), axis_names=("data",))
        prog = fluid.CompiledProgram(main).with_parallel(
            mesh=mesh, loss_name=fetches[0].name
        )
        feed = {
            "img": np.random.RandomState(0).randn(8, 3, 32, 32).astype(
                "float32"
            ),
            "label": np.zeros((8, 1), "int64"),
        }
        lowered, _ = hlo.lower_parallel_step(
            exe, prog, feed, [fetches[0]], scope
        )
        txt = lowered.compile().as_text()
    c = hlo.count_collectives(txt)
    assert c["all-reduce"] >= 1, c
    assert c["all-to-all"] == 0, c


# ---------------------------------------------------------------------------
# donation/aliasing + fused-optimizer gates (ROADMAP item 3's compiled-path
# prerequisites, asserted without a TPU)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def adam_step_lowered():
    """A small-but-real Adam train step (two fc layers), lowered through
    the production path (core/lowering.py) — shared with
    tools/hlo_report.py so the committed evidence asserts this exact
    computation."""
    return hlo.adam_mlp_step_lowered()


def test_train_step_aliases_params_and_optimizer_slots(adam_step_lowered):
    """Every donated buffer — parameters AND adam moment slots AND the
    beta-power scalars — must carry input/output aliasing in the emitted
    StableHLO: the in-place update contract behind donation (no
    second HBM copy of the model per step)."""
    lowered, donated, main = adam_step_lowered
    pairs = hlo.stablehlo_donated_args(lowered.as_text())
    assert len(pairs) == len(donated), (
        f"{len(donated)} donated inputs but only {len(pairs)} aliased "
        f"args: {pairs}"
    )
    # aliased outputs must be distinct (each donated buffer backs exactly
    # one written output)
    outs = [o for _a, o in pairs]
    assert len(set(outs)) == len(outs)
    # the donation plan must cover params and both adam moments
    params = {p.name for p in main.all_parameters()}
    assert params <= set(donated)
    for p in params:
        assert p + "_moment1_0" in donated, f"moment1 slot of {p} not donated"
        assert p + "_moment2_0" in donated, f"moment2 slot of {p} not donated"


def test_no_unfused_adam_chains(adam_step_lowered):
    """The adam update chain (m/v update, sqrt, divide, param delta) must
    fuse: no standalone sqrt/divide/power survives at ENTRY level in the
    optimized HLO. A regression here means per-parameter HBM round-trips
    every step."""
    lowered, _donated, _main = adam_step_lowered
    offenders = hlo.unfused_adam_chain_ops(lowered.compile().as_text())
    assert offenders == [], "unfused adam-chain ops:\n" + "\n".join(offenders)
