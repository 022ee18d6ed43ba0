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
    return hlo.bert_train_step_text(
        flash, seq_len=S, vocab=VOCAB, max_pred=P_PRED
    )


LAYERS = 2     # bert_train_step_text's defaults
HEADS = 12
@pytest.fixture(scope="module")
def bert_flash_lowering():
    """(StableHLO text, ``kernels.flash_grid_snapshot()`` before and after)
    of the module's flash lowering."""
    from paddle_tpu import kernels

    before = kernels.flash_grid_snapshot()
    text = _lower_bert(flash=True)
    return text, before, kernels.flash_grid_snapshot()


@pytest.fixture(scope="module")
def bert_flash_stablehlo(bert_flash_lowering):
    return bert_flash_lowering[0]


def test_flash_train_step_no_s2_buffers(bert_flash_stablehlo):
    """The whole train step — fwd, bwd, optimizer — must never materialize
    an [S, S]-shaped tensor when flash attention is on. Catches both an
    unfused forward AND a grad op differentiating the unfused path."""
    tensors = hlo.stablehlo_tensors(bert_flash_stablehlo)
    s2 = hlo.tensors_with_trailing(tensors, (S, S))
    assert not s2, f"S^2 buffers on the flash path: {set(s2)}"


def test_flash_grid_serves_several_heads_a_step(bert_flash_lowering):
    """``flash_grid_steps_total`` adds calls x B*H / G x S / block at
    lowering, with G from the shapes: here 48 (batch, head) pairs of
    [512, 64] bf16 go 8 to a grid step in all three kernels, and a layer
    lowers the forward twice (the op, and the grad op's re-run: PERF.md)."""
    from paddle_tpu.ops.pallas.flash_attention import _heads_per_step

    _text, before, after = bert_flash_lowering
    bh, blocks = BATCH * HEADS, S // 128
    calls = {"fwd": 2 * LAYERS, "bwd_dkdv": LAYERS, "bwd_dq": LAYERS}
    blocked = {"fwd": 2, "bwd_dkdv": 4, "bwd_dq": 3}
    assert set(after) == set(calls)
    for kernel, n in calls.items():
        g = _heads_per_step(bh, S, 128, HIDDEN // HEADS, "bfloat16",
                            blocked=blocked[kernel])
        assert g == after[kernel]["heads_per_step"] == 8
        steps = after[kernel]["grid_steps"] - before[kernel]["grid_steps"]
        assert steps == n * (bh // g) * blocks, kernel


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
    back (4 GB at bench shapes)."""
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


def test_paged_attention_tables_are_derived_once_a_step():
    """Every ``paged_attention`` call of a step program reads the same
    ``rows`` and ``bias`` (the one ``paged_step_feeds`` op's outputs), and
    the kernel's wrapper derives its scalar-prefetch operands from them at
    every call: the block table, the lengths (a max over each bias row),
    each slot's next live slot (a min), the bias in tiles. Four layers
    trace four of each (the control: the unoptimized HLO holds 2 x 4
    reductions to an ``s32[S]``); XLA's CSE leaves ONE, under the wrapper's
    named scope. On a v5e the derivation is ~2 us against 192 calls of
    ~33 us in ouro_2_6b's step (PERF.md, PR 44)."""
    import re

    from paddle_tpu import kernels
    from paddle_tpu.kernels import attention
    from paddle_tpu.serving.decode import build_decoder_model

    S, L, H, N = 5, 96, 24, 4
    m = build_decoder_model(vocab_size=40, hidden=H, num_layers=N, slots=S,
                            max_len=L, block_size=8, num_blocks=36,
                            name="hlo_tables", version="1")
    feed = {n: np.zeros(shape, dtype)
            for n, shape, dtype in m.decode_feed_sig()}
    scope = fluid.Scope()
    with fluid.scope_guard(scope), kernels.scoped_mode("interpret"):
        fluid.Executor(fluid.CPUPlace()).run(m.startup_program)
        lowered = hlo.lower_program_step(
            m.decode_program, feed, [m.logits_fetch], scope=scope)
    to_slots = r"= s32\[%d\]\{0\} reduce\(" % S
    traced = lowered.compiler_ir(dialect="hlo").as_hlo_text()
    assert len(re.findall(to_slots, traced)) == 2 * N
    compiled = lowered.compile().as_text()
    kept = [line for line in compiled.splitlines()
            if re.search(to_slots, line)]
    assert sorted(re.findall(r"/(reduce_\w+)\"", " ".join(kept))) == [
        "reduce_max", "reduce_min"], kept
    assert all(attention.TABLES_SCOPE in line for line in kept), kept
    gathers = [line for line in compiled.splitlines()
               if " gather(" in line and attention.TABLES_SCOPE in line]
    assert len(gathers) <= 1, gathers


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


GEO = dict(seq_len=24, max_pred=20, with_param_shapes=True)


def _arm(tag):
    """mesh shape, axis names and placement of one sharding arm."""
    from paddle_tpu.parallel.sharding import MEGATRON_RULES
    from paddle_tpu.parallel.spec_layout import SpecLayout

    return {
        "tp_registry": ((2, 4), ("data", "model"),
                        dict(spec_layout=SpecLayout())),
        "dp_fsdp_tp_registry": ((2, 2, 2), ("data", "fsdp", "model"),
                                dict(spec_layout=SpecLayout())),
        # the PR-4-era rule table leaves pos/type embeddings, pooler and
        # MLM head replicated and pays weight-sized collectives for
        # them: the positive control of every scan below
        "megatron_control": ((2, 4), ("data", "model"),
                             dict(param_rules=MEGATRON_RULES)),
    }[tag]


@pytest.fixture(scope="module")
def lowered_arm():
    """tag -> (optimized HLO text, rank>=2 parameter shapes) of the
    tiny-BERT step on that arm's 8-device mesh, lowered once a module.
    seq_len=24 keeps activation shapes disjoint from every parameter
    shape (at 16, [B_local*S, H] == the qkv weight shape and the scan
    could not tell them apart)."""
    cache = {}

    def get(tag):
        if tag not in cache:
            shape, axes, placement = _arm(tag)
            cache[tag] = hlo.tiny_bert_parallel_text(
                shape, axes, **placement, **GEO)
        return cache[tag]
    return get


@pytest.fixture(scope="module")
def tp_registry_lowering(lowered_arm):
    return lowered_arm("tp_registry")


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
    parameter — the quantitative form of the shape scan above."""
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


def test_dp_fsdp_tp_registry_no_weight_sized_collectives(lowered_arm):
    """The full dp x fsdp x tp factorization (2x2x2) through the
    registry: parameters and optimizer state ZeRO-sliced over fsdp and
    tensor-sharded over tp, still zero full-weight collectives and
    byte-bounded wire traffic."""
    assert jax.device_count() >= 8
    txt, param_shapes = lowered_arm("dp_fsdp_tp_registry")
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


def test_weight_shaped_scan_is_zero_on_registry_and_fires_on_megatron(
        lowered_arm):
    """Both registry arms move ZERO full-parameter-shaped operands and
    no collective of theirs materializes anything near the largest
    parameter; the same scan over the MEGATRON_RULES lowering finds
    weight-shaped collectives — if that control stops firing, the zeros
    prove nothing."""
    ctl_txt, ctl_shapes = lowered_arm("megatron_control")
    assert len(hlo.weight_shaped_collectives(ctl_txt, ctl_shapes)) > 0
    for tag in ("tp_registry", "dp_fsdp_tp_registry"):
        txt, shapes = lowered_arm(tag)
        assert hlo.weight_shaped_collectives(txt, shapes) == [], tag
        largest = max(4 * int(np.prod(shp)) for shp in shapes)
        rep = hlo.collective_byte_report(txt)
        assert rep["by_kind"] and 0 < rep["max_bytes"] < 2 * largest, rep


def test_static_sharding_analysis_predicts_the_live_collectives(lowered_arm):
    """analysis/sharding.py, with no XLA in the loop, tells the same
    collective story the lowered HLO does, arm by arm: no weight-sized
    event and a passing 192 KB budget on the registry arms, both firing
    on the Megatron control; every live weight-shaped collective has a
    static prediction of that shape within 2x of its bytes; and the
    predicted all-reduce bytes are within 2x of the live all-reduce
    bytes SUMMED (a sum does not move when XLA combines instructions;
    their count does, and is not held)."""
    from paddle_tpu.analysis.sharding import (
        analyze_sharding,
        collective_budget_diagnostics,
        weight_param_shapes,
        weight_sized_events,
    )
    from paddle_tpu.models import bert
    from paddle_tpu.parallel.env import make_mesh

    cfg = bert.BertConfig.tiny()
    cfg.hidden_dropout_prob = cfg.attention_probs_dropout_prob = 0.0
    main, _startup, _feeds, _fetches = bert.build_bert_pretrain(
        cfg, seq_len=GEO["seq_len"], lr=1e-3,
        max_predictions_per_seq=GEO["max_pred"])
    data = bert.synthetic_batch(
        np.random.RandomState(0), 8, GEO["seq_len"], cfg,
        max_predictions_per_seq=GEO["max_pred"])
    feed_shapes = {k: tuple(np.asarray(v).shape) for k, v in data.items()}
    param_shapes = weight_param_shapes(main)
    for tag in ("tp_registry", "dp_fsdp_tp_registry", "megatron_control"):
        shape, axes, placement = _arm(tag)
        rep = analyze_sharding(main, make_mesh(shape, axes),
                               feed_shapes=feed_shapes, **placement)
        predicted = weight_sized_events(rep, param_shapes)
        over = collective_budget_diagnostics(rep, 192 * 1024)
        txt, shapes = lowered_arm(tag)
        offenders = hlo.weight_shaped_collectives(txt, shapes)
        if tag == "megatron_control":
            assert predicted and over and offenders, tag
        else:
            assert not predicted and not over and not offenders, tag
        for kind, shp, _line in offenders:
            nbytes = 4 * int(np.prod(shp))
            preds = [e.bytes for e in predicted
                     if tuple(e.shape or ()) == tuple(shp) and e.bytes]
            assert preds, f"{tag}: live {kind} {shp} was not predicted"
            ratio = min(max(b, nbytes) / min(b, nbytes) for b in preds)
            assert ratio <= 2.0, (tag, shp, preds, nbytes)
        static_ar = rep.by_kind()["all-reduce"]["total_bytes"]
        live_ar = hlo.collective_byte_report(txt)["by_kind"][
            "all-reduce"]["total_bytes"]
        assert max(static_ar, live_ar) <= 2.0 * min(static_ar, live_ar), (
            tag, static_ar, live_ar)


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
    the production path (core/lowering.py)."""
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


# ---------------------------------------------------------------------------
# the exact gelu as the TPU's own compiler leaves it (PERF.md section 6, PR 49):
# BERT's step at the training cells' widths, compiled for a described v5e
# ---------------------------------------------------------------------------

FFN = 3072     # BertConfig's intermediate size, the cells'


@pytest.fixture(scope="module")
def v5e_device():
    import os

    from jax.experimental import topologies

    # as tests/test_kernels_tpu_aot.py: a compile-only client holds no
    # device, so parallel test workers may each load libtpu
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    try:
        return topologies.get_topology_desc("v5e:2x2", "tpu").devices[0]
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _bert_cell_step_for(device):
    return hlo.bert_train_step_text(
        False, seq_len=128, layers=2, batch=256, max_pred=20, device=device)


@pytest.fixture(scope="module")
def bert_step_on_v5e(v5e_device):
    """(optimized HLO, ``gelu_lowerings_total`` by form before and after)."""
    from paddle_tpu.ops.common import gelu_lowering_counts

    before = gelu_lowering_counts()
    return _bert_cell_step_for(v5e_device), before, gelu_lowering_counts()


def test_compiled_bert_step_holds_no_erfc_expansion(bert_step_on_v5e):
    text, _before, _after = bert_step_on_v5e
    assert hlo.erfc_expansions(text) == []


def test_ffn_products_keep_a_light_gelu_epilogue(bert_step_on_v5e):
    """FFN1's forward product (bias add and gelu on its result) and FFN2's
    data-gradient product (gelu's derivative and the bias gradient on its
    result) are each ONE fusion built around the product, with one ``erf``
    and a few float32 operations an element: the compiler neither split
    the activation off into a pass of its own over ``[256, 128, 3072]``
    nor left gelu's result unwritten to evaluate it again inside every
    product that reads it (it does, without the AMP rewrite's
    ``round_dtype``: 24 ms of a 256 ms step on the chip)."""
    text, _before, _after = bert_step_on_v5e
    fusions = hlo.activation_epilogues(text, FFN)
    assert len(fusions) == 2 * LAYERS, fusions
    for f in fusions:
        assert f["kind"] == "kOutput", f
        assert f["erf"] == 1, f
        assert f["float32_ops"] <= 30, f
    rest = [f["name"] for f in hlo.fusion_census(text)
            if any(op == "erf" for op, dims, *_ in f["body"]
                   if dims[-1:] == (FFN,))]
    assert sorted(rest) == sorted(f["name"] for f in fusions)


def test_bert_step_lowers_every_gelu_through_erf(bert_step_on_v5e):
    """Two layers and the MLM transform: three gelu ops, each lowered at
    the program's build (shape inference), in the step and in the grad
    op's ``jax.vjp``."""
    _text, before, after = bert_step_on_v5e
    assert after["erf"] - before["erf"] == 3 * (LAYERS + 1)
    assert after["tanh"] == before["tanh"]


def test_erfc_detector_fires(v5e_device, monkeypatch):
    """Positive control: with jax's own exact gelu in the lowering's place
    the expansion is in the step and weighs on both products."""
    from paddle_tpu.ops import nn as nn_ops

    monkeypatch.setattr(
        nn_ops, "gelu",
        lambda x, approximate=False, round_dtype=None: jax.nn.gelu(
            x, approximate=approximate))
    text = _bert_cell_step_for(v5e_device)
    assert len(hlo.erfc_expansions(text)) > 100
    fusions = hlo.activation_epilogues(text, FFN)
    assert len(fusions) == 2 * LAYERS, fusions
    for f in fusions:
        assert f["erf"] == 0 and f["float32_ops"] > 30, f
