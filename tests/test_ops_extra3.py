"""OpTest-style numeric tests for the third/fourth op tranches
(ops/misc_extra.py, ops/vision_extra.py) — numpy references per op,
modeled on the reference's test_*_op.py files."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import get_op_def

import paddle_tpu  # noqa: F401  (registers ops)


def lower(op, ins, attrs=None):
    ins = {k: [jnp.asarray(v) for v in vs] for k, vs in ins.items()}
    return get_op_def(op).lower(ins, attrs or {})


def test_trivial_math_shape(rng):
    x = rng.randn(3, 4).astype("float32")
    y = rng.randn(3, 4).astype("float32")
    np.testing.assert_allclose(
        lower("minus", {"X": [x], "Y": [y]})["Out"][0], x - y
    )
    out = lower("fill", {}, {"shape": [2, 3], "value": list(range(6)),
                             "dtype": "float32"})["Out"][0]
    np.testing.assert_allclose(out, np.arange(6).reshape(2, 3))
    np.testing.assert_allclose(
        lower("fill_any_like", {"X": [x]}, {"value": 2.5})["Out"][0],
        np.full_like(x, 2.5),
    )
    b = rng.rand(2, 3) > 0.5
    np.testing.assert_array_equal(
        lower("reduce_all", {"X": [b]}, {"dim": [1]})["Out"][0],
        b.all(axis=1),
    )
    np.testing.assert_array_equal(
        lower("reduce_any", {"X": [b]}, {"reduce_all": True})["Out"][0],
        b.any(),
    )
    x3 = rng.randn(2, 1, 3, 1).astype("float32")
    assert lower("squeeze", {"X": [x3]}, {"axes": [1]})["Out"][0].shape == \
        (2, 3, 1)
    assert lower("squeeze", {"X": [x3]}, {})["Out"][0].shape == (2, 3)
    assert lower("flatten", {"X": [x3]}, {"axis": 2})["Out"][0].shape == \
        (2, 3)
    c = lower("crop", {"X": [x]}, {"shape": [2, 2], "offsets": [1, 1]})
    np.testing.assert_allclose(c["Out"][0], x[1:3, 1:3])


def test_cross_entropy2_and_teacher_student(rng):
    p = rng.rand(4, 5).astype("float32") * 0.8 + 0.1
    lab = rng.randint(0, 5, (4, 1)).astype("int64")
    out = lower("cross_entropy2", {"X": [p], "Label": [lab]})
    expect = -np.log(p[np.arange(4), lab[:, 0]])
    np.testing.assert_allclose(out["Y"][0].reshape(-1), expect, rtol=1e-5)

    x = rng.randn(6).astype("float32")
    # labels: -2 (z=0), -1 (z=1), 0.3 (z=0,z'=0.3), 1.4 (z=1,z'=0.4)
    lab2 = np.array([-2.0, -1.0, 0.3, 1.4, -2.0, 1.0], "float32")
    y = lower("teacher_student_sigmoid_loss",
              {"X": [x.reshape(-1, 1)], "Label": [lab2.reshape(-1, 1)]}
              )["Y"][0].reshape(-1)

    def ce(xv, z):
        return max(xv, 0) - xv * z + np.log1p(np.exp(-abs(xv)))

    expect2 = [
        ce(x[0], 0.0), ce(x[1], 1.0),
        ce(x[2], 0.0) + ce(x[2], 0.3),
        ce(x[3], 1.0) + ce(x[3], 0.4 if False else 1.4 - 1.0),
        ce(x[4], 0.0), ce(x[5], 1.0) + ce(x[5], 0.0),
    ]
    np.testing.assert_allclose(y, expect2, rtol=1e-5)


def test_fsp_matrix(rng):
    x = rng.randn(2, 3, 4, 5).astype("float32")
    y = rng.randn(2, 6, 4, 5).astype("float32")
    out = lower("fsp", {"X": [x], "Y": [y]})["Out"][0]
    expect = np.einsum("nchw,ndhw->ncd", x, y) / 20.0
    np.testing.assert_allclose(out, expect, rtol=1e-4)


def test_sample_logits_accidental_hits(rng):
    logits = rng.randn(3, 50).astype("float32")
    labels = rng.randint(0, 50, (3, 2)).astype("int64")
    outs = lower(
        "sample_logits",
        {"Logits": [logits], "Labels": [labels],
         "__rng_key__": [jax.random.PRNGKey(0)]},
        {"num_samples": 8, "remove_accidental_hits": True},
    )
    samples = np.asarray(outs["Samples"][0])
    sampled = np.asarray(outs["SampledLogits"][0])
    assert samples.shape == (3, 10) and sampled.shape == (3, 10)
    np.testing.assert_array_equal(samples[:, :2], labels)
    # any accidental hit among negatives is crushed to huge negative
    for i in range(3):
        for j in range(2, 10):
            if samples[i, j] in labels[i]:
                assert sampled[i, j] < -1e18


def test_proximal_updates(rng):
    p = rng.randn(5).astype("float32")
    g = rng.randn(5).astype("float32")
    lr = np.array([0.1], "float32")
    out = lower("proximal_gd", {"Param": [p], "Grad": [g],
                                "LearningRate": [lr]},
                {"l1": 0.05, "l2": 0.1})["ParamOut"][0]
    prox = p - 0.1 * g
    expect = np.sign(prox) * np.maximum(np.abs(prox) - 0.1 * 0.05, 0) / (
        1 + 0.1 * 0.1)
    np.testing.assert_allclose(out, expect, rtol=1e-5)

    m = np.abs(rng.randn(5)).astype("float32")
    outs = lower("proximal_adagrad",
                 {"Param": [p], "Grad": [g], "Moment": [m],
                  "LearningRate": [lr]}, {"l1": 0.0, "l2": 0.1})
    m2 = m + g * g
    lr_eff = 0.1 / np.sqrt(m2)
    np.testing.assert_allclose(
        outs["ParamOut"][0], (p - lr_eff * g) / (1 + lr_eff * 0.1),
        rtol=1e-5,
    )
    np.testing.assert_allclose(outs["MomentOut"][0], m2, rtol=1e-6)


def _levenshtein(a, b):
    m, n = len(a), len(b)
    d = np.zeros((m + 1, n + 1))
    d[:, 0] = np.arange(m + 1)
    d[0, :] = np.arange(n + 1)
    for i in range(1, m + 1):
        for j in range(1, n + 1):
            d[i, j] = min(d[i - 1, j] + 1, d[i, j - 1] + 1,
                          d[i - 1, j - 1] + (a[i - 1] != b[j - 1]))
    return d[m, n]


def test_edit_distance_matches_dp(rng):
    B, Tm, Tn = 5, 7, 6
    hyps = rng.randint(0, 4, (B, Tm)).astype("int64")
    refs = rng.randint(0, 4, (B, Tn)).astype("int64")
    hl = rng.randint(1, Tm + 1, (B,)).astype("int64")
    rl = rng.randint(1, Tn + 1, (B,)).astype("int64")
    out = lower("edit_distance",
                {"Hyps": [hyps], "Refs": [refs],
                 "HypsLength": [hl], "RefsLength": [rl]})["Out"][0]
    expect = [
        _levenshtein(list(hyps[i, :hl[i]]), list(refs[i, :rl[i]]))
        for i in range(B)
    ]
    np.testing.assert_allclose(np.asarray(out).reshape(-1), expect)


def test_edit_distance_normalized_and_full_length(rng):
    hyps = np.array([[1, 2, 3]], dtype="int64")
    refs = np.array([[1, 3, 3, 4]], dtype="int64")
    out = lower("edit_distance", {"Hyps": [hyps], "Refs": [refs]},
                {"normalized": True})["Out"][0]
    np.testing.assert_allclose(np.asarray(out).reshape(-1), [2.0 / 4.0])


def test_positive_negative_pair():
    score = np.array([0.9, 0.2, 0.5, 0.6], "float32").reshape(-1, 1)
    label = np.array([1, 0, 0, 1], "float32").reshape(-1, 1)
    qid = np.array([0, 0, 0, 0], "int64").reshape(-1, 1)
    outs = lower("positive_negative_pair",
                 {"Score": [score], "Label": [label], "QueryID": [qid]})
    # pairs (hi-label vs lo-label): (0,1)+, (0,2)+, (3,1)+, (3,2)+ -> 4 pos
    assert float(np.asarray(outs["PositivePair"][0])[0]) == 4.0
    assert float(np.asarray(outs["NegativePair"][0])[0]) == 0.0


def test_match_matrix_tensor(rng):
    x = rng.randn(2, 3, 4).astype("float32")
    y = rng.randn(2, 5, 6).astype("float32")
    w = rng.randn(4, 2, 6).astype("float32")
    out = lower("match_matrix_tensor", {"X": [x], "Y": [y], "W": [w]}
                )["Out"][0]
    expect = np.einsum("bid,dte,bje->btij", x, w, y)
    np.testing.assert_allclose(out, expect, rtol=1e-4)


def test_rnn_units(rng):
    B, H = 3, 4
    # lstm_unit
    x = rng.randn(B, 4 * H).astype("float32")
    c_prev = rng.randn(B, H).astype("float32")
    outs = lower("lstm_unit", {"X": [x], "C_prev": [c_prev]},
                 {"forget_bias": 1.0})
    sig = lambda v: 1 / (1 + np.exp(-v))
    i, f, o, g = (x[:, :H], x[:, H:2*H], x[:, 2*H:3*H], x[:, 3*H:])
    c = sig(f + 1.0) * c_prev + sig(i) * np.tanh(g)
    np.testing.assert_allclose(outs["C"][0], c, rtol=1e-4)
    np.testing.assert_allclose(outs["H"][0], sig(o) * np.tanh(c), rtol=1e-4)

    # gru_unit
    xp = rng.randn(B, 3 * H).astype("float32")
    h_prev = rng.randn(B, H).astype("float32")
    w = rng.randn(H, 3 * H).astype("float32")
    outs = lower("gru_unit", {"Input": [xp], "HiddenPrev": [h_prev],
                              "Weight": [w]})
    gates = xp[:, :2*H] + h_prev @ w[:, :2*H]
    u = sig(gates[:, :H])
    r = sig(gates[:, H:])
    c2 = np.tanh(xp[:, 2*H:] + (r * h_prev) @ w[:, 2*H:])
    np.testing.assert_allclose(
        outs["Hidden"][0], u * h_prev + (1 - u) * c2, rtol=1e-4
    )

    # lstmp shapes
    T, P = 5, 2
    xs = rng.randn(B, T, 4 * H).astype("float32")
    wp = rng.randn(P, 4 * H).astype("float32")
    proj = rng.randn(H, P).astype("float32")
    outs = lower("lstmp", {"Input": [xs], "Weight": [wp],
                           "ProjWeight": [proj]})
    assert outs["Projection"][0].shape == (B, T, P)
    assert np.isfinite(np.asarray(outs["Projection"][0])).all()


def test_hash_deterministic():
    x = np.array([[1, 2], [1, 2], [3, 4]], dtype="int64")
    o1 = np.asarray(lower("hash", {"X": [x]},
                          {"mod_by": 1000, "num_hash": 3})["Out"][0])
    o2 = np.asarray(lower("hash", {"X": [x]},
                          {"mod_by": 1000, "num_hash": 3})["Out"][0])
    assert o1.shape == (3, 3, 1)
    np.testing.assert_array_equal(o1, o2)
    np.testing.assert_array_equal(o1[0], o1[1])  # same row -> same hash
    assert (o1[0] != o1[2]).any()
    assert (o1 >= 0).all() and (o1 < 1000).all()


def test_sampling_id(rng):
    probs = np.zeros((4, 6), "float32")
    probs[np.arange(4), [1, 3, 5, 0]] = 1.0
    out = lower("sampling_id",
                {"X": [probs], "__rng_key__": [jax.random.PRNGKey(0)]})
    np.testing.assert_array_equal(np.asarray(out["Out"][0]), [1, 3, 5, 0])


def test_gaussian_random_batch_size_like(rng):
    ref = np.zeros((7, 3), "float32")
    out = lower("gaussian_random_batch_size_like",
                {"Input": [ref], "__rng_key__": [jax.random.PRNGKey(0)]},
                {"shape": [-1, 5], "mean": 2.0, "std": 0.1})["Out"][0]
    assert out.shape == (7, 5)
    assert abs(float(np.asarray(out).mean()) - 2.0) < 0.1


def test_max_pool3d_with_index(rng):
    x = rng.randn(1, 1, 4, 4, 4).astype("float32")
    outs = lower("max_pool3d_with_index", {"X": [x]},
                 {"ksize": [2, 2, 2], "strides": [2, 2, 2]})
    out = np.asarray(outs["Out"][0])
    mask = np.asarray(outs["Mask"][0])
    assert out.shape == (1, 1, 2, 2, 2)
    expect = x.reshape(1, 1, 2, 2, 2, 2, 2, 2).transpose(
        0, 1, 2, 4, 6, 3, 5, 7).reshape(1, 1, 2, 2, 2, 8).max(-1)
    np.testing.assert_allclose(out, expect, rtol=1e-6)
    # mask indexes into the flattened input volume
    flat = x.reshape(-1)
    np.testing.assert_allclose(flat[mask.reshape(-1)], out.reshape(-1))


def test_shrink_rnn_memory():
    x = np.arange(12, dtype="float32").reshape(4, 3)
    table = np.array([5, 4, 2, 1], dtype="int64")  # sorted desc lengths
    out = lower("shrink_rnn_memory",
                {"X": [x], "I": [np.array([3], "int64")],
                 "RankTable": [table]})["Out"][0]
    # step 3: sequences with length > 3 -> first 2 rows stay
    np.testing.assert_allclose(np.asarray(out)[:2], x[:2])
    np.testing.assert_allclose(np.asarray(out)[2:], 0.0)


# ---------------------------------------------------------------------------
# vision_extra
# ---------------------------------------------------------------------------


def test_deformable_conv_zero_offset_matches_conv(rng):
    """With zero offsets and unit mask, DCN == standard convolution."""
    N, C, H, W, Co, k = 1, 2, 5, 5, 3, 3
    x = rng.randn(N, C, H, W).astype("float32")
    w = rng.randn(Co, C, k, k).astype("float32")
    offset = np.zeros((N, 2 * k * k, H - 2, W - 2), "float32")
    mask = np.ones((N, k * k, H - 2, W - 2), "float32")
    out = lower("deformable_conv",
                {"Input": [x], "Offset": [offset], "Mask": [mask],
                 "Filter": [w]},
                {"strides": [1, 1], "paddings": [0, 0],
                 "dilations": [1, 1]})["Output"][0]

    expect = np.zeros((N, Co, H - 2, W - 2), "float32")
    for o in range(Co):
        for i in range(H - 2):
            for j in range(W - 2):
                expect[0, o, i, j] = np.sum(
                    x[0, :, i:i + k, j:j + k] * w[o]
                )
    np.testing.assert_allclose(out, expect, rtol=1e-3, atol=1e-4)


def test_deformable_conv_v1_shift_offset(rng):
    """A whole-pixel offset equals sampling the shifted image (out-of-
    bounds rows fade to 0, the kernel's zero-padding)."""
    x = np.arange(25, dtype="float32").reshape(1, 1, 5, 5)
    w = np.ones((1, 1, 1, 1), "float32")
    offset = np.zeros((1, 2, 5, 5), "float32")
    offset[:, 0] = 1.0  # shift +1 in y for the single 1x1 tap
    out = lower("deformable_conv_v1",
                {"Input": [x], "Offset": [offset], "Filter": [w]},
                {"strides": [1, 1], "paddings": [0, 0],
                 "dilations": [1, 1]})["Output"][0]
    expect = np.vstack([x[0, 0, 1:5, :], np.zeros((1, 5), "float32")])
    np.testing.assert_allclose(np.asarray(out)[0, 0], expect)


def test_psroi_pool(rng):
    PH = PW = 2
    oc = 2
    C = oc * PH * PW
    x = rng.randn(1, C, 6, 6).astype("float32")
    rois = np.array([[0, 0, 3, 3]], "float32")
    out = lower("psroi_pool", {"X": [x], "ROIs": [rois]},
                {"pooled_height": PH, "pooled_width": PW,
                 "output_channels": oc, "spatial_scale": 1.0})["Out"][0]
    assert out.shape == (1, oc, PH, PW)
    # bin (0,0) of channel c pools input channel c*4+0 over rows 0..1
    np.testing.assert_allclose(
        np.asarray(out)[0, 0, 0, 0], x[0, 0, 0:2, 0:2].mean(), rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(out)[0, 1, 1, 1], x[0, 7, 2:4, 2:4].mean(), rtol=1e-5
    )


def test_prroi_pool_constant_field(rng):
    x = np.full((1, 3, 8, 8), 2.5, "float32")
    rois = np.array([[1.0, 1.0, 5.0, 5.0]], "float32")
    out = lower("prroi_pool", {"X": [x], "ROIs": [rois]},
                {"pooled_height": 2, "pooled_width": 2,
                 "spatial_scale": 1.0})["Out"][0]
    np.testing.assert_allclose(np.asarray(out), 2.5, rtol=1e-5)


def test_distribute_and_collect_fpn(rng):
    rois = np.array([
        [0, 0, 10, 10],      # small -> low level
        [0, 0, 224, 224],    # refer scale -> refer level
        [0, 0, 500, 500],    # large -> high level
    ], "float32")
    outs = lower("distribute_fpn_proposals", {"FpnRois": [rois]},
                 {"min_level": 2, "max_level": 5, "refer_level": 4,
                  "refer_scale": 224})
    counts = np.asarray(outs["MultiLevelRoIsNum"][0])
    assert counts.sum() == 3
    assert counts[2] == 1  # the 224 box sits at refer_level=4 (index 2)
    multi = [np.asarray(t) for t in outs["MultiFpnRois"]]
    scores = [np.asarray([0.9]), np.asarray([0.1]),
              np.asarray([0.5]), np.asarray([0.2])]
    col = lower("collect_fpn_proposals",
                {"MultiLevelRois": [t[:1] for t in multi],
                 "MultiLevelScores": scores},
                {"post_nms_topN": 2})
    assert np.asarray(col["FpnRois"][0]).shape == (2, 4)


def test_generate_proposals_basic(rng):
    H = W = 4
    A = 2
    scores = rng.rand(1, A, H, W).astype("float32")
    deltas = np.zeros((1, 4 * A, H, W), "float32")
    anchors = np.zeros((H, W, A, 4), "float32")
    ys, xs = np.meshgrid(np.arange(H), np.arange(W), indexing="ij")
    for a in range(A):
        anchors[:, :, a, 0] = xs * 4
        anchors[:, :, a, 1] = ys * 4
        anchors[:, :, a, 2] = xs * 4 + 7
        anchors[:, :, a, 3] = ys * 4 + 7
    im_info = np.array([[16.0, 16.0, 1.0]], "float32")
    outs = lower("generate_proposals",
                 {"Scores": [scores], "BboxDeltas": [deltas],
                  "ImInfo": [im_info], "Anchors": [anchors]},
                 {"pre_nms_topN": 12, "post_nms_topN": 5,
                  "nms_thresh": 0.5, "min_size": 2.0})
    rois = np.asarray(outs["RpnRois"][0])
    assert rois.shape == (5, 4)
    assert (rois >= 0).all() and (rois <= 15).all()
    assert int(outs["RpnRoisNum"][0][0]) >= 1


def test_multiclass_nms2_and_locality_aware(rng):
    boxes = np.array([[[0, 0, 10, 10], [0, 0, 10.5, 10.5],
                       [20, 20, 30, 30]]], "float32")
    scores = np.array([[[0.9, 0.85, 0.7]]], "float32")  # [B=1, C=1, N=3]
    outs = lower("multiclass_nms2", {"BBoxes": [boxes], "Scores": [scores]},
                 {"score_threshold": 0.1, "nms_threshold": 0.5,
                  "keep_top_k": 3, "background_label": -1})
    out = np.asarray(outs["Out"][0])
    assert int(outs["NumDetections"][0][0]) == 2  # overlap suppressed
    la = lower("locality_aware_nms", {"BBoxes": [boxes], "Scores": [scores]},
               {"score_threshold": 0.1, "nms_threshold": 0.5,
                "keep_top_k": 3, "background_label": -1})
    assert int(la["NumDetections"][0][0]) >= 1


def test_retinanet_detection_output(rng):
    anchors = np.array([[0, 0, 10, 10], [20, 20, 30, 30]], "float32")
    deltas = np.zeros((1, 2, 4), "float32")
    scores = np.array([[[0.9, 0.1], [0.8, 0.2]]], "float32")  # [B, N, C]
    im_info = np.array([[40.0, 40.0, 1.0]], "float32")
    outs = lower("retinanet_detection_output",
                 {"BBoxes": [deltas], "Scores": [scores],
                  "Anchors": [anchors], "ImInfo": [im_info]},
                 {"score_threshold": 0.05, "nms_threshold": 0.5,
                  "keep_top_k": 5})
    assert int(outs["NumDetections"][0][0]) >= 2


def test_random_crop_and_similarity_focus(rng):
    x = rng.randn(2, 3, 8, 8).astype("float32")
    out = lower("random_crop",
                {"X": [x], "__rng_key__": [jax.random.PRNGKey(1)]},
                {"shape": [5, 5]})["Out"][0]
    assert out.shape == (2, 3, 5, 5)
    sf = lower("similarity_focus", {"X": [x]}, {"indexes": [1]})["Out"][0]
    sf = np.asarray(sf)
    assert sf.shape == x.shape and set(np.unique(sf)) <= {0.0, 1.0}
    # the global argmax of the selected channel is always marked
    n, hw = 0, np.unravel_index(np.argmax(x[0, 1]), (8, 8))
    assert sf[0, 0, hw[0], hw[1]] == 1.0


def test_quant_ops_roundtrip(rng):
    x = rng.randn(4, 6).astype("float32")
    q = lower("fake_quantize_abs_max", {"X": [x]}, {"bit_length": 8})
    scale = float(np.asarray(q["OutScale"][0])[0])
    assert abs(scale - np.abs(x).max()) < 1e-6
    deq = lower("fake_dequantize_max_abs",
                {"X": [q["Out"][0]], "Scale": [q["OutScale"][0]]},
                {"max_range": 127.0})["Out"][0]
    np.testing.assert_allclose(np.asarray(deq), x, atol=scale / 100)

    cq = lower("fake_channel_wise_quantize_abs_max", {"X": [x]},
               {"bit_length": 8})
    assert np.asarray(cq["OutScale"][0]).shape == (4,)
    cdq = lower("fake_channel_wise_dequantize_max_abs",
                {"X": [cq["Out"][0]], "Scales": [cq["OutScale"][0]]},
                {"quant_bits": [8]})["Out"][0]
    np.testing.assert_allclose(np.asarray(cdq), x, atol=0.05)

    mv = lower("fake_quantize_moving_average_abs_max",
               {"X": [x], "InScale": [np.ones(1, "float32")],
                "InState": [np.ones(1, "float32")],
                "InAccum": [np.ones(1, "float32")]},
               {"moving_rate": 0.9})
    assert "OutState" in mv and "OutAccum" in mv
    rng_q = lower("fake_quantize_range_abs_max",
                  {"X": [x], "InScale": [np.zeros(1, "float32")]},
                  {"bit_length": 8})
    assert float(np.asarray(rng_q["OutScale"][0])[0]) >= np.abs(x).max() - 1e-6
    dq = lower("dequantize_abs_max",
               {"X": [np.array([[127.0]], "float32")],
                "Scale": [np.array([2.0], "float32")]},
               {"max_range": 127.0})["Out"][0]
    np.testing.assert_allclose(np.asarray(dq), [[2.0]])


@pytest.mark.parametrize("op,make", [
    ("fsp", lambda rng: (
        {"X": [rng.randn(1, 2, 3, 3).astype("float32")],
         "Y": [rng.randn(1, 2, 3, 3).astype("float32")]}, {}, "Out")),
    ("match_matrix_tensor", lambda rng: (
        {"X": [rng.randn(1, 2, 3).astype("float32")],
         "Y": [rng.randn(1, 2, 4).astype("float32")],
         "W": [rng.randn(3, 2, 4).astype("float32")]}, {}, "Out")),
    ("psroi_pool", lambda rng: (
        {"X": [rng.randn(1, 4, 6, 6).astype("float32")],
         "ROIs": [np.array([[0, 0, 4, 4]], "float32")]},
        {"pooled_height": 2, "pooled_width": 2, "output_channels": 1,
         "spatial_scale": 1.0}, "Out")),
    ("deformable_conv", lambda rng: (
        {"Input": [rng.randn(1, 2, 5, 5).astype("float32")],
         "Offset": [rng.randn(1, 2 * 9, 3, 3).astype("float32") * 0.3],
         "Mask": [rng.rand(1, 9, 3, 3).astype("float32")],
         "Filter": [rng.randn(2, 2, 3, 3).astype("float32")]},
        {"strides": [1, 1], "paddings": [0, 0], "dilations": [1, 1]},
        "Output")),
])
def test_numeric_gradients(rng, op, make):
    """Finite-difference check of the first float input's gradient through
    the registered lowering (the OpTest pattern, reference:
    python/paddle/fluid/tests/unittests/op_test.py check_grad)."""
    ins, attrs, out_name = make(rng)
    key0 = next(iter(ins))

    def f(x0):
        ins2 = {k: [jnp.asarray(v) for v in vs] for k, vs in ins.items()}
        ins2[key0] = [x0] + ins2[key0][1:]
        return jnp.sum(get_op_def(op).lower(ins2, attrs)[out_name][0])

    x0 = jnp.asarray(ins[key0][0])
    g = np.asarray(jax.grad(f)(x0))
    eps = 1e-3
    flat = np.asarray(x0).reshape(-1).copy()
    for idx in rng.choice(flat.size, size=min(6, flat.size), replace=False):
        fp = flat.copy(); fp[idx] += eps
        fm = flat.copy(); fm[idx] -= eps
        num = (f(jnp.asarray(fp.reshape(x0.shape)))
               - f(jnp.asarray(fm.reshape(x0.shape)))) / (2 * eps)
        np.testing.assert_allclose(
            g.reshape(-1)[idx], float(num), rtol=5e-2, atol=5e-3
        )


def test_layer_builders_program_path(rng):
    """The fluid.layers.* surface over the new ops builds and runs."""
    import paddle_tpu as fluid
    from paddle_tpu.core.ir import Program, program_guard

    main, startup = Program(), Program()
    with program_guard(main, startup):
        hyp = fluid.data("hyp", [2, 4], dtype="int64")
        ref = fluid.data("ref", [2, 4], dtype="int64")
        dist, _ = fluid.layers.edit_distance(hyp, ref, normalized=False)

        logits = fluid.data("logits", [4, 100])
        lab = fluid.data("lab", [4, 1], dtype="int64")
        ssce = fluid.layers.sampled_softmax_with_cross_entropy(
            logits, lab, num_samples=10
        )

        x = fluid.data("x", [2, 8, 6, 6])
        rois = fluid.data("rois", [3, 4])
        ps = fluid.layers.psroi_pool(x, rois, output_channels=2,
                                     spatial_scale=1.0, pooled_height=2,
                                     pooled_width=2)
        pr = fluid.layers.prroi_pool(x, rois, 1.0, 2, 2)
        ts = fluid.layers.fsp_matrix(
            fluid.data("fa", [2, 3, 5, 5]), fluid.data("fb", [2, 4, 5, 5])
        )
        h = fluid.layers.hash(fluid.data("ids", [5, 2], dtype="int64"),
                              hash_size=1000, num_hash=2)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    outs = exe.run(main, feed={
        "hyp": rng.randint(0, 5, (2, 4)).astype("int64"),
        "ref": rng.randint(0, 5, (2, 4)).astype("int64"),
        "logits": rng.randn(4, 100).astype("float32"),
        "lab": rng.randint(0, 100, (4, 1)).astype("int64"),
        "x": rng.randn(2, 8, 6, 6).astype("float32"),
        "rois": np.abs(rng.rand(3, 4) * 4).astype("float32"),
        "fa": rng.randn(2, 3, 5, 5).astype("float32"),
        "fb": rng.randn(2, 4, 5, 5).astype("float32"),
        "ids": rng.randint(0, 9, (5, 2)).astype("int64"),
    }, fetch_list=[dist, ssce, ps, pr, ts, h])
    assert outs[0].shape == (2, 1)
    assert outs[1].shape == (4, 1) and np.isfinite(outs[1]).all()
    assert outs[2].shape == (3, 2, 2, 2)
    assert outs[3].shape == (3, 8, 2, 2)
    assert outs[4].shape == (2, 3, 4)
    assert outs[5].shape == (5, 2, 1)


def test_layer_deformable_conv_trains(rng):
    import paddle_tpu as fluid
    from paddle_tpu.core.ir import Program, program_guard

    main, startup = Program(), Program()
    with program_guard(main, startup):
        img = fluid.data("img", [1, 3, 8, 8])
        off = fluid.data("off", [1, 18, 6, 6])
        msk = fluid.data("msk", [1, 9, 6, 6])
        y = fluid.layers.deformable_conv(
            img, off, msk, num_filters=4, filter_size=3
        )
        loss = fluid.layers.mean(fluid.layers.square(y))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = {"img": rng.randn(1, 3, 8, 8).astype("float32"),
            "off": (rng.randn(1, 18, 6, 6) * 0.2).astype("float32"),
            "msk": rng.rand(1, 9, 6, 6).astype("float32")}
    c = [float(np.asarray(exe.run(main, feed=feed, fetch_list=[loss])[0]
                          ).reshape(-1)[0]) for _ in range(8)]
    assert np.isfinite(c).all() and c[-1] < c[0]


def test_lstmp_cell_output_is_cell_state(rng):
    """Code-review r4: Cell must be the cell state c, not o*tanh(c)."""
    B, T, H, P = 2, 3, 4, 2
    xs = rng.randn(B, T, 4 * H).astype("float32")
    wp = rng.randn(P, 4 * H).astype("float32")
    proj = rng.randn(H, P).astype("float32")
    outs = lower("lstmp", {"Input": [xs], "Weight": [wp],
                           "ProjWeight": [proj]})
    sig = lambda v: 1 / (1 + np.exp(-v))
    r = np.zeros((B, P), "float32")
    c = np.zeros((B, H), "float32")
    for t in range(T):
        gates = xs[:, t] + r @ wp
        i, f = sig(gates[:, :H]), sig(gates[:, H:2*H])
        g = np.tanh(gates[:, 2*H:3*H])
        o = sig(gates[:, 3*H:])
        c = f * c + i * g
        r = (o * np.tanh(c)) @ proj
    np.testing.assert_allclose(
        np.asarray(outs["Cell"][0])[:, -1], c, rtol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(outs["Projection"][0])[:, -1], r, rtol=1e-4
    )


def test_multiclass_nms2_index_points_at_kept_boxes(rng):
    """Code-review r4: Index identifies WHICH input boxes survived."""
    boxes = np.array([[[0, 0, 10, 10], [0, 0, 10.5, 10.5],
                       [20, 20, 30, 30]]], "float32")
    # box 1 has the best score but overlaps box 0; box 2 is separate
    scores = np.array([[[0.5, 0.9, 0.7]]], "float32")
    outs = lower("multiclass_nms2", {"BBoxes": [boxes], "Scores": [scores]},
                 {"score_threshold": 0.1, "nms_threshold": 0.5,
                  "keep_top_k": 3, "background_label": -1})
    idx = np.asarray(outs["Index"][0]).reshape(-1)
    n = int(np.asarray(outs["NumDetections"][0])[0])
    assert n == 2
    assert set(idx[:n].tolist()) == {1, 2}, idx
    assert (idx[n:] == -1).all()


def test_fpn_restore_roundtrip(rng):
    """concat(level slates)[restore[i]] == original roi i."""
    rois = np.abs(rng.rand(6, 2)) * 20
    rois = np.concatenate([rois, rois + [[30, 30]] * 6], axis=1
                          ).astype("float32")
    outs = lower("distribute_fpn_proposals", {"FpnRois": [rois]},
                 {"min_level": 2, "max_level": 5, "refer_level": 4,
                  "refer_scale": 24})
    concat = np.concatenate([np.asarray(t) for t in outs["MultiFpnRois"]])
    restore = np.asarray(outs["RestoreIndex"][0]).reshape(-1)
    np.testing.assert_allclose(concat[restore], rois, rtol=1e-6)


def test_collect_fpn_skips_padding_rows(rng):
    """Zero-padded slate rows must not outrank real proposals."""
    lvl1 = np.array([[1, 1, 5, 5], [0, 0, 0, 0]], "float32")
    lvl2 = np.array([[0, 0, 0, 0], [2, 2, 9, 9]], "float32")
    scores = [np.array([0.2, 0.0], "float32"),
              np.array([0.0, 0.1], "float32")]
    outs = lower("collect_fpn_proposals",
                 {"MultiLevelRois": [lvl1, lvl2],
                  "MultiLevelScores": scores},
                 {"post_nms_topN": 3})
    rois = np.asarray(outs["FpnRois"][0])
    n = int(np.asarray(outs["RoisNum"][0])[0])
    assert n == 2, (n, rois)
    got = {tuple(r) for r in rois[:n].tolist()}
    assert got == {(1, 1, 5, 5), (2, 2, 9, 9)}, got


def test_reduce_int_dim_and_gaussian_dtype(rng):
    b = rng.rand(2, 3) > 0.5
    np.testing.assert_array_equal(
        np.asarray(lower("reduce_all", {"X": [b]}, {"dim": 1})["Out"][0]),
        b.all(axis=1),
    )
    out = lower("gaussian_random_batch_size_like",
                {"Input": [np.zeros((3, 2), "float32")],
                 "__rng_key__": [jax.random.PRNGKey(0)]},
                {"shape": [-1, 4], "dtype": "float16"})["Out"][0]
    assert str(out.dtype) == "float16"


def test_nas_controller_handles_below_minus_one_rewards():
    from paddle_tpu.contrib.nas import SAController

    c = SAController(seed=0)
    c.reset([3, 3], [0, 0])
    c.update([0, 0], -7.5)
    c.update([1, 0], -5.0)
    c.update([2, 0], -9.0)
    assert c.best_tokens == [1, 0]
    assert c.max_reward == -5.0


def test_density_prior_box(rng):
    feat = np.zeros((1, 8, 4, 4), "float32")
    img = np.zeros((1, 3, 32, 32), "float32")
    outs = lower("density_prior_box", {"Input": [feat], "Image": [img]},
                 {"densities": [2], "fixed_sizes": [8.0],
                  "fixed_ratios": [1.0], "offset": 0.5})
    boxes = np.asarray(outs["Boxes"][0])
    assert boxes.shape == (4, 4, 4, 4)  # H, W, density^2*ratios, 4
    assert (boxes >= 0).all() and (boxes <= 1).all()
    # box sizes ~ fixed_size/img normalized
    w = boxes[2, 2, 0, 2] - boxes[2, 2, 0, 0]
    assert abs(w - 8.0 / 32.0) < 1e-5


def test_target_assign(rng):
    x = rng.randn(2, 5, 3).astype("float32")
    match = np.array([[0, -1, 4], [2, 2, -1]], "int32")
    outs = lower("target_assign", {"X": [x], "MatchIndices": [match]},
                 {"mismatch_value": 7})
    out = np.asarray(outs["Out"][0])
    wt = np.asarray(outs["OutWeight"][0])
    np.testing.assert_allclose(out[0, 0], x[0, 0])
    np.testing.assert_allclose(out[1, 1], x[1, 2])
    assert (out[0, 1] == 7).all() and wt[0, 1, 0] == 0.0
    assert wt[0, 0, 0] == 1.0


def test_rpn_target_assign(rng):
    anchors = np.array([
        [0, 0, 10, 10], [20, 20, 30, 30], [100, 100, 110, 110],
        [1, 1, 11, 11],
    ], "float32")
    gt = np.array([[0, 0, 10, 10]], "float32")
    outs = lower("rpn_target_assign",
                 {"Anchor": [anchors], "GtBoxes": [gt],
                  "__rng_key__": [jax.random.PRNGKey(0)]},
                 {"rpn_positive_overlap": 0.7,
                  "rpn_negative_overlap": 0.3,
                  "rpn_batch_size_per_im": 4, "rpn_fg_fraction": 0.5})
    labels = np.asarray(outs["TargetLabel"][0]).reshape(-1)
    assert labels[0] == 1          # exact-overlap anchor is fg
    assert labels[1] in (0, -1) and labels[2] in (0, -1)
    tgt = np.asarray(outs["TargetBBox"][0])
    np.testing.assert_allclose(tgt[0], 0.0, atol=1e-6)  # perfect match


def test_rpn_target_assign_unreachable_gt_and_crowd(rng):
    """Code-review r4: a zero-IoU gt column (padding) must not promote
    every anchor; crowd gts are excluded from matching."""
    anchors = np.array([
        [0, 0, 10, 10], [20, 20, 22, 22], [100, 100, 110, 110],
    ], "float32")
    gt = np.array([[0, 0, 10, 10], [500, 500, 510, 510]], "float32")
    outs = lower("rpn_target_assign",
                 {"Anchor": [anchors], "GtBoxes": [gt],
                  "__rng_key__": [jax.random.PRNGKey(0)]},
                 {"rpn_positive_overlap": 0.7,
                  "rpn_negative_overlap": 0.3})
    labels = np.asarray(outs["TargetLabel"][0]).reshape(-1)
    assert labels[0] == 1
    assert labels[1] != 1 and labels[2] != 1, labels
    # crowd exclusion: marking gt 0 as crowd leaves no fg
    outs2 = lower("rpn_target_assign",
                  {"Anchor": [anchors], "GtBoxes": [gt[:1]],
                   "IsCrowd": [np.array([1], "int32")],
                   "__rng_key__": [jax.random.PRNGKey(0)]},
                  {"rpn_positive_overlap": 0.7,
                   "rpn_negative_overlap": 0.3})
    labels2 = np.asarray(outs2["TargetLabel"][0]).reshape(-1)
    assert (labels2 != 1).all(), labels2


def test_filter_by_instag(rng):
    x = rng.randn(4, 3).astype("float32")
    tags = np.array([[1, -1], [2, 3], [7, -1], [3, 9]], "int64")
    filt = np.array([3], "int64")
    outs = lower("filter_by_instag",
                 {"Ins": [x], "Ins_tag": [tags], "Filter_tag": [filt]})
    out = np.asarray(outs["Out"][0])
    lw = np.asarray(outs["LossWeight"][0]).reshape(-1)
    np.testing.assert_allclose(out[1], x[1])
    np.testing.assert_allclose(out[3], x[3])
    np.testing.assert_allclose(out[0], 0.0)
    np.testing.assert_array_equal(lw, [0, 1, 0, 1])


def test_split_merge_ids_roundtrip(rng):
    V, D, n = 20, 4, 2
    table = rng.randn(V, D).astype("float32")
    ids = np.array([3, 8, 5, 14], "int64")
    sp = lower("split_ids", {"Ids": [ids]}, {"nshards": n})["Out"]
    rows_list, x_list = [], []
    for s in range(n):
        shard_ids = np.asarray(sp[s]).reshape(-1)
        rows = shard_ids[shard_ids >= 0]
        rows_list.append(rows)
        x_list.append(table[rows])
    outs = lower("merge_ids",
                 {"Ids": [ids], "Rows": rows_list, "X": x_list})
    np.testing.assert_allclose(np.asarray(outs["Out"][0]), table[ids],
                               rtol=1e-6)


def test_filter_by_instag_fill_and_empty_semantics(rng):
    """Code-review r4: dropped rows are ZERO; the fill value + zero loss
    weights apply only when nothing matches."""
    x = rng.randn(3, 2).astype("float32")
    tags = np.array([[1], [3], [2]], "int64")
    outs = lower("filter_by_instag",
                 {"Ins": [x], "Ins_tag": [tags],
                  "Filter_tag": [np.array([3], "int64")]},
                 {"out_val_if_empty": 7})
    out = np.asarray(outs["Out"][0])
    np.testing.assert_allclose(out[0], 0.0)   # dropped -> 0, NOT 7
    np.testing.assert_allclose(out[1], x[1])
    # nothing matches: fill value everywhere, weights all zero
    outs2 = lower("filter_by_instag",
                  {"Ins": [x], "Ins_tag": [tags],
                   "Filter_tag": [np.array([99], "int64")]},
                  {"out_val_if_empty": 7})
    np.testing.assert_allclose(np.asarray(outs2["Out"][0]), 7.0)
    np.testing.assert_allclose(np.asarray(outs2["LossWeight"][0]), 0.0)


def test_merge_ids_empty_shard_and_split_requires_nshards(rng):
    import pytest as _pytest

    from paddle_tpu.utils.enforce import EnforceError

    table = rng.randn(10, 3).astype("float32")
    ids = np.array([2, 4, 6], "int64")  # all even -> odd shard empty
    outs = lower("merge_ids",
                 {"Ids": [ids],
                  "Rows": [ids, np.zeros((0,), "int64")],
                  "X": [table[ids], np.zeros((0, 3), "float32")]})
    np.testing.assert_allclose(np.asarray(outs["Out"][0]), table[ids],
                               rtol=1e-6)
    with _pytest.raises(EnforceError, match="nshards"):
        lower("split_ids", {"Ids": [ids]}, {})


def test_filter_by_instag_padding_sentinel(rng):
    """-1 padded filter slots must not match -1 padded tag slots."""
    x = rng.randn(2, 2).astype("float32")
    tags = np.array([[5, -1], [3, -1]], "int64")
    outs = lower("filter_by_instag",
                 {"Ins": [x], "Ins_tag": [tags],
                  "Filter_tag": [np.array([3, -1], "int64")]})
    lw = np.asarray(outs["LossWeight"][0]).reshape(-1)
    np.testing.assert_array_equal(lw, [0, 1])


def test_roi_perspective_transform_axis_aligned(rng):
    """An axis-aligned rectangular quad reduces to plain cropping."""
    x = np.arange(100, dtype="float32").reshape(1, 1, 10, 10)
    # rectangle corners clockwise from top-left: (1,1),(4,1),(4,4),(1,4)
    rois = np.array([[1, 1, 4, 1, 4, 4, 1, 4]], "float32")
    outs = lower("roi_perspective_transform", {"X": [x], "ROIs": [rois]},
                 {"transformed_height": 4, "transformed_width": 4,
                  "spatial_scale": 1.0})
    out = np.asarray(outs["Out"][0])
    assert out.shape == (1, 1, 4, 4)
    np.testing.assert_allclose(out[0, 0], x[0, 0, 1:5, 1:5], rtol=1e-4)


def test_sequence_topk_avg_pooling(rng):
    x = rng.randn(2, 3, 4, 6).astype("float32")
    outs = lower("sequence_topk_avg_pooling", {"X": [x]},
                 {"topks": [1, 3]})
    out = np.asarray(outs["Out"][0])
    assert out.shape == (2, 4, 6)  # [B, N, C*K]
    srt = -np.sort(-x, axis=-1)
    expect1 = srt[..., 0]                      # top-1 avg
    expect3 = srt[..., :3].mean(-1)
    got = out.reshape(2, 4, 3, 2)
    np.testing.assert_allclose(got[..., 0], expect1.transpose(0, 2, 1),
                               rtol=1e-5)
    np.testing.assert_allclose(got[..., 1], expect3.transpose(0, 2, 1),
                               rtol=1e-5)


def test_sequence_topk_avg_divides_by_full_k(rng):
    x = rng.randn(1, 1, 2, 2).astype("float32")
    outs = lower("sequence_topk_avg_pooling", {"X": [x]}, {"topks": [3]})
    out = np.asarray(outs["Out"][0])
    expect = (-np.sort(-x, axis=-1)).sum(-1) / 3.0  # sum of 2 / k=3
    np.testing.assert_allclose(out.reshape(1, 2), expect.reshape(1, 2),
                               rtol=1e-5)


def test_final_parity_tranche(rng):
    # unsqueeze v1
    x = rng.randn(3, 4).astype("float32")
    assert lower("unsqueeze", {"X": [x]}, {"axes": [1]})["Out"][0].shape \
        == (3, 1, 4)
    # uniform_random_batch_size_like
    out = lower("uniform_random_batch_size_like",
                {"Input": [np.zeros((5, 2), "float32")],
                 "__rng_key__": [jax.random.PRNGKey(0)]},
                {"shape": [-1, 3], "min": 0.0, "max": 1.0})["Out"][0]
    assert out.shape == (5, 3) and (np.asarray(out) >= 0).all()
    # unique / unique_with_counts
    ids = np.array([5, 3, 5, 7, 3, 3], "int64")
    u = lower("unique_with_counts", {"X": [ids]})
    uniq = np.asarray(u["Out"][0])
    idx = np.asarray(u["Index"][0])
    cnt = np.asarray(u["Count"][0])
    np.testing.assert_array_equal(uniq[idx], ids)  # inverse mapping
    assert cnt[np.where(uniq == 3)[0][0]] == 3
    # lookup_table_dequant: out = q*(max-min)/256 + min (reference)
    w = np.zeros((2, 4), "float32")
    w[0] = [1.0, 2.0, 0, 128]      # min 1, max 2
    got = np.asarray(lower("lookup_table_dequant",
                           {"W": [w], "Ids": [np.array([0], "int64")]}
                           )["Out"][0])
    np.testing.assert_allclose(got, [[1.0, 1.0 + 128.0 / 256.0]], rtol=1e-6)
    # unsqueeze applies axes in declaration order (reference semantics)
    x2 = rng.randn(3, 4).astype("float32")
    assert lower("unsqueeze", {"X": [x2]}, {"axes": [1, 0]})["Out"][0].shape \
        == (1, 3, 1, 4)
    # dgc_clip_by_norm: pre-rampup passthrough, post-rampup clipped
    g = np.full((4,), 3.0, "float32")
    pre = lower("dgc_clip_by_norm",
                {"X": [g], "current_step": [np.zeros(1, "float32")]},
                {"rampup_begin_step": 10.0, "max_norm": 1.0})["Out"][0]
    np.testing.assert_allclose(np.asarray(pre), g)
    post = lower("dgc_clip_by_norm",
                 {"X": [g], "current_step": [np.full(1, 20.0, "float32")]},
                 {"rampup_begin_step": 10.0, "max_norm": 1.0})["Out"][0]
    np.testing.assert_allclose(np.linalg.norm(np.asarray(post)), 1.0,
                               rtol=1e-5)


def test_yolov3_loss(rng):
    N, S, K, H = 2, 3, 4, 8
    anchors = [10, 13, 16, 30, 33, 23, 30, 61, 62, 45, 59, 119]
    mask = [0, 1, 2]
    C = S * (5 + K)
    x = (rng.randn(N, C, H, H) * 0.1).astype("float32")
    gtbox = np.zeros((N, 5, 4), "float32")
    gtbox[0, 0] = [0.5, 0.5, 0.06, 0.07]   # matches small anchors
    gtbox[1, 0] = [0.25, 0.75, 0.1, 0.12]
    gtlabel = np.zeros((N, 5), "int64")
    gtlabel[0, 0] = 2
    gtlabel[1, 0] = 1
    outs = lower("yolov3_loss",
                 {"X": [x], "GTBox": [gtbox], "GTLabel": [gtlabel]},
                 {"anchors": anchors, "anchor_mask": mask, "class_num": K,
                  "ignore_thresh": 0.7, "downsample_ratio": 32})
    loss = np.asarray(outs["Loss"][0])
    assert loss.shape == (N,) and np.isfinite(loss).all() and (loss > 0).all()
    match = np.asarray(outs["GTMatchMask"][0])
    assert match[0, 0] >= 0 and match[1, 0] >= 0  # matched slot index
    assert (match[:, 1:] == -1).all()  # padding boxes unassigned
    om = np.asarray(outs["ObjectnessMask"][0])
    assert ((om == 1.0) | (om == 0.0) | (om == -1.0)).all()
    assert (om == 1.0).sum() == 2  # one positive cell per image

    # gradient flows to predictions
    import jax.numpy as jnp

    from paddle_tpu.core.registry import get_op_def

    def f(xv):
        return get_op_def("yolov3_loss").lower(
            {"X": [xv], "GTBox": [jnp.asarray(gtbox)],
             "GTLabel": [jnp.asarray(gtlabel)]},
            {"anchors": anchors, "anchor_mask": mask, "class_num": K,
             "ignore_thresh": 0.7, "downsample_ratio": 32},
        )["Loss"][0].sum()

    g = np.asarray(jax.grad(f)(jnp.asarray(x)))
    assert np.isfinite(g).all() and np.abs(g).sum() > 0


def test_multihead_matmul_and_bert_input_fusion(rng):
    B, S, H, D = 2, 5, 2, 4
    x = rng.randn(B, S, 3 * H * D).astype("float32")
    out = lower("multihead_matmul", {"Input": [x]},
                {"head_number": H, "alpha": 1.0 / np.sqrt(D)})["Out"][0]
    assert out.shape == (B, S, H * D)
    # parity vs manual attention
    qkv = x.reshape(B, S, 3, H, D)
    q, k, v = (np.transpose(qkv[:, :, i], (0, 2, 1, 3)) for i in range(3))
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bhkd->bhqd", p, v).transpose(0, 2, 1, 3
                                                      ).reshape(B, S, H * D)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)

    ids1 = rng.randint(0, 10, (B, S)).astype("int64")
    ids2 = rng.randint(0, 4, (B, S)).astype("int64")
    w1 = rng.randn(10, 6).astype("float32")
    w2 = rng.randn(4, 6).astype("float32")
    sc = rng.rand(6).astype("float32")
    bi = rng.randn(6).astype("float32")
    out2 = lower("fused_embedding_eltwise_layernorm",
                 {"Ids": [ids1, ids2], "Embs": [w1, w2],
                  "Scale": [sc], "Bias": [bi]})["Out"][0]
    tot = w1[ids1] + w2[ids2]
    mu = tot.mean(-1, keepdims=True)
    ref2 = (tot - mu) / np.sqrt(tot.var(-1, keepdims=True) + 1e-5) * sc + bi
    np.testing.assert_allclose(np.asarray(out2), ref2, rtol=1e-4, atol=1e-5)


def test_stage2_and_retinanet_targets(rng):
    rois = np.array([[0, 0, 10, 10], [0, 0, 9, 9], [50, 50, 60, 60],
                     [100, 100, 110, 110]], "float32")
    gt = np.array([[0, 0, 10, 10]], "float32")
    outs = lower("generate_proposal_labels",
                 {"RpnRois": [rois], "GtClasses": [np.array([3], "int32")],
                  "GtBoxes": [gt],
                  "__rng_key__": [jax.random.PRNGKey(0)]},
                 {"batch_size_per_im": 8, "fg_fraction": 0.5,
                  "fg_thresh": 0.5, "bg_thresh_hi": 0.5,
                  "bg_thresh_lo": 0.0})
    lab = np.asarray(outs["LabelsInt32"][0]).reshape(-1)
    # rois gained the appended gt row (index 4)
    assert lab.shape[0] == 5
    assert lab[0] == 3 and lab[1] == 3 and lab[4] == 3
    assert np.isin(lab[2:4], [0, -1]).all(), lab
    tgt = np.asarray(outs["BboxTargets"][0])
    np.testing.assert_allclose(tgt[0], 0.0, atol=1e-6)  # exact match
    # class_nums expansion: targets land in the matched class slot
    outs_c = lower("generate_proposal_labels",
                   {"RpnRois": [rois[:2]],
                    "GtClasses": [np.array([1], "int32")],
                    "GtBoxes": [gt],
                    "__rng_key__": [jax.random.PRNGKey(0)]},
                   {"batch_size_per_im": 8, "fg_fraction": 1.0,
                    "fg_thresh": 0.5, "bg_thresh_hi": 0.5,
                    "bg_thresh_lo": 0.0, "class_nums": 3})
    te = np.asarray(outs_c["BboxTargets"][0])
    wi = np.asarray(outs_c["BboxInsideWeights"][0])
    assert te.shape[1] == 12 and wi.shape[1] == 12
    assert (wi[0, 4:8] == 1.0).all()        # class-1 slot active
    assert (wi[0, :4] == 0.0).all() and (wi[0, 8:] == 0.0).all()

    routs = lower("retinanet_target_assign",
                  {"Anchor": [rois], "GtBoxes": [gt],
                   "GtLabels": [np.array([5], "int32")]},
                  {"positive_overlap": 0.5, "negative_overlap": 0.4})
    rlab = np.asarray(routs["TargetLabel"][0]).reshape(-1)
    assert rlab[0] == 5 and rlab[1] == 5
    assert rlab[3] == 0
    assert int(np.asarray(routs["ForegroundNumber"][0])[0]) == 2


def test_fused_embedding_fc_lstm_and_seqexpand_fc(rng):
    V, B, S, D = 12, 2, 4, 3
    emb = rng.randn(V, 4 * D).astype("float32")
    ids = rng.randint(0, V, (B, S)).astype("int64")
    wh = rng.randn(D, 4 * D).astype("float32")
    outs = lower("fused_embedding_fc_lstm",
                 {"Ids": [ids], "Embeddings": [emb], "WeightH": [wh]})
    assert np.asarray(outs["Hidden"][0]).shape == (B, S, D)

    seq = rng.randn(B, S, 3).astype("float32")
    vec = rng.randn(B, 2).astype("float32")
    w = rng.randn(5, 4).astype("float32")
    out = lower("fusion_seqexpand_concat_fc",
                {"X": [seq, vec], "FCWeight": [w]},
                {"fc_activation": "relu"})["Out"][0]
    cat = np.concatenate(
        [seq, np.broadcast_to(vec[:, None], (B, S, 2))], axis=-1)
    np.testing.assert_allclose(
        np.asarray(out), np.maximum(cat @ w, 0), rtol=1e-4, atol=1e-5)


def test_retinanet_best_anchor_promotion(rng):
    """A gt below positive_overlap still claims its best anchor."""
    anchors = np.array([[0, 0, 20, 20], [100, 100, 120, 120]], "float32")
    gt = np.array([[0, 0, 10, 8]], "float32")  # IoU with anchor0 ~ 0.2
    outs = lower("retinanet_target_assign",
                 {"Anchor": [anchors], "GtBoxes": [gt],
                  "GtLabels": [np.array([4], "int32")]},
                 {"positive_overlap": 0.5, "negative_overlap": 0.4})
    lab = np.asarray(outs["TargetLabel"][0]).reshape(-1)
    assert lab[0] == 4, lab  # promoted despite IoU < pos_thr
    assert lab[1] == 0


def test_var_conv_2d(rng):
    B, C, H, W = 2, 2, 6, 8
    x = rng.randn(B, C, H, W).astype("float32")
    OC, kh, kw = 3, 3, 3
    w = rng.randn(OC, C * kh * kw).astype("float32")
    rows = np.array([6, 3], "int64")
    cols = np.array([8, 4], "int64")
    out = np.asarray(lower(
        "var_conv_2d",
        {"X": [x], "W": [w], "ROW": [rows], "COLUMN": [cols]},
        {"KernelH": kh, "KernelW": kw, "StrideH": 1, "StrideW": 1,
         "InputChannel": C, "OutputChannel": OC},
    )["Out"][0])
    assert out.shape == (B, OC, H, W)
    # sample 1's cells beyond (3, 4) are zeroed
    assert np.abs(out[1, :, 3:, :]).sum() == 0
    assert np.abs(out[1, :, :, 4:]).sum() == 0
    assert np.abs(out[1, :, :3, :4]).sum() > 0
    assert np.abs(out[0]).sum() > 0
    # input junk beyond the extent must not leak into valid border cells:
    # result is identical when the padded region is overwritten
    x2 = x.copy()
    x2[1, :, 3:, :] = 99.0
    x2[1, :, :, 4:] = -77.0
    out2 = np.asarray(lower(
        "var_conv_2d",
        {"X": [x2], "W": [w], "ROW": [rows], "COLUMN": [cols]},
        {"KernelH": kh, "KernelW": kw, "StrideH": 1, "StrideW": 1},
    )["Out"][0])
    np.testing.assert_allclose(out2, out, rtol=1e-6)
    # stride-2 path: ceil-div extents and mask
    outs2 = np.asarray(lower(
        "var_conv_2d",
        {"X": [x], "W": [w], "ROW": [rows], "COLUMN": [cols]},
        {"KernelH": kh, "KernelW": kw, "StrideH": 2, "StrideW": 2},
    )["Out"][0])
    assert outs2.shape == (B, OC, 3, 4)     # ceil(6/2), ceil(8/2)
    # sample 1 extent (3,4) -> valid (2,2)
    assert np.abs(outs2[1, :, 2:, :]).sum() == 0
    assert np.abs(outs2[1, :, :, 2:]).sum() == 0
    assert np.abs(outs2[1, :, :2, :2]).sum() > 0


def test_distributed_lookup_table_alias(rng):
    w = rng.randn(10, 4).astype("float32")
    ids = rng.randint(0, 10, (3, 1)).astype("int64")
    outs = lower("distributed_lookup_table", {"W": [w], "Ids": [ids]})
    np.testing.assert_allclose(
        np.asarray(outs["Outputs"][0]), w[ids[:, 0]], rtol=1e-6
    )


def test_unique_layers(rng):
    """layers.unique / unique_with_counts reach their ops end to end."""
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[6], dtype="int64")
        out, index = fluid.layers.unique(x)
        out2, idx2, count = fluid.layers.unique_with_counts(x)
    exe = fluid.Executor(fluid.CPUPlace())
    arr = np.array([3, 1, 3, 2, 1, 3], dtype="int64")
    with fluid.scope_guard(fluid.Scope()):
        ov, iv, cv = exe.run(
            main, feed={"x": arr},
            fetch_list=[out.name, idx2.name, count.name],
        )
    # reconstruct: every position maps back to its value
    np.testing.assert_array_equal(np.asarray(ov)[iv], arr)
    # counts for the 3 real uniques (front-compacted, sorted: 1, 2, 3)
    assert cv[:3].tolist() == [2, 1, 3]


# ---------------------------------------------------------------------------
# batched_gather: rows move, forward and backward (PR 26)
# ---------------------------------------------------------------------------


def _batched_gather_case(rng, trailing, x_dtype, idx_dtype):
    B, S, P = 3, 16, 24
    x = jnp.asarray(rng.randn(B, S, *trailing), x_dtype)
    idx = rng.randint(0, S, (B, P))
    idx[0, :] = 5             # one row names a single position P times
    idx[1, 4:20] = 0          # padding slots that repeat position 0
    # a cotangent of one sign, so that a sum over duplicates grows and a
    # low-precision accumulator has to round at every step
    g = jnp.asarray(rng.uniform(1.0, 2.0, (B, P) + trailing), x_dtype)
    return x, idx.astype(idx_dtype), g


def _batched_gather_grad(x, idx, g):
    """The synthesized grad op's outputs, as the executor would call it."""
    from paddle_tpu.core.backward import resolve_op_def

    return resolve_op_def("batched_gather_grad").lower(
        {"X": [jnp.asarray(x)], "Index": [jnp.asarray(idx)],
         "Out@GRAD": [g]},
        {"__fwd_inputs__": ["X", "Index"], "__fwd_outputs__": ["Out"]},
    )


@pytest.mark.parametrize("idx_dtype", ["int64", "int32"])
@pytest.mark.parametrize("x_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("trailing", [(), (8,), (3, 4)],
                         ids=["rank2", "rank3", "rank4"])
def test_batched_gather_moves_rows(rng, trailing, x_dtype, idx_dtype):
    """Forward equals np.take_along_axis bit for bit; the synthesized
    batched_gather_grad equals the dense one-hot contraction (float64),
    duplicates included: to float32 tolerance for float32 X, to one bf16
    ulp of the exact sum for bfloat16 X (so duplicates are NOT summed in
    bfloat16); Index gets no gradient."""
    x, idx, g = _batched_gather_case(rng, trailing, x_dtype, idx_dtype)
    out = lower("batched_gather", {"X": [x], "Index": [idx]})["Out"][0]
    assert out.dtype == x.dtype and out.shape == idx.shape + trailing
    idx_e = idx.reshape(idx.shape + (1,) * len(trailing))
    np.testing.assert_array_equal(
        np.asarray(out.astype(jnp.float32)),
        np.take_along_axis(np.asarray(x.astype(jnp.float32)), idx_e, axis=1),
    )

    grads = _batched_gather_grad(x, idx, g)
    assert set(grads) == {"X@GRAD"}
    (dx,) = grads["X@GRAD"]
    assert dx.dtype == x.dtype and dx.shape == x.shape
    onehot = np.eye(x.shape[1], dtype="float64")[idx]          # [B, P, S]
    want = np.einsum("bps,bp...->bs...", onehot,
                     np.asarray(g.astype(jnp.float32), "float64"))
    got = np.asarray(dx.astype(jnp.float32), "float64")
    if x_dtype == "float32":
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=0)
    else:
        # one bf16 ulp of v > 0 is 2**floor(log2 v) * eps; where nothing
        # was gathered both sides are exactly 0
        ulp = (2.0 ** np.floor(np.log2(np.maximum(want, 1e-30)))
               * float(jnp.finfo(jnp.bfloat16).eps))
        assert np.all(np.abs(got - want) <= ulp), (
            np.abs(got - want).max(), ulp.max())


@pytest.mark.parametrize("x_dtype", ["int32", "int64"])
def test_batched_gather_integer_x(rng, x_dtype):
    """An integer X is gathered exactly and has no grad op outputs."""
    x = rng.randint(-9, 9, (2, 7, 5)).astype(x_dtype)
    idx = rng.randint(0, 7, (2, 4)).astype("int64")
    out = lower("batched_gather", {"X": [x], "Index": [idx]})["Out"][0]
    np.testing.assert_array_equal(
        np.asarray(out), np.take_along_axis(x, idx[:, :, None], axis=1))
    assert out.dtype == jnp.asarray(x).dtype
    assert _batched_gather_grad(x, idx, out) == {}
