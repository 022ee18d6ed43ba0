"""The counters of a model that keeps an indexer's arena
(serving/decode/metrics.py ``sparse_rows_selected_*``,
``index_rows_scanned_*``): they move by the by-hand amounts for a two-slot
example, they are the sums of what the launches were actually fed, and they
stay 0 for a model without an indexer (``sdar_moe``, ``afmoe``)."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddle_tpu.serving import (  # noqa: E402
    GenerationEngine, build_afmoe_model, build_keye_vl_model,
    build_sdar_model)
from paddle_tpu.serving.decode.metrics import DecodeMetrics  # noqa: E402

NAMES = ("sparse_rows_selected_step", "index_rows_scanned_step",
         "sparse_rows_selected_chunk", "index_rows_scanned_chunk")
TINY = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            num_experts=4, router_experts=8, num_experts_per_tok=2)
GEOMETRY = dict(slots=4, max_len=48, block_size=4, chunk_tokens=8)


def test_a_two_slot_step_and_a_chunk_by_hand():
    """Two slots step at lengths 5 and 40 under topk 8 over 3 layers: 5 + 8
    rows kept and 45 scanned a layer. A chunk of positions [6, 14): the
    query at p keeps min(8, p + 1) and scans p + 1."""
    m = DecodeMetrics(engine_label="by-hand")
    for length in (5, 40):
        m.observe_sparse_step(length, 8, 3)
    got = m.snapshot()
    assert got["sparse_rows_selected_step"] == 3 * (5 + 8)
    assert got["index_rows_scanned_step"] == 3 * 45
    assert got["attention_rows_in_context_step"] == 3 * 45
    m.observe_sparse_chunk(6, 14, 8, 3)
    got = m.snapshot()
    assert got["sparse_rows_selected_chunk"] == 3 * (7 + 7 * 8)
    assert got["index_rows_scanned_chunk"] == 3 * sum(range(7, 15))
    # a chunk wholly under topk keeps all it scans; wholly past, topk each
    m = DecodeMetrics(engine_label="by-hand-2")
    m.observe_sparse_chunk(0, 8, 8, 1)
    m.observe_sparse_chunk(16, 24, 8, 1)
    got = m.snapshot()
    assert got["sparse_rows_selected_chunk"] == 36 + 64
    assert got["index_rows_scanned_chunk"] == 36 + sum(range(17, 25))


def _serve(make, lengths, answers=6):
    engine = GenerationEngine(prefix_cache_size=0, host_tier_mb=0)
    entry = engine.register_model(make)
    m, fed = entry.model, {"steps": [], "chunks": []}
    launch = entry._run

    def run(kind, feeds, span=None):
        if kind == "step":
            fed["steps"].append(
                np.array(feeds[m.DEC_STEP])[:, m.STEP_LENGTH].copy())
        elif kind == "chunk":
            fed["chunks"].append(tuple(int(x) for x in feeds[m.CHU_SPAN]))
        return launch(kind, feeds, span)

    entry._run = run
    rng = np.random.default_rng(2)
    engine.start()
    try:
        for r in [engine.submit([int(t) for t in rng.integers(1, 90, n)],
                                max_new_tokens=answers) for n in lengths]:
            r.result(timeout=600)
    finally:
        engine.shutdown()
    return entry.metrics.snapshot(), fed


def test_the_counters_are_the_sums_of_what_the_launches_were_fed():
    topk, layers = 8, 2

    def make():
        model = build_keye_vl_model(
            96, 64, layers, moe_intermediate_size=24,
            sa_config=dict(indexer_head_dim=8, indexer_num_heads=4,
                           indexer_num_kv_heads=1, topk=topk),
            initializer_range=0.3, dtype="float32", name="kvcount", **TINY,
            **GEOMETRY)
        model.startup_program.random_seed = 3
        return model

    got, fed = _serve(make, (5, 21))
    lengths = np.concatenate(fed["steps"])
    lengths = lengths[lengths > 0]
    assert len(fed["chunks"]) == 1 + 3 and len(lengths) >= 2 * 5
    assert got["sparse_rows_selected_step"] == layers * int(
        np.minimum(lengths, topk).sum())
    assert got["index_rows_scanned_step"] == layers * int(lengths.sum())
    at = np.concatenate([np.arange(start, start + real) + 1
                         for start, real in fed["chunks"]])
    assert sorted(at) == sorted(list(range(1, 6)) + list(range(1, 22)))
    assert got["sparse_rows_selected_chunk"] == layers * int(
        np.minimum(at, topk).sum())
    assert got["index_rows_scanned_chunk"] == layers * int(at.sum())
    assert 0 < got["sparse_rows_selected_step"] < got[
        "index_rows_scanned_step"]


def _sdar():
    m = build_sdar_model(96, 64, 2, moe_intermediate_size=24, block_len=4,
                         denoising_steps=4, mask_token_id=95,
                         initializer_range=0.12, dtype="float32",
                         name="sdcount", **TINY, **GEOMETRY)
    m.startup_program.random_seed = 3
    return m


def _afmoe():
    m = build_afmoe_model(
        96, 64, ["sliding_attention", "full_attention"],
        intermediate_size=96, num_dense_layers=1, moe_intermediate_size=32,
        sliding_window=8, route_scale=2.448, initializer_range=0.3,
        dtype="float32", num_blocks=40, window_num_blocks=30, name="afcount",
        **TINY, **GEOMETRY)
    m.startup_program.random_seed = 3
    return m


@pytest.mark.parametrize("make", [_sdar, _afmoe], ids=["sdar", "afmoe"])
def test_a_model_without_an_indexer_moves_none_of_them(make):
    got, fed = _serve(make, (5, 13), answers=8)
    assert fed["steps"] and fed["chunks"]
    for name in NAMES:
        assert got.get(name, 0) == 0, name
