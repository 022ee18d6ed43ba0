"""The ``keye_vl_2_0_30b_a3b`` configuration and its cell: the file against
the catalog's row, the bytes the cut was reckoned by, the count functions by
hand, the metric files through their readers, the cell rehearsed on the CPU,
and the cell's own comparison on sound answers and on the controls a CPU can
plant.

Like its neighbours, this module loads no TPU library while it is imported.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, readers  # noqa: E402
from benchmark.counts import keye_vl  # noqa: E402

BENCH = manifest.load_manifest()
NAME = "keye_vl_2_0_30b_a3b"
TRAFFIC = "long_doc_answers_32k"
CELL = f"{NAME}.{TRAFFIC}"
with open(os.path.join(ROOT, "tests", "benchmark_grid",
                       "catalog_row_keye_vl_2_0.json")) as _f:
    ROW = json.load(_f)
CUT = {"num_hidden_layers": 12, "num_experts": 16, "num_local_experts": 16,
       "vocab_size": 18992}


# -- the configuration's file ---------------------------------------------------

def test_the_file_passes_the_drivers_rule_against_the_catalogs_row():
    (entry,) = [c for c in BENCH["configs"] if c["name"] == NAME]
    cfg = manifest.load_config(BENCH, NAME)
    assert ROW["name"] == "Keye-VL-2.0-30B-A3B"
    assert entry["source"] == cfg["source"] == ROW["source_url"]
    assert manifest.check_against_source(cfg, entry["reduced"],
                                         ROW["config"]) is None
    assert cfg["source_values"] == ROW["config"]
    assert sorted(cfg["source_keys"]) == sorted(ROW["config"])
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == sorted(CUT)
    for key, theirs in ROW["config"].items():
        assert cfg[key] == CUT.get(key, theirs), key
    # sa_config and rope_scaling whole, every width as published
    assert cfg["sa_config"] == ROW["config"]["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    assert cfg["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert cfg["max_window_layers"] == 48


@pytest.mark.parametrize("change,complaint", [
    ({"head_dim": 64}, "head_dim"),
    ({"sa_config": {"indexer_head_dim": 32, "indexer_num_heads": 16,
                    "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                    "q_chunk_size": 512, "topk": 2048}},
     "sa_config.indexer_head_dim"),
    ({"sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                    "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                    "q_chunk_size": 512, "topk": 1024}}, "sa_config.topk"),
    ({"moe_intermediate_size": 512}, "moe_intermediate_size"),
    ({"num_experts_per_tok": 4}, "num_experts_per_tok"),
    ({"num_experts": 8, "reduced": ["num_hidden_layers", "num_local_experts",
                                    "vocab_size"]}, "reduced does not list")])
def test_the_rule_refuses_a_width_or_an_unlisted_cut(change, complaint):
    cfg = dict(manifest.load_config(BENCH, NAME))
    reduced = change.pop("reduced", cfg["reduced"])
    cfg.update(change)
    said = manifest.check_against_source(dict(cfg, reduced=reduced), reduced,
                                         ROW["config"])
    assert said is not None and complaint in said


def test_the_model_the_issue_sized():
    cfg = manifest.load_config(BENCH, NAME)
    sizes = manifest.model_sizes(cfg, False)
    assert sizes["router_experts"] == 128 and sizes["block_size"] == 16
    assert sizes["slots"] == 16 and sizes["max_len"] == 32768
    # ONE pool, smaller than slots x length: admission reserves
    assert sizes["num_blocks"] == 20480 < 16 * 32768 // 16
    assert sizes["chunk_tokens"] % sizes["block_size"] == 0
    assert cfg["settings"]["dtype"] == "bfloat16"
    assert cfg["settings"]["expert_rank"] == 0
    assert cfg["settings"]["engine"] == {"prefix_cache_size": 0,
                                         "host_tier_mb": 0}
    for said in ("qk_norm", "rotation", "indexer_input", "indexer_form",
                 "no_hadamard_no_float8", "chunk_sizes", "selection",
                 "index_arena", "moe", "draw"):
        assert said in cfg["assumed"]
    assert "LOWER s" in cfg["assumed"]["selection"]
    assert "0.50 GB" in cfg["assumed"]["index_arena"]
    for said in ("32 v5e chips", "4 pipeline groups of 12 layers",
                 "16 of 128 experts", "nothing stands in", "all-to-all",
                 "vision tower"):
        assert said in cfg["deployment"]
    small = manifest.published(cfg, True)
    assert small["sa_config"]["topk"] == 8


def test_the_bytes_the_issue_reckoned():
    """An expert 4.72 M parameters (9.44 MB), attention 18.9 M (37.7 MB),
    the indexer 2.26 M (4.5 MB), the router 1.0 MB in float32: a layer
    194.3 MB, twelve 2.33 GB, embedding and head 0.16 GB: 2.49 GB; K and V
    8.05 GB; the indexer's arena 0.50 GB at 64 lanes, 1.01 GB at the 128 it
    is declared with; published 30.6 B parameters and 104,448 B of cache a
    token."""
    cfg = manifest.load_config(BENCH, NAME)
    c, pub = manifest.published(cfg, False), ROW["config"]
    sizes = manifest.model_sizes(cfg, False)
    h, d, sa = c["hidden_size"], c["head_dim"], c["sa_config"]
    q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    expert = 3 * h * c["moe_intermediate_size"]
    assert expert == 4_718_592 and round(2 * expert / 1e6, 2) == 9.44
    attention = h * (q + 2 * kv) + q * h
    assert round(2 * attention / 1e6, 1) == 37.7
    indexer = h * (sa["indexer_num_heads"] * sa["indexer_head_dim"]
                   + sa["indexer_head_dim"] + sa["indexer_num_heads"])
    assert indexer == 2_097_152 + 131_072 + 32_768
    assert round(2 * indexer / 1e6, 1) == 4.5
    router = 4 * h * sizes["router_experts"]
    assert round(router / 1e6, 1) == 1.0
    layer = 2 * (c["num_experts"] * expert + attention + indexer) + router
    assert round(layer / 1e6, 1) == 194.3
    vocab = 2 * 2 * c["vocab_size"] * h
    assert round(vocab / 1e9, 2) == 0.16
    assert round((c["num_hidden_layers"] * layer + vocab) / 1e9, 2) == 2.49
    rows = sizes["num_blocks"] * sizes["block_size"]
    assert rows == 327_680
    assert round(c["num_hidden_layers"] * rows * 2 * kv * 2 / 1e9, 2) == 8.05
    packed = c["num_hidden_layers"] * rows * sa["indexer_head_dim"] * 2
    assert round(packed / 1e9, 2) == 0.50 and round(2 * packed / 1e9, 2) == 1.01
    # published: 48 layers of 128 experts, the whole vocabulary
    outside = attention + indexer + h * 128
    total = pub["num_hidden_layers"] * (128 * expert + outside) \
        + 2 * pub["vocab_size"] * h
    assert round(total / 1e9, 1) == 30.6
    assert round(outside / 1e6, 1) == 21.4
    token = pub["num_hidden_layers"] * (2 * kv * 2 + sa["indexer_head_dim"] * 2)
    assert token == 104_448
    assert round(pub["max_position_embeddings"] * token / 1e9, 1) == 27.4


# -- the count functions, by hand -----------------------------------------------

def test_index_score_calls_by_hand():
    # 1,000 step rows and 5,000 chunk pairs; 2 layers of launches that read
    # 300 + 100 rows: 16 heads x (2 x 64 + 3) a pair, 128 bytes a row
    ops, moved = keye_vl.index_score_calls(1_000, 5_000, 300, 100, 2, 16, 64,
                                           2)
    assert ops == 6_000 * 16 * 131
    assert moved == (1_000 + 2 * 400) * 128


def test_index_select_calls_by_hand():
    assert keye_vl.index_select_calls(1_000, 5_000) == (6_000, 30_000)


def test_masked_chunk_calls_by_hand():
    ops, moved = keye_vl.masked_chunk_calls(7_000, 300, 100, 2, 4, 32, 128, 2)
    assert ops == 4 * 7_000 * 32 * 128
    assert moved == 2 * 2 * 400 * 4 * 128 * 2


def test_sparse_step_calls_by_hand():
    ops, moved = keye_vl.sparse_step_calls(2_048, 4, 32, 128, 2)
    assert ops == 4 * 2_048 * 4_096
    assert moved == 2_048 * 2 * 1_024 == 4_194_304   # the issue's 4.19 MB


def test_served_tokens_by_hand():
    sizes = dict(hidden=2048, vocab=18992, layers=12, query_heads=32,
                 kv_heads=4, head_dim=128, index_heads=16, index_width=64,
                 router_experts=128, ffn=768)
    per_token = 12 * (2 * 2048 * (4096 + 1024) + 2 * 4096 * 2048
                      + 2 * 2048 * (1024 + 64 + 16) + 2 * 2048 * 128)
    assert keye_vl.served_tokens(1, 0, 0, 0, 0, 0, 0, 0, **sizes)[0] == \
        per_token + 2 * 2048 * 18992
    assert keye_vl.served_tokens(0, 1, 0, 0, 0, 0, 0, 0, **sizes)[0] == \
        per_token
    assert keye_vl.served_tokens(0, 0, 3, 4, 0, 0, 0, 0, **sizes)[0] == \
        7 * 4 * 4096
    assert keye_vl.served_tokens(0, 0, 0, 0, 3, 4, 0, 0, **sizes)[0] == \
        7 * 16 * 131
    assert keye_vl.served_tokens(0, 0, 0, 0, 0, 0, 3, 4, **sizes) == (
        7 * 6 * 2048 * 768, 0)


def test_a_step_at_16k_by_the_issues_arithmetic():
    """8 slots at 16k of context read, a layer, 8 x 4.19 MB of kept rows
    and 8 x 128 B x 16k of index keys: 50 MB where whole-context attention
    reads 268 MB."""
    _ops, kept = keye_vl.sparse_step_calls(8 * 2048, 4, 32, 128, 2)
    _ops, keys = keye_vl.index_score_calls(8 * 16384, 0, 0, 0, 1, 16, 64, 2)
    assert round((kept + keys) / 1e6) == 50
    assert round(8 * 16384 * 2048 / 1e6) == 268


# -- the metric files through their readers ----------------------------------------

LABEL = '{engine="e"}'
NEW = ["sparse_rows_read_share", "index_select_device_share",
       "index_scores_device_share", "masked_chunk_attention_device_share",
       "index_scores_roofline", "index_select_roofline",
       "masked_chunk_attention_roofline", "paged_sparse_attention_roofline",
       "serve_device_mfu.keye_vl", "moe_touched_share.keye_vl",
       "moe_peak_expert_tokens.keye_vl"]


def _run(moved):
    """A traced stretch [1.0, 4.0] of a second of busy time in which each
    of the kernels' events takes 0.2 s, with the counters of ``moved``
    moving inside it. The masked chunk kernel's instruction names the
    selection's (its mask operand), as the profiler's do."""
    cfg = manifest.load_config(BENCH, NAME)
    traffic = manifest.load_traffic(TRAFFIC)

    def event(name, t0, operands=""):
        return [f'%{name}.3 = custom-call({operands}), custom_call_target='
                f'"tpu_custom_call", metadata={{op_name="jit(call)/{name}"}}',
                t0, 0.2]

    ops = [event("index_scores", 1.0), event("index_select", 1.5),
           event("masked_chunk_attn", 2.0, "s8[1024,32768] %index_select.2"),
           event("paged_attention", 2.5),
           ["%fusion.1 = f32[512,2048]{1,0} fusion(%paged_attention.3)", 3.0,
            0.2]]
    before = {family: {LABEL: 100} for family in moved}
    after = {family: {LABEL: 100 + n} for family, n in moved.items()}
    device = {"ops": ops, "async_ops": [], "modules": []}
    return {"trace": {"devices": {"0": device}},
            "trace_window": (1.0, 4.0), "spans": [("decode::feeds", 2.0, 2.5)],
            "registry": (before, after), "stretch_registry": [before, after],
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            "sizes": manifest.run_sizes(cfg, traffic, 1, False),
            "facts": {"window_s": 51.0}, "config": cfg, "chips": 1}


def _read(name, run):
    spec = manifest.load_metric(name)
    return readers.READERS[spec["reader"]](spec["args"], run)


MOVED = {"serving_sparse_rows_selected_step_total": 2_000_000,
         "serving_attention_rows_in_context_step_total": 16_000_000,
         "serving_index_rows_scanned_step_total": 16_000_000,
         "serving_sparse_rows_selected_chunk_total": 2_000_000_000,
         "serving_index_rows_scanned_chunk_total": 8_000_000_000,
         "serving_chunk_context_rows_total": 900_000,
         "serving_chunk_tokens_total": 100_000}


def test_the_selections_share_of_a_steps_rows():
    assert _read("sparse_rows_read_share", _run(MOVED)) == pytest.approx(12.5)


def test_each_kernels_share_is_its_own_events_alone():
    """A fifth of the second of busy time each: an instruction that NAMES
    another kernel among its operands is not that kernel's."""
    run = _run(MOVED)
    for name in ("index_select_device_share", "index_scores_device_share",
                 "masked_chunk_attention_device_share"):
        assert _read(name, run) == pytest.approx(20.0), name


def test_the_kernels_rooflines_by_the_count_functions():
    run = _run(MOVED)
    ops, moved = keye_vl.index_score_calls(
        16_000_000, 8_000_000_000, 900_000, 100_000, 12, 16, 64, 2)
    assert _read("index_scores_roofline", run) == pytest.approx(
        100 * max(ops / 197e12, moved / 819e9) / 0.2)
    ops, moved = keye_vl.index_select_calls(16_000_000, 8_000_000_000)
    assert _read("index_select_roofline", run) == pytest.approx(
        100 * max(ops / 197e12, moved / 819e9) / 0.2)
    ops, moved = keye_vl.masked_chunk_calls(
        2_000_000_000, 900_000, 100_000, 12, 4, 32, 128, 2)
    assert ops / 197e12 > moved / 819e9
    assert _read("masked_chunk_attention_roofline", run) == pytest.approx(
        100 * ops / 197e12 / 0.2)
    ops, moved = keye_vl.sparse_step_calls(2_000_000, 4, 32, 128, 2)
    assert moved / 819e9 > ops / 197e12
    assert _read("paged_sparse_attention_roofline", run) == pytest.approx(
        100 * moved / 819e9 / 0.2)


def test_the_whole_devices_share_counts_steps_and_chunks():
    moved = dict(MOVED, **{"serving_active_slot_steps_total": 3_000,
                           "serving_moe_held_assignments_total": 6_000,
                           "serving_moe_grouped_pairs_total": 200_000})
    ops, _ = keye_vl.served_tokens(
        3_000, 100_000, 2_000_000, 2_000_000_000, 16_000_000, 8_000_000_000,
        6_000, 200_000, hidden=2048, vocab=18992, layers=12, query_heads=32,
        kv_heads=4, head_dim=128, index_heads=16, index_width=64,
        router_experts=128, ffn=768)
    assert _read("serve_device_mfu.keye_vl", _run(moved)) == pytest.approx(
        100 * ops / 197e12 / 1.0)


def test_the_experts_shares_at_twelve_layers():
    run = _run({"serving_moe_touched_experts_total": 9_600,
                "serving_moe_peak_expert_tokens_total": 2_400,
                "serving_decode_steps_total": 100})
    assert _read("moe_touched_share.keye_vl", run) == pytest.approx(50.0)
    assert _read("moe_peak_expert_tokens.keye_vl", run) == pytest.approx(2.0)


def test_a_program_without_the_counters_reads_nothing_and_does_not_raise():
    """What the parent commit's program gives the new readers: no counter,
    no span and no kernel of these names."""
    run = _run({})
    run["trace"]["devices"]["0"]["ops"] = [
        ["%fusion.1 = f32[512,2048]{1,0} fusion()", 2.5, 0.2]]
    for name in NEW:
        assert _read(name, run) is None, name
    run["stretch_registry"] = None
    run["trace"] = run["spans"] = None
    for name in NEW:
        assert _read(name, run) is None, name


def test_every_new_metric_lists_the_cell_and_is_registered():
    mine = {m["name"]: m
            for m in manifest.metrics_of(BENCH, "per_layer", CELL)}
    assert set(NEW) <= set(mine)
    for name, entry in mine.items():
        spec = manifest.load_metric(name)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == entry[key], (name, key)
        assert "workloads" not in spec
    for name in NEW:
        assert mine[name]["workloads"] == [CELL] or CELL in \
            mine[name]["workloads"]
    # the readings an accepted file gives with the same args: the cell
    # joins that entry's list, no second file
    assert {"decode_step_ms", "moe_experts_roofline.gated",
            "chunk_context_tokens", "prefill_tokens_per_s",
            "moe_grouped_roofline", "moe_grouped_padding_share",
            "moe_grouped_device_share", "admissions_deferred",
            "paged_attention_device_share", "moe_held_share"} <= set(mine)
    # sdar's two scale by ITS 48 layers, the window's by window groups
    assert not {"moe_touched_share.sdar", "moe_peak_expert_tokens.sdar",
                "window_rows_read_share"} & set(mine)
    (latency,) = [m for m in BENCH["end_to_end"]
                  if m["name"] == "serve_token_latency_p50"]
    assert CELL in latency["workloads"]
    assert CELL in [w["name"] for w in BENCH["workloads"]]
    assert NAME in [c["name"] for c in BENCH["configs"]]
    assert len(BENCH["per_layer"]) <= 128
    assert {mine[n]["layer"] for n in NEW} <= {
        m["layer"] for m in BENCH["per_layer"] if m["name"] not in NEW}


@pytest.mark.parametrize("group,name", [("configs", NAME),
                                        ("workloads", CELL)])
def test_what_this_entry_says_in_words_fits_the_drivers_lines(group, name):
    (entry,) = [e for e in BENCH[group] if e["name"] == name]
    words = [entry[key] for key in ("why", "source") if key in entry]
    if group == "workloads":
        words += [m["layer"] for m in BENCH["per_layer"]
                  if name in m.get("workloads", ())]
    for line in words:
        assert 1 <= len(line) <= 200 and line.isprintable(), line


def test_the_traffic_is_the_issues_letter_for_letter():
    t = manifest.load_traffic(TRAFFIC)
    assert (t["kind"], t["arrivals"], t["sharing"]) == (
        "open_loop", "poisson", "none")
    assert t["preroll_s"] == 30 and t["trace_seconds"] == 10
    assert t["prompt_len"] == {"dist": "lognormal", "median": 12288,
                               "sigma": 0.5, "min": 4096, "max": 30720}
    assert t["answer_len"] == {"dist": "lognormal", "median": 512,
                               "sigma": 0.6, "min": 128, "max": 2048}
    cfg = manifest.load_config(BENCH, NAME)
    assert t["max_total_len"] == manifest.model_sizes(
        cfg, False)["max_len"] == 32768
    assert 8 <= t["check_requests"] <= 16 and t["check_tokens"] == 16
    assert t["check_tokens"] <= t["answer_len"]["min"]
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    # EVERY prompt is past topk, and at least half are over 8,192
    from benchmark import workgen
    lengths = workgen.stratified_lengths(t["prompt_len"], 1000)
    assert min(lengths) >= 4096 > cfg["sa_config"]["topk"]
    assert lengths[250] > 8192 and lengths[499] <= 12288 <= lengths[500]


# -- the cell, rehearsed ---------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_and_prints_every_metric_a_cpu_run_can_name(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "6300000077", "--seconds", "1",
         "--trace", str(trace), "--rehearse-cpu"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert all(n["holds"] for n in line["compared"].values())
    if not trace:
        assert set(line["metrics"]) == {"serve_token_latency_p50", "setup_s"}
        return
    entries = manifest.metrics_of(BENCH, "per_layer", CELL)
    want = {m["name"] for m in entries if m["source"] != "device_trace"}
    assert set(line["metrics"]) == want
    assert {"sparse_rows_read_share", "moe_touched_share.keye_vl",
            "moe_peak_expert_tokens.keye_vl", "moe_grouped_padding_share",
            "reserved_blocks_per_admission"} <= want
    assert all(m["value"] is None for m in line["metrics"].values())


def test_the_parent_refuses_an_unknown_cell_before_it_builds_anything():
    """What the parent commit does with this cell's name: ``run.py`` looks
    the cell up before it imports jax or a builder, and exits non-zero."""
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "no_such_config.long_doc_answers_32k", "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0 and "no workload" in p.stderr
    assert not p.stdout.strip()


# -- the cell's own comparison: sound answers, and the controls -------------------

class _Sent:
    def __init__(self, prompt, response):
        self.prompt, self.response = prompt, response


@pytest.fixture(scope="module")
def served():
    """The cell's system at its rehearsal size (topk 8 under prompts of 9
    to 30): a dozen requests served sound."""
    import importlib

    from benchmark import workgen

    cfg = manifest.load_config(BENCH, NAME)
    traffic = manifest.sizes(manifest.load_traffic(TRAFFIC), True)
    builder = importlib.import_module("benchmark.builders." + cfg["builder"])
    system = builder.build(cfg, traffic, 6300000078, True)
    rng = np.random.default_rng(5)
    lengths = workgen.stratified_lengths(traffic["prompt_len"], 12)
    prompts = [workgen.prompt_tokens(rng, n, system.vocab_size)
               for n in lengths]
    system.engine.start()
    try:
        sent = [_Sent(p, system.engine.submit(p, max_new_tokens=10))
                for p in prompts]
        for s in sent:
            s.response.result(timeout=300)
    finally:
        system.engine.shutdown()
    return system, dict(traffic, check_requests=len(prompts),
                        check_tokens=10), sent


def _check(system, sent, traffic, **control):
    from benchmark import serve

    own = type(system).reference_logits
    try:
        type(system).reference_logits = lambda self, t, p: own(
            self, t, p, **control)
        return serve._check_against_reference(system, sent, traffic, 1)
    finally:
        type(system).reference_logits = own


def test_sound_answers_are_the_references(served):
    system, traffic, sent = served
    checked, right, worst = _check(system, sent, traffic)
    assert checked == right == len(sent) and worst <= traffic[
        "check_tolerance"]


@pytest.mark.parametrize("control", [
    {"select": False}, {"topk": 4}, {"relu": False},
    {"rotate_index_keys": False}, {"index_lag": True},
    {"round_to": "float8_e4m3fn"}], ids=lambda c: next(iter(c)))
def test_a_reference_read_otherwise_reads_not_correct(served, control):
    system, traffic, sent = served
    checked, right, worst = _check(system, sent, traffic, **control)
    assert right < traffic["check_min_equal"] * checked
    assert worst > traffic["check_tolerance"]
