"""The ``trinity_large_preview`` configuration and its cell: the file against
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
from benchmark.counts import afmoe  # noqa: E402
from tools import check_hybrid_logits  # noqa: E402

BENCH = manifest.load_manifest()
NAME = "trinity_large_preview"
TRAFFIC = "mixed_lengths_32k"
CELL = f"{NAME}.{TRAFFIC}"
with open(os.path.join(ROOT, "tests", "benchmark_grid",
                       "catalog_row_trinity.json")) as _f:
    ROW = json.load(_f)
CUT = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 32,
       "vocab_size": 25024,
       "layer_types": ["sliding_attention"] * 4 + ["full_attention"]}


# -- the configuration's file ---------------------------------------------------

def test_the_file_passes_the_drivers_rule_against_the_catalogs_row():
    (entry,) = [c for c in BENCH["configs"] if c["name"] == NAME]
    cfg = manifest.load_config(BENCH, NAME)
    assert ROW["name"] == "Trinity-Large-Preview"
    assert entry["source"] == cfg["source"] == ROW["source_url"]
    assert manifest.check_against_source(cfg, entry["reduced"],
                                         ROW["config"]) is None
    assert cfg["source_values"] == ROW["config"]
    assert sorted(cfg["source_keys"]) == sorted(ROW["config"])
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == sorted(CUT)
    for key, theirs in ROW["config"].items():
        assert cfg[key] == CUT.get(key, theirs), key
    # one period of the published pattern behind the one dense layer
    period = ROW["config"]["global_attn_every_n_layers"]
    assert cfg["layer_types"][1:] == ROW["config"]["layer_types"][:period]
    for width in ("hidden_size", "head_dim", "intermediate_size",
                  "moe_intermediate_size", "num_experts_per_tok",
                  "num_attention_heads", "num_key_value_heads",
                  "sliding_window", "route_scale"):
        assert cfg[width] == ROW["config"][width]


@pytest.mark.parametrize("change,complaint", [
    ({"head_dim": 64}, "head_dim"),
    ({"sliding_window": 1024}, "sliding_window"),
    ({"moe_intermediate_size": 1024}, "moe_intermediate_size"),
    ({"num_experts_per_tok": 2}, "num_experts_per_tok"),
    ({"num_experts": 16, "reduced": ["num_hidden_layers", "layer_types",
                                     "num_dense_layers", "vocab_size"]},
     "reduced does not list")])
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
    assert sizes["router_experts"] == 256 and sizes["block_size"] == 16
    assert sizes["slots"] == 24 and sizes["max_len"] == 33792
    # a block count a GROUP: the full layer's pool is smaller than slots x
    # length (admission reserves), the sliding layers' holds every slot's
    # window and chunk
    assert sizes["num_blocks"] < 24 * 33792 // 16
    assert sizes["window_num_blocks"] == 24 * (
        (4096 + sizes["chunk_tokens"] + 16 - 3) // 16 + 1)
    assert cfg["settings"]["dtype"] == "bfloat16"
    assert cfg["settings"]["expert_rank"] == 0
    assert cfg["settings"]["engine"] == {"prefix_cache_size": 0,
                                         "host_tier_mb": 0}
    for said in ("gate", "full_layers_nope", "window_edge", "mup", "router",
                 "draws", "sizes", "sandwich_norm"):
        assert said in cfg["assumed"]
    assert "1e-20" in cfg["assumed"]["router"]
    assert "NOT in the weight" in cfg["assumed"]["router"]
    for said in ("8 v5e chips share each layer", "32 of 256",
                 "nothing stands in", "all-to-all"):
        assert said in cfg["deployment"]
    small = manifest.published(cfg, True)
    assert small["sliding_window"] == 8
    assert set(small["layer_types"]) == {"sliding_attention",
                                         "full_attention"}


def test_the_bytes_the_issue_reckoned():
    """Attention with its gate 62.9 M parameters, a dense layer 176 M, an
    expert 28.3 M, an expert layer here 998 M = 2.00 GB, this chip 8.64 GB;
    4,096 B of cache a token a layer; a slot of 33,792 positions 223 MB
    where one table for all five layers would hold 692 MB."""
    c = manifest.published(manifest.load_config(BENCH, NAME), False)
    h, d = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    attention = h * (2 * q + 2 * kv) + q * h
    assert round(attention / 1e6, 1) == 62.9
    dense = attention + 3 * h * c["intermediate_size"]
    assert round(dense / 1e6) == 176
    expert = 3 * h * c["moe_intermediate_size"]
    assert round(expert / 1e6, 1) == 28.3
    layer = attention + 32 * expert + expert + 256 * h
    assert round(layer / 1e6) == 998 and round(2 * layer / 1e9, 2) == 2.0
    assert 256 * 2 * expert > 14e9          # no whole expert layer fits
    vocab = 2 * c["vocab_size"] * h
    assert round(2 * vocab / 1e9, 2) == 0.31
    assert round(2 * (dense + 4 * layer + vocab) / 1e9, 2) == 8.64
    row = 2 * kv * 2
    assert row == 4096
    window_slot = ((4096 + 1024 + 16 - 3) // 16 + 1) * 16 * row
    # (the issue's 222 took a sliding layer's 321 blocks as 21 MB)
    assert round((33792 * row + 4 * window_slot) / 1e6) == 223
    assert round(5 * 33792 * row / 1e6) == 692
    sizes = manifest.model_sizes(manifest.load_config(BENCH, NAME), False)
    assert round(sizes["num_blocks"] * 16 * row / 1e9, 2) == 2.01
    assert round(sizes["window_num_blocks"] * 16 * row * 4 / 1e9, 2) == 2.02


# -- the count functions, by hand -----------------------------------------------

def test_windowed_chunk_calls_by_hand():
    # 100 pairs, 30 rows, 2 K/V heads, 6 query heads of 8, 3 layers
    ops, moved = afmoe.windowed_chunk_calls(100, 30, 2, 6, 8, 3, 2)
    assert ops == 3 * 2 * 2 * 100 * 6 * 8 == 57600
    assert moved == 3 * 2 * 30 * 2 * 8 * 2 == 5760
    # a chunk of 1,024 behind 16k of context: W + C - 1 rows at most, and
    # the issue's 1.1e11 operations a layer
    pairs, rows = 1024 * 4096, 4096 + 1024 - 1
    ops, moved = afmoe.windowed_chunk_calls(pairs, rows, 8, 48, 128, 1, 2)
    assert round(ops / 1e11, 1) == 1.0 and moved == 2 * rows * 2048


def test_step_attention_calls_by_hand():
    # the full layer: 5 live blocks of 4 rows; 2 sliding layers: 9 rows
    ops, moved = afmoe.step_attention_calls(9, 5, 4, 2, 6, 8, 2, 1, 2)
    rows = 1 * 20 + 2 * 9
    assert ops == 2 * 2 * rows * 6 * 8 == 7296
    assert moved == 2 * rows * 2 * 8 * 2 == 2432
    # the issue's step: 20 slots at 12k read 20 x (12k + 4 x 4,096) rows of
    # 4 KB, 2.3 GB, where all five layers full would read 5.7 GB
    slots, context = 20, 12288
    _ops, windowed = afmoe.step_attention_calls(
        slots * 4096, slots * context // 16, 16, 8, 48, 128, 4, 1, 2)
    _ops, full = afmoe.step_attention_calls(
        slots * context, slots * context // 16, 16, 8, 48, 128, 4, 1, 2)
    assert round(windowed / 1e9, 1) == 2.3 and round(full / 1e9, 1) == 5.0


def test_served_tokens_by_hand():
    sizes = dict(block_size=4, hidden=8, vocab=50, query_heads=2, kv_heads=1,
                 head_dim=4, window_layers=2, full_layers=1, dense_layers=1,
                 dense_ffn=16, router_experts=4, ffn=6, shared_experts=1)
    attention = 2 * 8 * (16 + 8) + 2 * 8 * 8          # 512 a layer
    per_token = 3 * attention + 6 * 8 * 16 + 2 * (2 * 8 * 4 + 6 * 8 * 6)
    assert per_token == 1536 + 768 + 704
    # 3 stepped tokens, 5 chunk tokens
    bare = afmoe.served_tokens(3, 0, 0, 5, 0, 0, 0, 0, **sizes)[0]
    assert bare == 8 * per_token + 3 * 2 * 8 * 50
    rows = afmoe.served_tokens(3, 2, 7, 5, 11, 13, 0, 0, **sizes)[0] - bare
    assert rows == 2 * 2 * 8 * (1 * (2 * 4 + 11) + 2 * (7 + 13))
    routed = afmoe.served_tokens(0, 0, 0, 0, 0, 0, 3, 4, **sizes)
    assert routed == (7 * 6 * 8 * 6, 0)


def test_a_chunk_at_16k_of_context_by_the_issues_arithmetic():
    """Attention is about two fifths of a chunk of 1,024 behind 16k: the
    four sliding layers 4 x 1.0e11, the full layer 4.3e11, every product of
    the chunk 2.0e12 (the issue's reckoning took 4 x 1.1e11, 4.1e11 and
    1.2e12 with the experts' share of 1/8 where the program counts the
    pairs; here at 1/8 of 4 pairs a token)."""
    sizes = dict(block_size=16, hidden=3072, vocab=25024, query_heads=48,
                 kv_heads=8, head_dim=128, window_layers=4, full_layers=1,
                 dense_layers=1, dense_ffn=12288, router_experts=256,
                 ffn=3072, shared_experts=1)
    c, ctx = 1024, 16384
    full_pairs = sum(ctx + i + 1 for i in range(c))
    held = c * 4 * 4 // 8                   # pairs on this rank, 4 layers
    bare = afmoe.served_tokens(0, 0, 0, c, 0, 0, 0, held, **sizes)[0]
    both = afmoe.served_tokens(0, 0, 0, c, full_pairs, c * 4096, 0, held,
                               **sizes)[0]
    window = afmoe.served_tokens(0, 0, 0, c, 0, c * 4096, 0, held,
                                 **sizes)[0] - bare
    assert round(window / 4e11, 1) == 1.0
    assert round((both - bare - window) / 1e11, 1) == 4.3
    assert 0.3 < (both - bare) / both < 0.5


# -- the metric files through their readers ----------------------------------------

LABEL = '{engine="e"}'
NEW = ["window_rows_read_share", "window_blocks_released_per_s",
       "pool_live_share.window", "pool_live_share.full", "window_release_ms",
       "windowed_chunk_attention_roofline",
       "windowed_chunk_attention_device_share",
       "paged_attention_roofline.gqa6x128",
       "chunk_attention_roofline.gqa6x128", "serve_device_mfu.afmoe"]


def _run(moved, histograms=None):
    """A traced stretch [1.0, 4.0] of a second of busy time in which each
    of the kernels' events takes 0.2 s, with the counters of ``moved``
    moving inside it."""
    cfg = manifest.load_config(BENCH, NAME)
    traffic = manifest.load_traffic(TRAFFIC)

    def event(name, t0):
        return [f'%{name}.3 = custom-call(), custom_call_target='
                f'"tpu_custom_call", metadata={{op_name="jit(call)/{name}"}}',
                t0, 0.2]

    ops = [event("windowed_chunk_attn", 1.0), event("chunk_attention", 1.5),
           event("paged_attention", 2.0), event("moe_grouped", 2.5),
           ["%fusion.1 = f32[512,3072]{1,0} fusion()", 3.0, 0.2]]
    before = {family: {LABEL: 100} for family in moved}
    after = {family: {LABEL: 100 + n} for family, n in moved.items()}
    for family, (total, count) in (histograms or {}).items():
        before[family] = {LABEL: {"sum": 5.0, "count": 2}}
        after[family] = {LABEL: {"sum": 5.0 + total, "count": 2 + count}}
    device = {"ops": ops, "async_ops": [], "modules": []}
    spans = [("decode::window_release", 1.1, 1.1004),
             ("decode::window_release", 2.0, 2.0002),
             ("decode::feeds", 2.0, 2.5)]
    return {"trace": {"devices": {"0": device}},
            "trace_window": (1.0, 4.0), "spans": spans,
            "registry": (before, after), "stretch_registry": [before, after],
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            "sizes": manifest.run_sizes(cfg, traffic, 1, False),
            "facts": {"window_s": 51.0}, "config": cfg, "chips": 1}


def _read(name, run):
    spec = manifest.load_metric(name)
    return readers.READERS[spec["reader"]](spec["args"], run)


def test_the_windows_share_of_a_steps_rows_and_the_pools_occupancy():
    run = _run({"serving_attention_rows_read_step_total": 4_000,
                "serving_attention_rows_in_context_step_total": 16_000,
                "serving_kv_blocks_live_window_total": 300,
                "serving_kv_pool_blocks_window_total": 1_200,
                "serving_kv_blocks_live_full_total": 900,
                "serving_kv_pool_blocks_full_total": 1_800},
               {"serving_window_release_blocks": (1_020.0, 400)})
    assert _read("window_rows_read_share", run) == pytest.approx(25.0)
    assert _read("pool_live_share.window", run) == pytest.approx(25.0)
    assert _read("pool_live_share.full", run) == pytest.approx(50.0)
    assert _read("window_blocks_released_per_s", run) == pytest.approx(20.0)
    assert _read("window_release_ms", run) == pytest.approx(0.3)


def test_the_windowed_chunk_kernels_roofline_and_share():
    moved = {"serving_attention_window_pairs_chunk_total": 2_000_000_000,
             "serving_attention_window_rows_chunk_total": 2_500_000}
    ops, moved_bytes = afmoe.windowed_chunk_calls(
        2_000_000_000, 2_500_000, 8, 48, 128, 4, 2)
    assert ops / 197e12 > moved_bytes / 819e9
    run = _run(moved)
    assert _read("windowed_chunk_attention_roofline", run) == pytest.approx(
        100 * ops / 197e12 / 0.2)
    assert _read("windowed_chunk_attention_device_share", run) == \
        pytest.approx(20.0)
    # the full layer's kernel by ITS name: the windowed events are not its
    assert _read("chunk_attention_device_share", run) == pytest.approx(20.0)
    full = {"serving_chunk_attended_rows_total": 500_000_000,
            "serving_chunk_context_rows_total": 900_000,
            "serving_chunk_tokens_total": 100_000}
    assert 0 < _read("chunk_attention_roofline.gqa6x128", _run(full)) < 100


def test_the_step_kernels_roofline_is_by_bytes_over_both_groups():
    run = _run({"serving_attention_rows_read_step_total": 2_000_000,
                "serving_decode_live_blocks_total": 400_000})
    rows = 400_000 * 16 + 4 * 2_000_000
    moved = 2 * rows * 8 * 128 * 2
    assert moved / 819e9 > 2 * 2 * rows * 48 * 128 / 197e12
    assert _read("paged_attention_roofline.gqa6x128", run) == pytest.approx(
        100 * moved / 819e9 / 0.2)
    assert _read("paged_attention_device_share", run) == pytest.approx(20.0)


def test_the_whole_devices_share_counts_steps_and_chunks():
    moved = {"serving_active_slot_steps_total": 3_000,
             "serving_decode_live_blocks_total": 400_000,
             "serving_attention_rows_read_step_total": 2_000_000,
             "serving_chunk_tokens_total": 100_000,
             "serving_chunk_attended_rows_total": 500_000_000,
             "serving_attention_window_pairs_chunk_total": 300_000_000,
             "serving_moe_held_assignments_total": 6_000,
             "serving_moe_grouped_pairs_total": 200_000}
    ops, _ = afmoe.served_tokens(
        3_000, 400_000, 2_000_000, 100_000, 500_000_000, 300_000_000, 6_000,
        200_000, block_size=16, hidden=3072, vocab=25024, query_heads=48,
        kv_heads=8, head_dim=128, window_layers=4, full_layers=1,
        dense_layers=1, dense_ffn=12288, router_experts=256, ffn=3072,
        shared_experts=1)
    assert _read("serve_device_mfu.afmoe", _run(moved)) == pytest.approx(
        100 * ops / 197e12 / 1.0)


def test_a_program_without_the_counters_reads_nothing_and_does_not_raise():
    """What the parent commit's program gives the new readers: no counter,
    no span and no kernel of these names."""
    run = _run({})
    run["spans"] = [("decode::feeds", 2.0, 2.5)]
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
    assert set(NEW) <= set(mine) and len(NEW) <= 10
    for name, entry in mine.items():
        spec = manifest.load_metric(name)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == entry[key], (name, key)
        assert "workloads" not in spec
    for name in NEW:
        # (IN the list: a later cell may have joined it)
        assert CELL in mine[name]["workloads"]
    # the lists the latent-attention cell before it is in and whose reading
    # this system gives: every one but the latent kernels' and that model's
    # own share of the peak
    theirs = {m["name"] for m in manifest.metrics_of(
        BENCH, "per_layer", "mistral_small_4_119b.doc_qa_32k")}
    apart = {n for n in theirs if n.startswith("latent_")} | {
        "serve_device_mfu.mistral4"}
    assert theirs - apart <= set(mine)
    assert not apart & set(mine)
    # (the step's expert KERNEL's two among them: its 24 tokens are padded
    # to whole sublane tiles of bfloat16 inside `kernels/moe.py moe_experts`)
    assert {"moe_grouped_roofline", "moe_held_share",
            "chunk_attention_device_share", "moe_experts_device_share",
            "moe_experts_roofline.gated"} <= set(mine)
    # IN the lists: never the last of them, never their length (PERF.md
    # section 7, PR 51's finding)
    (latency,) = [m for m in BENCH["end_to_end"]
                  if m["name"] == "serve_token_latency_p50"]
    assert CELL in latency["workloads"]
    assert CELL in [w["name"] for w in BENCH["workloads"]]
    assert NAME in [c["name"] for c in BENCH["configs"]]
    assert len(BENCH["per_layer"]) <= 128
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert {mine[n]["layer"] for n in NEW} <= layers


@pytest.mark.parametrize("group,name", [("configs", NAME),
                                        ("workloads", CELL)])
def test_what_this_entry_says_in_words_fits_the_drivers_lines(group, name):
    # the driver refused this PR once for a configuration's ``why`` of 246
    # characters: every ``why``, ``source`` and ``layer`` is one printable
    # line of at most 200, with no tab
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
    assert t["prompt_len"] == {"dist": "lognormal", "median": 4096,
                               "sigma": 1.2, "min": 256, "max": 32768}
    assert t["answer_len"] == {"dist": "lognormal", "median": 192,
                               "sigma": 0.7, "min": 32, "max": 1024}
    cfg = manifest.load_config(BENCH, NAME)
    assert t["max_total_len"] == manifest.model_sizes(
        cfg, False)["max_len"] == 33792
    assert (t["check_requests"], t["check_tokens"]) == (16, 16)
    assert t["check_tolerance"] == 0.25
    assert t["check_tokens"] <= t["answer_len"]["min"]
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert "1/8" in cell["why"] and "one queue" in cell["why"]
    # short and long in ONE queue: a tenth under ~900, half under the
    # window, a tenth over ~19k
    from benchmark import workgen

    lengths = workgen.stratified_lengths(t["prompt_len"], 1000)
    assert 800 < lengths[100] < 1000 and 18000 < lengths[900] < 20000
    assert lengths[499] <= 4096 <= lengths[500]


# -- the cell, rehearsed ---------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_and_prints_every_metric_a_cpu_run_can_name(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "5900000077", "--seconds", "1",
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
    assert {"window_rows_read_share", "window_blocks_released_per_s",
            "pool_live_share.window", "pool_live_share.full",
            "window_release_ms", "moe_grouped_padding_share",
            "reserved_blocks_per_admission"} <= want
    assert all(m["value"] is None for m in line["metrics"].values())


def test_the_parent_refuses_an_unknown_cell_before_it_builds_anything():
    """What the parent commit does with this cell's name: ``run.py`` looks
    the cell up before it imports jax or a builder, and exits non-zero."""
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "no_such_config.no_such_traffic", "--seed", "1",
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
    """The cell's system at its rehearsal size (a window of 8 positions): a
    dozen requests served sound; the same prompts with the window group's
    oldest block given back a block early (the tool's own fault)."""
    import importlib

    from benchmark import workgen

    cfg = manifest.load_config(BENCH, NAME)
    traffic = manifest.sizes(manifest.load_traffic(TRAFFIC), True)
    builder = importlib.import_module("benchmark.builders." + cfg["builder"])
    system = builder.build(cfg, traffic, 5900000078, True)
    rng = np.random.default_rng(5)
    lengths = [n for n in workgen.stratified_lengths(
        traffic["prompt_len"], 24) if n > 9][:12]
    prompts = [workgen.prompt_tokens(rng, n, system.vocab_size)
               for n in lengths]

    def serve():
        sent = [_Sent(p, system.engine.submit(p, max_new_tokens=10))
                for p in prompts]
        for s in sent:
            s.response.result(timeout=300)
        return sent

    system.engine.start()
    try:
        runs = {"sound": serve()}
        undo = check_hybrid_logits._stale(system.entry, "window_early_block")
        runs["window_early_block"] = serve()
        undo()
    finally:
        system.engine.shutdown()
    return system, dict(traffic, check_requests=len(prompts),
                        check_tokens=10), runs


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
    system, traffic, runs = served
    checked, right, worst = _check(system, runs["sound"], traffic)
    # (the rehearsal builds float32: the configuration's ``assumed`` says
    # why)
    assert (checked, right) == (12, 12)
    assert worst <= traffic["check_tolerance"]
    # neither group's arenas nor the device's sum of the chunks' routing
    # counts is a weight
    names = set(system.weights())
    assert not any("cache" in n or n == "grouped_counts" for n in names)
    assert {"l0.gate_proj.w", "l1.router", "l3.shared_down.w",
            "l0.down.w"} <= names
    pools = [system.entry.kv.pool] + system.entry.kv.window_pools
    assert len(pools) == 2
    for pool in pools:
        assert pool.check_conservation()["blocks_live"] == 0
        assert pool.reserved == 0


def test_a_block_given_back_early_reads_not_correct(served):
    system, traffic, runs = served
    checked, right, worst = _check(system, runs["window_early_block"],
                                   traffic)
    assert right < traffic["check_min_equal"] * checked
    assert worst > traffic["check_tolerance"]


@pytest.mark.parametrize("control", [
    {"round_to": "float8_e4m3fn"}, {"sliding_window": 10 ** 6},
    {"sliding_window": 12}, {"rotate_full": True}, {"gate": False},
    {"route_scale": 1.0}],
    ids=["reference_in_float8", "window_left_out", "window_a_block_wide",
         "full_layer_rotated", "gate_left_out", "route_scale_left_out"])
def test_a_reference_read_otherwise_reads_not_correct(served, control):
    """The sound tokens against the reference with its weights through
    float8 (the cell's own control; through bfloat16 the rehearsal's
    twelve float32 answers still read right: four norms a layer bring
    every sub-layer's output back to unit size), or with one part of the
    description misread."""
    system, traffic, runs = served
    checked, right, worst = _check(system, runs["sound"], traffic, **control)
    assert right < traffic["check_min_equal"] * checked
    assert worst > traffic["check_tolerance"]
