"""The ``lfm2_24b_a2b`` configuration and its cell: the file against the
catalog's row, the count functions by hand, the metric files through their
readers, the cell rehearsed on the CPU, and a run whose convolution tail
goes stale read NOT correct.

Like its neighbours, this module loads no TPU library while it is imported.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, readers  # noqa: E402
from benchmark.counts import lfm2  # noqa: E402

BENCH = manifest.load_manifest()
NAME = "lfm2_24b_a2b"
CELL = NAME + ".assistant_steady"
# the catalog's row in a fixture of its own (catalog_rows.json is an
# accepted file and takes no new row)
with open(os.path.join(ROOT, "tests", "benchmark_grid",
                       "catalog_row_lfm2.json")) as _f:
    (ROW,) = json.load(_f)["rows"]


# -- the configuration's file ---------------------------------------------------

def test_the_file_passes_the_drivers_rule_against_the_catalogs_row():
    (entry,) = [c for c in BENCH["configs"] if c["name"] == NAME]
    cfg = manifest.load_config(BENCH, NAME)
    assert ROW["name"] == "LFM2-24B-A2B"
    assert entry["source"] == cfg["source"] == ROW["source_url"]
    assert manifest.check_against_source(cfg, entry["reduced"],
                                         ROW["config"]) is None
    assert cfg["source_values"] == ROW["config"]
    assert sorted(cfg["source_keys"]) == sorted(ROW["config"])


def test_only_the_held_experts_differ_and_they_keep_the_guides_floor():
    cfg = manifest.load_config(BENCH, NAME)
    differs = sorted(k for k, v in ROW["config"].items() if cfg[k] != v)
    assert differs == cfg["reduced"] == ["num_experts"]
    assert cfg["num_experts"] == 8 >= 8
    kinds = cfg["layer_types"]
    assert len(kinds) == cfg["num_hidden_layers"] == 40
    assert [kinds.count(k) for k in ("conv", "full_attention")] == [30, 10]
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] == list(
        range(2, 40, 4))
    # the router keeps its published width; the share is one of eight
    sizes = manifest.model_sizes(cfg, False)
    assert sizes["router_experts"] == ROW["config"]["num_experts"] == 64
    assert sizes["router_experts"] // cfg["num_experts"] == 8
    assert (sizes["slots"], sizes["max_len"], sizes["block_size"],
            sizes["chunk_tokens"]) == (128, 2048, 16, 128)
    assert cfg["settings"]["expert_rank"] == 0
    assert cfg["settings"]["dtype"] == "bfloat16"
    assert cfg["settings"]["engine"] == {"prefix_cache_size": 0,
                                         "host_tier_mb": 0}
    for said in ("head_dim", "tie_embedding", "norm_topk_epsilon", "rotary",
                 "conv_tail_dtype", "weights", "layouts"):
        assert said in cfg["assumed"]
    for said in ("v5e-8", "expert parallelism 8", "rank 0", "all 40 layers"):
        assert said in cfg["deployment"]
    assert "an eighth of the expert load" in cfg["why"]
    # the rehearsal: a whole period, both dense layers, four expert layers
    small = manifest.published(cfg, True)
    assert small["layer_types"].count("full_attention") == 1
    assert len(small["layer_types"]) == small["num_hidden_layers"] == 6
    assert small["num_hidden_layers"] - small["num_dense_layers"] >= 2


def test_the_bytes_the_issue_reckoned():
    """3.76 B parameters, 7.5 GB in bfloat16; 20,480 bytes of K and V a
    token, 5.37 GB over 128 slots x 2,048 positions."""
    c = manifest.published(manifest.load_config(BENCH, NAME), False)
    h = c["hidden_size"]
    head = h // c["num_attention_heads"]
    kv = c["num_key_value_heads"] * head
    expert = 3 * h * c["moe_intermediate_size"]
    conv = h * 3 * h + h * h + c["conv_L_cache"] * h
    attention = 2 * h * h + 2 * h * kv + 2 * head
    dense = 3 * h * c["intermediate_size"]
    router = 64 * h + 64
    assert round(expert / 1e6, 3) == 9.437
    assert round(conv / 1e6, 1) == 16.8 and round(attention / 1e6, 1) == 10.5
    assert round(dense / 1e6, 1) == 72.4
    held = 38 * c["num_experts"] * expert
    total = (held + 30 * conv + 10 * attention + 38 * router + 2 * dense
             + c["vocab_size"] * h + 81 * h)
    assert round(held / 1e9, 2) == 2.87 and round(total / 1e9, 2) == 3.76
    whole = total - held + 38 * 64 * expert
    assert round(whole / 1e9, 1) == 23.8
    row = 10 * 2 * kv * 2
    assert row == 20480 and round(128 * 2048 * row / 1e9, 2) == 5.37


# -- the count functions, by hand -----------------------------------------------

def test_expert_calls_by_hand():
    # 3 experts touched by 5 (token, expert) pairs; hidden 4, width 6, 2 B:
    # an expert's three matrices are 3*4*6 = 72 elements, 144 bytes, three
    # 432; a pair is three products, 3 * 2*4*6 = 144 operations, five 720
    assert lfm2.expert_calls(3, 5, 4, 6, 2) == (720, 432)
    assert lfm2.expert_calls(0, 0, 4, 6, 2) == (0, 0)


def test_attention_calls_by_hand():
    # 5 live blocks of 4 positions, rows of 2 K/V heads x 8, 6 query heads,
    # 3 layers, 2 bytes: a layer reads 20 rows of K and of V, 16 elements
    # each: 2*20*16*2 = 1280 bytes, three 3840; q.k^T and p.v over 20
    # positions for 6 heads of 8: 2*2*20*48 = 3840 operations, three 11520
    assert lfm2.attention_calls(5, 4, 2, 6, 8, 3, 2) == (11520, 3840)


def test_stepped_tokens_by_hand():
    sizes = dict(block_size=4, hidden=4, vocab=10, conv_layers=3,
                 attention_layers=1, dense_layers=2, expert_layers=2, taps=3,
                 query_heads=2, kv_heads=1, head_dim=2, dense_ffn=7,
                 router_experts=8, ffn=6)
    # a conv layer: in_proj 2*4*12 = 96, out_proj 2*4*4 = 32, the taps and
    # the two gates (2*3 + 2) * 4 = 32: 160. An attention layer: q 4, k and
    # v 2: 2*4*8 = 64, out 2*4*4 = 32: 96. A dense layer's three matrices
    # 3*2*4*7 = 168. A router 2*4*8 = 64. The tied head 2*4*10 = 80.
    # A token: 3*160 + 96 + 2*168 + 2*64 + 80 = 1120
    per_token = 1120
    # attention over 5 blocks of 4: 2*2*20*4 = 320; 9 held pairs of three
    # products: 9 * 3*2*4*6 = 9 * 144
    assert lfm2.stepped_tokens(6, 5, 9, **sizes) == (
        6 * per_token + 320 + 9 * 144, 0)
    assert lfm2.stepped_tokens(0, 0, 0, **sizes) == (0, 0)


# -- the metric files through their readers ----------------------------------------

LABEL = '{engine="e"}'
NEW = ["moe_experts_roofline.gated", "moe_experts_device_share",
       "paged_attention_roofline.gqa64", "paged_attention_device_share",
       "serve_device_mfu.lfm2", "moe_held_share",
       "moe_touched_share.lfm2", "moe_tokens_per_touched_expert",
       "moe_peak_expert_tokens", "chunk_tokens_per_launch"]
# the two readings whose file differs by configuration (a count function, a
# scale): the cell reports its own and not the one it parts from
OWN = {"serve_device_mfu.lfm2": "serve_device_mfu",
       "moe_touched_share.lfm2": "moe_touched_share"}


def _run(moved):
    """A traced stretch [1.0, 4.0] in which each of the two kernels' events
    take 0.2 s, with the counters of ``moved`` moving inside it."""
    cfg = manifest.load_config(BENCH, NAME)
    traffic = manifest.load_traffic("assistant_steady")

    def event(name, t0):
        return [f'%{name}.3 = custom-call(), custom_call_target='
                f'"tpu_custom_call", metadata={{op_name="jit(call)/{name}"}}',
                t0, 0.2]

    ops = [event("moe_experts", 1.0), event("paged_attention", 2.0),
           ["%fusion.1 = f32[128,2048]{1,0} fusion()", 2.5, 1.0]]
    before = {family: {LABEL: 100} for family in moved}
    after = {family: {LABEL: 100 + n} for family, n in moved.items()}
    device = {"ops": ops, "async_ops": [], "modules": []}
    return {"trace": {"devices": {"0": device}},
            "trace_window": (1.0, 4.0), "spans": [],
            "registry": (before, after), "stretch_registry": [before, after],
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            "sizes": manifest.run_sizes(cfg, traffic, 1, False),
            "facts": {}, "config": cfg, "chips": 1}


def _read(name, run):
    spec = manifest.load_metric(name)
    return readers.READERS[spec["reader"]](spec["args"], run)


def test_the_rooflines_follow_the_counters_of_the_stretch():
    run = _run({"serving_moe_touched_experts_total": 4_000,
                "serving_moe_held_assignments_total": 20_000,
                "serving_decode_live_blocks_total": 50_000})
    expert = 3 * 2048 * 1536 * 2
    assert _read("moe_experts_roofline.gated", run) == pytest.approx(
        100 * 4_000 * expert / 819e9 / 0.2)
    assert _read("paged_attention_roofline.gqa64", run) == pytest.approx(
        100 * 50_000 * 16 * 512 * 2 * 2 * 10 / 819e9 / 0.2)
    busy = 0.2 * 2 + 1.0
    assert _read("moe_experts_device_share", run) == pytest.approx(
        100 * 0.2 / busy)
    assert _read("paged_attention_device_share", run) == pytest.approx(
        100 * 0.2 / busy)


def test_the_whole_steps_share_is_over_every_event_of_the_stretch():
    run = _run({"serving_active_slot_steps_total": 14_000,
                "serving_decode_live_blocks_total": 50_000,
                "serving_moe_held_assignments_total": 6_000})
    ops, _ = lfm2.stepped_tokens(
        14_000, 50_000, 6_000, block_size=16, hidden=2048, vocab=65536,
        conv_layers=30, attention_layers=10, dense_layers=2,
        expert_layers=38, taps=3, query_heads=32, kv_heads=8, head_dim=64,
        dense_ffn=11776, router_experts=64, ffn=1536)
    assert _read("serve_device_mfu.lfm2", run) == pytest.approx(
        100 * ops / 197e12 / (0.2 * 2 + 1.0))


def test_the_routing_readings_are_ratios_of_counters():
    run = _run({"serving_moe_assignments_total": 48_000,
                "serving_moe_held_assignments_total": 6_000,
                "serving_moe_touched_experts_total": 2_400,
                "serving_moe_peak_expert_tokens_total": 1_900,
                "serving_decode_steps_total": 10,
                "serving_chunk_tokens_total": 900,
                "serving_chunk_runs_total": 10})
    assert _read("moe_held_share", run) == pytest.approx(12.5)
    assert _read("moe_touched_share.lfm2", run) == pytest.approx(
        100 * 2_400 / 3_040)
    assert _read("moe_tokens_per_touched_expert", run) == pytest.approx(2.5)
    assert _read("moe_peak_expert_tokens", run) == pytest.approx(5.0)
    assert _read("chunk_tokens_per_launch", run) == pytest.approx(90.0)


def test_a_program_without_the_counters_reads_nothing_and_does_not_raise():
    """What the parent commit's program gives the new readers."""
    run = _run({})
    for name in NEW:
        if manifest.load_metric(name)["reader"] != "device_share":
            assert _read(name, run) is None, name
    run["trace"] = None
    for name in NEW:
        assert _read(name, run) is None, name


def test_every_new_metric_lists_the_cell_and_is_registered():
    mine = {m["name"]: m for m in manifest.metrics_of(BENCH, "per_layer", CELL)}
    # the ten, the readings every serving cell shares, and the two that
    # every cell reports; a later entry that lists the cell adds to them
    assert set(NEW) <= set(mine) and len(mine) >= 37
    for name, entry in mine.items():
        spec = manifest.load_metric(name)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == entry[key], (name, key)
        assert "workloads" not in spec      # the entry alone lists the cells
    for name in NEW:
        assert CELL in mine[name]["workloads"]
    (latency,) = [m for m in BENCH["end_to_end"]
                  if m["name"] == "serve_token_latency_p50"]
    assert CELL in latency["workloads"]


@pytest.mark.parametrize("name", sorted(OWN))
def test_a_file_of_the_cells_own_differs_from_the_one_it_parts_from(name):
    """One entry a quantity: a second file under a cell's suffix is there
    only where the reading itself differs by configuration, and then a
    cell reports one of the two."""
    mine, theirs = manifest.load_metric(name), manifest.load_metric(OWN[name])
    for key in ("reader", "unit", "better", "source", "layer", "moves"):
        assert mine[key] == theirs[key], (name, key)
    assert mine["args"] != theirs["args"]
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    assert CELL in entries[name]["workloads"]
    assert CELL not in entries[OWN[name]]["workloads"]


def test_the_traffic_is_the_issues_letter_for_letter():
    t = manifest.load_traffic("assistant_steady")
    assert (t["kind"], t["arrivals"], t["sharing"], t["schedule_seed"]) == (
        "open_loop", "poisson", "none", 40)
    assert (t["preroll_s"], t["abandon_after_s"], t["trace_seconds"],
            t["block_requests"], t["max_total_len"]) == (30, 3, 10, 16, 2047)
    assert t["prompt_len"] == {"dist": "lognormal", "median": 256,
                               "sigma": 0.7, "min": 32, "max": 1536}
    assert t["answer_len"] == {"dist": "lognormal", "median": 160,
                               "sigma": 0.6, "min": 16, "max": 512}
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200


def test_the_cells_limit_is_a_count_of_answers():
    """``serve.py`` decides by the answers that have a token beyond the
    tolerance among those checked: 16 of 64 here, the first 16 tokens of
    each at 0.2 standard deviations (sound runs read 4 to 10, the decode
    steps' positions off by one 21 to 22, a float8 reference 34 to 44:
    PERF.md section 2). The rehearsal's tiny size holds every one of its answers."""
    traffic = manifest.load_traffic("assistant_steady")
    n = traffic["check_requests"]
    assert (n, traffic["check_tokens"], traffic["check_tolerance"]) == (
        64, 16, 0.2)
    assert int(n - traffic["check_min_equal"] * n) == 16
    small = manifest.sizes(traffic, True)
    assert (small["check_min_equal"], small["check_tolerance"]) == (1.0, 0.1)


# -- the cell, rehearsed ---------------------------------------------------------

def _rehearse(extra=""):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    code = (f"import sys; sys.path.insert(0, {ROOT!r})\n"
            "from benchmark import run\n" + extra +
            f"run.main(['--workload', {CELL!r}, '--seed', '4000000077', "
            "'--seconds', '1', '--trace', '1', '--rehearse-cpu'])\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_the_cell_rehearses_and_prints_every_metric_a_cpu_run_can_name():
    line = _rehearse()
    assert line["correct"] is True and line["failed"] == 0
    entries = manifest.metrics_of(BENCH, "per_layer", CELL)
    # what reads the device's trace has nothing to read off the chip; every
    # other metric of the cell is in the line, each value null
    want = {m["name"] for m in entries if m["source"] != "device_trace"}
    assert set(line["metrics"]) == want
    assert {"cache_load_s", "hbm_compiled_gb", "moe_held_share",
            "moe_touched_share.lfm2", "moe_tokens_per_touched_expert",
            "moe_peak_expert_tokens", "chunk_tokens_per_launch"} <= want
    assert all(m["value"] is None for m in line["metrics"].values())


# one conv layer's tail put back after every decode step: the two inputs the
# next step's taps read are a step stale, for every slot
TAIL_STALE = '''
import jax.numpy as jnp
from paddle_tpu.serving.decode.engine import _ModelEntry
launch = _ModelEntry._run
def stale(self, kind, feeds, span=None):
    if kind != "step":
        return launch(self, kind, feeds, span)
    name = [n for n, _s, _d in self._model.slot_states if ".conv" in n][0]
    kept = jnp.array(self._scope.find_var(name), copy=True)
    out = launch(self, kind, feeds, span)
    self._scope.set(name, kept)
    return out
_ModelEntry._run = stale
'''


def test_a_stale_convolution_tail_reads_not_correct():
    line = _rehearse(TAIL_STALE)
    assert line["correct"] is False
    failed = [name for name, n in line["compared"].items() if not n["holds"]]
    assert failed == ["worst_token_sigma_behind", "checked_answers_wrong"]
