"""The ``sdar_30b_a3b`` configuration and its cell: the file against the
catalog's row, the count functions by hand, the metric files through their
readers, the cell rehearsed on the CPU, and the builder's replay of the
order in which a block was filled: sound answers read correct, the same
answers replayed left to right and answers served without their blocks'
commit passes read NOT correct.

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
from benchmark.counts import lfm2, sdar  # noqa: E402
from tools import check_hybrid_logits  # noqa: E402

BENCH = manifest.load_manifest()
NAME = "sdar_30b_a3b"
CELL = NAME + ".chat_blocks"
# the catalog's row in a fixture of its own (catalog_rows.json is an
# accepted file and takes no new row)
with open(os.path.join(ROOT, "tests", "benchmark_grid",
                       "catalog_row_sdar.json")) as _f:
    (ROW,) = json.load(_f)["rows"]


# -- the configuration's file ---------------------------------------------------

def test_the_file_passes_the_drivers_rule_against_the_catalogs_row():
    (entry,) = [c for c in BENCH["configs"] if c["name"] == NAME]
    cfg = manifest.load_config(BENCH, NAME)
    assert ROW["name"] == "SDAR-30B-A3B-Chat"
    assert entry["source"] == cfg["source"] == ROW["source_url"]
    assert manifest.check_against_source(cfg, entry["reduced"],
                                         ROW["config"]) is None
    assert cfg["source_values"] == ROW["config"]
    assert sorted(cfg["source_keys"]) == sorted(ROW["config"])
    # a group that is a list, and the two published nulls, stand as given
    assert cfg["mlp_only_layers"] == [] and cfg["decoder_sparse_step"] == 1
    assert cfg["rope_scaling"] is None and cfg["sliding_window"] is None


def test_only_the_held_experts_differ_and_they_keep_the_guides_floor():
    cfg = manifest.load_config(BENCH, NAME)
    (entry,) = [c for c in BENCH["configs"] if c["name"] == NAME]
    differs = sorted(k for k, v in ROW["config"].items() if cfg[k] != v)
    assert differs == cfg["reduced"] == entry["reduced"] == ["num_experts"]
    assert cfg["num_experts"] == 16 >= 8
    assert cfg["num_hidden_layers"] == 48 and cfg["vocab_size"] == 151936
    # the router keeps its published width; the share is one of eight
    sizes = manifest.model_sizes(cfg, False)
    assert sizes["router_experts"] == ROW["config"]["num_experts"] == 128
    assert sizes["router_experts"] // cfg["num_experts"] == 8
    assert (sizes["slots"], sizes["max_len"], sizes["block_size"],
            sizes["chunk_tokens"]) == (32, 1024, 16, 128)
    assert (sizes["block_len"], sizes["denoising_steps"],
            sizes["mask_token_id"]) == (4, 4, 151669)
    assert cfg["settings"] == {
        "dtype": "bfloat16", "expert_rank": 0,
        "engine": {"prefix_cache_size": 0, "host_tier_mb": 0}}
    for said in ("block_len", "denoising_steps", "mask_token_id",
                 "unshifted_rows", "qk_norm", "rotary", "router",
                 "activations", "weights"):
        assert said in cfg["assumed"]
    assert "-inf" in cfg["assumed"]["mask_token_id"]
    for said in ("v5e-8", "expert parallelism 8", "rank 0", "all 48 layers",
                 "whole vocabulary"):
        assert said in cfg["deployment"]
    assert "an eighth of the expert load" in cfg["why"]
    small = manifest.published(cfg, True)
    assert small["num_hidden_layers"] >= 2 and small["num_experts"] >= 2


def test_the_bytes_the_issue_reckoned():
    """5.17 B parameters, 10.33 GB in bfloat16; 98,304 bytes of K and V a
    token, 3.22 GB over 32 slots x 1,024 positions."""
    c = manifest.published(manifest.load_config(BENCH, NAME), False)
    h, d = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    expert = 3 * h * c["moe_intermediate_size"]
    outside = 2 * h * q + 2 * h * kv + 128 * h
    assert expert == 4_718_592 and 2 * h * q + 2 * h * kv == 18_874_368
    assert outside == 19_136_512
    held = 48 * (c["num_experts"] * expert + outside)
    both = 2 * c["vocab_size"] * h
    assert round(held / 1e9, 2) == 4.54 and round(both / 1e6) == 622
    assert round((held + both) / 1e9, 2) == 5.16    # the issue's 5.17
    assert round(2 * (held + both) / 1e9, 1) == 10.3
    whole = 48 * (128 * expert + outside) + both
    assert round(whole / 1e9, 1) == 30.5
    row = 48 * 2 * kv * 2
    assert row == 98_304 and round(32 * 1024 * row / 1e9, 2) == 3.22


# -- the count functions, by hand -----------------------------------------------

def test_attention_calls_by_hand():
    # 5 live blocks of 4 rows, rows of 2 K/V heads x 8, 6 query heads, a
    # block pass of 4 positions, 3 layers, 2 bytes: a layer reads 20 rows
    # of K and of V ONCE, 16 elements each: 2*20*16*2 = 1280 bytes, three
    # 3840; q.k^T and p.v over 20 positions for 4 x 6 query rows of 8:
    # 2*2*20*4*48 = 15360 operations, three 46080
    assert sdar.attention_calls(5, 4, 2, 6, 8, 4, 3, 2) == (46080, 3840)
    # the gated experts are counted by the function that counts LFM2's
    assert lfm2.expert_calls(3, 5, 4, 6, 2) == (720, 432)


def test_stepped_tokens_by_hand():
    sizes = dict(block_len=4, block_size=4, hidden=4, vocab=10, layers=3,
                 query_heads=2, kv_heads=1, head_dim=2, router_experts=8,
                 ffn=6)
    # a layer of one position: q 4, k and v 2: 2*4*8 = 64, o 2*4*4 = 32,
    # the router 2*4*8 = 64: 160, three 480; the head 2*4*10 = 80: 560.
    # 10 slot passes read 50 blocks of 4: 20 positions a pass; q.k^T and
    # p.v over them for 2 heads of 2, three layers: 3*2*2*20*4 = 960.
    # 80 held pairs over 10 x 4 positions: 2 a position, three products of
    # 2*4*6: 2 * 144 = 288. A token: 560 + 960 + 288 = 1808
    assert sdar.stepped_tokens(7, 10, 50, 80, **sizes) == (7 * 1808, 0)
    assert sdar.stepped_tokens(0, 0, 0, 0, **sizes) == (0, 0)


# -- the metric files through their readers ----------------------------------------

LABEL = '{engine="e"}'
NEW = ["block_passes_per_token", "block_commit_pass_share",
       "paged_attention_roofline.gqa_block4", "moe_touched_share.sdar",
       "moe_peak_expert_tokens.sdar", "serve_device_mfu.sdar"]
# the readings whose file differs by configuration (a count function, a
# scale): the cell reports its own and not the ones it parts from
OWN = {"serve_device_mfu.sdar": "serve_device_mfu.lfm2",
       "moe_touched_share.sdar": "moe_touched_share.lfm2",
       "moe_peak_expert_tokens.sdar": "moe_peak_expert_tokens",
       "paged_attention_roofline.gqa_block4":
           "paged_attention_roofline.gqa64"}


def _run(moved):
    """A traced stretch [1.0, 4.0] in which each of the two kernels' events
    take 0.2 s, with the counters of ``moved`` moving inside it."""
    cfg = manifest.load_config(BENCH, NAME)
    traffic = manifest.load_traffic("chat_blocks")

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
                "serving_decode_live_blocks_total": 5_000})
    expert = 3 * 2048 * 768 * 2
    # the gated experts' file names no size of its own: it reads this
    # configuration's width (768) as it reads the other's (1,536)
    assert _read("moe_experts_roofline.gated", run) == pytest.approx(
        100 * 4_000 * expert / 819e9 / 0.2)
    # live blocks x 16 rows x 1,024 B x K and V x 48 layers
    assert _read("paged_attention_roofline.gqa_block4", run) == \
        pytest.approx(100 * 5_000 * 16 * 1024 * 2 * 48 / 819e9 / 0.2)


def test_the_whole_steps_share_counts_one_pass_a_decided_token():
    moved = {"serving_block_tokens_decided_total": 8_000,
             "serving_active_slot_steps_total": 10_000,
             "serving_decode_live_blocks_total": 200_000,
             "serving_moe_held_assignments_total": 2_000_000}
    ops, _ = sdar.stepped_tokens(
        8_000, 10_000, 200_000, 2_000_000, block_len=4, block_size=16,
        hidden=2048, vocab=151936, layers=48, query_heads=32, kv_heads=4,
        head_dim=128, router_experts=128, ffn=768)
    assert _read("serve_device_mfu.sdar", _run(moved)) == pytest.approx(
        100 * ops / 197e12 / (0.2 * 2 + 1.0))
    # twice the passes for the same tokens (at the same blocks and
    # assignments a pass) is no more required work
    doubled = dict(moved, **{k: 2 * moved[k] for k in moved
                             if "tokens_decided" not in k})
    assert _read("serve_device_mfu.sdar", _run(doubled)) == pytest.approx(
        100 * ops / 197e12 / (0.2 * 2 + 1.0))


def test_the_pass_and_routing_readings_are_ratios_of_counters():
    run = _run({"serving_block_passes_total": 1_300,
                "serving_block_tokens_decided_total": 1_000,
                "serving_blocks_committed_total": 260,
                "serving_moe_assignments_total": 48_000,
                "serving_moe_held_assignments_total": 6_000,
                "serving_moe_touched_experts_total": 7_000,
                "serving_moe_peak_expert_tokens_total": 4_800,
                "serving_decode_steps_total": 10})
    assert _read("block_passes_per_token", run) == pytest.approx(1.3)
    assert _read("block_commit_pass_share", run) == pytest.approx(20.0)
    assert _read("moe_held_share", run) == pytest.approx(12.5)
    assert _read("moe_touched_share.sdar", run) == pytest.approx(
        100 * 7_000 / (10 * 16 * 48))
    assert _read("moe_peak_expert_tokens.sdar", run) == pytest.approx(10.0)


def test_a_program_without_the_counters_reads_nothing_and_does_not_raise():
    """What the parent commit's program gives the new readers."""
    run = _run({})
    for name in NEW:
        assert _read(name, run) is None, name
    run["trace"] = None
    for name in NEW:
        assert _read(name, run) is None, name


def test_every_new_metric_lists_the_cell_and_is_registered():
    mine = {m["name"]: m for m in manifest.metrics_of(BENCH, "per_layer", CELL)}
    assert set(NEW) <= set(mine) and len(mine) >= 39
    for name, entry in mine.items():
        spec = manifest.load_metric(name)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == entry[key], (name, key)
        assert "workloads" not in spec      # the entry alone lists the cells
    for name in NEW:
        assert mine[name]["workloads"] == [CELL]
    assert CELL in mine["moe_experts_roofline.gated"]["workloads"]
    (latency,) = [m for m in BENCH["end_to_end"]
                  if m["name"] == "serve_token_latency_p50"]
    assert latency["workloads"][-1] == CELL
    assert len(BENCH["per_layer"]) <= 128
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len(BENCH["workloads"]) == 8


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
    t = manifest.load_traffic("chat_blocks")
    assert (t["kind"], t["arrivals"], t["sharing"]) == (
        "open_loop", "poisson", "none")
    assert (t["preroll_s"], t["trace_seconds"], t["block_requests"],
            t["max_total_len"]) == (30, 10, 16, 1023)
    assert t["prompt_len"] == {"dist": "lognormal", "median": 192,
                               "sigma": 0.7, "min": 16, "max": 640}
    assert t["answer_len"] == {"dist": "lognormal", "median": 160,
                               "sigma": 0.6, "min": 16, "max": 384}
    # whole blocks, at least two an answer, and every position of a
    # checked token's block inside the shortest answer
    assert t["check_tokens"] % 4 == 0 and t["check_tokens"] >= 8
    assert t["check_tokens"] + 3 <= t["answer_len"]["min"]
    small = manifest.sizes(t, True)
    assert small["check_tokens"] + 3 <= small["answer_len"]["min"]
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200


# -- the cell, rehearsed ---------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_and_prints_every_metric_a_cpu_run_can_name(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "4800000077", "--seconds", "1",
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
    # what reads the device's trace has nothing to read off the chip; every
    # other metric of the cell is in the line, each value null
    want = {m["name"] for m in entries if m["source"] != "device_trace"}
    assert set(line["metrics"]) == want
    assert {"block_passes_per_token", "block_commit_pass_share",
            "moe_touched_share.sdar", "moe_peak_expert_tokens.sdar",
            "moe_held_share", "decode_step_ms"} <= want
    assert all(m["value"] is None for m in line["metrics"].values())


# -- the replay of a block's filling order -----------------------------------------

class _Sent:
    def __init__(self, prompt, response):
        self.prompt, self.response = prompt, response


@pytest.fixture(scope="module")
def served():
    """The cell's system at its rehearsal size; a dozen requests served
    sound, and the same prompts again with every block's commit pass
    writing nowhere (the block's K/V rows stay as its last filling pass
    wrote them)."""
    import importlib

    from benchmark import workgen

    cfg = manifest.load_config(BENCH, NAME)
    traffic = manifest.sizes(manifest.load_traffic("chat_blocks"), True)
    builder = importlib.import_module("benchmark.builders." + cfg["builder"])
    system = builder.build(cfg, traffic, 4800000078, True)
    entry = system.entry
    rng = np.random.default_rng(5)
    prompts = [workgen.prompt_tokens(rng, n, system.vocab_size)
               for n in workgen.stratified_lengths(traffic["prompt_len"], 12)]
    def serve():
        sent = [_Sent(p, system.engine.submit(p, max_new_tokens=14))
                for p in prompts]
        for s in sent:
            s.response.result(timeout=300)
        return sent

    system.engine.start()
    try:
        sound = serve()
        # the tool's own fault: a commit pass's write goes nowhere
        undo = check_hybrid_logits._stale(entry, "skip_commit")
        faulty = serve()
        undo()
    finally:
        system.engine.shutdown()
    return system, dict(traffic, check_requests=12), sound, faulty


def _check(system, sent, traffic):
    from benchmark import serve

    for s in sent:      # the replay reads the response noted for a prompt
        system.engine.noted[tuple(s.prompt)] = s.response
    return serve._check_against_reference(system, sent, traffic, 1)


def test_sound_answers_replayed_in_their_served_order_are_the_references(
        served):
    system, traffic, sound, _faulty = served
    checked, right, worst = _check(system, sound, traffic)
    assert (checked, right) == (12, 12) and worst <= traffic["check_tolerance"]
    # the recorded order is no left-to-right one
    orders = [list(s.response.result()["decided_at"]) for s in sound]
    assert any(o[:4] != [0, 1, 2, 3] for o in orders)
    # a block that reaches past the served tokens cannot be replayed
    s = sound[0]
    tokens = [int(t) for t in s.response.result()["tokens"]]
    first = len(s.prompt) - 1
    with pytest.raises(ValueError, match="whole blocks"):
        system.reference_logits(list(s.prompt) + tokens[:-1],
                                range(first, first + len(tokens)))


def test_the_replay_that_ignores_the_order_reads_not_correct(served):
    system, traffic, sound, _faulty = served
    own = type(system).reference_logits
    try:
        type(system).reference_logits = lambda self, t, p: own(
            self, t, p, order="left_to_right")
        checked, right, worst = _check(system, sound, traffic)
    finally:
        type(system).reference_logits = own
    assert right < traffic["check_min_equal"] * checked
    assert worst > traffic["check_tolerance"]


def test_answers_served_without_their_commit_passes_read_not_correct(served):
    system, traffic, _sound, faulty = served
    checked, right, worst = _check(system, faulty, traffic)
    assert right < traffic["check_min_equal"] * checked
    assert worst > traffic["check_tolerance"]


def test_the_reference_under_a_causal_mask_reads_not_correct(served):
    system, traffic, sound, _faulty = served
    own = type(system).reference_logits
    try:
        type(system).reference_logits = lambda self, t, p: own(
            self, t, p, mask="causal")
        checked, right, _worst = _check(system, sound, traffic)
    finally:
        type(system).reference_logits = own
    assert right < traffic["check_min_equal"] * checked
