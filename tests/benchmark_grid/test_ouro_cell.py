"""The ``ouro_2_6b`` configuration and its cell: the file against the
catalog's row (nothing reduced), the count functions by hand, the metric
files through their readers, the cell rehearsed on the CPU on a pool that
makes admission reserve, and runs whose state goes wrong read NOT correct.

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
from benchmark.counts import ouro  # noqa: E402

BENCH = manifest.load_manifest()
NAME = "ouro_2_6b"
TRAFFIC = "reasoning_short"
CELL = f"{NAME}.{TRAFFIC}"
# the catalog's row in a fixture of its own (catalog_rows.json is an
# accepted file and takes no new row)
with open(os.path.join(ROOT, "tests", "benchmark_grid",
                       "catalog_row_ouro.json")) as _f:
    (ROW,) = json.load(_f)["rows"]


# -- the configuration's file ---------------------------------------------------

def test_the_file_passes_the_drivers_rule_against_the_catalogs_row():
    (entry,) = [c for c in BENCH["configs"] if c["name"] == NAME]
    cfg = manifest.load_config(BENCH, NAME)
    assert ROW["name"] == "Ouro-2.6B"
    assert entry["source"] == cfg["source"] == ROW["source_url"]
    assert manifest.check_against_source(cfg, entry["reduced"],
                                         ROW["config"]) is None
    assert cfg["source_values"] == ROW["config"]
    assert sorted(cfg["source_keys"]) == sorted(ROW["config"])


def test_nothing_is_reduced_and_the_file_says_what_it_assumed():
    (entry,) = [c for c in BENCH["configs"] if c["name"] == NAME]
    cfg = manifest.load_config(BENCH, NAME)
    assert entry["reduced"] == cfg["reduced"] == []
    assert all(cfg[k] == v for k, v in ROW["config"].items())
    assert (cfg["num_hidden_layers"], cfg["total_ut_steps"],
            cfg["early_exit_threshold"], cfg["vocab_size"]) == (
                48, 4, 1, 49152)
    for said in ("norms", "bias", "between_passes", "exit_gate",
                 "kv_per_pass", "rotary", "weights", "engine"):
        assert said in cfg["assumed"]
    for said in ("one v5e chip", "WHOLE", "Replicas, not shards"):
        assert said in cfg["deployment"]
    sizes = manifest.model_sizes(cfg, False)
    assert (sizes["slots"], sizes["max_len"], sizes["block_size"],
            sizes["chunk_tokens"]) == (16, 1024, 16, 256)
    # the arena cannot give every slot its full length: what makes the
    # engine admit by reservation
    assert sizes["num_blocks"] < sizes["slots"] * (
        sizes["max_len"] // sizes["block_size"])
    assert cfg["settings"]["engine"] == {"prefix_cache_size": 0,
                                         "host_tier_mb": 0}
    assert cfg["settings"]["dtype"] == "bfloat16"
    # the rehearsal meets the reservation too: a third of slots x length
    small = manifest.model_sizes(cfg, True)
    assert small["num_blocks"] * 3 == small["slots"] * (
        small["max_len"] // small["block_size"])
    keys = manifest.published(cfg, True)
    assert (keys["hidden_size"], keys["num_hidden_layers"],
            keys["total_ut_steps"], keys["vocab_size"]) == (64, 3, 2, 96)
    assert keys["num_attention_heads"] * keys["head_dim"] == 64


def test_the_bytes_the_issue_reckoned():
    """2.668 B parameters held once, 5.34 GB in bfloat16; 1.5 MiB of K and
    V a token; the arenas of the pool that is served."""
    cfg = manifest.load_config(BENCH, NAME)
    c = manifest.published(cfg, False)
    h, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    layer = 4 * h * h + 3 * h * f + 4 * h
    assert round(layer / 1e6, 2) == 51.39
    total = c["num_hidden_layers"] * layer + 2 * v * h + h + h + 1
    assert total == 2_667_974_657 and round(2 * total / 1e9, 2) == 5.34
    pairs = c["total_ut_steps"] * c["num_hidden_layers"]
    token = pairs * 2 * c["num_key_value_heads"] * c["head_dim"] * 2
    assert pairs == 192 and token == 1_572_864
    sizes = manifest.model_sizes(cfg, False)
    rows = sizes["num_blocks"] * sizes["block_size"]
    assert round(rows * token / 1e9, 2) == round(
        sizes["num_blocks"] * 0.025165824, 2)
    # a decode step's weights: the layers four times, the head once
    assert round((4 * 48 * layer + v * h) * 2 / 1e9, 1) == 19.9


# -- the count functions, by hand -----------------------------------------------

def test_attention_calls_by_hand():
    # 5 live blocks of 4 positions, rows of 2 heads x 8, 3 layers x 2
    # passes, 2 bytes: a call reads 20 rows of K and of V, 16 elements
    # each: 2*20*16*2 = 1280 bytes, six 7680; q.k^T and p.v over 20
    # positions for 2 heads of 8: 2*2*20*16 = 1280 operations, six 7680
    assert ouro.attention_calls(5, 4, 2, 8, 3, 2, 2) == (7680, 7680)
    assert ouro.attention_calls(0, 4, 2, 8, 3, 2, 2) == (0, 0)


def test_stepped_tokens_by_hand():
    sizes = dict(block_size=4, hidden=4, vocab=10, layers=3, passes=2,
                 heads=2, head_dim=2, ffn=7)
    # a layer: q, k, v 2*4*12 = 96, o 2*4*4 = 32, three feed-forward
    # matrices 3*2*4*7 = 168: 296. A pass: three layers and the gate's
    # 2*4 = 8: 896. A token: two passes and the head's 2*4*10 = 80: 1872
    per_token = 1872
    # attention over 5 blocks of 4, six calls: 6 * 2*2*20*4 = 1920
    assert ouro.stepped_tokens(6, 5, **sizes) == (6 * per_token + 1920, 0)
    assert ouro.stepped_tokens(0, 0, **sizes) == (0, 0)


# -- the metric files through their readers ----------------------------------------

LABEL = '{engine="e"}'
NEW = ["paged_attention_roofline.mha128", "paged_attention_device_share",
       "serve_device_mfu.ouro", "loop_passes_per_token",
       "loop_expected_exit_pass", "kv_live_gb_per_step",
       "reserved_blocks_per_admission", "admissions_deferred"]
# what PR 43 left off this cell for room and PR 47's fold brought back: the
# host's per-step halves, each an entry the other serving cells report too
BACK = ["decode_feeds_ms", "decode_sample_ms", "decode_put_ms",
        "serve_fed_mb_per_step", "serve_fetched_mb_per_step"]


def _run(moved):
    """A traced stretch [1.0, 4.0] in which the attention kernel's events
    take 0.2 s of 1.2 s busy, with the counters of ``moved`` moving
    inside it."""
    cfg = manifest.load_config(BENCH, NAME)
    traffic = manifest.load_traffic(TRAFFIC)
    ops = [['%paged_attention.3 = custom-call(), custom_call_target='
            '"tpu_custom_call", metadata={op_name="jit(call)/'
            'paged_attention"}', 2.0, 0.2],
           ["%fusion.1 = f32[16,2048]{1,0} fusion()", 2.5, 1.0]]
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


def test_the_roofline_follows_the_live_blocks_of_the_stretch():
    run = _run({"serving_decode_live_blocks_total": 40_000})
    # a block across the 192 pairs' K and V arenas is 25,165,824 bytes
    assert _read("paged_attention_roofline.mha128", run) == pytest.approx(
        100 * 40_000 * 25_165_824 / 819e9 / 0.2)
    assert _read("paged_attention_device_share", run) == pytest.approx(
        100 * 0.2 / 1.2)


def test_the_whole_steps_share_is_over_every_event_of_the_stretch():
    run = _run({"serving_active_slot_steps_total": 2_400,
                "serving_decode_live_blocks_total": 40_000})
    ops, _ = ouro.stepped_tokens(
        2_400, 40_000, block_size=16, hidden=2048, vocab=49152, layers=48,
        passes=4, heads=16, head_dim=128, ffn=5632)
    assert _read("serve_device_mfu.ouro", run) == pytest.approx(
        100 * ops / 197e12 / 1.2)


def test_the_loops_and_the_pools_readings_are_ratios_of_counters():
    run = _run({"serving_loop_pass_tokens_total": 9_600,
                "serving_loop_exit_pass_milli_total": 4_512_000,
                "serving_active_slot_steps_total": 2_400,
                "serving_decode_live_blocks_total": 40_000,
                "serving_decode_steps_total": 200,
                "serving_blocks_reserved_total": 960,
                "serving_reserved_admissions_total": 40,
                "serving_admissions_deferred_total": 17,
                "serving_chunk_tokens_total": 900,
                "serving_chunk_runs_total": 10})
    assert _read("loop_passes_per_token", run) == pytest.approx(4.0)
    assert _read("loop_expected_exit_pass", run) == pytest.approx(1.88)
    assert _read("kv_live_gb_per_step", run) == pytest.approx(
        200 * 25_165_824 / 1e9)
    assert _read("reserved_blocks_per_admission", run) == pytest.approx(24.0)
    assert _read("admissions_deferred", run) == 17
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


def test_the_program_registers_what_the_files_read():
    """Every counter a new file names is one ``DecodeMetrics`` registers
    from the start, so that a window in which it did not move still finds
    the family."""
    from paddle_tpu.serving.decode.metrics import DecodeMetrics

    for name in NEW:
        args = manifest.load_metric(name)["args"]
        families = [args.get(k) for k in ("numerator", "denominator",
                                          "family")]
        for kernel in args.get("kernels", ()):
            families += [v["counter"] for v in kernel["call"].values()
                         if isinstance(v, dict) and "counter" in v]
        for family in filter(None, families):
            assert family.startswith("serving_") and family.endswith("_total")
            assert family[len("serving_"):-len("_total")] in \
                DecodeMetrics.COUNTERS, family


def test_every_new_metric_lists_the_cell_and_is_registered():
    """The cell's entries, by membership: a later cell appends itself to
    their lists and a later entry follows them, so nothing here counts the
    lists or looks at their last place."""
    mine = {m["name"]: m for m in manifest.metrics_of(BENCH, "per_layer", CELL)}
    assert set(NEW) <= set(mine)
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
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


def test_the_cell_reports_the_shared_readings_and_what_was_left_out_for_room():
    """No file under the cell's suffix is left but the one whose count
    function is its own; the five host halves PR 43 dropped at 128 of 128
    are the cell's again, by an append to their entries' lists; the two
    that read 0 here by construction stay off."""
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    assert [n for n in entries if n.endswith(".ouro")] == [
        "serve_device_mfu.ouro"]
    mine, theirs = (manifest.load_metric("serve_device_mfu.ouro"),
                    manifest.load_metric("serve_device_mfu"))
    assert mine["reader"] == theirs["reader"] and mine["args"] != theirs["args"]
    for name in ["decode_step_ms", "decode_logits_fetch_ms", "decode_call_ms",
                 "chunk_tokens_per_launch", "decode_drains"] + BACK:
        assert CELL in entries[name]["workloads"], name
    for name in ("kv_arena_read_bytes", "serve_shed"):
        assert CELL not in entries[name]["workloads"], name


def test_the_traffic_is_the_issues():
    t = manifest.load_traffic(TRAFFIC)
    assert (t["kind"], t["arrivals"], t["sharing"]) == (
        "open_loop", "poisson", "none")
    assert (t["preroll_s"], t["trace_seconds"], t["block_requests"],
            t["max_total_len"]) == (30, 10, 16, 1023)
    assert t["prompt_len"] == {"dist": "lognormal", "median": 128,
                               "sigma": 0.6, "min": 32, "max": 512}
    assert t["answer_len"] == {"dist": "lognormal", "median": 192,
                               "sigma": 0.5, "min": 32, "max": 512}
    # a request's chain: 24 blocks of 16 in the mean, so the pool holds a
    # dozen, fewer than the 16 slots
    from benchmark import workgen
    pairs = workgen.request_multiset(t, 64)
    chains = [-(-(p + a) // 16) for p, a in pairs]
    cfg = manifest.load_config(BENCH, NAME)
    pool = manifest.model_sizes(cfg, False)["num_blocks"]
    assert 22 <= sum(chains) / len(chains) <= 26
    assert pool / (sum(chains) / len(chains)) < 16
    assert max(chains) <= 64


def test_the_cells_limit_is_a_count_of_answers():
    """``serve.py`` decides by the answers that have a token beyond the
    tolerance among those checked: 5 of 20 here, the first 16 tokens of
    each at 0.5 standard deviations (sound runs read 0 to 3 of 32, a
    float8 reference, three passes or passes sharing their arenas 31 of
    32 and up: PERF.md section 2). The rehearsal's tiny size holds every
    one of its answers."""
    traffic = manifest.load_traffic(TRAFFIC)
    n = traffic["check_requests"]
    assert (n, traffic["check_tokens"], traffic["check_tolerance"]) == (
        20, 16, 0.5)
    assert int(n - traffic["check_min_equal"] * n) == 5
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
    assert {"cache_load_s", "hbm_compiled_gb", "loop_passes_per_token",
            "loop_expected_exit_pass", "kv_live_gb_per_step",
            "reserved_blocks_per_admission", "admissions_deferred",
            "decode_drains"} | set(BACK) <= want
    assert all(m["value"] is None for m in line["metrics"].values())


# every (pass, layer) K arena put back after every decode step: the rows
# the next step's attention reads are a step stale, for every slot (ONE
# stale arena of the six moves a served token's logit by under the
# tolerance: tools/check_hybrid_logits.py --stale-arena reads it)
ARENAS_STALE = '''
import jax.numpy as jnp
from paddle_tpu.serving.decode.engine import _ModelEntry
launch = _ModelEntry._run
def stale(self, kind, feeds, span=None):
    if kind != "step":
        return launch(self, kind, feeds, span)
    names = [kv[0] for kv in self._model.state_names]
    kept = [jnp.array(self._scope.find_var(n), copy=True) for n in names]
    out = launch(self, kind, feeds, span)
    for name, was in zip(names, kept):
        self._scope.set(name, was)
    return out
_ModelEntry._run = stale
'''

# every pass of a layer on pass 0's arena pair: a token's older rows are
# its last pass's, for every pass
PASSES_SHARE = '''
from paddle_tpu.serving.decode import hybrid
own = hybrid._Parts.arenas
hybrid._Parts.arenas = lambda parts, program, key: own(
    parts, program, (0, key[1]))
'''


@pytest.mark.parametrize("fault", [ARENAS_STALE, PASSES_SHARE],
                         ids=["every_k_arena_a_step_stale",
                              "passes_sharing_one_arena_pair"])
def test_a_run_whose_rows_go_wrong_reads_not_correct(fault):
    line = _rehearse(fault)
    assert line["correct"] is False
    failed = [name for name, n in line["compared"].items() if not n["holds"]]
    assert failed == ["worst_token_sigma_behind", "checked_answers_wrong"]
