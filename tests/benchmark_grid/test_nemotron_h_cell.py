"""The ``nemotron3_nano_30b_a3b`` configuration and its cell: the file against
the catalog's row, the count functions by hand, the metric files through
their readers, and a run whose recurrent state goes stale read NOT correct.

Like its neighbours, this module loads no TPU library while it is imported;
the cell itself is rehearsed by ``test_benchmark_grid.py``'s parametrised
rehearsal, which takes its cells from ``BENCHMARK.json``.
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
from benchmark.counts import nemotron_h  # noqa: E402

BENCH = manifest.load_manifest()
NAME = "nemotron3_nano_30b_a3b"
CELL = NAME + ".reasoning_steady"
with open(os.path.join(ROOT, "tests", "benchmark_grid",
                       "catalog_rows.json")) as _f:
    ROW = {r["name"]: r for r in json.load(_f)["rows"]}[
        "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"]


# -- the configuration's file ---------------------------------------------------

def test_the_file_passes_the_drivers_rule_against_the_catalogs_row():
    (entry,) = [c for c in BENCH["configs"] if c["name"] == NAME]
    cfg = manifest.load_config(BENCH, NAME)
    assert entry["source"] == cfg["source"] == ROW["source_url"]
    assert manifest.check_against_source(cfg, entry["reduced"],
                                         ROW["config"]) is None
    assert cfg["source_values"] == ROW["config"]
    assert sorted(cfg["source_keys"]) == sorted(ROW["config"])


def test_only_the_two_counts_differ_and_they_keep_the_guides_floors():
    cfg = manifest.load_config(BENCH, NAME)
    differs = sorted(k for k, v in ROW["config"].items() if cfg[k] != v)
    assert differs == sorted(cfg["reduced"]) == ["n_routed_experts",
                                                 "vocab_size"]
    assert cfg["n_routed_experts"] == 16 >= 8
    assert cfg["vocab_size"] == 16384 >= ROW["config"]["vocab_size"] // 8
    pattern = cfg["hybrid_override_pattern"]
    assert len(pattern) == cfg["num_hidden_layers"] == 52
    assert [pattern.count(kind) for kind in "ME*"] == [23, 23, 6]
    # the router keeps its published width; the share is one of eight
    sizes = manifest.model_sizes(cfg, False)
    assert sizes["router_experts"] == ROW["config"]["n_routed_experts"]
    assert sizes["router_experts"] // cfg["n_routed_experts"] == 8
    for said in ("expand", "position_encoding", "ssm_state_dtype",
                 "weights"):
        assert said in cfg["assumed"]
    for said in ("v5e-8", "expert parallelism 8", "rank 0"):
        assert said in cfg["deployment"]


def test_the_bytes_the_issue_reckoned():
    """5.26 B parameters, 10.5 GB in bfloat16; 48.2 MB of SSM state a slot."""
    c = manifest.published(manifest.load_config(BENCH, NAME), False)
    h = c["hidden_size"]
    d_inner = c["mamba_num_heads"] * c["mamba_head_dim"]
    groups = 2 * c["n_groups"] * c["ssm_state_size"]
    mamba = (h * (2 * d_inner + groups + c["mamba_num_heads"])
             + d_inner * h + (c["conv_kernel"] + 1) * (d_inner + groups)
             + 3 * c["mamba_num_heads"] + d_inner + h)
    q, kv = (c["num_attention_heads"] * c["head_dim"],
             c["num_key_value_heads"] * c["head_dim"])
    attention = h * (q + 2 * kv) + q * h + h
    expert = 2 * h * c["moe_intermediate_size"]
    outside = (128 * h + 128 + 2 * h * c["moe_shared_expert_intermediate_size"]
               + h)
    assert round(mamba / 1e6, 2) == 38.74
    assert round(attention / 1e6, 2) == 23.40
    assert round(outside / 1e6, 2) == 20.30
    assert round(expert / 1e6, 3) == 9.978
    total = (23 * mamba + 6 * attention
             + 23 * (outside + c["n_routed_experts"] * expert)
             + 2 * c["vocab_size"] * h + h)
    assert round(total / 1e9, 2) == 5.26
    state = 23 * c["mamba_num_heads"] * c["mamba_head_dim"] \
        * c["ssm_state_size"] * 4
    assert round(state / 1e6, 1) == 48.2


# -- the count functions, by hand -----------------------------------------------

def test_expert_calls_by_hand():
    # 3 experts touched by 5 (token, expert) pairs; hidden 4, width 6, 2 B:
    # an expert's two matrices are 2*4*6 = 48 elements, 96 bytes, three 288;
    # a pair is an up and a down product, 2 * 2*4*6 = 96 operations, five 480
    assert nemotron_h.expert_calls(3, 5, 4, 6, 2) == (480, 288)
    assert nemotron_h.expert_calls(0, 0, 4, 6, 2) == (0, 0)


def test_state_updates_by_hand():
    # 7 slot-steps through 3 layers of a 2 x 3 x 4 float32 state: 21
    # updates of 24 elements, read and written: 192 bytes each, 4032;
    # 5 operations an element: 2520
    assert nemotron_h.state_updates(7, 3, 2, 3, 4) == (2520, 4032)


def test_attention_calls_by_hand():
    # 5 live blocks of 4 positions, rows of 2 K/V heads x 8, 6 query heads,
    # 3 layers, 2 bytes: a layer reads 20 rows of K and of V, 16 elements
    # each: 2*20*16*2 = 1280 bytes, three 3840; q.k^T and p.v over 20
    # positions for 6 heads of 8: 2*2*20*48 = 3840 operations, three 11520
    assert nemotron_h.attention_calls(5, 4, 2, 6, 8, 3, 2) == (11520, 3840)


def test_stepped_tokens_by_hand():
    sizes = dict(block_size=4, hidden=4, vocab=10, mamba_layers=2,
                 attention_layers=1, expert_layers=3, mamba_heads=2,
                 mamba_head_dim=3, groups=1, state_size=5, query_heads=2,
                 kv_heads=1, head_dim=3, router_experts=8, ffn=6,
                 shared_ffn=7)
    # a Mamba layer: d_inner 6, in_proj 4 -> 2*6 + 2*5 + 2 = 24: 2*4*24 = 192,
    # out_proj 2*6*4 = 48, the state 5*6*5 = 150: 390. An attention layer:
    # q 6, k and v 3: 2*4*12 = 96, o 2*6*4 = 48: 144. An expert layer's
    # router 2*4*8 = 64 and shared expert 2*2*4*7 = 112: 176. The head
    # 2*4*10 = 80. A token: 2*390 + 144 + 3*176 + 80 = 1532
    per_token = 1532
    # attention over 5 blocks of 4: 2*2*20*6 = 480; 9 held pairs: 9 * 96
    assert nemotron_h.stepped_tokens(6, 5, 9, **sizes) == (
        6 * per_token + 480 + 9 * 96, 0)
    assert nemotron_h.stepped_tokens(0, 0, 0, **sizes) == (0, 0)


# -- the metric files through their readers ----------------------------------------

LABEL = '{engine="e"}'


def _run(moved):
    """A traced stretch [1.0, 4.0] in which each of the three kernels' events
    take 0.2 s, with the counters of ``moved`` moving inside it."""
    cfg = manifest.load_config(BENCH, NAME)
    traffic = manifest.load_traffic("reasoning_steady")

    def event(name, t0):
        return [f'%{name}.3 = custom-call(), custom_call_target='
                f'"tpu_custom_call", metadata={{op_name="jit(call)/{name}"}}',
                t0, 0.2]

    ops = [event("moe_experts", 1.0), event("ssm_update", 1.5),
           event("paged_attention", 2.0),
           ["%fusion.1 = f32[32,2688]{1,0} fusion()", 2.5, 1.0]]
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
                "serving_moe_held_assignments_total": 6_000,
                "serving_active_slot_steps_total": 10_000,
                "serving_decode_live_blocks_total": 50_000})
    expert = 2 * 2688 * 1856 * 2
    assert _read("moe_experts_roofline", run) == pytest.approx(
        100 * 4_000 * expert / 819e9 / 0.2)
    assert _read("ssm_update_roofline", run) == pytest.approx(
        100 * 10_000 * 23 * 64 * 64 * 128 * 4 * 2 / 819e9 / 0.2)
    assert _read("paged_attention_roofline.gqa", run) == pytest.approx(
        100 * 50_000 * 16 * 256 * 2 * 2 * 6 / 819e9 / 0.2)
    busy = 0.2 * 3 + 1.0
    assert _read("moe_experts_device_share", run) == pytest.approx(
        100 * 0.2 / busy)
    assert _read("ssm_update_device_share", run) == pytest.approx(
        100 * 0.2 / busy)


def test_the_whole_steps_share_is_over_every_event_of_the_stretch():
    run = _run({"serving_active_slot_steps_total": 14_000,
                "serving_decode_live_blocks_total": 50_000,
                "serving_moe_held_assignments_total": 6_000})
    c = run["sizes"]["published"]
    ops, _ = nemotron_h.stepped_tokens(
        14_000, 50_000, 6_000, block_size=16, hidden=c["hidden_size"],
        vocab=c["vocab_size"], mamba_layers=23, attention_layers=6,
        expert_layers=23, mamba_heads=64, mamba_head_dim=64, groups=8,
        state_size=128, query_heads=32, kv_heads=2, head_dim=128,
        router_experts=128, ffn=1856, shared_ffn=3712)
    assert _read("serve_device_mfu", run) == pytest.approx(
        100 * ops / 197e12 / (0.2 * 3 + 1.0))


def test_the_routing_shares_are_ratios_of_counters():
    run = _run({"serving_moe_assignments_total": 48_000,
                "serving_moe_held_assignments_total": 6_000,
                "serving_moe_touched_experts_total": 2_400,
                "serving_decode_steps_total": 10})
    assert _read("moe_held_share", run) == pytest.approx(12.5)
    assert _read("moe_touched_share", run) == pytest.approx(
        100 * 2_400 / 3_680)


def test_a_program_without_the_counters_reads_nothing_and_does_not_raise():
    run = _run({})
    for name in ("moe_experts_roofline", "ssm_update_roofline",
                 "paged_attention_roofline.gqa", "moe_held_share",
                 "moe_touched_share", "serve_device_mfu"):
        assert _read(name, run) is None


# what every serving cell reads, whatever its model: one file and one entry
# each, this cell in the entry's list (copies under ``.nemotron_h`` until
# PR 47)
SHARED = ["decode_step_ms", "decode_occupancy", "prefill_share",
          "device_idle.serve", "serve_requests_finished",
          "generator_lateness_ms", "serve_first_token_ms",
          "serve_inter_token_ms", "serve_token_latency_p90",
          "window_compiles.serve", "decode_logits_fetch_ms",
          "decode_feeds_ms", "decode_sample_ms", "serve_queue_wait_ms",
          "serve_fed_mb_per_step", "serve_fetched_mb_per_step", "serve_shed",
          "decode_live_block_share", "serve_output_rate",
          "paged_attention_device_share"]


def test_the_cell_reports_the_shared_serving_readings_under_their_one_name():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for name in SHARED:
        assert CELL in entries[name]["workloads"], name
        assert "workloads" not in manifest.load_metric(name), name
    assert not [n for n in entries if n.endswith(".nemotron_h")]
    files = os.listdir(os.path.join(ROOT, "benchmark", "metrics"))
    assert not [f for f in files if ".nemotron_h." in f]


# -- the limit that decides ``correct`` --------------------------------------

def test_the_cells_limit_is_a_count_of_answers_not_the_worst_token():
    """``serve.py`` decides by the answers that have a token beyond the
    tolerance among those checked: 40 of 64 here (sound runs read 7 to 26,
    a float8 reference 54 to 64: PERF.md section 2), the first 16 tokens of
    each at 0.25 standard deviations. The rehearsal's tiny size has no
    routing noise to allow for, so every one of its answers is held."""
    traffic = manifest.load_traffic("reasoning_steady")
    n = traffic["check_requests"]
    assert (n, traffic["check_tokens"], traffic["check_tolerance"]) == (
        64, 16, 0.25)
    assert int(n - traffic["check_min_equal"] * n) == 40
    small = manifest.sizes(traffic, True)
    assert (small["check_min_equal"], small["check_tolerance"]) == (1.0, 0.1)


# -- a fault under the timed path ---------------------------------------------

# one Mamba layer's convolution tail put back after every decode step: the
# state the next step reads is a step stale, for every slot. (At the
# rehearsal's size the SSM state itself weighs little beside the D x skip:
# dt is 0.001-0.1 and a prompt a dozen tokens; a stale tail moves every
# token's x, B and C.)
STATE_STALE = '''
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


def test_a_stale_state_row_reads_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    code = (f"import sys; sys.path.insert(0, {ROOT!r})\n"
            "from benchmark import run\n" + STATE_STALE +
            f"run.main(['--workload', {CELL!r}, '--seed', '3600000077', "
            "'--seconds', '1', '--trace', '0', '--rehearse-cpu'])\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    failed = [name for name, n in line["compared"].items() if not n["holds"]]
    assert failed == ["worst_token_sigma_behind", "checked_answers_wrong"]
    assert line["compared"]["worst_token_sigma_behind"]["value"] > 10 * \
        line["compared"]["worst_token_sigma_behind"]["limit"]
