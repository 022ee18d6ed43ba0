"""The ``granite_4_0_h_micro`` configuration and its cell: the file against
the catalog's row, the count functions by hand, the metric files through
their readers, the cell rehearsed on the CPU, and the cell's own comparison
on sound answers and on the controls a CPU can plant.

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
from benchmark.counts import granite_hybrid, lfm2, nemotron_h  # noqa: E402
from tools import check_hybrid_logits  # noqa: E402

BENCH = manifest.load_manifest()
NAME = "granite_4_0_h_micro"
CELL = NAME + ".doc_qa_long"
# the catalog's row in a fixture of its own (catalog_rows.json is an
# accepted file and takes no new row)
with open(os.path.join(ROOT, "tests", "benchmark_grid",
                       "catalog_row_granite.json")) as _f:
    (ROW,) = json.load(_f)["rows"]


# -- the configuration's file ---------------------------------------------------

def test_the_file_passes_the_drivers_rule_against_the_catalogs_row():
    (entry,) = [c for c in BENCH["configs"] if c["name"] == NAME]
    cfg = manifest.load_config(BENCH, NAME)
    assert ROW["name"] == "granite-4.0-h-micro"
    assert entry["source"] == cfg["source"] == ROW["source_url"]
    assert manifest.check_against_source(cfg, entry["reduced"],
                                         ROW["config"]) is None
    assert cfg["source_values"] == ROW["config"]
    assert sorted(cfg["source_keys"]) == sorted(ROW["config"])
    # nothing is cut: every key of the row stands as published, the two
    # zeros, the group that is a list and the published null among them
    assert cfg["reduced"] == entry["reduced"] == []
    assert all(cfg[k] == v for k, v in ROW["config"].items())
    assert cfg["num_local_experts"] == 0 and cfg["num_experts_per_tok"] == 0
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] == 40
    assert [i for i, kind in enumerate(cfg["layer_types"])
            if kind == "attention"] == [5, 15, 25, 35]
    assert cfg["rope_scaling"] is None and cfg["rope_theta"] == 10000


def test_the_model_the_issue_sized():
    cfg = manifest.load_config(BENCH, NAME)
    sizes = manifest.model_sizes(cfg, False)
    assert sizes == {"slots": 32, "max_len": 16896, "block_size": 16,
                     "num_blocks": 16896, "chunk_tokens": 512}
    assert sizes["max_len"] == 16384 + 512
    assert sizes["chunk_tokens"] == 2 * cfg["mamba_chunk_size"]
    # half of what every slot at full length would take: admission reserves
    assert sizes["num_blocks"] * 2 == sizes["slots"] * sizes["max_len"] // 16
    assert cfg["settings"] == {
        "dtype": "bfloat16", "state_dtype": "float32",
        "initializer_range": 0.02, "qk_initializer_range": 0.125,
        "engine": {"prefix_cache_size": 0, "host_tier_mb": 0},
        "rehearsal": {"initializer_range": 0.5, "qk_initializer_range": 0.5}}
    # the scaled scores' standard deviation under independent draws:
    # sqrt(head) x hidden x range^2 x attention_multiplier. 0.1 at the
    # family's 0.02 (a uniform softmax: K and the mask's edge go unread);
    # q and k are drawn so that it is 4 (a query's first row takes some 0.4)
    head = cfg["hidden_size"] // cfg["num_attention_heads"]
    spread = lambda r: (head ** 0.5 * cfg["hidden_size"] * r * r  # noqa: E731
                        * cfg["attention_multiplier"])
    assert abs(spread(cfg["settings"]["initializer_range"]) - 0.1024) < 1e-9
    assert spread(cfg["settings"]["qk_initializer_range"]) == 4.0
    assert "qk_initializer_range" in cfg["assumed"]["weights"]
    for said in ("rope_theta", "mamba_expand", "intermediate_size", "mlp",
                 "time_steps", "ssm_state_dtype", "activations", "weights",
                 "slots", "num_blocks", "chunk_tokens", "engine"):
        assert said in cfg["assumed"]
    assert "not read" in cfg["assumed"]["rope_theta"]
    assert "sqrt(40)" in cfg["assumed"]["weights"]
    for said in ("one v5e chip", "whole", "replicas"):
        assert said in cfg["deployment"]
    small = manifest.published(cfg, True)
    assert {"mamba", "attention"} == set(small["layer_types"])
    assert small["mamba_n_groups"] == 1


def test_the_bytes_the_issue_reckoned():
    """3.19 B parameters, 6.38 GB in bfloat16; 8,192 bytes of K and V a
    token; 77.4 MB of state and tails a slot; 2.21 GB of rows held."""
    c = manifest.published(manifest.load_config(BENCH, NAME), False)
    h, f = c["hidden_size"], c["shared_intermediate_size"]
    inner = c["mamba_n_heads"] * c["mamba_d_head"]
    conv = inner + 2 * c["mamba_n_groups"] * c["mamba_d_state"]
    in_width = 2 * inner + 2 * c["mamba_n_groups"] * c["mamba_d_state"] \
        + c["mamba_n_heads"]
    assert (inner, conv, in_width) == (4096, 4352, 8512)
    mamba = h * in_width + conv * (c["mamba_d_conv"] + 1) + inner * h \
        + 3 * c["mamba_n_heads"] + inner
    d = h // c["num_attention_heads"]
    kv = c["num_key_value_heads"] * d
    attention = 2 * h * h + 2 * h * kv
    mlp = 3 * h * f
    assert round(mamba / 1e6, 2) == 25.85 and attention == 10_485_760
    assert mlp == 50_331_648
    whole = 36 * (mamba + mlp) + 4 * (attention + mlp) + c["vocab_size"] * h
    assert round(whole / 1e9, 2) == 3.19
    assert round(2 * whole / 1e9, 2) == 6.38
    assert 4 * 2 * kv * 2 == 8192
    slot = 36 * (c["mamba_n_heads"] * c["mamba_d_head"] * c["mamba_d_state"]
                 + (c["mamba_d_conv"] - 1) * conv) * 4
    assert round(slot / 1e6, 1) == 77.4
    assert round(16896 * 16 * 8192 / 1e9, 2) == 2.21


# -- the count functions, by hand -----------------------------------------------

def test_chunk_attention_calls_by_hand():
    # two chunks of 4 real positions, one at the prompt's start and one
    # behind 4 rows: the masks open 1+2+3+4 and 5+6+7+8 pairs, 46; the rows
    # behind are 0 + 4. q.k^T and p.v for 6 heads of 8 over 46 pairs:
    # 2*2*46*48 = 8832 operations a layer, three 26496; a chunk has to read
    # the rows it can see once, 4 and 8: 12 rows of 2 K/V heads x 8 in K
    # and in V, 2 bytes: 2*12*16*2 = 768 a layer, three 2304
    assert granite_hybrid.chunk_attention_calls(
        46, 4, 8, 2, 6, 8, 3, 2) == (26496, 2304)
    assert granite_hybrid.chunk_attention_calls(
        0, 0, 0, 2, 6, 8, 3, 2) == (0, 0)


def test_served_tokens_by_hand():
    sizes = dict(block_size=4, hidden=4, vocab=10, mamba_layers=2,
                 attention_layers=1, mamba_heads=2, mamba_head_dim=3,
                 groups=1, state_size=5, query_heads=2, kv_heads=1,
                 head_dim=2, ffn=6)
    # a Mamba layer: inner 6, in_proj to 2*6 + 2*5 + 2 = 24: 2*4*24 = 192,
    # out_proj 2*6*4 = 48, the state update 5*6*5 = 150: 390, two 780.
    # The attention layer: q 4, k and v 2: 2*4*8 = 64, o 2*4*4 = 32: 96.
    # The MLP of all three layers: 3 * 3*2*4*6 = 432. A token: 1308.
    # The head, a stepped token alone: 2*4*10 = 80.
    # Attention over rows: 7 live blocks of 4 = 28 positions for the steps
    # and 46 pairs for the chunks, 2*2*4 = 16 each: 16 * 74 = 1184
    assert granite_hybrid.served_tokens(5, 7, 8, 46, **sizes) == (
        13 * 1308 + 5 * 80 + 1184, 0)
    assert granite_hybrid.served_tokens(0, 0, 0, 0, **sizes) == (0, 0)
    # the steps alone are what the other hybrids' functions count, less
    # their experts
    assert granite_hybrid.served_tokens(5, 7, 0, 0, **sizes)[0] == \
        5 * (1308 + 80) + 16 * 28


# -- the metric files through their readers ----------------------------------------

LABEL = '{engine="e"}'
NEW = ["chunk_attention_roofline", "chunk_attention_device_share",
       "ssm_scan_device_share", "chunk_context_tokens",
       "prefill_tokens_per_s", "serve_device_mfu.granite",
       "ssm_update_roofline.granite", "paged_attention_roofline.gqa64x4"]
# the readings whose file differs by configuration (a count function, a
# layer count): the cell reports its own and not the ones it parts from
OWN = {"serve_device_mfu.granite": "serve_device_mfu.lfm2",
       "ssm_update_roofline.granite": "ssm_update_roofline",
       "paged_attention_roofline.gqa64x4": "paged_attention_roofline.gqa64"}


def _run(moved, histograms=None):
    """A traced stretch [1.0, 4.0] in which each kernel's events take
    0.2 s and two scans' loops 0.1 s each, with the counters of ``moved``
    moving inside it (and the histograms' sums over the window)."""
    cfg = manifest.load_config(BENCH, NAME)
    traffic = manifest.load_traffic("doc_qa_long")

    def event(name, t0):
        return [f'%{name}.3 = custom-call(), custom_call_target='
                f'"tpu_custom_call", metadata={{op_name="jit(call)/{name}"}}',
                t0, 0.2]

    ops = [event("chunk_attention", 1.0), event("paged_attention", 2.0),
           event("ssm_update", 3.0),
           ["%while.7 = (s32[], f32[64,64,128]{2,1,0}) while(%tuple.1), "
            "condition=%cond, body=%body", 1.4, 0.1],
           ["%while.9 = (s32[], f32[64,64,128]{2,1,0}) while(%tuple.2), "
            "condition=%cond, body=%body", 1.6, 0.1],
           ["%fusion.1 = f32[512,2048]{1,0} fusion(%while.7)", 2.5, 0.2]]
    before = {family: {LABEL: 100} for family in moved}
    after = {family: {LABEL: 100 + n} for family, n in moved.items()}
    for family, total in (histograms or {}).items():
        before[family] = {LABEL: {"sum": 10.0, "count": 2}}
        after[family] = {LABEL: {"sum": 10.0 + total, "count": 9}}
    device = {"ops": ops, "async_ops": [], "modules": []}
    return {"trace": {"devices": {"0": device}},
            "trace_window": (1.0, 4.0), "spans": [],
            "registry": (before, after), "stretch_registry": [before, after],
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            "sizes": manifest.run_sizes(cfg, traffic, 1, False),
            "facts": {"window_s": 51.0}, "config": cfg, "chips": 1}


def _read(name, run):
    spec = manifest.load_metric(name)
    return readers.READERS[spec["reader"]](spec["args"], run)


def test_the_chunk_kernels_roofline_follows_the_counters_of_the_stretch():
    moved = {"serving_chunk_attended_rows_total": 3_000_000_000,
             "serving_chunk_context_rows_total": 900_000,
             "serving_chunk_tokens_total": 100_000}
    ops, moved_bytes = granite_hybrid.chunk_attention_calls(
        3_000_000_000, 900_000, 100_000, 8, 32, 64, 4, 2)
    assert ops == 4 * 2 * 2 * 3_000_000_000 * 2048
    assert moved_bytes == 4 * 2 * 1_000_000 * 512 * 2
    # bound by the operations at these lengths: 0.499 s at peak
    assert ops / 197e12 > moved_bytes / 819e9
    assert _read("chunk_attention_roofline", _run(moved)) == pytest.approx(
        100 * ops / 197e12 / 0.2)
    assert _read("chunk_attention_device_share", _run(moved)) == \
        pytest.approx(100 * 0.2 / 1.0)


def test_the_scans_share_is_the_loops_alone():
    # two loops of 0.1 s in a second of busy time; the fusion that names a
    # loop as its operand is not one
    assert _read("ssm_scan_device_share", _run({})) == pytest.approx(20.0)


def test_the_step_kernels_rooflines_at_this_models_layer_counts():
    run = _run({"serving_active_slot_steps_total": 3_000,
                "serving_decode_live_blocks_total": 900_000})
    state = 36 * 64 * 64 * 128 * 4 * 2
    assert _read("ssm_update_roofline.granite", run) == pytest.approx(
        100 * 3_000 * state / 819e9 / 0.2)
    # live blocks x 16 rows x 512 x 2 B x K and V x 4 layers
    assert _read("paged_attention_roofline.gqa64x4", run) == pytest.approx(
        100 * 900_000 * 16 * 1024 * 2 * 4 / 819e9 / 0.2)
    assert nemotron_h.state_updates(3_000, 36, 64, 64, 128)[1] == \
        3_000 * state
    assert lfm2.attention_calls(900_000, 16, 8, 32, 64, 4, 2)[1] == \
        900_000 * 16 * 1024 * 2 * 4


def test_the_whole_devices_share_counts_steps_and_chunks():
    moved = {"serving_active_slot_steps_total": 3_000,
             "serving_decode_live_blocks_total": 900_000,
             "serving_chunk_tokens_total": 100_000,
             "serving_chunk_attended_rows_total": 3_000_000_000}
    ops, _ = granite_hybrid.served_tokens(
        3_000, 900_000, 100_000, 3_000_000_000, block_size=16, hidden=2048,
        vocab=100352, mamba_layers=36, attention_layers=4, mamba_heads=64,
        mamba_head_dim=64, groups=1, state_size=128, query_heads=32,
        kv_heads=8, head_dim=64, ffn=8192)
    # some 6 GFLOP a token before attention's rows, and the chunks' tokens
    # carry the sum
    over_rows = 4 * 2 * 2 * 2048 * (900_000 * 16 + 3_000_000_000)
    assert 6.0e9 < (ops - over_rows) / 103_000 < 6.2e9
    assert _read("serve_device_mfu.granite", _run(moved)) == pytest.approx(
        100 * ops / 197e12 / 1.0)


def test_the_scheduler_readings_are_ratios_of_counters():
    run = _run({"serving_chunk_context_rows_total": 300_000,
                "serving_chunk_runs_total": 100,
                "serving_chunk_tokens_total": 40_000},
               histograms={"serving_chunk_prefill_tokens": 40_800.0})
    assert _read("chunk_context_tokens", run) == pytest.approx(3_000.0)
    assert _read("chunk_tokens_per_launch", run) == pytest.approx(400.0)
    assert _read("prefill_tokens_per_s", run) == pytest.approx(800.0)


def test_the_cell_lists_no_reading_by_launch_span():
    """``device_seconds_per_span`` gives a module to the host span that
    started last before it: in a cell whose device is behind its launches
    the chunk's and the step's modules start under a later fetch, and the
    traced run read 1.4 ms for a chunk of ~50 and 0.06 ms for a step of ~10
    (PR 51). The cell reports neither reading, as the other hybrids'."""
    mine = {m["name"] for m in manifest.metrics_of(BENCH, "per_layer", CELL)}
    by_span = {m["name"] for m in BENCH["per_layer"]
               if manifest.load_metric(m["name"])["reader"]
               == "device_seconds_per_span"}
    assert "decode_step_device_ms" in by_span
    assert not by_span & mine


def test_a_program_without_the_counters_reads_nothing_and_does_not_raise():
    """What the parent commit's program gives the new readers: no counter,
    no histogram, no kernel and no loop of these names."""
    run = _run({})
    run["trace"]["devices"]["0"]["ops"] = [
        ["%fusion.1 = f32[512,2048]{1,0} fusion()", 2.5, 0.2]]
    for name in NEW:
        assert _read(name, run) is None, name
    run["trace"] = None
    for name in NEW:
        assert _read(name, run) is None, name


def test_every_new_metric_lists_the_cell_and_is_registered():
    mine = {m["name"]: m
            for m in manifest.metrics_of(BENCH, "per_layer", CELL)}
    assert set(NEW) <= set(mine) and len(mine) >= 40
    for name, entry in mine.items():
        spec = manifest.load_metric(name)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == entry[key], (name, key)
        assert "workloads" not in spec      # the entry alone lists the cells
    for name in NEW:
        assert CELL in mine[name]["workloads"]
    # the lists the state-space cell is in and whose reading this system
    # gives: every one but the routed experts' and the files of its own
    theirs = {m["name"] for m in manifest.metrics_of(
        BENCH, "per_layer", "nemotron3_nano_30b_a3b.reasoning_steady")}
    apart = {n for n in theirs if n.startswith("moe_")} | {
        "serve_device_mfu", "ssm_update_roofline",
        "paged_attention_roofline.gqa"}
    assert theirs - apart <= set(mine)
    assert not apart & set(mine)
    (latency,) = [m for m in BENCH["end_to_end"]
                  if m["name"] == "serve_token_latency_p50"]
    assert CELL in latency["workloads"]
    assert len(BENCH["per_layer"]) <= 128
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len(BENCH["workloads"]) >= 9


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
    t = manifest.load_traffic("doc_qa_long")
    assert (t["kind"], t["arrivals"], t["sharing"]) == (
        "open_loop", "poisson", "none")
    assert (t["preroll_s"], t["block_requests"], t["max_total_len"]) == (
        30, 16, 16895)
    assert t["prompt_len"] == {"dist": "lognormal", "median": 4096,
                               "sigma": 0.8, "min": 512, "max": 16384}
    assert t["answer_len"] == {"dist": "lognormal", "median": 128,
                               "sigma": 0.6, "min": 32, "max": 512}
    assert t["check_tokens"] <= t["answer_len"]["min"]
    # of the checked prompts, drawn from the finished ones as they come,
    # at least four are expected over 8,192: a fifth of the stratified
    # lengths is
    from benchmark import workgen

    lengths = workgen.stratified_lengths(t["prompt_len"], 1000)
    long_share = sum(n > 8192 for n in lengths) / 1000
    assert 0.18 < long_share < 0.21
    assert t["check_requests"] * long_share >= 4
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200


# -- the cell, rehearsed ---------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_and_prints_every_metric_a_cpu_run_can_name(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "5100000077", "--seconds", "1",
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
    assert {"chunk_context_tokens", "prefill_tokens_per_s", "prefill_share",
            "chunk_tokens_per_launch", "reserved_blocks_per_admission",
            "decode_step_ms"} <= want
    assert all(m["value"] is None for m in line["metrics"].values())


# -- the cell's own comparison: sound answers, and the controls -------------------

class _Sent:
    def __init__(self, prompt, response):
        self.prompt, self.response = prompt, response


@pytest.fixture(scope="module")
def served():
    """The cell's system at its rehearsal size: a dozen requests served
    sound; the same prompts with the first Mamba layer's state dropped at
    the boundary before each prompt's last chunk; and with the first
    attention layer's K arena a chunk stale there (the tool's own
    faults)."""
    import importlib

    from benchmark import workgen

    cfg = manifest.load_config(BENCH, NAME)
    traffic = manifest.sizes(manifest.load_traffic("doc_qa_long"), True)
    builder = importlib.import_module("benchmark.builders." + cfg["builder"])
    system = builder.build(cfg, traffic, 5100000078, True)
    rng = np.random.default_rng(5)
    # every prompt longer than a chunk of 8: each has the faulted boundary
    lengths = [n for n in workgen.stratified_lengths(
        traffic["prompt_len"], 24) if n > 8][:12]
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
        for fault in ("chunk_ssm", "chunk_kv"):
            undo = check_hybrid_logits._chunk_fault(system.entry, fault)
            runs[fault] = serve()
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
    assert (checked, right) == (12, 12)
    assert worst <= traffic["check_tolerance"]


@pytest.mark.parametrize("fault", ["chunk_ssm", "chunk_kv"])
def test_one_chunk_boundary_gone_wrong_reads_not_correct(served, fault):
    system, traffic, runs = served
    checked, right, worst = _check(system, runs[fault], traffic)
    assert right < traffic["check_min_equal"] * checked
    assert worst > traffic["check_tolerance"]


@pytest.mark.parametrize("control", [
    {"round_to": "float8_e4m3fn"}, {"attention_multiplier": 0.125},
    {"residual_multiplier": 1.0}],
    ids=["reference_in_float8", "attention_multiplier_as_one_eighth",
         "residual_multiplier_as_one"])
def test_a_reference_read_otherwise_reads_not_correct(served, control):
    """The sound tokens against the reference in the precision below the
    served one, or with one multiplier misread: the published 1/64 read as
    the usual 1/sqrt(64) (here 1/16 as 1/8), the residual's 0.22 as 1."""
    system, traffic, runs = served
    checked, right, worst = _check(system, runs["sound"], traffic, **control)
    assert right < traffic["check_min_equal"] * checked
    assert worst > traffic["check_tolerance"]
