"""The ``mistral_small_4_119b`` configuration and its cell: the file against
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
from benchmark.counts import lfm2, mistral4  # noqa: E402
from tools import check_hybrid_logits  # noqa: E402

BENCH = manifest.load_manifest()
NAME = "mistral_small_4_119b"
CELL = NAME + ".doc_qa_32k"
with open(os.path.join(ROOT, "tests", "benchmark_grid",
                       "catalog_row_mistral_small_4.json")) as _f:
    ROW = json.load(_f)
CUT = {"num_hidden_layers": 12, "n_routed_experts": 16, "vocab_size": 16384}


# -- the configuration's file ---------------------------------------------------

def test_the_file_passes_the_drivers_rule_against_the_catalogs_row():
    (entry,) = [c for c in BENCH["configs"] if c["name"] == NAME]
    cfg = manifest.load_config(BENCH, NAME)
    assert ROW["name"] == "Mistral-Small-4-119B-2603"
    assert entry["source"] == cfg["source"] == ROW["source_url"]
    assert manifest.check_against_source(cfg, entry["reduced"],
                                         ROW["config"]) is None
    assert cfg["source_values"] == ROW["config"]
    assert sorted(cfg["source_keys"]) == sorted(ROW["config"])
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == sorted(CUT)
    for key, theirs in ROW["config"].items():
        assert cfg[key] == CUT.get(key, theirs), key
    # the nested group whole, and no width moved
    assert cfg["rope_parameters"] == ROW["config"]["rope_parameters"]
    for width in ("hidden_size", "q_lora_rank", "kv_lora_rank",
                  "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                  "moe_intermediate_size", "num_experts_per_tok",
                  "num_attention_heads"):
        assert cfg[width] == ROW["config"][width]


@pytest.mark.parametrize("change,complaint", [
    ({"kv_lora_rank": 128}, "kv_lora_rank"),
    ({"rope_parameters": {"factor": 64}}, "rope_parameters.factor"),
    ({"num_hidden_layers": 36, "reduced": ["n_routed_experts",
                                          "vocab_size"]}, "reduced"),
    ({"num_experts_per_tok": 2}, "num_experts_per_tok")])
def test_the_rule_refuses_a_width_or_an_unlisted_cut(change, complaint):
    cfg = dict(manifest.load_config(BENCH, NAME))
    reduced = change.pop("reduced", cfg["reduced"])
    for key, value in change.items():
        cfg[key] = dict(cfg[key], **value) if isinstance(value, dict) \
            else value
    said = manifest.check_against_source(dict(cfg, reduced=reduced), reduced,
                                         ROW["config"])
    if complaint == "reduced":
        # 36 layers is the source's own: the cut is gone, and a list that
        # still names it is not refused for that; the rule holds what
        # DIFFERS to the list
        assert said is None
    else:
        assert said is not None and complaint in said


def test_the_model_the_issue_sized():
    cfg = manifest.load_config(BENCH, NAME)
    sizes = manifest.model_sizes(cfg, False)
    assert sizes["router_experts"] == 128 and sizes["block_size"] == 16
    assert sizes["max_len"] == 32768 + 512
    assert sizes["slots"] == 16
    # a pool smaller than slots x length: admission reserves chains
    assert sizes["num_blocks"] * 16 == 262144 < 16 * sizes["max_len"]
    assert sizes["chunk_tokens"] == 1024
    assert cfg["settings"]["dtype"] == "bfloat16"
    assert cfg["settings"]["expert_rank"] == 0
    assert cfg["settings"]["engine"] == {"prefix_cache_size": 0,
                                         "host_tier_mb": 0}
    for said in ("router_scoring", "softmax_scale", "pairing", "vision",
                 "draws", "arena_row"):
        assert said in cfg["assumed"]
    assert "softmax" in cfg["assumed"]["router_scoring"]
    assert "1.4852" in cfg["assumed"]["softmax_scale"]
    assert "interleaved" in cfg["assumed"]["pairing"]
    for said in ("24 v5e chips", "3 pipeline stages", "8 chips",
                 "nothing stands in"):
        assert said in cfg["deployment"]
    small = manifest.published(cfg, True)
    assert small["rope_parameters"]["original_max_position_embeddings"] == 16
    assert small["qk_nope_head_dim"] + small["qk_rope_head_dim"] == \
        small["qk_head_dim"]


def test_the_bytes_the_issue_reckoned():
    """A layer outside its routed experts 107.5 MB, a routed expert 50.3 MB,
    this chip 10.95 GB of layers and 0.27 GB of vocabulary; 640 B of cache
    a token a layer required, 768 stored; the pool 2.42 GB."""
    c = manifest.published(manifest.load_config(BENCH, NAME), False)
    h, heads = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    attention = (h * c["q_lora_rank"] + c["q_lora_rank"] * heads * qk
                 + h * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
                 + c["kv_lora_rank"] * heads * (c["qk_nope_head_dim"]
                                                + c["v_head_dim"])
                 + heads * c["v_head_dim"] * h)
    assert round(attention / 1e6, 2) == 28.05
    expert = 3 * h * c["moe_intermediate_size"]
    assert round(2 * expert / 1e6, 1) == 50.3
    norms = 2 * h + c["q_lora_rank"] + c["kv_lora_rank"]
    layer = attention + 128 * h + expert + norms
    assert round(2 * layer / 1e6, 1) == 107.5
    held = 12 * (16 * expert + layer)
    assert round(2 * held / 1e9, 2) == 10.95
    assert round(2 * 2 * 16384 * h / 1e9, 2) == 0.27
    assert 128 * 2 * expert * 36 > 16e9 * 24 / 3     # no chip holds 4 whole
    assert (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * 2 == 640
    assert round(262144 * 384 * 2 * 12 / 1e9, 2) == 2.42


# -- the count functions, by hand -----------------------------------------------

def test_latent_step_calls_by_hand():
    # 5 live blocks of 4 rows, 3 layers: 20 positions. A row is 6 + 2
    # elements of 2 bytes read once for all heads: 3 * 20 * 16 = 960 B;
    # absorbed, each of 2 heads scores over 8 lanes and sums 6: 2 * (8 + 6)
    # = 28 operations a head and position: 3 * 20 * 2 * 28 = 3360
    assert mistral4.latent_step_calls(5, 4, 3, 2, 6, 2, 2) == (3360, 960)
    assert mistral4.latent_step_calls(0, 4, 3, 2, 6, 2, 2) == (0, 0)
    # the published widths: 640 B a row a layer, 57 operations a byte
    ops, moved = mistral4.latent_step_calls(1, 1, 1, 32, 256, 64, 2)
    assert moved == 640 and ops == 32 * 2 * (320 + 256) and ops // moved == 57


def test_latent_chunk_calls_by_hand():
    # 46 opened pairs, 4 rows behind, 8 real positions, 3 layers, 2 heads
    # of 3 + 2 | 5 over a latent of 6. Expanded: 2 * (3 + 2 + 5) = 20 a
    # head and pair and 2 * 6 * 2 * (3 + 5) = 192 a row of the 12 a chunk
    # can see: 3 * (46 * 2 * 20 + 12 * 192) = 12432; the 12 rows read
    # once: 3 * 12 * 8 * 2 = 576 B
    sizes = dict(layers=3, heads=2, nope=3, rope=2, value=5, latent=6,
                 bytes_per_el=2)
    assert mistral4.latent_chunk_calls(46, 4, 8, **sizes) == (12432, 576)
    assert mistral4.latent_chunk_calls(0, 0, 0, **sizes) == (0, 0)
    # the published widths: a pair 16,384 operations (the step's absorbed
    # pair costs 36,864: latent_step_calls), a row 3.1 M to up-project
    sizes = dict(layers=1, heads=32, nope=64, rope=64, value=128, latent=256,
                 bytes_per_el=2)
    assert mistral4.latent_chunk_calls(1, 0, 0, **sizes)[0] == 16384
    assert mistral4.latent_chunk_calls(0, 1, 0, **sizes) == (3145728, 640)


def test_grouped_calls_by_hand():
    # 10 pairs through three matrices of 4 x 6: 10 * 3 * 2 * 24 = 1440;
    # 3 touched experts' three matrices once: 3 * 3 * 24 * 2 = 432 B
    assert mistral4.grouped_calls(10, 3, 4, 6, 2) == (1440, 432)
    # gated experts' matrices as the step kernel's count reads them
    assert mistral4.grouped_calls(7, 5, 4096, 2048, 2)[1] == \
        lfm2.expert_calls(5, 7, 4096, 2048, 2)[1]


def test_served_tokens_by_hand():
    sizes = dict(block_size=4, layers=2, hidden=4, vocab=10, heads=2,
                 q_rank=3, latent=6, nope=3, rope=2, value=5, ffn=7,
                 shared_experts=1, router_experts=8)
    # projections 2 * (4*3 + 3*2*5 + 4*8 + 6*2*8 + 2*5*4) = 420; router
    # 2*4*8 = 64; shared 3*2*4*7 = 168: 652 a layer, 1304 a token; the
    # head 2*4*10 = 80 a stepped token; attention 2 * 2 * (3+2+5) = 40 a
    # row and layer: 2 * 40 * (7*4 + 46) = 5920; 9 + 11 pairs of 168
    assert mistral4.served_tokens(5, 7, 8, 46, 9, 11, **sizes) == (
        13 * 1304 + 5 * 80 + 5920 + 20 * 168, 0)
    assert mistral4.served_tokens(0, 0, 0, 0, 0, 0, **sizes) == (0, 0)


def test_a_prompt_token_at_the_traffics_mean_context():
    """The issue's arithmetic, by the count function: a prompt token costs
    56 MFLOP a layer in latent attention's projections; at 8k of context
    its pairs cost 134 MFLOP expanded (the issue's 168 took a key of 192
    lanes: the published head is 64 + 64 | 128), by the model's count and
    by the chunk loops' own."""
    sizes = dict(block_size=16, layers=1, hidden=4096, vocab=16384,
                 heads=32, q_rank=1024, latent=256, nope=64, rope=64,
                 value=128, ffn=2048, shared_experts=1, router_experts=128)
    bare = mistral4.served_tokens(0, 0, 1, 0, 0, 0, **sizes)[0]
    assert round((bare - 2 * 4096 * 128 - 6 * 4096 * 2048) / 1e6, 1) == 56.1
    pairs = mistral4.served_tokens(0, 0, 1, 8192, 0, 0, **sizes)[0] - bare
    assert round(pairs / 1e6) == 134
    assert round(mistral4.latent_chunk_calls(
        8192, 0, 0, 1, 32, 64, 64, 128, 256, 2)[0] / 1e6) == 134


# -- the metric files through their readers ----------------------------------------

LABEL = '{engine="e"}'
NEW = ["latent_attention_roofline", "latent_chunk_attention_roofline",
       "latent_attention_device_share", "moe_grouped_roofline",
       "moe_grouped_padding_share", "serve_device_mfu.mistral4"]


def _run(moved):
    """A traced stretch [1.0, 4.0] of a second of busy time in which each
    of the five kernels' events takes 0.2 s (the chunks' attention is a
    while loop of XLA's, named as the chip names one: opcode and carried
    shapes, no scope), with the counters of ``moved`` moving inside it."""
    cfg = manifest.load_config(BENCH, NAME)
    traffic = manifest.load_traffic("doc_qa_32k")

    def event(name, t0):
        return [f'%{name}.3 = custom-call(), custom_call_target='
                f'"tpu_custom_call", metadata={{op_name="jit(call)/{name}"}}',
                t0, 0.2]

    ops = [['%while.9 = (s32[], f32[512,32,128]{2,1,0}) while(%tuple.2), '
            'condition=%cond, body=%body', 1.0, 0.2],
           # a fusion of the loop's body is no second count of its time
           ['%fusion.8 = f32[512,32,512]{2,1,0} fusion(), metadata={op_name='
            '"jit(decode_x_chunk)/latent_chunk_expanded/while/body/exp"}',
            1.05, 0.05],
           event("latent_paged_attention", 1.5), event("moe_grouped", 2.0),
           event("moe_experts", 2.5),
           ["%fusion.1 = f32[512,4096]{1,0} fusion()", 3.0, 0.2]]
    before = {family: {LABEL: 100} for family in moved}
    after = {family: {LABEL: 100 + n} for family, n in moved.items()}
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


def test_the_step_kernels_roofline_is_by_bytes():
    run = _run({"serving_decode_live_blocks_total": 900_000})
    moved = 900_000 * 16 * 640 * 12
    ops = 900_000 * 16 * 12 * 32 * 2 * (320 + 256)
    assert moved / 819e9 > ops / 197e12
    assert _read("latent_attention_roofline", run) == pytest.approx(
        100 * moved / 819e9 / 0.2)


def test_the_chunks_roofline_is_by_the_operations_of_the_expanded_form():
    moved = {"serving_chunk_attended_rows_total": 1_000_000_000,
             "serving_chunk_context_rows_total": 900_000,
             "serving_chunk_tokens_total": 100_000}
    sizes = (1_000_000_000, 900_000, 100_000, 12, 32, 64, 64, 128, 256)
    ops, moved_bytes = mistral4.latent_chunk_calls(*sizes, 2)
    assert ops == 12 * (1_000_000_000 * 16384 + 1_000_000 * 3145728)
    assert moved_bytes == 12 * 1_000_000 * 640
    assert ops / 197e12 > moved_bytes / 819e9
    assert _read("latent_chunk_attention_roofline", _run(moved)) == \
        pytest.approx(100 * ops / 197e12 / 0.2)


def test_both_forms_share_of_the_busy_device_and_the_lists_it_joins():
    """The step's kernel by its name and the chunks' loops by theirs; the
    accepted share of the paged kernel reads the step's by its name's tail,
    the accepted share of the chunk KERNEL has nothing to read (the cell is
    not on its list)."""
    run = _run({})
    assert _read("latent_attention_device_share", run) == pytest.approx(40.0)
    assert _read("paged_attention_device_share", run) == pytest.approx(20.0)
    assert _read("chunk_attention_device_share", run) is None
    assert _read("moe_experts_device_share", run) == pytest.approx(20.0)
    assert NAME + ".doc_qa_32k" not in next(
        m for m in BENCH["per_layer"]
        if m["name"] == "chunk_attention_device_share")["workloads"]


def test_the_grouped_products_readings():
    moved = {"serving_moe_grouped_pairs_total": 3_000_000,
             "serving_moe_grouped_rows_total": 24_000_000,
             "serving_moe_grouped_experts_total": 180_000}
    weights = 180_000 * 3 * 4096 * 2048 * 2
    assert weights / 819e9 > 3_000_000 * 6 * 4096 * 2048 / 197e12
    assert _read("moe_grouped_roofline", _run(moved)) == pytest.approx(
        100 * weights / 819e9 / 0.2)
    assert _read("moe_grouped_padding_share", _run(moved)) == \
        pytest.approx(800.0)


def test_the_whole_devices_share_counts_steps_and_chunks():
    moved = {"serving_active_slot_steps_total": 3_000,
             "serving_decode_live_blocks_total": 900_000,
             "serving_chunk_tokens_total": 100_000,
             "serving_chunk_attended_rows_total": 1_000_000_000,
             "serving_moe_held_assignments_total": 18_000,
             "serving_moe_grouped_pairs_total": 600_000}
    ops, _ = mistral4.served_tokens(
        3_000, 900_000, 100_000, 1_000_000_000, 18_000, 600_000,
        block_size=16, layers=12, hidden=4096, vocab=16384, heads=32,
        q_rank=1024, latent=256, nope=64, rope=64, value=128, ffn=2048,
        shared_experts=1, router_experts=128)
    # (every event's time, the loop's body's fusion beside its loop: 1.05 s)
    assert _read("serve_device_mfu.mistral4", _run(moved)) == pytest.approx(
        100 * ops / 197e12 / 1.05)


def test_a_program_without_the_counters_reads_nothing_and_does_not_raise():
    """What the parent commit's program gives the new readers: no counter
    and no kernel of these names."""
    run = _run({})
    run["trace"]["devices"]["0"]["ops"] = [
        ["%fusion.1 = f32[512,2048]{1,0} fusion()", 2.5, 0.2]]
    for name in NEW:
        if name != "serve_device_mfu.mistral4":
            assert _read(name, run) is None, name
    run["stretch_registry"] = None
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
        assert "workloads" not in spec
    for name in NEW:
        assert mine[name]["workloads"] == [CELL]
    # the lists the document cell before it is in and whose reading this
    # system gives: every one but the state-space layers' and the files of
    # that model's own; and the routed experts' where the args do not differ
    theirs = {m["name"] for m in manifest.metrics_of(
        BENCH, "per_layer", "granite_4_0_h_micro.doc_qa_long")}
    # (the chunk KERNEL's share too: this cell's chunks attend expanded,
    # in XLA's loops, and the kernel of that name does not run)
    apart = {n for n in theirs if n.startswith("ssm_")} | {
        "chunk_attention_roofline", "chunk_attention_device_share",
        "serve_device_mfu.granite", "paged_attention_roofline.gqa64x4"}
    assert theirs - apart <= set(mine)
    assert not apart & set(mine)
    assert {"moe_experts_device_share", "moe_held_share",
            "moe_experts_roofline.gated",
            "moe_tokens_per_touched_expert"} <= set(mine)
    (latency,) = [m for m in BENCH["end_to_end"]
                  if m["name"] == "serve_token_latency_p50"]
    assert latency["workloads"][-1] == CELL
    assert BENCH["workloads"][-1]["name"] == CELL
    assert BENCH["configs"][-1]["name"] == NAME
    assert len(BENCH["per_layer"]) <= 128
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1


def test_the_traffic_is_the_issues_letter_for_letter():
    t = manifest.load_traffic("doc_qa_32k")
    assert (t["kind"], t["arrivals"], t["sharing"]) == (
        "open_loop", "poisson", "none")
    assert t["preroll_s"] == 30 and t["abandon_after_s"] >= 20
    assert t["prompt_len"] == {"dist": "lognormal", "median": 8192,
                               "sigma": 0.8, "min": 1024, "max": 32768}
    assert t["answer_len"] == {"dist": "lognormal", "median": 128,
                               "sigma": 0.6, "min": 32, "max": 512}
    cfg = manifest.load_config(BENCH, NAME)
    assert t["max_total_len"] < manifest.model_sizes(cfg, False)["max_len"] \
        == 33280
    assert t["check_tokens"] <= t["answer_len"]["min"]
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert "1/8" in cell["why"]


# -- the cell, rehearsed ---------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_and_prints_every_metric_a_cpu_run_can_name(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "5600000077", "--seconds", "1",
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
    assert {"moe_grouped_padding_share", "chunk_context_tokens",
            "prefill_tokens_per_s", "reserved_blocks_per_admission",
            "moe_held_share"} <= want
    assert all(m["value"] is None for m in line["metrics"].values())


# -- the cell's own comparison: sound answers, and the controls -------------------

class _Sent:
    def __init__(self, prompt, response):
        self.prompt, self.response = prompt, response


@pytest.fixture(scope="module")
def served():
    """The cell's system at its rehearsal size: a dozen requests served
    sound; the same prompts with the first layer's latent arena a chunk
    stale at the boundary before each prompt's last chunk, and a step stale
    after every decode step (the tool's own faults)."""
    import importlib

    from benchmark import workgen

    cfg = manifest.load_config(BENCH, NAME)
    traffic = manifest.sizes(manifest.load_traffic("doc_qa_32k"), True)
    builder = importlib.import_module("benchmark.builders." + cfg["builder"])
    system = builder.build(cfg, traffic, 5600000078, True)
    rng = np.random.default_rng(5)
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
        for fault, plant in (("chunk_kv", check_hybrid_logits._chunk_fault),
                             ("kv", check_hybrid_logits._stale)):
            # as the tool does before a fault: the arenas zeroed and the
            # pool emptied, so the prompts are prefilled anew (the pool
            # shares this model's full blocks) and a row that does not
            # land reads as zeros
            system.entry.kv.reset()
            undo = plant(system.entry, fault)
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
    # (the rehearsal builds float32: the configuration's ``assumed`` says
    # why)
    assert (checked, right) == (12, 12)
    assert worst <= traffic["check_tolerance"]
    # the device's sum of the chunks' routing counts is no weight
    assert "grouped_counts" not in system.weights()


@pytest.mark.parametrize("fault", ["chunk_kv", "kv"])
def test_a_stale_latent_row_reads_not_correct(served, fault):
    system, traffic, runs = served
    checked, right, worst = _check(system, runs[fault], traffic)
    assert right < traffic["check_min_equal"] * checked
    assert worst > traffic["check_tolerance"]


@pytest.mark.parametrize("control", [
    {"round_to": "bfloat16"}, {"rope_lanes": False},
    {"mscale_all_dim": 0.0}],
    ids=["reference_in_bfloat16", "rope_lanes_dropped",
         "m_squared_left_out"])
def test_a_reference_read_otherwise_reads_not_correct(served, control):
    """The sound tokens against the reference in the precision below the
    served one (the rehearsal serves float32: its weights through
    bfloat16), with the 8 rotary lanes left unrotated, or with ``m^2``
    left out of the softmax scale. (Step 4's scale left out is the chip's
    to show: at the rehearsal's 48 positions it is 1.07 at most.)"""
    system, traffic, runs = served
    checked, right, worst = _check(system, runs["sound"], traffic, **control)
    assert right < traffic["check_min_equal"] * checked
    assert worst > traffic["check_tolerance"]
