"""The benchmark's own tests (BENCHMARK.json lists this directory under
``paths``, and the tier-1 run collects it).

No TPU library is loaded while this module is imported: the cells are
rehearsed in child processes on the CPU platform, and nothing here
describes a TPU topology.
"""

import json
import math
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import flops, manifest, readers, workgen  # noqa: E402
from benchmark import trace as tr  # noqa: E402

BENCH = manifest.load_manifest()
CELLS = [w["name"] for w in BENCH["workloads"]]
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device",
             "compared"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


# -- the manifest and its data files ---------------------------------------

# an eighth cell, added the way benchmark/README.md's table says a cell is
# added: entries appended to BENCHMARK.json and no file touched. The
# configuration is the fixture in the format a published one takes, run
# through the decoder's builder, so the cell reports what the decoder's does
EIGHTH = {"name": "published_tiny.chat_steady", "config": "published_tiny",
          "traffic": "chat_steady", "chips": 1,
          "why": "a test's cell: the fixture configuration under the "
                 "decoder cell's traffic, added by appends alone"}
EIGHTH_FILE = "tests/benchmark_grid/fixtures/configs/published_tiny.json"
LIKE = "decoder_1024x24.chat_steady"     # the cell whose lists it joins


@pytest.fixture(scope="module")
def eighth(tmp_path_factory):
    """A root that differs from the checkout in BENCHMARK.json alone (every
    other entry of the checkout is linked into it), and its manifest."""
    root = tmp_path_factory.mktemp("an_eighth_cell")
    bench = json.loads(json.dumps(BENCH))
    cfg = manifest.load_config_file(os.path.join(ROOT, EIGHTH_FILE))
    bench["configs"].append({
        "name": cfg["name"], "source": cfg["source"], "file": EIGHTH_FILE,
        "reduced": cfg["reduced"], "why": cfg["why"]})
    bench["workloads"].append(EIGHTH)
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if LIKE in m.get("workloads", ()):
                m["workloads"].append(EIGHTH["name"])
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f, indent=1)
    for entry in os.listdir(ROOT):
        if entry != "BENCHMARK.json" and not entry.startswith("."):
            os.symlink(os.path.join(ROOT, entry), root / entry)
    return str(root), manifest.load_manifest(root=str(root))


@pytest.fixture(params=["as_accepted", "with_an_eighth_cell"])
def bench(request):
    """Every invariant of the grid holds on the manifest as it is and on
    the one a later PR's appends would leave."""
    if request.param == "as_accepted":
        return BENCH
    return request.getfixturevalue("eighth")[1]


def test_manifest_and_every_data_file_parse(bench):
    assert set(bench) == manifest.MANIFEST_KEYS
    assert len(json.dumps(bench)) < 64 * 1024
    assert 1 <= len(bench["per_layer"]) <= 128
    for path in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
    assert not any(part.startswith("/") or ".." in part
                   for part in bench["command"])
    files = set()
    for c in bench["configs"]:
        cfg = manifest.load_config(bench, c["name"])
        assert any(c["file"].startswith(path + "/")
                   for path in bench["paths"])
        assert c["reduced"] == cfg["reduced"]
        assert any(w["config"] == c["name"] for w in bench["workloads"])
        files.add(c["file"])
    assert len(files) == len(bench["configs"])
    for w in bench["workloads"]:
        manifest.load_traffic(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in bench["per_layer"]:
        spec = manifest.load_metric(m["name"])
        assert spec["reader"] in readers.READERS
        for key in ("layer", "unit", "better", "source", "moves"):
            assert spec[key] == m[key], (m["name"], key)


def test_unknown_keys_are_refused():
    with pytest.raises(manifest.ManifestError, match="unknown keys"):
        manifest._only({"name": 1, "colour": 2}, {"name"}, "a file")
    with pytest.raises(manifest.ManifestError):
        manifest._name("two words", "a name")


def test_names_units_and_bounds_hold_to_the_contract(bench):
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            assert manifest.NAME.match(m["name"])
            assert manifest.UNIT.match(m["unit"])
            assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert 1 <= bench["run_seconds"] <= 51
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]


def test_every_moves_names_a_metric_that_all_its_cells_report(bench):
    cells = [w["name"] for w in bench["workloads"]]
    for m in bench["per_layer"]:
        (target,) = [e for e in bench["end_to_end"]
                     if e["name"] == m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in target.get("workloads", cells), (m["name"], cell)
    for cell in cells:
        e2e = manifest.metrics_of(bench, "end_to_end", cell)
        assert {"setup_s"} < {m["name"] for m in e2e}
        assert manifest.metrics_of(bench, "per_layer", cell)


def test_at_most_a_quarter_of_the_cells_ask_for_four_chips(bench):
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


def test_the_harness_never_branches_on_a_name(bench):
    names = {x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in bench[key]}
    names |= {w["traffic"] for w in bench["workloads"]}
    # what the drivers measure
    names -= {"setup_s", "train_throughput", "serve_token_latency_p50"}
    bench_dir = os.path.join(ROOT, "benchmark")
    for dirpath, _dirs, files in os.walk(bench_dir):
        for f in files:
            if f.endswith(".py"):
                text = open(os.path.join(dirpath, f)).read()
                for name in names:
                    assert f'"{name}"' not in text, (f, name)


# -- one entry a quantity (PR 47) ------------------------------------------------

METRICS_DIR = os.path.join(ROOT, "benchmark", "metrics")
# what the fold of PR 47 left of 128 entries, in their order: a reading that
# every serving cell takes is ONE entry whose list names the cells
FOLDED = [
    "cache_load_s", "window_compiles.train", "window_compiles.serve",
    "host_step_ms.train", "train_mfu", "flash_device_share",
    "flash_attention_roofline", "collective_exposed_ms", "decode_step_ms",
    "decode_occupancy", "prefill_share", "serve_output_rate",
    "serve_requests_finished", "generator_lateness_ms",
    "decode_step_device_ms", "device_idle.train", "device_idle.serve",
    "hbm_compiled_gb", "serve_queue_wait_ms", "serve_first_token_ms",
    "serve_inter_token_ms", "prefill_kv_fetch_ms", "decode_logits_fetch_ms",
    "decode_feeds_ms", "decode_sample_ms", "serve_fed_mb_per_step",
    "serve_fetched_mb_per_step", "serve_shed", "decode_live_block_share",
    "paged_attention_device_share", "paged_attention_roofline",
    "decode_step_mfu", "serve_token_latency_p90", "moe_experts_roofline",
    "ssm_update_roofline", "paged_attention_roofline.gqa",
    "moe_experts_device_share", "ssm_update_device_share",
    "serve_device_mfu", "moe_held_share", "moe_touched_share",
    "decode_wait_share", "decode_put_ms", "decode_call_ms",
    "pool_evicted_alloc_share", "kv_arena_read_bytes",
    "moe_experts_roofline.gated", "paged_attention_roofline.gqa64",
    "serve_device_mfu.lfm2", "moe_touched_share.lfm2",
    "moe_tokens_per_touched_expert", "moe_peak_expert_tokens",
    "chunk_tokens_per_launch", "decode_drains",
    "paged_attention_roofline.mha128", "serve_device_mfu.ouro",
    "loop_passes_per_token", "loop_expected_exit_pass", "kv_live_gb_per_step",
    "reserved_blocks_per_admission", "admissions_deferred"]


def test_no_two_metric_files_are_copies_and_none_lists_cells(tmp_path):
    """The fold as a test: two files that differ in ``name`` and ``what``
    alone are one reading under two names, which is how ``per_layer`` came
    to hold 128 entries for 61 readings; a new cell appends its name to
    the one entry's list in BENCHMARK.json, which alone says which cells
    report what."""
    specs = {f[:-len(".json")]: manifest.load_metric_file(
                 os.path.join(METRICS_DIR, f))
             for f in sorted(os.listdir(METRICS_DIR)) if f.endswith(".json")}
    assert set(specs) == {m["name"] for m in BENCH["per_layer"]}
    readings = {}
    for name, spec in specs.items():
        assert spec["name"] == name
        assert "workloads" not in spec, name
        reading = json.dumps({k: v for k, v in spec.items()
                              if k not in ("name", "what")}, sort_keys=True)
        assert reading not in readings, (name, readings[reading])
        readings[reading] = name
    # and the loader refuses a file that carries the list
    carried = dict(specs["decode_step_ms"], workloads=[LIKE])
    path = tmp_path / "decode_step_ms.json"
    path.write_text(json.dumps(carried))
    with pytest.raises(manifest.ManifestError, match="unknown keys"):
        manifest.load_metric_file(str(path))


def test_the_fold_left_sixty_one_entries_and_they_keep_their_order():
    """128 -> 61 (under the 64 the issue asked for); the contract's cap is
    128 and later cells add entries behind these, so what is held is that
    the fold's survivors are there, once each, in their order."""
    names = [m["name"] for m in BENCH["per_layer"]]
    assert len(FOLDED) == len(set(FOLDED)) == 61 <= 64
    assert len(set(names)) == len(names) <= 128
    assert [n for n in names if n in set(FOLDED)] == FOLDED


# -- an eighth cell takes no edit (PR 47) ------------------------------------------

def test_an_eighth_cell_gets_the_folded_readings_from_the_manifest_alone(
        eighth):
    _root, bench = eighth
    cell = manifest.workload(bench, EIGHTH["name"])
    assert cell == EIGHTH
    for group in ("end_to_end", "per_layer"):
        mine = [m["name"] for m in manifest.metrics_of(bench, group,
                                                       EIGHTH["name"])]
        assert mine == [m["name"] for m in manifest.metrics_of(
            BENCH, group, LIKE)]
    # nothing else moved: every other cell reports what it did
    for w in BENCH["workloads"]:
        for group in ("end_to_end", "per_layer"):
            assert [m["name"] for m in manifest.metrics_of(
                bench, group, w["name"])] == [m["name"] for m in
                manifest.metrics_of(BENCH, group, w["name"])]


def test_an_eighth_cell_rehearses_with_no_file_touched(eighth):
    root, bench = eighth
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    procs = {trace: subprocess.Popen(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", EIGHTH["name"], "--seed", "4700000008",
         "--seconds", "1", "--trace", trace, "--rehearse-cpu"],
        cwd=root, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True) for trace in ("0", "1")}
    lines = {}
    for trace, p in procs.items():
        stdout, stderr = p.communicate(timeout=300)
        assert p.returncode == 0, stderr[-2000:]
        lines[trace] = json.loads(stdout.strip().splitlines()[-1])
        assert lines[trace]["correct"] is True
        assert lines[trace]["failed"] == 0
    assert set(lines["0"]["metrics"]) == {"serve_token_latency_p50",
                                          "setup_s"}
    # every reading of the cell that a CPU run can name, under its ONE name
    want = {m["name"] for m in manifest.metrics_of(
        bench, "per_layer", EIGHTH["name"]) if m["source"] != "device_trace"}
    assert set(lines["1"]["metrics"]) == want
    assert {"decode_step_ms", "decode_drains", "serve_first_token_ms",
            "pool_evicted_alloc_share"} <= want
    # the checkout was not written to
    assert not os.path.exists(os.path.join(ROOT, ".bench_trace",
                                           EIGHTH["name"]))


# -- traffic ------------------------------------------------------------------

STEADY = manifest.load_traffic("chat_steady")


def test_stratified_lengths_follow_the_stated_distribution():
    lens = workgen.stratified_lengths(STEADY["prompt_len"], 201)
    assert lens == sorted(lens)
    assert lens[0] == 16 and lens[-1] == 768          # clipped tails
    assert lens[100] == 128                           # the median
    answers = workgen.stratified_lengths(STEADY["answer_len"], 201)
    assert answers[100] == 96 and max(answers) == 192


def test_the_same_requests_at_the_same_times_for_two_seeds():
    runs = [workgen.open_loop_schedule(STEADY, seed, 70.0, 32000)
            for seed in (3, 3000000001)]
    shapes = [[(t, len(p), a) for t, p, a in run] for run in runs]
    assert shapes[0] == shapes[1]          # the work does not follow --seed
    assert sorted(shapes[0]) == shapes[0]
    assert [p for _t, p, _a in runs[0]] != [p for _t, p, _a in runs[1]]
    n = len(runs[0])
    assert n == 16 * math.ceil(70.0 * STEADY["rate_rps"] / 16)
    multiset = sorted(workgen.request_multiset(STEADY, n))
    assert sorted((len(p), a) for _t, p, a in runs[0]) == multiset
    assert all(len(p) + a <= STEADY["max_total_len"]
               for _t, p, a in runs[0])
    # every block of 16 holds one request of each stratum of the answers
    for j in range(n // 16):
        block = sorted(a for _t, _p, a in runs[0][16 * j:16 * j + 16])
        assert block[0] <= 48 and block[-1] >= 170
    other = dict(STEADY, schedule_seed=STEADY["schedule_seed"] + 1)
    again = workgen.open_loop_schedule(other, 3, 70.0, 32000)
    assert [(len(p), a) for _t, p, a in again] != \
        [(len(p), a) for _t, p, a in runs[0]]
    assert sorted((len(p), a) for _t, p, a in again) == multiset


def test_the_schedule_gives_poisson_gaps_at_the_cells_rate():
    run = workgen.open_loop_schedule(STEADY, 11, 64.0, 32000)
    due = [t for t, _p, _a in run]
    span = 16 / STEADY["rate_rps"]
    # the gaps of a block: the 16 stratified quantiles of the exponential
    # distribution, -ln(1 - (i + 0.5) / 16), scaled to the block's span
    q = [-math.log(1 - (i + 0.5) / 16) for i in range(16)]
    want = sorted(x * span / sum(q) for x in q)
    for j in range(len(due) // 16):
        edges = [j * span] + due[16 * j:16 * j + 16]
        gaps = sorted(b - a for a, b in zip(edges, edges[1:]))
        assert gaps == pytest.approx(want)
        assert edges[-1] == pytest.approx((j + 1) * span)


def test_open_loop_times_from_due_times_and_never_sends_early():
    import threading
    import time

    from benchmark import serve

    class Engine:
        def submit(self, prompt, max_new_tokens, deadline_at):
            time.sleep(0.03)        # a stall: the next send runs late
            return object()

    class System:
        engine = Engine()

    schedule = [(0.00, [1], 2), (0.01, [1], 2), (0.02, [1], 2),
                (5.0, [1], 2)]
    log = []
    t_start = time.perf_counter() + 0.01
    thread = threading.Thread(target=serve._open_loop, args=(
        System(), schedule, t_start, t_start + 1.0, None, log))
    thread.start()
    thread.join(timeout=10)
    assert not thread.is_alive()
    assert len(log) == 3                     # the one due after the close
    for (offset, _p, _a), sent in zip(schedule, log):
        assert sent.due == t_start + offset  # latency runs from here
        assert sent.sent >= sent.due
    assert log[2].sent - log[2].due > 0.03   # the stall shows as lateness


# -- the plain reference and the comparison that decides `correct` -----------

def _tiny_decoder_weights(rng, vocab, width, ffn, layers, positions):
    w = {"tok_emb": rng.normal(size=(vocab, width)),
         "pos_emb": rng.normal(size=(positions, width)),
         "head.w": rng.normal(size=(width, vocab)),
         "head.b": rng.normal(size=(vocab,))}
    for i in range(layers):
        for part, (a, b) in {"q": (width, width), "k": (width, width),
                             "v": (width, width), "out": (width, width),
                             "ffn1": (width, ffn), "ffn2": (ffn, width)
                             }.items():
            w[f"l{i}.{part}.w"] = rng.normal(size=(a, b)) / math.sqrt(a)
            w[f"l{i}.{part}.b"] = rng.normal(size=(b,)) * 0.1
    return {k: v.astype("float32") for k, v in w.items()}


def _decoder_by_hand(w, layers, tokens):
    """The same architecture in numpy, one position at a time: position t
    attends to positions 0..t, and nothing is padded or masked."""
    import numpy as np

    h = w["tok_emb"][tokens] + w["pos_emb"][:len(tokens)]
    for i in range(layers):
        def fc(x, part):
            return x @ w[f"l{i}.{part}.w"] + w[f"l{i}.{part}.b"]
        q, k, v = fc(h, "q"), fc(h, "k"), fc(h, "v")
        ctx = np.zeros_like(h)
        for t in range(len(tokens)):
            scores = k[:t + 1] @ q[t] / math.sqrt(h.shape[1])
            p = np.exp(scores - scores.max())
            ctx[t] = (p / p.sum()) @ v[:t + 1]
        h = h + fc(ctx, "out")
        h = h + fc(np.maximum(fc(h, "ffn1"), 0.0), "ffn2")
    return h @ w["head.w"] + w["head.b"]


@pytest.mark.parametrize("pad_to", [7, 16])
def test_plain_decoder_reference_against_a_forward_by_hand(pad_to):
    import numpy as np

    from benchmark.references import plain_decoder

    rng = np.random.default_rng(7)
    w = _tiny_decoder_weights(rng, vocab=11, width=8, ffn=16, layers=2,
                              positions=16)
    tokens = [3, 1, 4, 1, 5, 9, 2]
    want = _decoder_by_hand(w, 2, tokens)
    got = plain_decoder.logits(w, 2, tokens, [2, 6], pad_to=pad_to)
    # what is padded behind a position never reaches it
    assert got == pytest.approx(want[[2, 6]], rel=1e-4, abs=1e-4)


class _ReferenceOf:
    """A system whose reference gives the rows it is told to."""

    def __init__(self, rows):
        self.rows = rows

    def reference_logits(self, tokens, positions):
        import numpy as np

        assert len(tokens) == 3 + 2 - 1 and list(positions) == [2, 3]
        return np.asarray(self.rows, "float32")


@pytest.mark.parametrize("behind, right", [(0.0, 1), (0.04, 1), (0.06, 0),
                                          (3.0, 0)])
def test_a_served_token_has_to_be_the_references_top_or_tied(behind, right):
    import collections

    import numpy as np

    from benchmark import serve

    Response = collections.namedtuple("Response", "tokens")
    Response.result = lambda self: {"tokens": self.tokens}
    sent = serve._Sent(0.0, 0.0, [5, 6, 7], 2, Response([1, 2]), None)
    # row 0 serves its top; in row 1 token 0 is `behind` standard
    # deviations above the served token 2
    row = np.array([0.0, -1.0, 0.0, 1.0, -1.0, 1.0, -2.0, 2.0])
    row[0] = row.max() + 1.0
    row1 = row.copy()
    row1[2] = row1[0] - behind * row1.std()
    for _ in range(20):     # std moves with the entry: settle it
        row1[2] = row1[0] - behind * row1.std()
    row0 = np.roll(row, 1)
    traffic = {"check_requests": 4, "check_tokens": 8,
               "check_tolerance": 0.05}
    checked, ok, worst = serve._check_against_reference(
        _ReferenceOf([row0, row1]), [sent], traffic, seed=3000000001)
    assert (checked, ok) == (1, right)
    assert worst == pytest.approx(behind, abs=1e-3)


# -- operations and bytes, against counts worked out by hand -------------------

def test_bert_step_operations_by_hand():
    model = {"hidden_size": 4, "intermediate_size": 8,
             "num_hidden_layers": 2, "vocab_size": 10}
    # batch 2, sequence 3, 1 prediction: 6 tokens
    # a layer: q,k,v,out 4*2*6*4*4 = 768; q.k^T and p.v 2*2*2*3*3*4 = 288;
    # FFN 2*2*6*4*8 = 768 -> 1824; two layers 3648
    # MLM head: transform 2*2*4*4 = 64, projection 2*2*4*10 = 160
    # pooler 2*2*4*4 = 64, NSP 2*2*4*2 = 32 -> forward 3968
    assert flops.bert_pretrain_forward(model, 2, 3, 1) == 3968
    assert flops.bert_pretrain_step(
        model, {"max_predictions_per_seq": 1},
        {"batch": 2, "seq_len": 3}) == 3 * 3968


def test_resnet50_operations_by_hand():
    # the first two layers by hand at 224x224: the 7x7 stem at 112x112 is
    # 2*49*3*64*112*112 = 236,027,904; the stage-2 first block at 56x56:
    # 1x1 64->64, 3x3 64->64, 1x1 64->256 and the 1x1 shortcut 64->256
    stem = 2 * 49 * 3 * 64 * 112 * 112
    px = 56 * 56
    block_a = 2 * px * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
    block_b = 2 * px * (256 * 64 + 9 * 64 * 64 + 64 * 256)
    model = {"depth": 50, "image_shape": [3, 224, 224], "class_dim": 1000}
    total = flops.resnet_forward_per_image(model)
    assert stem == 236027904
    # the whole net: He et al. give 3.8e9 multiply-adds for ResNet-50
    assert 7.6e9 < total < 8.4e9
    tiny = {"depth": 50, "image_shape": [3, 32, 32], "class_dim": 10}
    # at 32x32 stage 2 runs at 8x8: the same blocks with 64 pixels
    scale = 64 / px
    rest = flops.resnet_forward_per_image(tiny) - 2 * 49 * 3 * 64 * 16 * 16
    assert rest > (block_a + 2 * block_b) * scale
    assert flops.resnet_train_step(model, {}, {"batch": 2}) == 6 * total


def test_flash_kernels_operations_and_bytes_by_hand():
    # 3 (batch x heads), 8 queries, 8 keys, head size 4, bf16
    assert flops.flash_forward(3, 8, 8, 4, 2) == (
        2 * 2 * 3 * 8 * 8 * 4, 2 * 3 * 4 * (8 + 8 + 8 + 8))
    assert flops.flash_backward_dkdv(3, 8, 8, 4, 2) == (
        4 * 2 * 3 * 8 * 8 * 4, 2 * 3 * 4 * (2 * 8 + 4 * 8))
    assert flops.flash_backward_dq(3, 8, 8, 4, 2) == (
        3 * 2 * 3 * 8 * 8 * 4, 2 * 3 * 4 * (3 * 8 + 2 * 8))


def test_decode_step_operations_and_bytes_by_hand():
    model = {"hidden": 4, "num_layers": 2, "vocab_size": 10, "slots": 3,
             "max_len": 5}
    # a layer: q,k,v,out 4*2*3*4*4 = 384; FFN (16 wide) 2*2*3*4*16 = 768;
    # attention over 5 positions 2*2*3*5*4 = 240 -> 1392; two layers 2784;
    # head 2*3*4*10 = 240
    # floats: weights 2*(4*16 + 2*64) + 40 = 424; K and V 2*2*3*5*4 = 240
    assert flops.decode_step(model) == (3024, 4 * (424 + 240))


# -- the trace reduction, on the hand-built trace -------------------------------

@pytest.fixture(scope="module")
def hand():
    with open(os.path.join(os.path.dirname(__file__),
                           "hand_trace.json")) as f:
        doc = json.load(f)
    trace = doc["trace"]
    spans = tr.spans_on_trace_clock(doc["tracer_spans"], trace)
    return trace, spans, tr.host_event(trace, tr.WINDOW)


def test_spans_land_on_the_traces_clock(hand):
    _trace, spans, window = hand
    # the anchors: 0.5 s on the trace, 0.1 s on the tracer -> shift 0.4 s
    assert window == (1.0, 4.0)
    by_name = {s[0]: s for s in spans}
    assert by_name["bench::window"][1:] == pytest.approx([1.0, 4.0])
    assert by_name["decode::step"][1:] == pytest.approx([1.5, 2.1])
    assert "bench::anchor" not in by_name


def test_busy_idle_union(hand):
    trace, _spans, window = hand
    busy, gaps = tr.busy_and_gaps(trace["devices"]["0"], window)
    # [1.0,1.6] + [2.0,2.5] + [3.0,3.2]
    assert busy == pytest.approx(1.3)
    assert gaps == [pytest.approx(g) for g in
                    ([1.6, 2.0], [2.5, 3.0], [3.2, 4.0])]
    run = {"trace": trace, "trace_window": window}
    assert readers.device_idle({}, run) == pytest.approx(100 * 1.7 / 3.0)


def test_device_time_by_event_name_pattern(hand):
    trace, _spans, window = hand
    dev = trace["devices"]["0"]
    assert tr.matching_seconds(dev, "flash_attention", window) == \
        pytest.approx(0.5)
    run = {"trace": trace, "trace_window": window}
    assert readers.device_share({"pattern": "flash_attention_fwd"}, run) \
        == pytest.approx(100 * 0.5 / 1.3)
    assert readers.device_share({"pattern": "no_such_kernel"}, run) is None
    assert tr.top_ops(dev, window, n=1) == [
        ["fusion.1 f32[8,128] fusion", pytest.approx(0.6)]]


def test_exposed_collective_time(hand):
    trace, _spans, window = hand
    # all-reduce [1.4,1.6] and its async start [1.3,1.7] cover [1.3,1.7];
    # the fusion runs until 1.4 -> [1.4,1.7] is exposed
    assert tr.exposed_seconds(trace["devices"]["0"], "all-reduce",
                              window) == pytest.approx(0.3)
    run = {"trace": trace, "trace_window": window,
           "facts": {"traced_steps": 3}}
    assert readers.device_exposed(
        {"pattern": "all-reduce", "scale": 1000.0}, run) == \
        pytest.approx(100.0)


def test_idle_gaps_go_to_the_innermost_span_of_the_program(hand):
    trace, spans, window = hand
    gaps = tr.busy_and_gaps(trace["devices"]["0"], window)[1]
    idle = tr.attribute_gaps(gaps, tr.span_segments(spans))
    # [1.6,2.0]: decode::step until executor::feed opens inside it at 1.9
    # [2.5,3.0]: decode::step ended at 2.1, decode::chunk opens at 2.9
    # [3.2,4.0]: decode::chunk ended at 3.1; only bench::window is open,
    #            and the program's spans come before the benchmark's own
    assert idle == {
        "decode::step": pytest.approx(0.3),
        "executor::feed": pytest.approx(0.1),
        "after:decode::step": pytest.approx(0.4),
        "decode::chunk": pytest.approx(0.1),
        "after:decode::chunk": pytest.approx(0.8),
    }
    assert sum(idle.values()) == pytest.approx(1.7)
    only_own = tr.attribute_gaps(
        gaps, tr.span_segments([s for s in spans if s[0] == tr.WINDOW]))
    assert only_own == {"bench::window": pytest.approx(1.7)}


def test_device_seconds_by_launching_span(hand):
    trace, spans, window = hand
    by_span = tr.module_seconds_by_span(trace["devices"]["0"], spans, window)
    # modules start at 1.0 (under executor::execute, open since 0.95), at
    # 2.0 (executor::feed, the latest started) and at 3.0 (decode::chunk)
    assert by_span == {"executor::execute": pytest.approx(0.7),
                       "executor::feed": pytest.approx(0.5),
                       "decode::chunk": pytest.approx(0.2)}
    run = {"trace": trace, "spans": spans, "trace_window": window}
    assert readers.device_seconds_per_span(
        {"span": "decode::chunk", "scale": 1000.0}, run) == \
        pytest.approx(200.0)


def test_roofline_share_from_shapes_and_device_time(hand):
    trace, _spans, window = hand
    run = {"trace": trace, "trace_window": window,
           "peaks": {"bf16_flops": 1e6, "hbm_bytes_per_s": 1e9},
           "sizes": {"model": {"heads": 4, "hidden": 256},
                     "traffic": {"batch": 2, "seq": 128}, "chips": 1}}
    args = {"kernels": [{
        "pattern": "flash_attention_fwd", "function": "flash_forward",
        "call": {"batch_heads": {"mul": ["traffic.batch", "model.heads"]},
                 "seq_q": "traffic.seq", "seq_k": "traffic.seq",
                 "head_dim": {"div": ["model.hidden", "model.heads"]},
                 "bytes_per_el": 2}}]}
    ops = 2 * 2 * 8 * 128 * 128 * 64          # 33,554,432 -> 33.55 s at 1e6
    assert readers.device_roofline(args, run) == \
        pytest.approx(100 * (ops / 1e6) / 0.5)


def test_counter_and_histogram_readers_read_the_window_only():
    before = {"serving_decode_steps_total": {'{engine="e"}': 10},
              "serving_active_slot_steps_total": {'{engine="e"}': 20},
              "serving_decode_step_seconds": {
                  '{engine="e"}': {"count": 10, "sum": 1.0}}}
    after = {"serving_decode_steps_total": {'{engine="e"}': 30},
             "serving_active_slot_steps_total": {'{engine="e"}': 80},
             "serving_decode_step_seconds": {
                 '{engine="e"}': {"count": 30, "sum": 4.0}}}
    run = {"registry": (before, after), "facts": {"window_s": 6.0},
           "sizes": {"model": {"slots": 4}}}
    assert readers.counter_delta(
        {"family": "serving_decode_steps_total"}, run) == 20
    assert readers.counter_ratio(
        {"numerator": "serving_active_slot_steps_total",
         "denominator": "serving_decode_steps_total",
         "denominator_times": "model.slots", "scale": 100.0}, run) == 75.0
    assert readers.histogram_mean(
        {"family": "serving_decode_step_seconds", "scale": 1000.0},
        run) == pytest.approx(150.0)
    assert readers.histogram_share(
        {"families": ["serving_decode_step_seconds"], "scale": 100.0},
        run) == pytest.approx(50.0)
    assert readers.counter_delta({"family": "no_such_family"}, run) is None


# -- every cell end to end, on the CPU at a tiny size ---------------------------

REHEARSALS = [("--workload", name) for name in CELLS] + [
    # a traffic file that no cell registers: a later PR adds it as data
    ("--config", "decoder_1024x24", "--traffic", "chat_saturated"),
    # a configuration FILE that BENCHMARK.json does not register, in the
    # format a published configuration takes (test_published_configs.py)
    ("--config", "tests/benchmark_grid/fixtures/configs/published_tiny.json",
     "--traffic", "chat_steady")]


@pytest.fixture(scope="module")
def rehearsed():
    """All rehearsals at once, each in a process of its own (four virtual
    devices for the mesh cell); the traced mode, so that the tracer, the
    span readers and the per-layer metric files are all on the path."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    procs = []
    for i, cell in enumerate(REHEARSALS):
        trace = "0" if "--traffic" in cell else "1"
        procs.append(subprocess.Popen(
            [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
             *cell, "--seed", str(3000000001 + i), "--seconds", "1",
             "--trace", trace, "--rehearse-cpu"],
            cwd=ROOT, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))
    out = {}
    for cell, p in zip(REHEARSALS, procs):
        stdout, stderr = p.communicate(timeout=300)
        out[cell] = (p.returncode, stdout, stderr)
    return out


@pytest.mark.parametrize("cell", REHEARSALS,
                         ids=lambda c: c[-1] if len(c) == 2 else
                         os.path.basename(c[1]) + "." + c[-1])
def test_cell_rehearses_on_the_cpu_with_the_contracts_last_line(
        rehearsed, cell):
    code, stdout, stderr = rehearsed[cell]
    assert code == 0, stderr[-2000:]
    line = json.loads(stdout.strip().splitlines()[-1])
    assert set(line) == LINE_KEYS          # no breakdown without a device
    assert list(line)[-1] == "compared"    # last, each number by its limit
    for name, number in line["compared"].items():
        assert set(number) == {"value", "limit", "holds"}
        assert number["holds"] is True, name
        assert f"compared {name}: " in stderr.strip().splitlines()[
            -len(line["compared"]):][list(line["compared"]).index(name)]
    assert set(line["device"]) == DEVICE_KEYS
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert line["metrics"]
    if "--workload" in cell:
        known = {m["name"]: m["unit"] for m in manifest.metrics_of(
            BENCH, "per_layer", cell[1])}
    else:
        known = {m["name"]: m["unit"] for m in BENCH["end_to_end"]}
    for name, metric in line["metrics"].items():
        assert set(metric) == {"value", "unit"}
        assert metric["unit"] == known[name]
        # a CPU run never supplies a number under a device metric's name
        assert metric["value"] is None


def test_off_the_chip_the_command_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert re.search(r"needs 1 device\(s\) of platform 'tpu'", p.stderr)


def test_an_unknown_device_kind_is_an_error():
    from benchmark import run

    assert run._peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        run._peaks("TPU v9 imaginary")
