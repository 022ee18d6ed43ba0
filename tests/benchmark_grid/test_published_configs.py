"""A published configuration is added with files alone (ISSUE 35): its
source's keys at the top level of its file and the driver's rule for them
(``manifest.check_against_source``), count functions in files of their own
(``benchmark/counts/``), sizes of a kernel call that follow the traffic (a
counter's movement over the traced stretch), and one tiny configuration in
that format (``fixtures/``) through the loader, the readers and, in
``test_benchmark_grid.py``'s rehearsals, ``run.py``.

Like its neighbours, this module loads no TPU library while it is imported.
"""

import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import flops, manifest, readers  # noqa: E402
from benchmark import trace as tr  # noqa: E402
from benchmark.counts import decoder, paged_attention  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = manifest.load_manifest()
FIXTURE = os.path.join(HERE, "fixtures", "configs", "published_tiny.json")
FIXTURE_METRIC = os.path.join(HERE, "fixtures", "metrics",
                              "published_tiny_roofline.json")
with open(os.path.join(HERE, "catalog_rows.json")) as f:
    ROWS = {row["name"]: row for row in json.load(f)["rows"]}
XING = ROWS["Xing4.0-29B-A4B"]
KANANA = ROWS["kanana-2-30b-a3b-instruct-2601"]
NEMOTRON = ROWS["NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"]


# -- the driver's rule, on copies of the catalog's rows -------------------------

def _file_of(row, reduced=(), **changed):
    """A configuration's file as a builder is told to write it: the row's
    ``config`` copied whole to the top level, then what is cut."""
    cfg = {"name": "x", "builder": "decoder_engine",
           "source": row["source_url"], "source_keys": sorted(row["config"]),
           "source_values": copy.deepcopy(row["config"]),
           "reduced": list(reduced), "model": {},
           **copy.deepcopy(row["config"])}
    cfg.update(changed)
    return cfg


def _without(cfg, key):
    return {k: v for k, v in cfg.items() if k != key}


@pytest.mark.parametrize("row", sorted(ROWS))
def test_the_rows_verbatim_copy_passes(row):
    cfg = _file_of(ROWS[row])
    assert manifest.check_against_source(cfg, [], ROWS[row]["config"]) is None


def _under_model_only(row):
    cfg = _file_of(row)
    for key in row["config"]:
        cfg.pop(key)
    cfg["model"] = copy.deepcopy(row["config"])
    return cfg


# (what, the row, the file, the entry's reduced, the key the complaint names)
REFUSED = [
    ("every key under model only, as PRs 27, 32 and 33 wrote them",
     XING, _under_model_only(XING), [], "ep_size only under model"),
    ("the same for the row whose first number comes second",
     KANANA, _under_model_only(KANANA), [],
     "first_k_dense_replace only under model"),
    ("a number given as null",
     XING, _file_of(XING, ep_size=None), [], "ep_size as null"),
    ("a key left out",
     KANANA, _without(_file_of(KANANA), "kv_lora_rank"), [],
     "kv_lora_rank nowhere"),
    ("a nested group left out",
     XING, _without(_file_of(XING), "rope_scaling"), [],
     "rope_scaling nowhere"),
    ("a depth cut and not listed",
     XING, _file_of(XING, num_hidden_layers=6), [], "num_hidden_layers"),
    ("a depth cut, listed in the file and not in BENCHMARK.json's entry",
     XING, _file_of(XING, ["num_hidden_layers"], num_hidden_layers=6), [],
     "reduced is"),
    ("a changed width, though listed",
     XING, _file_of(XING, ["hidden_size"], hidden_size=1024),
     ["hidden_size"], "hidden_size is 1024"),
    ("a changed head size, though listed",
     KANANA, _file_of(KANANA, ["v_head_dim"], v_head_dim=64),
     ["v_head_dim"], "v_head_dim is 64"),
    ("fewer experts a token, though listed",
     NEMOTRON, _file_of(NEMOTRON, ["num_experts_per_tok"],
                        num_experts_per_tok=2),
     ["num_experts_per_tok"], "num_experts_per_tok is 2"),
    ("a width changed inside a group, though the group is listed",
     XING, _file_of(XING, ["rope_scaling"], rope_scaling=dict(
         XING["config"]["rope_scaling"], factor=8)),
     ["rope_scaling"], "rope_scaling.factor"),
    ("a true where the source gives the number 1",
     XING, _file_of(XING, ep_size=True), [], "ep_size is True"),
    ("a layer pattern cut with the depth and not listed",
     NEMOTRON, _file_of(NEMOTRON, ["num_hidden_layers"],
                        num_hidden_layers=7,
                        hybrid_override_pattern="MEMEM*E"),
     ["num_hidden_layers"], "hybrid_override_pattern"),
    ("a source of more than 200 characters",
     KANANA, _file_of(KANANA, source="https://" + "x" * 200), [],
     "source has 208 characters"),
]


@pytest.mark.parametrize("what, row, cfg, entry_reduced, names", REFUSED,
                         ids=[case[0] for case in REFUSED])
def test_what_the_driver_would_refuse_is_refused_by_the_keys_name(
        what, row, cfg, entry_reduced, names):
    complaint = manifest.check_against_source(cfg, entry_reduced,
                                              row["config"])
    assert complaint is not None and names in complaint, complaint


ALLOWED = [
    ("a cut depth, listed in both places",
     XING, _file_of(XING, ["num_hidden_layers"], num_hidden_layers=6),
     ["num_hidden_layers"]),
    ("a chip's share of the experts, the heads and the vocabulary",
     KANANA, _file_of(
         KANANA, ["n_routed_experts", "num_attention_heads", "vocab_size"],
         n_routed_experts=16, num_attention_heads=8, vocab_size=16032),
     ["vocab_size", "num_attention_heads", "n_routed_experts"]),
    ("the layer pattern cut with the depth, both listed",
     NEMOTRON, _file_of(
         NEMOTRON, ["num_hidden_layers", "hybrid_override_pattern"],
         num_hidden_layers=7, hybrid_override_pattern="MEMEM*E"),
     ["num_hidden_layers", "hybrid_override_pattern"]),
    ("a published null left out (sliding_window)",
     NEMOTRON, _without(_file_of(NEMOTRON), "sliding_window"), []),
    ("a published null kept as null (q_lora_rank, rope_scaling)",
     KANANA, _file_of(KANANA), []),
    ("a string and a boolean left out: numbers and groups are demanded",
     XING, _without(_without(_file_of(XING), "hidden_act"),
                    "attention_bias"), []),
    ("the published keys under model as well: the hedge",
     XING, _file_of(XING, model=copy.deepcopy(XING["config"])), []),
]


@pytest.mark.parametrize("what, row, cfg, entry_reduced", ALLOWED,
                         ids=[case[0] for case in ALLOWED])
def test_what_the_guides_section_4_allows_passes(what, row, cfg,
                                                 entry_reduced):
    assert manifest.check_against_source(cfg, entry_reduced,
                                         row["config"]) is None


def test_every_catalog_configuration_of_the_benchmark_passes_the_rule():
    """The gate of the next ``model_config`` PR: a configuration whose
    ``source`` is a row's ``source_url`` is held to that row. Today no
    configuration names one."""
    by_url = {row["source_url"]: row for row in ROWS.values()}
    held = 0
    for entry in BENCH["configs"]:
        row = by_url.get(entry["source"])
        if row is None:
            continue
        cfg = manifest.load_config(BENCH, entry["name"])
        assert manifest.check_against_source(
            cfg, entry["reduced"], row["config"]) is None, entry["name"]
        held += 1
    assert held == sum(e["source"] in by_url for e in BENCH["configs"])


# -- the loader ------------------------------------------------------------------

def test_the_configurations_that_are_there_load_as_they_are_written():
    for entry in BENCH["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            assert manifest.load_config(BENCH, entry["name"]) == json.load(f)
        cfg = manifest.load_config(BENCH, entry["name"])
        if cfg.get("source_keys"):
            # a published configuration: its source's keys are one group,
            # and a size under model may name one of them
            assert set(manifest.published(cfg, False)) == set(
                cfg["source_keys"])
            continue
        assert manifest.published(cfg, False) == {}
        assert manifest.model_sizes(cfg, True) == manifest.sizes(
            cfg["model"], True)


def test_the_fixture_loads_and_its_sizes_come_from_the_published_group():
    cfg = manifest.load_config_file(FIXTURE)
    assert manifest.published(cfg, False) == {
        "n_embd": 1024, "n_layer": 4, "n_ctx": 1024, "n_head": 1,
        "n_vocab": 32000}
    assert manifest.published(cfg, True) == {
        "n_embd": 16, "n_layer": 2, "n_ctx": 48, "n_head": 1, "n_vocab": 64}
    assert manifest.model_sizes(cfg, False) == {
        "vocab_size": 32000, "hidden": 1024, "num_layers": 4,
        "max_len": 1024, "ffn_dim": 4096, "slots": 48, "block_size": 16,
        "chunk_tokens": 128}
    run = manifest.run_sizes(cfg, {"batch": 8, "rehearsal": {"batch": 2}},
                             1, True)
    assert run["model"]["hidden"] == run["published"]["n_embd"] == 16
    assert run["model"]["slots"] == 4 and run["traffic"] == {"batch": 2}
    # what the file states of its source is the driver's rule's row
    assert manifest.check_against_source(
        cfg, cfg["reduced"], cfg["source_values"]) is None


def _write(tmp_path, cfg):
    path = tmp_path / "config.json"
    path.write_text(json.dumps(cfg))
    return str(path)


def _fixture(**changed):
    with open(FIXTURE) as f:
        cfg = json.load(f)
    cfg.update(changed)
    return cfg


LOADER_REFUSES = [
    ("a top-level key that source_keys does not name",
     _fixture(n_inner=4096), "unknown keys ['n_inner']"),
    ("a name in source_keys that the file does not give",
     _without(_fixture(), "n_ctx"), "['n_ctx'], which the file does not"),
    ("a null where source_values gives a number",
     _fixture(n_embd=None), "n_embd as null"),
    ("a harness key among the source's",
     _fixture(source_keys=["n_embd", "model"]), "the harness's own"),
    ("a published key under model with another value",
     _fixture(model=dict(_fixture()["model"], n_embd=512)),
     "n_embd is 1024 at the top level and 512 under model"),
    ("a size taken from a published key that is not there",
     _fixture(model=dict(_fixture()["model"], hidden="published.d_model")),
     "published.d_model"),
    ("a rehearsal override of something that is no published key",
     _fixture(rehearsal={"hidden": 16}), "unknown keys ['hidden']"),
    ("a cut that reduced does not list",
     _fixture(reduced=["n_layer", "n_head"]), "n_vocab is 32000"),
    ("a changed width",
     _fixture(n_embd=512, reduced=["n_layer", "n_head", "n_vocab",
                                   "n_embd"]), "n_embd is 512"),
    ("a count function that is in no module",
     _fixture(flops={"function": "no_such_module.step"}),
     "count function 'no_such_module.step'"),
]


@pytest.mark.parametrize("what, cfg, says", LOADER_REFUSES,
                         ids=[case[0] for case in LOADER_REFUSES])
def test_the_loader_refuses_and_names_the_key(tmp_path, what, cfg, says):
    with pytest.raises(manifest.ManifestError) as e:
        manifest.load_config_file(_write(tmp_path, cfg))
    assert says in str(e.value)


def test_the_entrys_reduced_is_held_to_the_files(tmp_path):
    path = _write(tmp_path, _fixture())
    assert manifest.load_config_file(path, ["n_vocab", "n_head", "n_layer"])
    with pytest.raises(manifest.ManifestError, match="reduced is"):
        manifest.load_config_file(path, ["n_layer"])


# -- count functions in files of their own ------------------------------------------

def test_a_count_function_resolves_by_module_or_in_flops():
    assert manifest.count_function("flash_forward") is flops.flash_forward
    assert manifest.count_function("paged_attention.decode_calls") \
        is paged_attention.decode_calls
    assert manifest.count_function("decoder.stepped_tokens") \
        is decoder.stepped_tokens


@pytest.mark.parametrize("name", ["no_such_function", "paged_attention.nope",
                                  "no_such_module.f", "flops", "a b.c",
                                  "paged_attention.__doc__"])
def test_an_unknown_count_function_is_refused_at_load(tmp_path, name):
    with pytest.raises(manifest.ManifestError):
        manifest.count_function(name)
    with open(FIXTURE_METRIC) as f:
        spec = json.load(f)
    spec["args"]["kernels"][0]["function"] = name
    path = tmp_path / "metric.json"
    path.write_text(json.dumps(spec))
    with pytest.raises(manifest.ManifestError):
        manifest.load_metric_file(str(path))


def test_a_configuration_may_name_a_count_module_too(tmp_path):
    cfg = _fixture(flops={"function": "decoder.stepped_tokens"})
    assert manifest.load_config_file(_write(tmp_path, cfg))


def test_paged_attention_operations_and_bytes_by_hand():
    # 5 live blocks of 4 positions, rows 8 wide, 3 layers, float32:
    # 20 positions; a layer reads 20 rows of K and 20 of V, 8 floats each:
    # 2*20*8*4 = 1280 bytes, three layers 3840; q.k^T and p.v are
    # 2*2*20*8 = 640 operations a layer, 1920
    assert paged_attention.decode_calls(5, 4, 8, 3, 4) == (1920, 3840)
    assert paged_attention.decode_calls(0, 4, 8, 3, 4) == (0, 0)


def test_stepped_tokens_operations_by_hand():
    # 6 tokens stepped over 5 live blocks of 4 positions; hidden 4, FFN 16,
    # 2 layers, vocabulary 10. A token: q,k,v,out 4*2*4*4 = 128 and the FFN
    # 2*2*4*16 = 256 a layer -> 768, the head 2*4*10 = 80 -> 848; six 5088.
    # Attention: 2*2*20*4 = 320 a layer -> 640
    assert decoder.stepped_tokens(6, 5, 4, 4, 16, 2, 10) == (5088 + 640, 0)
    # flops.decode_step charges every slot the full length; with every slot
    # stepping over max_len positions the two agree on the operations
    model = {"hidden": 4, "num_layers": 2, "vocab_size": 10, "slots": 3,
             "max_len": 8, "ffn_dim": 16}
    assert decoder.stepped_tokens(3, 3 * 2, 4, 4, 16, 2, 10)[0] == \
        flops.decode_step(model)[0]


# -- sizes that follow the traffic: a counter's movement ---------------------------

LABEL = '{engine="e"}'
MOSAIC = ('%paged_attention.7 = f32[48,1,1024]{2,1,0:T(1,128)} custom-call('
          's32[3072]{0} %t, s32[48]{0} %n), custom_call_target='
          '"tpu_custom_call", metadata={op_name="jit(call)/paged_attention"}')
OTHER = "%fusion.1 = f32[48,4096]{1,0} fusion(f32[48,1024]{1,0} %p0)"


def _run(live_before=1_000, live_after=1_000 + 1_500, sizes=None):
    """A traced stretch [1.0, 4.0] in which the kernel's events take 0.2 s
    (a third event lies outside it), and the live-block counter moves by
    1,500 between the stretch's edges and by 9,000 over the whole
    window."""
    device = {"ops": [[OTHER, 1.0, 0.3], [MOSAIC, 1.3, 0.1],
                      [OTHER, 2.0, 0.5], [MOSAIC, 2.5, 0.1],
                      [MOSAIC, 9.0, 0.1]],
              "async_ops": [], "modules": []}
    family = "serving_decode_live_blocks_total"
    steps = "serving_step_launches_total"
    return {
        "registry": ({family: {LABEL: 0}, steps: {LABEL: 0}},
                     {family: {LABEL: 9_000}, steps: {LABEL: 600}}),
        "stretch_registry": ({family: {LABEL: live_before}, steps: {LABEL: 10}},
                             {family: {LABEL: live_after}, steps: {LABEL: 110}}),
        "facts": {"window_s": 51.0},
        "peaks": {"bf16_flops": 1e9, "hbm_bytes_per_s": 1e9},
        "sizes": sizes or {"model": {"block_size": 16, "hidden": 1024,
                                     "num_layers": 24}},
        "trace": {"devices": {"0": device}}, "trace_window": (1.0, 4.0)}


def _read(run, name):
    spec = manifest.load_metric(name)
    return readers.READERS[spec["reader"]](spec["args"], run)


def test_the_kernels_roofline_follows_the_live_blocks_of_the_stretch():
    # 1,500 live blocks x 16 positions x 1,024 floats x 4 bytes x 2 (K, V)
    # x 24 layers = 4,718,592,000 bytes: 4.718592 s at 1e9 bytes/s (the
    # operations, 2*2*24,000*1,024*24 = 2.36e9, need less), over the 0.2 s
    # of the two events inside the stretch; taken ONCE, not per event
    assert _read(_run(), "paged_attention_roofline") == pytest.approx(
        100 * 4.718592 / 0.2)
    # the movement over the whole window (9,000) is not what is read
    assert _read(_run(live_after=1_000), "paged_attention_roofline") == 0.0


def test_it_finds_nothing_where_a_part_is_missing():
    run = _run()
    for broken in (dict(run, stretch_registry=None),
                   dict(run, stretch_registry=({}, {})),
                   dict(run, trace=None),
                   dict(run, trace={"devices": {"0": {
                       "ops": [[OTHER, 1.0, 0.3]], "async_ops": [],
                       "modules": []}}})):
        assert _read(broken, "paged_attention_roofline") is None
        assert _read(broken, "decode_step_mfu") is None


def test_a_size_per_step_is_a_quotient_of_two_counters():
    run = _run()
    per_step = {"div": [{"counter": "serving_decode_live_blocks_total"},
                        {"counter": "serving_step_launches_total"}]}
    assert readers._value(per_step, run) == 15.0       # exact, not floored
    assert readers._value({"div": [7, 2]}, run) == 3   # whole sizes floor
    assert readers._value({"mul": [per_step, 2]}, run) == 30.0
    assert readers._value({"counter": "no_such_family"}, run) is None
    assert readers._value({"mul": [{"counter": "no_such_family"}, 2]},
                          run) is None
    # per call times calls: 15 live blocks a step, one layer, two events
    args = {"kernels": [{
        "pattern": "paged_attention",
        "function": "paged_attention.decode_calls",
        "call": {"live_blocks": per_step, "block_size": "model.block_size",
                 "width": "model.hidden", "layers": 1, "bytes_per_el": 4}}]}
    assert readers.device_roofline(args, run) == pytest.approx(
        100 * 2 * (2 * 15 * 16 * 1024 * 4 / 1e9) / 0.2)


def test_a_metric_file_reads_the_published_group_and_a_counter():
    cfg = manifest.load_config_file(FIXTURE)
    spec = manifest.load_metric_file(FIXTURE_METRIC)
    run = _run(sizes=manifest.run_sizes(cfg, {}, 1, False))
    # block 16, n_embd 1,024 and the 4 layers that are run, not the 24
    assert readers.READERS[spec["reader"]](spec["args"], run) == \
        pytest.approx(100 * (1_500 * 16 * 1024 * 4 * 2 * 4 / 1e9) / 0.2)


def test_the_decode_steps_share_of_the_peak_by_hand():
    with open(os.path.join(HERE, "hand_trace_scheduler.json")) as f:
        doc = json.load(f)
    trace = doc["trace"]
    spans = tr.spans_on_trace_clock(doc["tracer_spans"], trace)
    slot_steps = "serving_active_slot_steps_total"
    live = "serving_decode_live_blocks_total"
    run = {"trace": trace, "spans": spans,
           "trace_window": tr.host_event(trace, tr.WINDOW),
           "stretch_registry": ({slot_steps: {LABEL: 5}, live: {LABEL: 0}},
                                {slot_steps: {LABEL: 11}, live: {LABEL: 5}}),
           "peaks": {"bf16_flops": 1e4},
           "sizes": {"model": {"block_size": 4, "hidden": 4, "ffn_dim": 16,
                               "num_layers": 2, "vocab_size": 10}}}
    # 6 tokens over 5 live blocks need 5,728 operations (above); the two
    # modules launched under decode::step ran 0.84 s
    assert _read(run, "decode_step_mfu") == pytest.approx(
        100 * 5728 / (0.84 * 1e4))


def test_the_new_metrics_pass_the_manifest():
    for name, reader in (("paged_attention_roofline", "device_roofline"),
                         ("decode_step_mfu", "span_mfu")):
        spec = manifest.load_metric(name)
        (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert spec["reader"] == reader
        for key in ("layer", "unit", "better", "source", "moves"):
            assert spec[key] == entry[key], (name, key)
        assert "workloads" not in spec      # the entry alone lists the cells
        assert (entry["unit"], entry["better"], entry["source"]) == (
            "%", "higher", "device_trace")
        assert entry["moves"] == "serve_token_latency_p50"
        assert spec["what"] and "\n" not in spec["what"]
