"""The per-layer metrics that read the decode loop's own accounts (ISSUE 38):
five data files under ``benchmark/metrics/`` over readers that exist. Three
read the scheduler (the share of the window the loop slept, the two halves of
a step's launch), two the KV block pool and the host tier (the share of
handed-out blocks that evicted a cached one, the bytes of arena brought to the
host to spill rows). Held here to the manifest, to a CPU rehearsal of both
serving cells and of a training cell, to a registry that lacks the new
families (the parent's program) and to a registry that moved.

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

BENCH = manifest.load_manifest()
SERVING = ("decoder_1024x24.chat_steady",
           "nemotron3_nano_30b_a3b.reasoning_steady")
TRAINING = "resnet50.train_b128"
SCHEDULER, POOL = "decode scheduler", "KV block pool and host tier"

# metric -> (reader, layer, unit, better, the families it reads)
NEW = {
    "decode_wait_share": ("histogram_share", SCHEDULER, "%", "higher",
                          ["serving_decode_wait_seconds"]),
    "decode_put_ms": ("histogram_mean", SCHEDULER, "ms", "lower",
                      ["serving_decode_step_put_seconds"]),
    "decode_call_ms": ("histogram_mean", SCHEDULER, "ms", "lower",
                       ["serving_decode_step_call_seconds"]),
    "pool_evicted_alloc_share": ("counter_ratio", POOL, "%", "lower",
                                 ["serving_pool_evictions_total",
                                  "serving_pool_block_allocs_total"]),
    "kv_arena_read_bytes": ("counter_delta", POOL, "bytes", "lower",
                            ["serving_arena_read_bytes_total"]),
}
NAMES = list(NEW)


def _read(run, name):
    """What the metric's file reads out of ``run``, through its reader."""
    spec = manifest.load_metric(name)
    return readers.READERS[spec["reader"]](spec["args"], run)


# -- the files ----------------------------------------------------------------

@pytest.mark.parametrize("name", NAMES)
def test_metric_file_passes_the_manifest_and_names_what_it_reads(name):
    spec = manifest.load_metric(name)
    reader, layer, unit, better, families = NEW[name]
    assert spec["reader"] == reader and reader in readers.READERS
    for family in families:
        assert family in json.dumps(spec["args"])
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    for key in ("layer", "unit", "better", "source", "moves"):
        assert spec[key] == entry[key], key
    assert (entry["layer"], entry["unit"], entry["better"]) \
        == (layer, unit, better)
    # the entry alone lists the cells; a later cell appends itself
    assert "workloads" not in spec
    assert set(SERVING) <= set(entry["workloads"])
    assert entry["moves"] == "serve_token_latency_p50"
    assert entry["source"] == "program_counter"
    assert spec["what"] and "\n" not in spec["what"]
    # what the file reads is what the program registers, by name
    from paddle_tpu.serving.decode.metrics import DecodeMetrics

    registered = {f"serving_{c}_total" for c in DecodeMetrics.COUNTERS} | {
        "serving_decode_wait_seconds", "serving_decode_step_put_seconds",
        "serving_decode_step_call_seconds"}
    assert set(families) <= registered


@pytest.mark.parametrize("name", NAMES)
def test_the_entries_keep_the_order_they_were_added_in(name):
    """PR 38's five stand in the order they were added, behind the readings
    that were there before them; an entry appended behind them, or a cell
    appended to a list, moves none of it."""
    names = [m["name"] for m in BENCH["per_layer"]]
    assert len(set(names)) == len(names)
    places = [names.index(n) for n in NAMES]
    assert places == sorted(places)
    assert names.index("serve_device_mfu") < names.index(name)
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert set(m.get("workloads", ())) <= cells


# -- a rehearsal of both serving cells and of a training cell ------------------

@pytest.fixture(scope="module")
def rehearsed():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    # one after the other: the suite's other workers share these cores, and
    # tests elsewhere hold a loaded engine to tenths of a second
    lines = {}
    for i, cell in enumerate(SERVING + (TRAINING,)):
        p = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
             "--workload", cell, "--seed", str(3800000011 + i),
             "--seconds", "1", "--trace", "1", "--rehearse-cpu"],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        lines[cell] = json.loads(p.stdout.strip().splitlines()[-1])
    return lines


@pytest.mark.parametrize("cell", SERVING)
@pytest.mark.parametrize("name", NAMES)
def test_a_serving_rehearsal_reads_the_metric(rehearsed, name, cell):
    line = rehearsed[cell]
    assert line["correct"] is True and line["failed"] == 0
    # present: its reader found its histogram or its counters (both cells
    # allocate from the pool); null, as every value of a CPU run is
    assert line["metrics"][name] == {"value": None, "unit": NEW[name][2]}


@pytest.mark.parametrize("name", NAMES)
def test_a_training_cell_leaves_it_out(rehearsed, name):
    line = rehearsed[TRAINING]
    assert line["correct"] is True and line["metrics"]
    assert name not in line["metrics"]


# -- a registry without the families, and one that moved -----------------------

@pytest.mark.parametrize("name", NAMES)
def test_the_parents_registry_gives_none_and_no_exception(name):
    """The parent's program has every serving family but the new ones, and
    a training cell has none at all: each reader returns None there (a
    ratio whose BOTH counters are new does, where one over an accepted
    denominator would raise)."""
    label = '{engine="e"}'
    parent = {"serving_decode_steps_total": {label: 40},
              "serving_step_launches_total": {label: 40},
              "serving_fetched_bytes_total": {label: 7680},
              "serving_decode_step_seconds": {
                  label: {"count": 40, "sum": 0.13}},
              "serving_prefill_seconds": {label: {"count": 3, "sum": 0.05}}}
    for after in (parent, {"executor_cache_misses_total": {"{}": 1}}, {}):
        run = {"registry": ({}, after), "facts": {"window_s": 51.0},
               "sizes": {"model": {}}}
        assert _read(run, name) is None


def _moved():
    label = '{engine="e"}'
    before = {
        "serving_decode_wait_seconds": {label: {"count": 100, "sum": 2.0}},
        "serving_decode_step_put_seconds": {label: {"count": 10, "sum": 0.01}},
        "serving_decode_step_call_seconds": {
            label: {"count": 10, "sum": 0.01}},
        "serving_pool_block_allocs_total": {label: 3000},
        "serving_pool_evictions_total": {label: 0},
        "serving_arena_read_bytes_total": {label: 0}}
    after = {
        "serving_decode_wait_seconds": {label: {"count": 1500, "sum": 27.5}},
        "serving_decode_step_put_seconds": {
            label: {"count": 7010, "sum": 7.85}},
        "serving_decode_step_call_seconds": {
            label: {"count": 7010, "sum": 6.52}},
        "serving_pool_block_allocs_total": {label: 4000},
        "serving_pool_evictions_total": {label: 250},
        "serving_arena_read_bytes_total": {label: 250 * 9_663_676_416}}
    return {"registry": (before, after), "facts": {"window_s": 51.0},
            "sizes": {"model": {"slots": 48}}}


@pytest.mark.parametrize("name, value", [
    ("decode_wait_share", 50.0),            # 25.5 s asleep of 51
    ("decode_put_ms", 1.12),                # 7.84 s over 7,000 launches
    ("decode_call_ms", 0.93),               # 6.51 s
    ("pool_evicted_alloc_share", 25.0),     # 250 of 1,000 blocks
    ("kv_arena_read_bytes", 250 * 9_663_676_416),
])
def test_the_reader_reads_the_window(name, value):
    assert _read(_moved(), name) == pytest.approx(value)


def test_a_pool_that_evicted_nothing_reads_zero_and_an_idle_one_is_left_out():
    run = _moved()
    before, after = run["registry"]
    after["serving_pool_evictions_total"] = dict(
        before["serving_pool_evictions_total"])
    after["serving_arena_read_bytes_total"] = dict(
        before["serving_arena_read_bytes_total"])
    assert _read(run, "pool_evicted_alloc_share") == 0.0
    assert _read(run, "kv_arena_read_bytes") == 0.0
    # nothing handed out in the window: no share to give
    after["serving_pool_block_allocs_total"] = dict(
        before["serving_pool_block_allocs_total"])
    assert _read(run, "pool_evicted_alloc_share") is None
