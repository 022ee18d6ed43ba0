"""The per-layer metric that counts the decode scheduler's drains (ISSUE 42):
one data file, ``benchmark/metrics/decode_drains.json``, over a reader that
exists (``counter_delta``) and a family the program registers whole, every
reason a series from the start (``serving_decode_drains_total{why=}``,
``serving/decode/metrics.py DRAIN_REASONS``). Held here to the manifest, to
registries built by hand in the form ``serve.py`` snapshots them (one that
moved, one that did not, the parent's that lacks the family, a training
cell's), to the program's own registry, and to a CPU rehearsal of a serving
cell and of a training cell.

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
NAME = "decode_drains"
FAMILY = "serving_decode_drains_total"
SERVING = ["decoder_1024x24.chat_steady",
           "nemotron3_nano_30b_a3b.reasoning_steady",
           "lfm2_24b_a2b.assistant_steady"]
TRAINING = "resnet50.train_b128"
REASONS = ("admission", "prefill", "slots", "park", "parked", "spec", "idle",
           "brownout", "breaker", "shutdown")


def _read(run):
    spec = manifest.load_metric(NAME)
    return readers.READERS[spec["reader"]](spec["args"], run)


def _series(counts, engine="e"):
    return {f'{{engine="{engine}",why="{why}"}}': counts.get(why, 0)
            for why in REASONS}


def _run(before, after):
    return {"registry": (before, after), "facts": {"window_s": 51.0},
            "sizes": {"model": {"slots": 128}}}


# -- the file and its entry ---------------------------------------------------

def test_the_file_passes_the_manifest_and_names_the_family():
    spec = manifest.load_metric(NAME)
    assert spec["reader"] == "counter_delta" in readers.READERS
    assert spec["args"] == {"family": FAMILY}
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    for key in ("layer", "unit", "better", "source", "moves"):
        assert spec[key] == entry[key], key
    assert "workloads" not in spec          # the entry alone lists the cells
    assert (entry["layer"], entry["unit"], entry["better"],
            entry["source"], entry["moves"]) == (
        "decode scheduler", "count", "lower", "program_counter",
        "serve_token_latency_p50")
    assert spec["what"] and "\n" not in spec["what"]
    for why in REASONS:
        assert why in spec["what"]


def test_it_lists_every_cell_of_the_latency_metric_whatever_their_number():
    """Every engine's loop drains, so every cell that reports the metric it
    moves reports it: the entry's list is the latency metric's, and a cell
    that joins the one joins the other."""
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    assert set(SERVING) <= set(entry["workloads"])
    (latency,) = [m for m in BENCH["end_to_end"]
                  if m["name"] == "serve_token_latency_p50"]
    assert entry["workloads"] == latency["workloads"]


def test_the_entry_follows_what_was_there_and_no_list_names_a_stranger():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index("chunk_tokens_per_launch") < names.index(NAME)
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert set(m.get("workloads", ())) <= cells
    configs = {c["name"] for c in BENCH["configs"]}
    assert {w["config"] for w in BENCH["workloads"]} == configs


def test_the_program_registers_every_reason_from_the_start():
    """What the file reads is what the program registers, by name: a
    window without a drain still finds the family (and reads 0), where a
    series made at the first drain would leave the metric out of a line
    that has to carry it."""
    from paddle_tpu.observability import metrics as obs_metrics
    from paddle_tpu.serving.decode.metrics import DRAIN_REASONS, DecodeMetrics

    assert tuple(DRAIN_REASONS) == REASONS
    reg = obs_metrics.MetricsRegistry()
    m = DecodeMetrics(engine_label="grid", registry=reg)
    before = reg.snapshot()
    assert sorted(before[FAMILY]) == sorted(_series({}, engine="grid"))
    assert _read(_run(before, reg.snapshot())) == 0.0
    m.count_drain("admission")
    m.count_drain("idle")
    m.count_drain("idle")
    after = reg.snapshot()
    assert _read(_run(before, after)) == 3.0
    assert m.drains() == dict.fromkeys(REASONS, 0) | {"admission": 1,
                                                       "idle": 2}
    assert "serving_chunk_launches_ahead_total" in after


# -- registries by hand -------------------------------------------------------

@pytest.mark.parametrize("counts, value", [
    # the parent's order on the LFM2 cell: two drains an arrival
    ({"admission": 408, "prefill": 407, "idle": 3}, 818.0),
    # the change's: the idle ones alone
    ({"idle": 3}, 3.0),
    # a one-shot cell keeps its admissions
    ({"admission": 57, "idle": 40, "brownout": 2}, 99.0),
    ({}, 0.0),
])
def test_the_reader_sums_the_windows_drains_over_every_reason(counts, value):
    before = {FAMILY: _series({"admission": 12, "idle": 5})}
    moved = {why: before[FAMILY][k] + counts.get(why, 0)
             for why, k in zip(REASONS, _series({}))}
    assert _read(_run(before, {FAMILY: _series(moved)})) == value


def test_two_engines_of_one_process_are_summed():
    before = {FAMILY: {**_series({}, "a"), **_series({"idle": 4}, "b")}}
    after = {FAMILY: {**_series({"slots": 2}, "a"),
                      **_series({"idle": 9}, "b")}}
    assert _read(_run(before, after)) == 7.0


def test_a_series_that_appears_inside_the_window_counts_from_zero():
    after = {FAMILY: _series({"parked": 6})}
    assert _read(_run({}, after)) == 6.0


@pytest.mark.parametrize("after", [
    # the parent's program: every serving family but the new one
    {"serving_decode_steps_total": {'{engine="e"}': 40},
     "serving_decode_steps_ahead_total": {'{engine="e"}': 31},
     "serving_chunk_runs_total": {'{engine="e"}': 9}},
    # a training cell
    {"executor_cache_misses_total": {"{}": 1}},
    {},
])
def test_a_registry_without_the_family_gives_none_and_no_exception(after):
    assert _read(_run({}, after)) is None
    assert _read(_run(after, after)) is None


# -- a rehearsal of a serving cell and of a training cell ---------------------

@pytest.fixture(scope="module")
def rehearsed():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    lines = {}
    for i, cell in enumerate((SERVING[0], TRAINING)):
        p = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
             "--workload", cell, "--seed", str(4200000011 + i),
             "--seconds", "1", "--trace", "1", "--rehearse-cpu"],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        lines[cell] = json.loads(p.stdout.strip().splitlines()[-1])
    return lines


def test_a_serving_rehearsal_reads_the_metric(rehearsed):
    line = rehearsed[SERVING[0]]
    assert line["correct"] is True and line["failed"] == 0
    # present: its reader found the family; null, as every value of a CPU
    # run is
    assert line["metrics"][NAME] == {"value": None, "unit": "count"}


def test_a_training_cell_leaves_it_out(rehearsed):
    line = rehearsed[TRAINING]
    assert line["correct"] is True and line["metrics"]
    assert NAME not in line["metrics"]
