"""The reading PR 62 adds beside the step's expert kernel
(``benchmark/metrics/moe_touched_share.afmoe.json``: a data file, read by
the reader the benchmark has, ``counter_ratio``): the share of the Trinity
cell's held experts a decode step touches, which since that PR is the share
of them the kernel's grid walks. Synthetic registry snapshots read the share
the cell's scale gives; a program without the counters reads nothing and
does not raise. (The Mistral cell's twin waits on a ``benchmark`` PR:
``tests/benchmark_grid/test_trinity_cell.py`` holds every reading of that
cell outside its set ``apart`` to be Trinity's too: ROADMAP B14.)

(The file stands outside ``tests/benchmark_grid``: that directory's files
are the benchmark's own, which a PR that claims a gain does not edit. Like
them, this module loads no TPU library while it is imported.)
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, readers  # noqa: E402

BENCH = manifest.load_manifest()
#: metric -> (its one cell, the configuration's file, the key of its held
#: experts, expert layers of the cut stack)
METRICS = {
    "moe_touched_share.afmoe": (
        "trinity_large_preview.mixed_lengths_32k", "trinity_large_preview",
        "num_experts", 4),
}
TOUCHED = "serving_moe_touched_experts_total"
STEPS = "serving_decode_steps_total"


def _run(touched, steps):
    """A window in which the counters moved by ``touched`` and ``steps``
    from a standing 7 and 3 (None: the program lacks the counter)."""
    before, after = {}, {}
    for name, moved, stood in ((TOUCHED, touched, 7), (STEPS, steps, 3)):
        if moved is not None:
            before[name] = {"": stood}
            after[name] = {"": stood + moved}
    return {"trace": None, "trace_window": None, "spans": [],
            "registry": (before, after), "stretch_registry": [{}, {}],
            "facts": {"window_s": 51.0}, "chips": 1}


def _read(name, run):
    spec = manifest.load_metric(name)
    return readers.READERS[spec["reader"]](spec["args"], run)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_scale_is_the_cells_held_experts_by_its_expert_layers(name):
    _cell, config, held_key, layers = METRICS[name]
    with open(os.path.join(ROOT, "benchmark", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    assert cfg["num_hidden_layers"] - cfg["num_dense_layers"] == layers
    spec = manifest.load_metric(name)
    assert spec["args"]["scale"] == pytest.approx(
        100.0 / (cfg[held_key] * layers))
    # two held experts touched in every expert layer of every step
    steps = 1300
    assert _read(name, _run(2 * layers * steps, steps)) == pytest.approx(
        100.0 * 2 / cfg[held_key])
    # every held expert touched: the grid as tall as it was before PR 62
    assert _read(name, _run(cfg[held_key] * layers * steps, steps)) == (
        pytest.approx(100.0))


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_program_without_the_counters_reads_nothing(name):
    assert _read(name, _run(None, None)) is None
    assert _read(name, _run(0, 0)) is None


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_manifest_lists_it_for_its_one_cell(name):
    cell = METRICS[name][0]
    mine = {m["name"]: m
            for m in manifest.metrics_of(BENCH, "per_layer", cell)}
    entry, spec = mine[name], manifest.load_metric(name)
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    assert entry["workloads"] == [cell] and "workloads" not in spec
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        "decode scheduler", "serve_token_latency_p50", "program_counter")
    assert (spec["reader"], spec["args"]["numerator"],
            spec["args"]["denominator"]) == ("counter_ratio", TOUCHED, STEPS)
    # beside the family's other shares: the same reader over the same
    # counters, a scale a cell
    lfm2 = manifest.load_metric("moe_touched_share.lfm2")
    assert {k: v for k, v in spec["args"].items() if k != "scale"} == {
        k: v for k, v in lfm2["args"].items() if k != "scale"}
    # no other cell reports it
    others = [w["name"] for w in BENCH["workloads"] if w["name"] != cell]
    for other in others:
        assert name not in {m["name"] for m in manifest.metrics_of(
            BENCH, "per_layer", other)}
