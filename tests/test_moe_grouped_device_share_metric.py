"""The reading PR 60 adds beside the grouped expert product
(``benchmark/metrics/moe_grouped_device_share.json``: a data file, read by
the reader the benchmark has, ``device_share``): synthetic device events
named as the chip names the kernel's read its share of the busy device in
both cells whose chunks take it; the step's kernel and XLA's operations do
not reach it; a program without the kernel (or a run without a trace) reads
nothing and does not raise.

(The file stands outside ``tests/benchmark_grid``: that directory's files
are the benchmark's own, which a PR that claims a gain does not edit. Like
them, this module loads no TPU library while it is imported.)
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, readers  # noqa: E402

BENCH = manifest.load_manifest()
NAME = "moe_grouped_device_share"
CELLS = ("mistral_small_4_119b.doc_qa_32k",
         "trinity_large_preview.mixed_lengths_32k")
GROUPED = ('%moe_grouped.{n} = f32[1024,4096]{{1,0:T(8,128)S(1)}} custom-call('
           '), custom_call_target="tpu_custom_call", metadata={{op_name='
           '"jit(decode_x_chunk)/moe_grouped"}}')
STEP = ('%moe_experts.4 = f32[16,16,256]{2,1,0} custom-call(), '
        'custom_call_target="tpu_custom_call"')
COPY = "%copy.795 = bf16[1024,4096]{1,0} copy()"


def _run(events):
    """A traced stretch [1.0, 4.0] of ``events`` back to back, 0.2 s
    each."""
    ops = [[e, 1.0 + 0.2 * n, 0.2] for n, e in enumerate(events)]
    device = {"ops": ops, "async_ops": [], "modules": []}
    return {"trace": {"devices": {"0": device}}, "trace_window": (1.0, 4.0),
            "spans": [], "registry": ({}, {}), "stretch_registry": [{}, {}],
            "facts": {"window_s": 51.0}, "chips": 1}


def _read(run):
    spec = manifest.load_metric(NAME)
    return readers.READERS[spec["reader"]](spec["args"], run)


def test_the_kernels_events_read_their_share_of_the_busy_device():
    run = _run([GROUPED.format(n=1), COPY, GROUPED.format(n=12), STEP, COPY])
    assert _read(run) == pytest.approx(100 * 0.4 / 1.0)
    # the step's kernel reads its own events, not these
    step = manifest.load_metric("moe_experts_device_share")
    assert readers.READERS[step["reader"]](step["args"], run) == (
        pytest.approx(20.0))


def test_a_program_without_the_kernel_reads_nothing():
    run = _run([STEP, COPY, COPY])
    assert _read(run) is None
    run["trace"] = None
    assert _read(run) is None


@pytest.mark.parametrize("cell", CELLS)
def test_the_manifest_lists_it_for_the_cell(cell):
    mine = {m["name"]: m
            for m in manifest.metrics_of(BENCH, "per_layer", cell)}
    entry, spec = mine[NAME], manifest.load_metric(NAME)
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    # (IN the list, in their order: a later cell may have joined it)
    assert entry["workloads"][:len(CELLS)] == list(CELLS)
    assert "workloads" not in spec
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        "kernels", "serve_token_latency_p50", "device_trace")
    assert spec["args"] == {"pattern": "moe_grouped"}
    # the cells that report it are the ones that report the kernel's roofline
    roofline = next(m for m in BENCH["per_layer"]
                    if m["name"] == "moe_grouped_roofline")
    assert roofline["workloads"] == entry["workloads"]
