"""The two readings PR 57 adds beside the latent chunk kernel
(``benchmark/metrics/latent_chunk_kernel_roofline.json`` and
``latent_chunk_kernel_device_share.json``: data files, read by the readers
and the count function the benchmark has): synthetic device events named as
the chip names the kernel's read the roofline ``counts/mistral4.py
latent_chunk_calls`` gives by hand and the kernel's share of the busy
device; events named as XLA's loops do not reach them, and the two accepted
files that find loops (``^%?while``) read nothing of the kernel's own events
(on the chip they find the loop XLA runs the four calls of a layer in).

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
from benchmark.counts import mistral4  # noqa: E402

BENCH = manifest.load_manifest()
NAME = "mistral_small_4_119b"
CELL = NAME + ".doc_qa_32k"
LABEL = '{engine="e"}'
NEW = ("latent_chunk_kernel_roofline", "latent_chunk_kernel_device_share")
MOVED = {"serving_chunk_attended_rows_total": 1_000_000_000,
         "serving_chunk_context_rows_total": 900_000,
         "serving_chunk_tokens_total": 100_000}
KERNEL = ('%latent_chunk_attention.{n} = bf16[1024,4096]{{1,0}} custom-call('
          '), custom_call_target="tpu_custom_call", metadata={{op_name='
          '"jit(decode_x_chunk)/latent_chunk_attention"}}')
LOOP = ('%while.{n} = (s32[], f32[512,32,1]{{2,1,0}}, f32[512,32,128]{{2,1,0}}'
        ') while(%tuple.2), condition=%cond, body=%body')
STEP = ('%latent_paged_attention.4 = bf16[16,32,256]{2,1,0} custom-call(), '
        'custom_call_target="tpu_custom_call"')
OTHER = "%fusion.1 = f32[512,4096]{1,0} fusion()"


def _run(chunk_events, moved=MOVED):
    """A traced stretch [1.0, 4.0] holding a second of busy time: the
    chunks' attention as two events of 0.2 s named ``chunk_events``, the
    step's kernel 0.2 s, another fusion 0.4 s; the chunk counters of
    ``moved`` moving inside it."""
    cfg = manifest.load_config(BENCH, NAME)
    traffic = manifest.load_traffic("doc_qa_32k")
    ops = [[chunk_events.format(n=7), 1.0, 0.2],
           [chunk_events.format(n=8), 1.5, 0.2],
           [STEP, 2.0, 0.2], [OTHER, 3.0, 0.4]]
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


def test_the_kernels_events_read_the_count_by_hand_and_the_busy_share():
    # 1e9 pairs at 16,384 FLOP, a million rows at 3,145,728, 12 layers
    ops, moved_bytes = mistral4.latent_chunk_calls(
        1_000_000_000, 900_000, 100_000, 12, 32, 64, 64, 128, 256, 2)
    assert ops == 12 * (1_000_000_000 * 16384 + 1_000_000 * 3145728)
    assert ops / 197e12 > moved_bytes / 819e9
    run = _run(KERNEL)
    assert _read("latent_chunk_kernel_roofline", run) == pytest.approx(
        100 * ops / 197e12 / 0.4)
    assert _read("latent_chunk_kernel_device_share", run) == pytest.approx(
        100 * 0.4 / 1.0)
    # the accepted pair finds the loops by their name: of the kernel the
    # first reads nothing and the second the step's kernel alone
    assert _read("latent_chunk_attention_roofline", run) is None
    assert _read("latent_attention_device_share", run) == pytest.approx(20.0)
    # (a kernel of Granite's, whose name ends this one's, is not in it)
    assert NAME + ".doc_qa_32k" not in next(
        m for m in BENCH["per_layer"]
        if m["name"] == "chunk_attention_device_share")["workloads"]


def test_the_loops_events_do_not_reach_them():
    """The parent's program: the chunks' attention as ``while`` events. The
    new pair reads nothing and does not raise; the accepted pair reads what
    it read."""
    run = _run(LOOP)
    for name in NEW:
        assert _read(name, run) is None, name
    ops, _ = mistral4.latent_chunk_calls(
        1_000_000_000, 900_000, 100_000, 12, 32, 64, 64, 128, 256, 2)
    assert _read("latent_chunk_attention_roofline", run) == pytest.approx(
        100 * ops / 197e12 / 0.4)
    assert _read("latent_attention_device_share", run) == pytest.approx(60.0)
    run["stretch_registry"] = None
    run["trace"] = None
    for name in NEW:
        assert _read(name, run) is None, name


def test_the_kernels_events_without_the_counters_read_no_roofline():
    run = _run(KERNEL, moved={})
    assert _read("latent_chunk_kernel_roofline", run) is None
    assert _read("latent_chunk_kernel_device_share", run) == pytest.approx(
        40.0)


def test_the_manifest_lists_both_for_the_cell_and_still_validates():
    mine = {m["name"]: m
            for m in manifest.metrics_of(BENCH, "per_layer", CELL)}
    # IN the list and in this order, wherever a later PR's entries stand
    # (PERF.md section 7, PR 51's finding)
    names = [m["name"] for m in BENCH["per_layer"]]
    assert [n for n in names if n in NEW] == list(NEW)
    old = manifest.load_metric("latent_chunk_attention_roofline")
    for name in NEW:
        entry, spec = mine[name], manifest.load_metric(name)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == entry[key], (name, key)
        assert entry["workloads"] == [CELL] and "workloads" not in spec
        assert (entry["layer"], entry["moves"], entry["source"]) == (
            "kernels", "serve_token_latency_p50", "device_trace")
    roof = manifest.load_metric(NEW[0])["args"]["kernels"]
    (theirs,) = old["args"]["kernels"]
    assert roof == [dict(theirs, pattern="^%?latent_chunk_attention")]
    assert mine[NEW[0]]["better"] == "higher"
    assert mine[NEW[1]]["better"] == "lower"
    assert len(BENCH["per_layer"]) <= 128
