"""The two per-layer metrics of the blocked paged-attention kernel (ISSUE
29): data files under ``benchmark/metrics/`` over readers that exist, held
here to the manifest, to a registry and a device table built by hand, to a
run that lacks what they read (the parent commit, a training cell), and to
a CPU rehearsal of the serving cell.

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
SERVING = "decoder_1024x24.chat_steady"

# metric -> (reader, source, layer, what it reads in the program)
NEW = {
    "decode_live_block_share": (
        "counter_ratio", "program_counter", "decode scheduler",
        ["serving_decode_live_blocks_total",
         "serving_decode_block_slots_total"]),
    "paged_attention_device_share": (
        "device_share", "device_trace", "kernels", ["paged_attention"]),
}


def _read(run, name):
    spec = manifest.load_metric(name)
    return readers.READERS[spec["reader"]](spec["args"], run)


@pytest.mark.parametrize("name", sorted(NEW))
def test_metric_file_passes_the_manifest_and_names_what_it_reads(name):
    spec = manifest.load_metric(name)
    reader, source, layer, reads = NEW[name]
    assert spec["reader"] == reader and reader in readers.READERS
    for what in reads:
        assert what in json.dumps(spec["args"])
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    for key in ("layer", "unit", "better", "source", "moves"):
        assert spec[key] == entry[key], key
    # the entry alone lists the cells; a later cell appends itself
    assert "workloads" not in spec and SERVING in entry["workloads"]
    assert entry["moves"] == "serve_token_latency_p50"
    assert (entry["source"], entry["layer"], entry["unit"]) == (
        source, layer, "%")
    assert spec["what"] and "\n" not in spec["what"]


def test_the_two_entries_come_last_and_nothing_before_them_moved():
    """Found by name, so that a later PR can append after them: the two
    follow PR 25's ten, which follow ``hbm_compiled_gb``."""
    names = [m["name"] for m in BENCH["per_layer"]]
    first = names.index("decode_live_block_share")
    assert names[first:first + 2] == ["decode_live_block_share",
                                      "paged_attention_device_share"]
    # PR 25's ten, in their order, directly before
    assert names[first - 10:first] == [
        "serve_queue_wait_ms", "serve_first_token_ms", "serve_inter_token_ms",
        "prefill_kv_fetch_ms", "decode_logits_fetch_ms", "decode_feeds_ms",
        "decode_sample_ms", "serve_fed_mb_per_step",
        "serve_fetched_mb_per_step", "serve_shed"]
    assert names[first - 11] == "hbm_compiled_gb"


def test_the_kernel_is_named_as_the_pattern_reads_it():
    """``device_share`` matches device event names; the event of a Mosaic
    call carries the ``pallas_call``'s name."""
    import inspect

    from paddle_tpu.kernels import attention

    spec = manifest.load_metric("paged_attention_device_share")
    assert f'name="{spec["args"]["pattern"]}"' in inspect.getsource(
        attention.paged_attention)


def test_the_readers_read_the_window():
    label = '{engine="e"}'
    before = {"serving_decode_live_blocks_total": {label: 1_000},
              "serving_decode_block_slots_total": {label: 30_720}}
    after = {"serving_decode_live_blocks_total": {label: 1_000 + 645 * 120},
             "serving_decode_block_slots_total": {
                 label: 30_720 + 645 * 48 * 64}}
    mosaic = ('%paged_attention.7 = f32[48,1,1024]{2,1,0:T(1,128)} '
              'custom-call(s32[3072]{0} %t, s32[48]{0} %n), '
              'custom_call_target="tpu_custom_call", metadata={op_name='
              '"jit(call)/paged_attention"}')
    other = "%fusion.1 = f32[48,4096]{1,0} fusion(f32[48,1024]{1,0} %p0)"
    device = {"ops": [[other, 1.0, 0.3], [mosaic, 1.3, 0.1],
                      [other, 2.0, 0.5], [mosaic, 2.5, 0.1],
                      [mosaic, 9.0, 0.1]],     # outside the window
              "async_ops": [], "modules": []}
    run = {"registry": (before, after), "facts": {"window_s": 51.0},
           "sizes": {"model": {"slots": 48}},
           "trace": {"devices": {"0": device}}, "trace_window": (1.0, 4.0)}
    assert _read(run, "decode_live_block_share") == pytest.approx(
        100 * 120 / (48 * 64))
    assert _read(run, "paged_attention_device_share") == pytest.approx(
        100 * 0.2 / 1.0)


def test_their_readers_find_nothing_in_a_run_that_lacks_them():
    """What the parent commit's program, or a training cell, gives these
    readers: a registry without the two counters, a device table without
    the kernel's events, or no trace at all. Each returns None and does
    not raise, and the line leaves the metric out."""
    families = {"serving_decode_steps_total": {'{engine="e"}': 40},
                "serving_active_slot_steps_total": {'{engine="e"}': 900}}
    gather = "%fusion.206 = f32[49152,1024]{1,0} fusion(f32[49152,1024] %k)"
    device = {"ops": [[gather, 1.0, 0.5]], "async_ops": [], "modules": []}
    for trace in ({"devices": {"0": device}}, None):
        run = {"registry": ({}, families), "facts": {"window_s": 1.0},
               "sizes": {"model": {}}, "spans": [], "trace": trace,
               "trace_window": (0.0, 2.0)}
        for name in NEW:
            assert _read(run, name) is None, name


def test_the_serving_rehearsal_reads_the_counter():
    """A CPU rehearsal of the cell: the scheduler counted its live blocks,
    so the share is there (null, as every value of a CPU run is); there is
    no device table, so the kernel's share is left out."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", SERVING, "--seed", "3000000029", "--seconds", "1",
         "--trace", "1", "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["decode_live_block_share"] == {
        "value": None, "unit": "%"}
    assert "paged_attention_device_share" not in line["metrics"]
