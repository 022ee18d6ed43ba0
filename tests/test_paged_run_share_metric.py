"""The reading ISSUE 64 adds beside the pool's order
(``benchmark/metrics/paged_run_share.json``: a data file, read by the
reader the benchmark has, ``counter_ratio``): of the blocks a decode step's
paged kernel copies, the share that goes in runs of one descriptor. The
host counts it over its copy of a slot's block table by the kernel's rule:
held here to the flags the kernel's wrapper derives on the device from the
same table, to a hand-stepped engine's script, and to the file.

(The file stands outside ``tests/benchmark_grid``: that directory's files
are the benchmark's own, which a PR that claims a gain does not edit. Like
them, this module loads no TPU library while it is imported.)
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, readers  # noqa: E402

BENCH = manifest.load_manifest()
NAME = "paged_run_share"
RUN = "serving_paged_run_blocks_total"
COPIED = "serving_paged_copy_blocks_total"
SERVING = [w["name"] for w in BENCH["workloads"]
           if any(e["name"] == "serve_token_latency_p50"
                  for e in manifest.metrics_of(BENCH, "end_to_end",
                                               w["name"]))]


def _run(run_blocks, copied):
    """A window in which the counters moved by ``run_blocks`` and
    ``copied`` from a standing 5 and 9 (None: the program lacks them)."""
    before, after = {}, {}
    for name, moved, stood in ((RUN, run_blocks, 5), (COPIED, copied, 9)):
        if moved is not None:
            before[name] = {"": stood}
            after[name] = {"": stood + moved}
    # (a program from before the counters still counts its live blocks)
    before["serving_decode_live_blocks_total"] = {"": 11}
    after["serving_decode_live_blocks_total"] = {"": 11 + (copied or 400)}
    return {"trace": None, "trace_window": None, "spans": [],
            "registry": (before, after), "stretch_registry": [{}, {}],
            "facts": {"window_s": 51.0}, "chips": 1}


def _read(run):
    spec = manifest.load_metric(NAME)
    return readers.READERS[spec["reader"]](spec["args"], run)


def test_the_share_is_run_blocks_over_copied_blocks():
    assert _read(_run(700, 875)) == pytest.approx(80.0)
    assert _read(_run(0, 875)) == 0.0
    assert _read(_run(875, 875)) == pytest.approx(100.0)


def test_a_program_without_the_counters_reads_nothing():
    """The parent's program has neither counter (and still counts its live
    blocks): the reader finds nothing, and does not raise."""
    assert _read(_run(None, None)) is None
    assert _read(_run(0, 0)) is None


def test_the_manifest_lists_it_for_the_nine_serving_cells():
    assert len(SERVING) == 9
    entry = BENCH["per_layer"][-1]
    spec = manifest.load_metric(NAME)
    assert entry["name"] == NAME
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    assert entry["workloads"] == SERVING and "workloads" not in spec
    assert (entry["layer"], entry["moves"], entry["source"], entry["better"],
            entry["unit"]) == ("KV block pool and host tier",
                               "serve_token_latency_p50", "program_counter",
                               "higher", "%")
    assert (spec["reader"], spec["args"]) == ("counter_ratio", {
        "numerator": RUN, "denominator": COPIED, "scale": 100.0})
    for cell in SERVING:
        assert NAME in {m["name"] for m in manifest.metrics_of(
            BENCH, "per_layer", cell)}
    for w in BENCH["workloads"]:
        if w["name"] not in SERVING:
            assert NAME not in {m["name"] for m in manifest.metrics_of(
                BENCH, "per_layer", w["name"])}


TABLES = {
    "ascending": lambda rng: 7 + np.arange(40),
    "descending": lambda rng: 60 - np.arange(40),
    "shuffled": lambda rng: rng.permutation(64)[:40],
    "hole": lambda rng: np.where(np.arange(40) == 11, 2, 7 + np.arange(40)),
    "runs_off_the_grid": lambda rng: np.concatenate(
        [rng.permutation(5) + 90, 7 + np.arange(35)]),
    "two_chains": lambda rng: np.concatenate(
        [100 + np.arange(16), 20 + np.arange(24)]),
}


@pytest.mark.parametrize("live", [40, 39, 33, 17, 8, 7, 1])
@pytest.mark.parametrize("kind", sorted(TABLES))
def test_the_hosts_count_is_the_devices_flags_on_the_same_table(kind, live):
    """``SeqKV.run_blocks`` over the footing's table against the flags
    ``_copy_runs`` derives from the same table on the device: the blocks
    of the flagged groups that lie wholly among the first ``live``."""
    import jax.numpy as jnp

    from paddle_tpu.kernels import attention as A
    from paddle_tpu.serving.decode import build_decoder_model
    from paddle_tpu.serving.decode.kvstate import SeqKV
    from paddle_tpu.serving.decode.pool import Block

    bs = 4
    m = build_decoder_model(vocab_size=16, hidden=8, num_layers=1, slots=2,
                            max_len=40 * bs, block_size=bs, num_blocks=128,
                            name="run_count", version="1")
    table = TABLES[kind](np.random.default_rng(live))
    run = A.paged_run_blocks(16, m.num_blocks)
    assert run == A._RUN_BLOCKS == 8
    kv = SeqKV(m, m.groups[0], blocks=[Block(int(b), int(b) * bs)
                                       for b in table], run=run)
    assert kv.table[:40].tolist() == table.tolist()
    got_run, flags = A._copy_runs(jnp.asarray(table[None], jnp.int32), 16,
                                  jnp.zeros((m.num_blocks * bs, 8)), bs)
    assert got_run == run
    want = run * int(np.asarray(flags)[:live // run].sum())
    assert kv.run_blocks(live) == want
    if kind == "ascending":
        assert want == live // run * run
    if kind in ("descending", "shuffled"):
        assert want == 0
    # a footing without runs (a tiny arena, no kernel) counts none
    assert SeqKV(m, m.groups[0], blocks=kv.blocks).run_blocks(live) == 0


@pytest.mark.parametrize("kind", sorted(TABLES))
def test_a_chain_that_grows_at_its_tail_counts_as_one_built_whole(kind):
    """`remap` recounts the last counted group and the new ones, not the
    chain: a footing grown a block or a chunk at a time, its tail copied on
    write now and then, counts at every length what a footing built whole
    from the same blocks counts."""
    from paddle_tpu.serving.decode import build_decoder_model
    from paddle_tpu.serving.decode.kvstate import SeqKV
    from paddle_tpu.serving.decode.pool import Block

    bs, run = 4, 8
    m = build_decoder_model(vocab_size=16, hidden=8, num_layers=1, slots=2,
                            max_len=40 * bs, block_size=bs, num_blocks=128,
                            name="run_grow", version="1")
    rng = np.random.default_rng(7)
    table = [int(b) for b in TABLES[kind](rng)]
    kv = SeqKV(m, m.groups[0], run=run)
    n = 0
    while n < len(table):
        n = min(n + int(rng.choice([1, 1, 1, 8, 11])), len(table))
        kv.blocks = [Block(b, b * bs) for b in table[:n]]
        if rng.random() < 0.3:
            # the tail copied on write: another block in its place
            table[n - 1] = 127 - n
            kv.blocks[-1] = Block(table[n - 1], table[n - 1] * bs)
        kv.remap()
        whole = SeqKV(m, m.groups[0], blocks=list(kv.blocks), run=run)
        assert kv.runs == whole.runs, (kind, n)
        assert [kv.run_blocks(k) for k in range(n + 3)] == [
            whole.run_blocks(k) for k in range(n + 3)]
    groups = np.asarray(table).reshape(5, run)
    assert kv.runs[-1] == run * int(
        np.all(np.diff(groups, axis=1) == 1, axis=1).sum())


def test_the_counters_follow_a_hand_stepped_engines_script():
    """Two requests against a fresh pool, copy units of 16 blocks: every
    step adds each stepping slot's live blocks to
    ``serving_paged_copy_blocks_total`` (here the live blocks: a kernel
    serves the geometry) and the blocks of its whole ascending groups of 8
    to ``serving_paged_run_blocks_total``. A fresh pool hands both chains
    out ascending, the second prompt's after the first's."""
    from paddle_tpu import kernels
    from paddle_tpu.kernels import attention as A
    from paddle_tpu.observability import metrics as obs_metrics
    from paddle_tpu.serving.decode import (
        GenerationEngine, build_decoder_model,
    )

    bs, max_len = 4, 256
    prompts, max_new = [list(range(1, 70)), [3, 1, 4]], [5, 4]
    with kernels.scoped_mode("off"):
        engine = GenerationEngine(queue_depth=8, breaker_threshold=0)
        entry = engine.register_model(lambda: build_decoder_model(
            vocab_size=80, hidden=8, num_layers=1, slots=2, max_len=max_len,
            block_size=bs, num_blocks=96, name="run_script", version="1"))
        assert entry.kv.copy_unit % 8 == 0
        assert entry.kv.run_blocks == A._RUN_BLOCKS
        resps = [engine.submit(p, max_new_tokens=n, model="run_script")
                 for p, n in zip(prompts, max_new)]
        for _ in range(400):
            if all(r.done() for r in resps):
                break
            entry._iterate()
        assert all(r.error() is None for r in resps)
        st = entry.stats()
        engine.shutdown()
    live = [-(-(len(p) + t + 1) // bs)
            for p, n in zip(prompts, max_new) for t in range(n - 1)]
    assert st["decode_live_blocks"] == st["paged_copy_blocks"] == sum(live)
    assert st["paged_run_blocks"] == sum(n // 8 * 8 for n in live) > 0
    families = obs_metrics.registry().snapshot()
    assert RUN in families and COPIED in families
