"""Pallas kernel registry (paddle_tpu/kernels/): the registry-enumerated
parity gate, mode/fingerprint wiring and fused-op memory accounting
(the chip-free compile gate is tests/test_kernels_tpu_aot.py).

The parity gate is the CI contract of the subsystem: it parametrizes
over ``kernels.all_specs()``, so a kernel registered without a parity
check cannot even register, and one whose interpret-mode output drifts
from its composite fallback fails here by name.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import kernels
from paddle_tpu.kernels import registry as kreg


# ---------------------------------------------------------------------------
# the registry-enumerated parity gate
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "name", [s.name for s in kernels.all_specs()])
def test_kernel_parity(name, rng):
    """EVERY registered kernel/policy runs its interpret-mode parity
    assertion. Enumerated from the registry — a new kernel lands in this
    gate automatically; registration itself refuses a spec without a
    parity check (see test below)."""
    kernels.get(name).parity_check(rng)


def test_registration_requires_parity_check():
    with pytest.raises(ValueError, match="parity_check"):
        kernels.KernelSpec("bogus", ("x",), "bit", None, tpu_cases=list)
    with pytest.raises(ValueError, match="parity"):
        kernels.KernelSpec("bogus", ("x",), "sorta", lambda rng: None,
                           tpu_cases=list)


def test_every_kernel_spec_is_complete():
    specs = kernels.all_specs()
    assert {s.name for s in specs} >= {
        "flash_attention", "cached_attention", "paged_attention",
        "remat_policy",
    }
    for s in specs:
        assert s.op_types, s.name
        assert callable(s.parity_check), s.name
        assert s.parity in ("bit", "tolerance"), s.name


# ---------------------------------------------------------------------------
# mode resolution + scoped override
# ---------------------------------------------------------------------------


def test_mode_env_and_scoped(monkeypatch):
    monkeypatch.delenv(kernels.MODE_ENV, raising=False)
    assert kernels.mode() == "auto"
    # on this CPU rig auto resolves to composites everywhere
    assert kernels.resolved_mode() == "off"
    assert kernels.selected("cached_attention") is None
    with kernels.scoped_mode("interpret"):
        assert kernels.resolved_mode() == "interpret"
        sel = kernels.selected("cached_attention")
        assert sel is not None and sel.interpret
        with kernels.scoped_mode("off"):          # nesting: innermost wins
            assert kernels.selected("cached_attention") is None
        assert kernels.selected("cached_attention") is not None
    monkeypatch.setenv(kernels.MODE_ENV, "off")
    assert kernels.mode() == "off"
    monkeypatch.setenv(kernels.MODE_ENV, "bogus")
    from paddle_tpu.utils.enforce import EnforceError

    with pytest.raises(EnforceError, match="bogus"):
        kernels.mode()


def test_policy_kind_not_mode_selected():
    """The remat policy enumerates in the parity gate but is never
    selected by the mode (an IR attr drives it)."""
    with kernels.scoped_mode("interpret"):
        assert kernels.selected("remat_policy") is None


def test_probe():
    with kernels.scoped_mode("interpret"):
        assert kernels.probe("flash_attention")
    with kernels.scoped_mode("off"):
        assert not kernels.probe("flash_attention")


# ---------------------------------------------------------------------------
# compile-cache fingerprint join (the core/lowering.py chokepoint)
# ---------------------------------------------------------------------------


def test_kernel_sig_modes():
    with kernels.scoped_mode("off"):
        assert kernels.kernel_sig() is None
    with kernels.scoped_mode("auto"):
        # auto on a CPU backend = composites = pre-registry fingerprints
        assert (kernels.kernel_sig() is None) == (
            jax.default_backend() != "tpu")
    with kernels.scoped_mode("interpret"):
        sig = kernels.kernel_sig()
        assert sig is not None and sig[0] == "interpret"
        assert ("cached_attention", 1) in sig[1]


def _tiny_cached_attention_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        q = fluid.data("q", shape=[4, 8], dtype="float32")
        k = fluid.data("k", shape=[4, 16, 8], dtype="float32")
        v = fluid.data("v", shape=[4, 16, 8], dtype="float32")
        b = fluid.data("b", shape=[4, 1, 16], dtype="float32")
        out = fluid.layers.cached_attention(q, k, v, b, sm_scale=0.3,
                                            fused=True)
    return main, startup, out


def test_mode_flip_retraces_and_stays_bit_identical(rng):
    """The end-to-end chokepoint property: flipping PADDLE_TPU_KERNELS
    must MISS the content-addressed cache (kernel_sig joins the
    fingerprint — a stale composite executable must never serve the
    kernel mode) while the outputs stay BIT-identical."""
    from paddle_tpu.observability import metrics as obs_metrics

    main, startup, out = _tiny_cached_attention_program()
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {
        "q": rng.randn(4, 8).astype("float32"),
        "k": rng.randn(4, 16, 8).astype("float32"),
        "v": rng.randn(4, 16, 8).astype("float32"),
        "b": np.where(rng.rand(4, 1, 16) > 0.3, 0, -1e9).astype("float32"),
    }
    jits = obs_metrics.registry().counter("lowering_jit_total", "")
    outs, trace_counts = {}, {}
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for mode in ("off", "interpret", "off"):
            j0 = jits.value
            with kernels.scoped_mode(mode):
                got = np.asarray(
                    exe.run(main, feed=feed, fetch_list=[out])[0])
            traced = jits.value - j0
            outs.setdefault(mode, []).append(got)
            trace_counts.setdefault(mode, []).append(traced)
    # first "off" and "interpret" each traced; second "off" hit the
    # memory tier (same fingerprint as the first)
    assert trace_counts["off"][0] > 0
    assert trace_counts["interpret"][0] > 0, (
        "interpret mode served the composite executable — kernel_sig "
        "did not join the fingerprint")
    assert trace_counts["off"][1] == 0
    a, b_, c = outs["off"][0], outs["interpret"][0], outs["off"][1]
    assert a.tobytes() == b_.tobytes() == c.tobytes()


# ---------------------------------------------------------------------------
# fused-op static memory accounting
# ---------------------------------------------------------------------------


def test_paged_memory_accounting_orders():
    """kernel-path < composite-path, the gap is (at least ~) the dense
    gather views — and the LIVE estimate follows the registry: the
    composite's views are charged where the composite runs ("off", and a
    paged op that names no block size), not where the blocked kernel
    serves the op."""
    from paddle_tpu.analysis.memory import estimate_peak_hbm
    from paddle_tpu.serving.decode import build_decoder_model

    geom = dict(vocab_size=64, hidden=16, num_layers=2, slots=4,
                max_len=256)
    m = build_decoder_model(name="acct", version="1", block_size=16,
                            num_blocks=24, **geom)
    fs = {n: s for n, s, _d in m.decode_feed_sig()}
    comp = estimate_peak_hbm(m.decode_program, feed_shapes=fs,
                             fetch_names=[m.logits_fetch],
                             kernel_path=False)
    kern = estimate_peak_hbm(m.decode_program, feed_shapes=fs,
                             fetch_names=[m.logits_fetch],
                             kernel_path=True)
    assert kern.peak_total_bytes < comp.peak_total_bytes
    gather = 2 * geom["slots"] * geom["max_len"] * geom["hidden"] * 4
    assert comp.peak_total_bytes - kern.peak_total_bytes >= 0.5 * gather
    # default (None) consults the live registry: off-mode == composite
    with kernels.scoped_mode("off"):
        live = estimate_peak_hbm(m.decode_program, feed_shapes=fs,
                                 fetch_names=[m.logits_fetch])
    assert live.peak_total_bytes == comp.peak_total_bytes
    with kernels.scoped_mode("interpret"):
        live_k = estimate_peak_hbm(m.decode_program, feed_shapes=fs,
                                   fetch_names=[m.logits_fetch])
        assert live_k.peak_total_bytes == kern.peak_total_bytes
        # an op without the attribute (a program serialized before the
        # kernel) lowers to the composite under every mode
        for op in m.decode_program.global_block().ops:
            if op.type == "paged_attention":
                del op.attrs["block_size"]
        old = estimate_peak_hbm(m.decode_program, feed_shapes=fs,
                                fetch_names=[m.logits_fetch])
    assert old.peak_total_bytes == comp.peak_total_bytes


# ---------------------------------------------------------------------------
# the blocked paged kernel against its composite
# ---------------------------------------------------------------------------

#: (seqs, length, block, hidden, lengths, share): ragged lengths around a
#: block's and a group's edges, a full slot, free slots, shared prefix
#: blocks, a length that is no multiple of the block, one block a group
PAGED_CASES = {
    "ragged": (6, 64, 16, 128, [1, 15, 16, 17, 64, 0], ()),
    "shared_prefix": (4, 64, 16, 128, [40, 33, 64, 48], [(1, 0, 2),
                                                        (3, 2, 3)]),
    "group_edges": (4, 300, 16, 128, [300, 129, 128, 127], ()),
    "rehearsal_geometry": (4, 24, 4, 8, [1, 5, 24, 0], ()),
    "length_not_a_block_multiple": (3, 30, 4, 8, [30, 7, 0], ()),
    "one_block_a_group": (3, 512, 256, 128, [512, 257, 3], ()),
    # what the copy pipeline hands from slot to slot (PR 44)
    "first_slot_free": (4, 64, 16, 128, [0, 20, 64, 7], ()),
    "last_slot_the_only_live_one": (4, 64, 16, 128, [0, 0, 0, 33], ()),
    "live_and_free_alternating": (6, 64, 16, 128, [17, 0, 64, 0, 5, 0], ()),
    "all_free": (3, 64, 16, 128, [0, 0, 0], ()),
    # hidden 1,024 in float32: a copy unit is one 128-row tile
    "one_unit_then_eight": (2, 1024, 16, 1024, [100, 1024], ()),
    "eight_units_then_one": (2, 1024, 16, 1024, [1024, 100], ()),
    # hidden 128 in float32: a copy unit is eight tiles, 1,024 rows
    "unit_edges": (4, 2048, 16, 128, [1023, 1024, 0, 1025], ()),
    "unit_larger_than_the_slot": (3, 48, 16, 128, [48, 17, 0], ()),
}


_paged_both = kernels._paged_both


@pytest.mark.parametrize("case", sorted(PAGED_CASES))
@pytest.mark.parametrize("shuffle", [False, True],
                         ids=["table_in_order", "table_out_of_order"])
def test_paged_kernel_matches_composite(case, shuffle, rng):
    """Both ways at 1e-5 (an online softmax regroups float32 sums: a
    tolerance, not bytes). A free slot's row is finite (zeros) and is not
    compared: the composite averages whatever rows the table names."""
    S, L, bs, H, lengths, share = PAGED_CASES[case]
    args = kernels._paged_case(rng, S, L, bs, H, lengths, share=share,
                               shuffle=shuffle)
    kernels._assert_paged_parity(args, lengths, case, S, L, bs, H)


#: (K/V heads a row, query heads to each, head size, rows of a copy unit in
#: float32 at block 16): the serving cells' three grouped layouts
GROUPED = {"gqa128": (2, 4, 128, 512), "gqa64_packed": (8, 4, 64, 256),
           "mha128": (16, 1, 128, 128)}


@pytest.mark.parametrize("layout", sorted(GROUPED))
def test_paged_grouped_kernel_at_a_copy_units_edges(layout, rng):
    """The grouped body at lengths one under, at and one over its
    geometry's copy unit, a free slot between them, then two units."""
    from paddle_tpu.kernels import attention as A

    G, per, D, unit_rows = GROUPED[layout]
    assert A._paged_group(16, 2 * unit_rows // 16, G * D,
                          "float32") * 16 == unit_rows
    kernels._parity_paged_unit_edges(rng, G, per, D, unit_rows)


def _poison_dead_rows(k, v, rows, lengths, L, bs):
    """NaNs in every arena row that no live block of any slot names."""
    live_rows = set()
    for s, n in enumerate(lengths):
        for blk in range(-(-n // bs)):
            r0 = rows.reshape(len(lengths), L)[s, blk * bs]
            live_rows.update(range(r0, r0 + bs))
    dead = np.array(sorted(set(range(k.shape[0])) - live_rows))
    k2, v2 = k.copy(), v.copy()
    k2[dead], v2[dead] = np.nan, np.nan
    return k2, v2


PERMUTATIONS = {"reversed": [5, 4, 3, 2, 1, 0], "rotated": [2, 3, 4, 5, 0, 1],
                "interleaved": [3, 0, 4, 1, 5, 2]}


@pytest.mark.parametrize("order", sorted(PERMUTATIONS))
@pytest.mark.parametrize("body", ["plain", "grouped"])
def test_paged_kernel_slot_output_is_the_same_wherever_it_stands(
        body, order, rng):
    """A slot's output bytes depend on its own rows, bias and query alone:
    not on which slots stand before it (whose last unit its first copy
    rode under), not on which half of the scratch that copy landed in, not
    on what a short unit left stale there. One case with its slots
    permuted, NaNs planted in every dead arena row. Rows wide enough that a
    copy unit is ONE 128-row tile: slots of one to four units."""
    L, bs = 512, 16
    lengths = [130, 0, 512, 17, 0, 300]
    if body == "plain":
        G, geometry = 0, (len(lengths), L, bs, 1024)
        q, k, v, rows, bias = kernels._paged_case(rng, *geometry, lengths)
    else:
        G = 16
        (q, k, v, rows, bias), geometry = kernels._paged_heads_case(
            rng, G, 1, 128, L, lengths)
    k, v = _poison_dead_rows(k, v, rows, lengths, L, bs)
    base, _ = _paged_both((q, k, v, rows, bias), *geometry, kv_heads=G)
    assert np.isfinite(base).all()
    perm = PERMUTATIONS[order]
    moved, _ = _paged_both(
        (q[perm], k, v, rows.reshape(len(lengths), L)[perm].reshape(-1),
         bias[perm]), *geometry, kv_heads=G)
    assert moved.tobytes() == base[perm].tobytes()


def test_paged_kernel_free_slot_moves_no_other_slot(rng):
    """Freeing a slot (an all-masked bias row, its rows zeroed as the
    engine's feeds do) changes no byte of any other slot's output, and
    dead blocks are never read: NaNs planted in every row past the live
    lengths do not reach the result."""
    S, L, bs, H = 4, 64, 16, 128
    lengths = [20, 64, 33, 7]
    q, k, v, rows, bias = kernels._paged_case(rng, S, L, bs, H, lengths)
    full, _ = _paged_both((q, k, v, rows, bias), S, L, bs, H)
    rows2, bias2 = rows.reshape(S, L).copy(), bias.copy()
    rows2[2], bias2[2] = 0, -1e9
    freed, _ = _paged_both((q, k, v, rows2.reshape(-1), bias2), S, L, bs, H)
    keep = [0, 1, 3]
    assert freed[keep].tobytes() == full[keep].tobytes()
    assert not freed[2].any()
    k2, v2 = _poison_dead_rows(k, v, rows, lengths, L, bs)
    poisoned, _ = _paged_both((q, k2, v2, rows, bias), S, L, bs, H)
    assert poisoned.tobytes() == full.tobytes()


def test_paged_kernel_any_bias(rng):
    """The bias tile is added inside the kernel, so a bias that is not a
    prefix (holes, finite penalties) gives the composite's answer too:
    the lengths only bound which blocks are read."""
    S, L, bs, H = 3, 64, 16, 128
    q, k, v, rows, bias = kernels._paged_case(rng, S, L, bs, H, [64, 50, 30])
    bias[0, 0, 5:40] = -1e9          # a hole over two whole blocks
    bias[1, 0, :50] = rng.randn(50)  # finite penalties
    bias[2, 0, ::3] = -1e9
    got, ref = _paged_both((q, k, v, rows, bias), S, L, bs, H)
    kernels._assert_close_both_ways(got, ref, "any bias", 1e-5, 1e-5)


def test_paged_kernel_geometry_fallbacks_are_counted(rng):
    """A block Mosaic cannot tile runs the composite on the compiled
    path and counts in kernel_fallbacks_total; the interpreter has no
    such limit."""
    from paddle_tpu.kernels import attention as A

    S, L, bs, H = 2, 8, 4, 8
    args = kernels._paged_case(rng, S, L, bs, H, [8, 3])
    c = kernels.fallback_counter()
    c0 = c.value
    out = jax.jit(lambda *a: A.paged_attention(
        *a, S, L, bs, 0.5, interpret=False))(*args)
    ref = jax.jit(lambda *a: A.paged_attention_composite(
        *a, S, L, 0.5))(*args)
    assert c.value == c0 + 1
    assert np.asarray(out).tobytes() == np.asarray(ref).tobytes()
    assert A._mosaic_tiles(16, 1024, "float32")
    assert not A._mosaic_tiles(8, 1024, "bfloat16")
    # a copy unit is about a megabyte of K plus V in whole reduce tiles
    # (decoder, ouro: one tile; lfm2 four; nemotron eight), never more
    # than the slot, and shrinks to fit the VMEM budget before it gives up
    assert A._paged_group(16, 64, 1024, "float32") == 8
    assert A._paged_group(16, 64, 2048, "bfloat16") == 8
    assert A._paged_group(16, 128, 512, "bfloat16") == 32
    assert A._paged_group(16, 128, 256, "bfloat16") == 64
    assert A._paged_group(16, 4, 256, "bfloat16") == 4
    assert A._paged_group(16, 64, 8192, "float32") == 6
    assert A._paged_group(256, 4, 8192, "float32") == 0
    assert A._paged_tile(16, 128, 256, "bfloat16") == 8


# ---------------------------------------------------------------------------
# on-device embedding admission
# ---------------------------------------------------------------------------


def test_embedding_device_admission_bit_identical_and_no_roundtrips():
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.embedding.store import EmbeddingEngine
    from paddle_tpu.embedding.table import TableConfig
    from paddle_tpu.kernels.embedding import admission_roundtrip_counter

    def drive(mode):
        with kernels.scoped_mode(mode):
            sc = Scope()
            eng = EmbeddingEngine(scope=sc)
            rt = eng.register(TableConfig(name="kadm", dim=4, capacity=24,
                                          ep=2, seed=7))
            r = np.random.RandomState(0)
            for _ in range(6):
                ids = r.randint(0, 64, 10).astype(np.int64)
                rt.lookup(ids, dedup=True, train=True)
                slab = np.asarray(sc.find_var(rt.cfg.slab_name))
                sc.set(rt.cfg.slab_name, slab + 0.001)
            rt.flush()
            blocks = rt.store.snapshot_blocks()
            eng.close()
            return [(i.tobytes(), v.tobytes()) for i, v in blocks]

    c = admission_roundtrip_counter()
    c0 = c.value
    legacy = drive("off")
    c1 = c.value
    assert c1 - c0 > 0, "legacy path stopped counting round-trips"
    device = drive("auto")
    assert c.value == c1, "device admission round-tripped the slab"
    assert legacy == device
