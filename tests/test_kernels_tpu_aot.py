"""Chip-free compile guard: every Pallas kernel in the registry must
AOT-compile for a TPU v5e.

Interpret mode (tests/test_kernels.py) accepts programs Mosaic refuses —
PR 21 found the flash backward, the paged kernel, the admission scatter and
both flag-gated kernels all interpreting cleanly while none of them lowered
for the chip. The installed libtpu compiles against a device-less topology
under ``JAX_PLATFORMS=cpu``, so the compiled (``interpret=False``) path of
each spec's ``tpu_cases`` is lowered and compiled here, in seconds, with no
accelerator. AOT compiling is a pre-check, not the proof: VMEM limits, HBM
fit and numerics are settled by ``chip_smoke.py`` on the chip.
"""

import os
import re

import numpy as np
import pytest

import jax
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from paddle_tpu import kernels

KERNEL_SPECS = [s for s in kernels.all_specs() if s.kind == "kernel"]


@pytest.fixture(scope="module")
def v5e_devices():
    # libtpu takes a machine-wide lock for the life of the process that
    # loads it; a compile-only client holds no device, so let parallel
    # test workers (xdist) each load their own
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    devices = topologies.get_topology_desc("v5e:2x2", "tpu").devices
    assert len(devices) == 4
    assert devices[0].device_kind == "TPU v5 lite", devices[0].device_kind
    return devices


def test_every_kernel_declares_tpu_cases():
    assert {s.name for s in KERNEL_SPECS} >= {
        "flash_attention", "cached_attention", "paged_attention"}
    with pytest.raises(ValueError, match="tpu_cases"):
        kernels.KernelSpec("bogus", ("x",), "bit", lambda rng: None)


@pytest.mark.parametrize("name", [s.name for s in KERNEL_SPECS])
def test_kernel_compiles_for_v5e(name, v5e_devices):
    sharding = SingleDeviceSharding(v5e_devices[0])
    for label, fn, arg_specs in kernels.get(name).tpu_cases():
        args = [jax.ShapeDtypeStruct(shape, np.dtype(dtype), sharding=sharding)
                for shape, dtype in arg_specs]
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert 'custom_call_target="tpu_custom_call"' in text, (
            f"{name}[{label}]: compiled without a Pallas custom call — the "
            "case ran a composite, so it guards nothing")


#: the six serving cells' paged-attention geometries (``_tpu_cases_paged``'s
#: labels) and the blocks of a copy unit at each: about a megabyte of K
#: plus V whatever the row width
PAGED_UNITS = {
    "s48_l1024_b16_h1024": 8,                   # decoder_1024x24, float32
    "s32_l2048_b16_g2x16x128_bf16": 64,         # nemotron3_nano_30b_a3b
    "s128_l2048_b16_g8x4x64_bf16": 32,          # lfm2_24b_a2b
    "s16_l1024_b16_g16x1x128_bf16": 8,          # ouro_2_6b
    "s32_l1024_b16_g4x32x128_bf16": 32,         # sdar_30b_a3b, a block pass
    "s32_l16896_b16_g8x4x64_bf16": 32,          # granite_4_0_h_micro: 1,056
}                                               # blocks a slot


@pytest.mark.parametrize("label", sorted(PAGED_UNITS))
def test_paged_kernel_serves_every_cells_geometry(label, v5e_devices):
    """Mosaic takes the paged kernel's body (the copy pipeline carried
    from slot to slot through SMEM, a unit waited for in one descriptor an
    arena, several slots a grid step) at each serving cell's geometry with
    no fallback to the composite, and two halves of K and of V at the
    cell's copy unit stay under the kernel's VMEM budget."""
    from paddle_tpu.kernels import attention as A

    # (the two-arena cases: a latent cache's one arena has its own test)
    cases = {c[0]: c for c in kernels.get("paged_attention").tpu_cases()
             if len(c[2]) == 5}
    assert set(cases) == set(PAGED_UNITS)
    _label, fn, arg_specs = cases[label]
    (rows, hidden), dtype = arg_specs[1]
    seqs = arg_specs[4][0][0]
    per_slot = rows // seqs // 16
    unit = A._paged_group(16, per_slot, hidden, dtype)
    assert unit == PAGED_UNITS[label]
    assert unit % A._paged_tile(16, per_slot, hidden, dtype) == 0
    scratch = 4 * unit * 16 * hidden * np.dtype(dtype).itemsize
    assert scratch == 2 * 2 ** 20 <= A.VMEM_BUDGET
    sharding = SingleDeviceSharding(v5e_devices[0])
    args = [jax.ShapeDtypeStruct(shape, np.dtype(dt), sharding=sharding)
            for shape, dt in arg_specs]
    before = kernels.fallback_counter().value
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert kernels.fallback_counter().value == before
    assert text.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("label", sorted(PAGED_UNITS))
def test_every_cells_copy_unit_is_whole_runs(label):
    """A copy unit of every serving cell's geometry is whole groups of
    ``_RUN_BLOCKS`` blocks, so the compile above lowered the run
    descriptors (ISSUE 64: a flagged, wholly live group goes in ONE copy
    of 8 x 16 rows), not the static block-by-block path of a tiny arena."""
    from paddle_tpu.kernels import attention as A

    unit = PAGED_UNITS[label]
    assert A._RUN_BLOCKS == 8 and unit % 8 == 0
    assert A.paged_run_blocks(unit, 20480) == 8
    assert A.paged_run_blocks(unit, 7) == 0
    assert A.paged_run_blocks(12, 20480) == 0


@pytest.mark.parametrize("run", [0, 4, 16])
def test_the_paged_kernel_lowers_at_other_runs_and_at_none(
        run, v5e_devices, monkeypatch):
    """The copy pipeline lowers for the chip whatever ``_RUN_BLOCKS`` is
    (what ``tools/check_paged_copies.py --run-blocks`` sweeps), and with
    no runs at all (the path of an arena smaller than a run): at the
    widest two-arena geometry, granite's 1,056 blocks a slot."""
    from paddle_tpu.kernels import attention as A

    monkeypatch.setattr(A, "_RUN_BLOCKS", run)
    (case,) = [c for c in kernels.get("paged_attention").tpu_cases()
               if c[0] == "s32_l16896_b16_g8x4x64_bf16"]
    _label, fn, arg_specs = case
    sharding = SingleDeviceSharding(v5e_devices[0])
    args = [jax.ShapeDtypeStruct(shape, np.dtype(dt), sharding=sharding)
            for shape, dt in arg_specs]
    before = kernels.fallback_counter().value
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert kernels.fallback_counter().value == before
    assert text.count('custom_call_target="tpu_custom_call"') == 1


def test_paged_kernel_serves_one_latent_arena(v5e_devices):
    """Handed ONE arena (mistral_small_4_119b: 16 slots of 33,280 positions,
    rows of 384 lanes, 32 absorbed query heads, values the first 256 lanes)
    the grouped body compiles with no fallback at the geometry it RUNS:
    ``paged_copy_unit`` for one arena, a copy unit of one reduce tile of
    512 rows (384 KB of the ONE arena), two halves of it under the VMEM
    budget."""
    from paddle_tpu.kernels import attention as A

    (case,) = [c for c in kernels.get("paged_attention").tpu_cases()
               if len(c[2]) == 4]
    _label, fn, arg_specs = case
    (rows, width), dtype = arg_specs[1]
    assert (width, dtype) == (384, "bfloat16")
    per_slot = 33280 // 16
    unit = A.paged_copy_unit(16, per_slot, width, dtype, arenas=1)
    assert unit == 32 == A._paged_tile(16, per_slot, width, dtype,
                                       A._LATENT_TILE_ROWS)
    assert unit * 16 == A._LATENT_TILE_ROWS
    assert 2 * unit * 16 * width * 2 <= A.VMEM_BUDGET
    sharding = SingleDeviceSharding(v5e_devices[0])
    args = [jax.ShapeDtypeStruct(shape, np.dtype(dt), sharding=sharding)
            for shape, dt in arg_specs]
    before = kernels.fallback_counter().value
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert kernels.fallback_counter().value == before
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "latent_paged_attention" in text


@pytest.mark.parametrize("chunk", [1024, 512])
def test_latent_chunk_kernel_serves_the_cells_chunk(chunk, v5e_devices):
    """mistral_small_4_119b's chunk of 1,024 queries, and a prompt's short
    last launch of 512 (32 heads of 64 + 64 | 128 over a latent of 256, a
    slot of 33,280 positions in blocks of 16 rows of 384 lanes), compiles
    as ONE call named ``latent_chunk_attention`` (8 heads a call) in ONE
    loop over the four groups of heads, counted a lowered call and no
    fallback, and makes nothing of a chunk's expanded size in HBM:
    beside its arguments and its output the executable keeps the queries
    and the output turned (8 MB each at 1,024 queries) and little else (a
    slot's expanded keys and values would be 537 MB)."""
    from paddle_tpu.kernels import attention as A
    from paddle_tpu.kernels import registry

    (case,) = [c for c in kernels.get("latent_chunk_attention").tpu_cases()
               if c[2][0][0][0] == chunk]
    _label, fn, arg_specs = case
    sharding = SingleDeviceSharding(v5e_devices[0])
    args = [jax.ShapeDtypeStruct(shape, np.dtype(dt), sharding=sharding)
            for shape, dt in arg_specs]
    served, fell = registry.latent_chunk_counter(), kernels.fallback_counter()
    before = served.value, fell.value
    compiled = jax.jit(fn).lower(*args).compile()
    assert (served.value - before[0], fell.value - before[1]) == (1, 0)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert A.LATENT_CHUNK_KERNEL in text and text.count(" while(") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 24 << 20


#: the flash cases' geometries (``_tpu_cases_flash``'s labels): B*H, S and
#: the (batch, head) pairs a grid step of the forward, dk/dv and dq kernels
#: serves there — the largest divisor of B*H a 4 MiB set of blocks holds
FLASH_GROUPS = {
    "bert_s128": (96, 128, (24, 16, 16)),
    "bert_s128_b256": (3072, 128, (24, 16, 16)),   # the training cells'
    "bert_s128_odd": (21, 128, (21, 7, 21)),
    "causal_s512": (16, 512, (8, 8, 8)),
}


@pytest.mark.parametrize("label", sorted(FLASH_GROUPS))
def test_flash_kernels_group_heads_at_every_geometry(label, v5e_devices):
    """Mosaic takes all three flash kernels with several (batch, head)
    pairs a grid step (the loop over a block's leading index) at the
    training cells' full per-chip geometry, at an odd B*H and at a causal
    multi-block length, without a fallback; the grids the lowering counts
    are B*H / G x S / 128 with the G the shapes give."""
    cases = {c[0]: c for c in kernels.get("flash_attention").tpu_cases()}
    assert {c[:-5] for c in cases if c.endswith("_grad")} == set(FLASH_GROUPS)
    bh, seq, groups = FLASH_GROUPS[label]
    _label, fn, arg_specs = cases[label + "_grad"]
    assert arg_specs[0][0][0] * arg_specs[0][0][1] == bh
    sharding = SingleDeviceSharding(v5e_devices[0])
    args = [jax.ShapeDtypeStruct(shape, np.dtype(dt), sharding=sharding)
            for shape, dt in arg_specs]
    before = kernels.flash_grid_snapshot()
    fallbacks = kernels.fallback_counter().value
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert kernels.fallback_counter().value == fallbacks
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    after = kernels.flash_grid_snapshot()
    for kernel, g in zip(kernels.FLASH_KERNELS, groups):
        assert after[kernel]["heads_per_step"] == g
        assert (after[kernel]["grid_steps"] - before[kernel]["grid_steps"]
                == bh // g * (seq // 128))


def test_moe_experts_takes_a_step_of_no_whole_sublane_tiles(v5e_devices):
    """A step of 24 slots (trinity_large_preview) is no whole number of
    bfloat16 sublane tiles: ``moe_experts`` pads it to 32 tokens that choose
    no expert and Mosaic takes it, ONE custom call and no fallback to the
    dense product over every held expert; the result has the step's rows."""
    (case,) = [c for c in kernels.get("moe_experts").tpu_cases()
               if c[0].startswith("t24_")]
    _label, fn, arg_specs = case
    sharding = SingleDeviceSharding(v5e_devices[0])
    args = [jax.ShapeDtypeStruct(shape, np.dtype(dt), sharding=sharding)
            for shape, dt in arg_specs]
    before = kernels.fallback_counter().value
    lowered = jax.jit(fn).lower(*args)
    text = lowered.compile().as_text()
    assert kernels.fallback_counter().value == before
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert lowered.out_info.shape == arg_specs[0][0]


#: ``moe_experts``' traced program (the call's grid, block maps, scratches
#: and body: ``str(jax.make_jaxpr(...))``, which names no file and no line)
#: at the step geometries of the five routed serving cells
#: (``kernels.MOE_EXPERTS_STEPS``), as PR 62 left it (the grid's expert axis
#: a traced bound): sha256. An edit beside the kernel (the grouped
#: product's body, PR 60) must not move the five cells' step; a deliberate
#: edit to ``moe_experts`` recomputes these AND bumps its ``KernelSpec``
#: version
EXPERTS_DIGESTS = {
    "nemotron3_nano_30b_a3b": "9af8962f4d132952",
    "lfm2_24b_a2b": "b1199ed721335b3d",
    "sdar_30b_a3b": "021d63bb1cebc6d9",
    "mistral_small_4_119b": "2475faf3bb8613f2",
    "trinity_large_preview": "65720a41c3457090",
}


@pytest.mark.parametrize("cell", sorted(EXPERTS_DIGESTS))
def test_moe_experts_lowers_to_the_module_it_did(cell):
    import hashlib

    from paddle_tpu.kernels import moe

    assert set(EXPERTS_DIGESTS) == set(kernels.MOE_EXPERTS_STEPS)
    T, H, F, E, matrices = kernels.MOE_EXPERTS_STEPS[cell]
    args = [jax.ShapeDtypeStruct((T, H), np.dtype("bfloat16")),
            jax.ShapeDtypeStruct((T, E), np.dtype("float32"))] + [
        jax.ShapeDtypeStruct((E, F, H), np.dtype("bfloat16"))] * matrices
    text = str(jax.make_jaxpr(moe.moe_experts)(*args))
    assert "moe.py" not in text and "name=moe_experts" in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == (
        EXPERTS_DIGESTS[cell])


def _arrays_of_rows(text, rows):
    """Shapes in a compiled program's text with a dimension of ``rows``
    and more than a number a row: ``bf16[6144,4096]``, ``f32[6144,16,256]``
    (the layout's own vectors, ``s32[6144]`` and ``f32[6144,1]``, are a
    number a row)."""
    shapes = set(re.findall(r"\b[a-z]+\d*\[([\d,]+)\]", text))
    return sorted(s for s in shapes if str(rows) in s.split(",")
                  and np.prod([int(n) for n in s.split(",")]) > rows)


#: the two cells whose chunks take the grouped product: the chunk's tokens,
#: hidden size, expert width, held experts, choices (``_tpu_cases_moe_
#: grouped`` labels a case by them)
GROUPED_CELLS = {
    "mistral_small_4_119b": (1024, 4096, 2048, 16, 4),
    "trinity_large_preview": (1024, 3072, 3072, 32, 4),
}


@pytest.mark.parametrize("cell", sorted(GROUPED_CELLS))
def test_moe_grouped_serves_both_cells_chunk(cell, v5e_devices):
    """At each cell's geometry the grouped product lowers through Mosaic,
    ONE custom call and no fallback, and the compiled function holds no
    array of ``grouped_rows`` rows but the layout's own vectors: what XLA
    carried to the kernel and from it (the sorted rows made, turned,
    written in float32, turned back, gathered) is gone."""
    from paddle_tpu.kernels import moe

    T, H, F, held, k = GROUPED_CELLS[cell]
    (case,) = [c for c in kernels.get("moe_grouped").tpu_cases()
               if c[0] == f"t{T}_h{H}_f{F}_e{held}_k{k}_m3_bf16"]
    _label, fn, arg_specs = case
    sharding = SingleDeviceSharding(v5e_devices[0])
    args = [jax.ShapeDtypeStruct(shape, np.dtype(dt), sharding=sharding)
            for shape, dt in arg_specs]
    before = kernels.fallback_counter().value
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert kernels.fallback_counter().value == before
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    rows = moe.grouped_rows(T, k, held)
    assert rows in (6144, 8192) and f"s32[{rows}]" in text
    assert _arrays_of_rows(text, rows) == []


@pytest.mark.parametrize("cell", sorted(GROUPED_CELLS))
def test_no_chunk_program_holds_the_grouped_products_rows(cell, v5e_devices,
                                                          monkeypatch):
    """The chunk program of each of the two configurations, built at the
    cell's size from a scope of SHAPES (no weights) and compiled for the
    described chip: a ``moe_grouped`` call a routed layer, no fallback, and
    no array of ``grouped_rows`` rows (``[6144, 4096]``, ``[8192, 3072]``,
    in any dtype or tiling) outside them. PR 57 found a chunk launch 12 ms
    slower when XLA moved such a 100 MB array out of VMEM; there is none to
    place now."""
    import importlib

    from benchmark import manifest
    from paddle_tpu.core import lowering
    from paddle_tpu.kernels import moe, registry
    from paddle_tpu import serving

    bench = manifest.load_manifest()
    cfg = manifest.load_config(bench, cell)
    builder = importlib.import_module("benchmark.builders." + cfg["builder"])
    keys = manifest.published(cfg, False)
    settings = manifest.sizes(cfg["settings"], False)
    model = manifest.model_sizes(cfg, False)
    build = {"mistral4_engine": serving.build_latent_moe_model,
             "afmoe_engine": serving.build_afmoe_model}[cfg["builder"]]
    m = build(name=cell, version="1", dtype=settings["dtype"],
              expert_rank=settings["expert_rank"],
              initializer_range=settings["initializer_range"],
              **{k: keys[k] for k in builder._BUILDER_KEYS}, **model)
    sharding = SingleDeviceSharding(v5e_devices[0])

    class Shapes:
        """A scope of shapes: the programs' persistables, no values."""

        def __init__(self):
            self.vars = {
                name: jax.ShapeDtypeStruct(tuple(v.shape), np.dtype(v.dtype),
                                           sharding=sharding)
                for program in (m.startup_program, m.chunk_program)
                for block in program.blocks
                for name, v in block.vars.items() if v.persistable}

        def has_var(self, name):
            return name in self.vars

        def find_var(self, name):
            return self.vars.get(name)

    scope = Shapes()
    monkeypatch.setattr(registry, "_on_tpu", lambda: True)
    sig = sorted(m.chunk_feed_sig())
    before = kernels.fallback_counter().value
    entry, _src = lowering.lower_step(
        m.chunk_program, scope,
        tuple((n, shape, str(np.dtype(dt))) for n, shape, dt in sig),
        [m.chunk_logits_fetch], donate=True, use_cache=False, persist=False,
        label="hlo")
    text = entry.fn.lower(
        tuple(jax.ShapeDtypeStruct(shape, np.dtype(dt), sharding=sharding)
              for _n, shape, dt in sig),
        tuple(scope.find_var(n) for n in entry.donated),
        tuple(scope.find_var(n) for n in entry.readonly),
        jax.ShapeDtypeStruct((2,), np.uint32, sharding=sharding),
    ).compile().as_text()
    assert kernels.fallback_counter().value == before
    routed = sum(op.type == "moe_routed_experts"
                 for op in m.chunk_program.global_block().ops)
    assert routed and len(re.findall(
        r"= [^=]*custom-call\([^\n]*moe_grouped", text)) == routed
    T, H, _F, held, k = GROUPED_CELLS[cell]
    assert (T, H) == (model["chunk_tokens"], keys["hidden_size"])
    rows = moe.grouped_rows(T, k, held)
    assert rows in (6144, 8192) and f"s32[{rows}]" in text
    assert _arrays_of_rows(text, rows) == []


#: ``_tpu_cases_ssm_update``'s labels and the heads of one slot a grid step
#: of ``ssm_update`` carries there: the hybrid serving cells' layer (both
#: publish 64 heads x 64 x 128: a slot's whole 2 MB state a grid step) and
#: the smallest block the chooser can return
UPDATE_BLOCKS = {
    "s32_h64_p64_n128": 64,
    "s4_h3_p512_n1024": 1,
}


@pytest.mark.parametrize("label", sorted(UPDATE_BLOCKS))
def test_ssm_update_carries_the_block_the_shapes_give(label, v5e_devices):
    """Mosaic takes ``ssm_update`` at the block ``_update_heads`` gives
    from the operands' shapes, under the default VMEM limit, as ONE custom
    call with no fallback to the composite."""
    from paddle_tpu.kernels import mamba

    cases = {c[0]: c for c in kernels.get("ssm_update").tpu_cases()}
    assert set(cases) == set(UPDATE_BLOCKS)
    _label, fn, arg_specs = cases[label]
    _slots, heads, p, n_state = arg_specs[0][0]
    assert mamba._update_heads(heads, p, n_state) == UPDATE_BLOCKS[label]
    sharding = SingleDeviceSharding(v5e_devices[0])
    args = [jax.ShapeDtypeStruct(shape, np.dtype(dt), sharding=sharding)
            for shape, dt in arg_specs]
    before = kernels.fallback_counter().value
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert kernels.fallback_counter().value == before
    assert text.count('custom_call_target="tpu_custom_call"') == 1


#: the hybrid serving cells' Mamba layers as a launch of the chunk program
#: meets them: (tokens a launch, groups, scan chunk) at 64 heads of 64 over
#: a state of 128, and the loops the compiled mixer keeps (a launch of ONE
#: scan chunk has none: XLA drops a loop of one trip)
SCAN_LAYERS = {
    "granite_4_0_h_micro": (512, 1, 256, 1),
    "nemotron3_nano_30b_a3b": (128, 8, 128, 0),
}


@pytest.mark.parametrize("cell", sorted(SCAN_LAYERS))
def test_ssm_scan_kernel_is_the_body_of_the_mixers_one_loop(cell,
                                                            v5e_devices):
    """``mixer_chunk`` with the ``ssm_scan`` kernel, compiled for the chip
    at each hybrid cell's geometry: no fallback, one custom call, and the
    loop over the scan chunks still there around it (what
    ``ssm_scan_device_share`` matches, ``^%?while``, prices kernel and glue
    alike), with nothing of a chunk's size copied inside it: the body reads
    and writes its chunk of the launch's ``x`` and ``y`` in place."""
    import jax.numpy as jnp

    from paddle_tpu.kernels import mamba

    tokens, groups, chunk, loops = SCAN_LAYERS[cell]
    H, P, N, S = 64, 64, 128, 32
    conv_dim = H * P + 2 * groups * N
    width = 2 * H * P + 2 * groups * N + H
    sharding = SingleDeviceSharding(v5e_devices[0])
    spec = lambda shape, dtype="float32": jax.ShapeDtypeStruct(  # noqa: E731
        shape, np.dtype(dtype), sharding=sharding)
    params = {"conv_w": spec((4, conv_dim)), "conv_b": spec((conv_dim,)),
              "dt_bias": spec((H,)), "a_log": spec((H,)), "d": spec((H,)),
              "norm_w": spec((H * P,))}

    def layer(z, params, conv, ssm, slot, mask):
        return mamba.mixer_chunk(
            z, params, conv, ssm, slot, mask, False, heads=H, head_dim=P,
            groups=groups, n_state=N, chunk=chunk, eps=1e-5,
            out_dtype=jnp.bfloat16, kernel=False)

    before = kernels.fallback_counter().value
    text = jax.jit(layer).lower(
        spec((tokens, width), "bfloat16"), params, spec((S, 3, conv_dim)),
        spec((S, H, P, N)), spec((), "int32"), spec((tokens,), "bool"),
    ).compile().as_text()
    assert kernels.fallback_counter().value == before
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert len(re.findall(r" while\(", text)) == loops
    if loops:
        name = re.search(r" while\(.*body=%?([\w.-]+)", text).group(1)
        body = text.split(f"\n%{name} (", 1)[1].split("\n}", 1)[0]
        assert "custom-call(" in body
        assert f"f32[{H * P},{chunk}]" not in body


@pytest.mark.parametrize("shape,names", [
    ((4,), ("data",)), ((2, 2), ("data", "model")), ((2, 2), ("dcn", "data")),
])
def test_flash_on_a_mesh_runs_per_shard(shape, names, v5e_devices,
                                        monkeypatch):
    """GSPMD cannot partition a Mosaic call ("wrap the call in a
    shard_map" — first seen on the four-chip host in PR 21, where the
    single-device guard above had passed): under a CompiledProgram mesh
    the sdpa lowering must run the compiled kernel per shard, forward and
    backward, with the batch and head splits really applied."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.core.registry import OpRegistry
    from paddle_tpu.kernels import registry
    from paddle_tpu.parallel.env import mesh_context
    from paddle_tpu.utils.hlo import pallas_custom_calls

    monkeypatch.setattr(registry, "_on_tpu", lambda: True)
    mesh = Mesh(np.array(v5e_devices).reshape(shape), names)
    sdpa = OpRegistry.get("scaled_dot_product_attention").lowering()
    B, H, S, D = 16, 12, 128, 64

    def loss(q, k, v, bias):
        out = sdpa({"Q": [q], "K": [k], "V": [v], "Bias": [bias]},
                   {"sm_scale": 0.125})["Out"][0]
        return jnp.sum(out.astype(jnp.float32))

    fed = NamedSharding(mesh, P(names[0]))
    args = [jax.ShapeDtypeStruct((B, H, S, D), jnp.bfloat16, sharding=fed)
            ] * 3 + [jax.ShapeDtypeStruct((B, S), jnp.float32, sharding=fed)]
    with mesh_context(mesh):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
            *args).compile().as_text()
    census = pallas_custom_calls(text)
    assert set(census) == {"flash_attention_fwd", "flash_attention_bwd_dkdv",
                           "flash_attention_bwd_dq"}, census
    # 16 x 12 (batch x heads) rows split four ways, whichever axes do it
    assert census["flash_attention_fwd"]["result"] == "bf16[48,128,64]"


_RNG_SHAPE = re.compile(r"= u32\[([\d,]*)\][^ ]* rng-bit-generator\(")


def _dropout_step_rng_shapes(devices, names, mesh_shape, dims,
                             batch_first=True):
    """The result shapes of every ``rng-bit-generator`` in the optimized
    HLO of mean(dropout(x)) and its gradient, the Program's own ops run by
    the block interpreter as a CompiledProgram step runs them, compiled
    for the described v5e devices under a mesh."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import paddle_tpu as fluid
    from paddle_tpu.core.executor import _interpret_block
    from paddle_tpu.parallel.env import mesh_context

    B, S, H = dims
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[B, S, H], dtype="bfloat16")
        x.stop_gradient = False
        h = x if batch_first else fluid.layers.transpose(x, [1, 0, 2])
        out = fluid.layers.dropout(
            h, 0.1, dropout_implementation="upscale_in_train")
        loss = fluid.layers.mean(fluid.layers.cast(out, "float32"))
        (gx,) = fluid.gradients(loss, x)

    def step(x, key):
        env = _interpret_block(main.global_block(), {"x": x}, key)
        return env[loss.name], env[gx.name]

    mesh = Mesh(np.array(devices).reshape(mesh_shape), names)
    key = jax.random.key(0, impl="rbg")
    args = (jax.ShapeDtypeStruct((B, S, H), jnp.bfloat16,
                                 sharding=NamedSharding(mesh, P(names[0]))),
            jax.ShapeDtypeStruct(key.shape, key.dtype,
                                 sharding=NamedSharding(mesh, P())))
    with mesh_context(mesh):
        text = jax.jit(step).lower(*args).compile().as_text()
    shapes = [tuple(int(d) for d in found.split(","))
              for found in _RNG_SHAPE.findall(text)]
    assert shapes and len(shapes) == text.count(" rng-bit-generator("), (
        "a rng-bit-generator of the compiled dropout step went unread")
    return shapes


@pytest.mark.parametrize("names, mesh_shape, shards", [
    (("data",), (4,), 4), (("dcn", "data"), (2, 2), 4),
    (("data", "model"), (2, 2), 2)])
def test_dropout_on_a_data_mesh_draws_the_shards_bits(
        names, mesh_shape, shards, v5e_devices):
    """GSPMD does not partition ``rng-bit-generator``: left to it, every
    device of a data mesh draws the GLOBAL batch's bits and keeps its
    slice (49 draws of ``u32[1024,128,768]`` a step on each chip of the
    dp4 cell, 32 ms of 269: PERF.md section 6, PR 50). ``ops/common.py keep_mask`` draws
    inside a ``shard_map``, so every generator of the compiled step has the
    PER-SHARD leading dimension. Only the TPU compiler can hold this: the
    CPU backend expands the generator into threefry arithmetic BEFORE it
    partitions, so a virtual CPU mesh shows neither the fault nor the
    cure."""
    shapes = _dropout_step_rng_shapes(v5e_devices, names, mesh_shape,
                                      (64, 128, 256))
    assert set(shapes) == {(64 // shards, 128, 256)}, shapes


def test_dropout_whose_rows_the_mesh_does_not_divide_draws_globally(
        v5e_devices):
    """What the lowering can see is dim 0: a ``[S, B, H]`` operand of 126
    rows under four shards keeps the one draw of the whole array."""
    shapes = _dropout_step_rng_shapes(v5e_devices, ("data",), (4,),
                                      (64, 126, 256), batch_first=False)
    assert set(shapes) == {(126, 64, 256)}, shapes


@pytest.mark.parametrize("kind", ["step", "chunk"])
def test_the_sparse_cells_programs_lower_with_no_fallback(kind, v5e_devices,
                                                          monkeypatch):
    """``keye_vl_2_0_30b_a3b``'s decode step and chunk programs, built at
    the cell's size from a scope of SHAPES (no weights) and lowered for the
    described chip: ``kernel_fallbacks_total`` does not move, and every
    layer's selection is the kernels' (``index_scores`` then
    ``index_select``), a step's attention ``paged_attention`` under the
    selection's bias and a chunk's ``masked_chunk_attn`` under its mask."""
    import importlib

    from benchmark import manifest
    from paddle_tpu.core import lowering
    from paddle_tpu.kernels import registry
    from paddle_tpu import serving

    cell = "keye_vl_2_0_30b_a3b"
    bench = manifest.load_manifest()
    cfg = manifest.load_config(bench, cell)
    builder = importlib.import_module("benchmark.builders." + cfg["builder"])
    keys = manifest.published(cfg, False)
    settings = manifest.sizes(cfg["settings"], False)
    model = manifest.model_sizes(cfg, False)
    m = serving.build_keye_vl_model(
        name=cell, version="1", dtype=settings["dtype"],
        expert_rank=settings["expert_rank"],
        initializer_range=settings["initializer_range"],
        **{k: keys[k] for k in builder._BUILDER_KEYS}, **model)
    sharding = SingleDeviceSharding(v5e_devices[0])
    program, sig, fetch = {
        "step": (m.decode_program, m.decode_feed_sig(), m.counts_fetch),
        "chunk": (m.chunk_program, m.chunk_feed_sig(), m.chunk_logits_fetch),
    }[kind]

    class Shapes:
        """A scope of shapes: the programs' persistables, no values."""

        def __init__(self):
            self.vars = {
                name: jax.ShapeDtypeStruct(tuple(v.shape), np.dtype(v.dtype),
                                           sharding=sharding)
                for prog in (m.startup_program, program)
                for block in prog.blocks
                for name, v in block.vars.items() if v.persistable}

        def has_var(self, name):
            return name in self.vars

        def find_var(self, name):
            return self.vars.get(name)

    scope = Shapes()
    monkeypatch.setattr(registry, "_on_tpu", lambda: True)
    sig = sorted(sig)
    before = kernels.fallback_counter().value
    entry, _src = lowering.lower_step(
        program, scope,
        tuple((n, shape, str(np.dtype(dt))) for n, shape, dt in sig),
        [fetch], donate=True, use_cache=False, persist=False, label="hlo")
    text = entry.fn.lower(
        tuple(jax.ShapeDtypeStruct(shape, np.dtype(dt), sharding=sharding)
              for _n, shape, dt in sig),
        tuple(scope.find_var(n) for n in entry.donated),
        tuple(scope.find_var(n) for n in entry.readonly),
        jax.ShapeDtypeStruct((2,), np.uint32, sharding=sharding),
    ).as_text()
    assert kernels.fallback_counter().value == before
    layers = keys["num_hidden_layers"]
    assert len(m.index_names) == layers == 12

    def calls(name):
        return len(re.findall(r'kernel_name\s*=\s*"%s"' % name, text))

    assert calls("index_scores") == calls("index_select") == layers
    attends = "paged_attention" if kind == "step" else "masked_chunk_attn"
    assert calls(attends) == layers
    assert calls("chunk_attention") == 0
