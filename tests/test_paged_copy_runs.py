"""A run of neighbouring blocks is ONE copy (ISSUE 64): the paged kernels
read an aligned group of ``_RUN_BLOCKS`` block-table entries in one
descriptor an arena where the table names them side by side in the arena,
ascending, and all of them are live, and block by block as before anywhere
else. Every kernel that copies through ``_start_copies`` is held, in
interpret mode, against its composite over tables that make the flags say
yes, no, and both inside one copy unit; and the descriptors a call issues
are counted against the rule.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import attention as A
from paddle_tpu.kernels import sparse as S

BS, L, NB = 8, 320, 96          # 40 blocks a slot, copy units of 16 blocks
PER_SLOT = L // BS
G, PER, D = 2, 2, 16            # K/V heads, query heads to each, head width
H = G * D
SLOTS, C = 3, 8
LENGTHS = (300, 0, 163)         # 38 and 21 live blocks; a slot without any
TABLES = ("ascending", "shuffled", "descending", "hole", "crossing",
          "partly_live", "small_arena")
KERNELS = ("paged_plain", "paged_grouped", "paged_latent", "chunk",
           "chunk_windowed", "index_scores", "masked_chunk")


@pytest.fixture(autouse=True)
def _units_of_sixteen_blocks(monkeypatch):
    """Two whole groups a copy unit and three units a slot at this toy
    geometry, for every kernel."""
    monkeypatch.setattr(A, "_UNIT_BYTES", 1)
    monkeypatch.setattr(A, "_LATENT_TILE_ROWS", 128)
    monkeypatch.setattr(A, "_CHUNK_TILE_ROWS", 128)
    monkeypatch.setattr(S, "_SCORE_TILE_ROWS", 128)
    monkeypatch.setattr(S, "_MASKED_TILE_ROWS", 128)
    assert A._RUN_BLOCKS == 8


def _table(kind, rng, slots):
    """``[slots, PER_SLOT]`` block ids and the arena's block count."""
    first = 3 + 41 * np.arange(slots)[:, None]
    up = first + np.arange(PER_SLOT)[None, :]
    if kind in ("ascending", "partly_live"):
        return up, NB + 41 * slots
    if kind == "descending":
        return up[:, ::-1].copy(), NB + 41 * slots
    if kind == "hole":
        # one entry inside the second group of each slot is elsewhere
        table = up.copy()
        table[:, 11] = 1
        return table, NB + 41 * slots
    if kind == "crossing":
        # runs that start in the middle of a group and cross the edge of a
        # copy unit: only the groups wholly inside one are flagged
        table = np.stack([rng.permutation(NB)[:PER_SLOT]
                          for _ in range(slots)]) + 200
        table[:, 5:29] = first + np.arange(24)[None, :]
        return table, NB + 200
    if kind == "small_arena":
        return rng.integers(0, 7, (slots, PER_SLOT)), 7
    table = np.stack([rng.permutation(NB)[:PER_SLOT] for _ in range(slots)])
    return table, NB


def _rows(table):
    return jnp.asarray((table[:, :, None] * BS
                        + np.arange(BS)).reshape(-1), jnp.int32)


def _arena(rng, blocks, width):
    return jnp.asarray(rng.standard_normal((blocks * BS, width)), jnp.float32)


def _close(got, want, what):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.isfinite(got).all(), what
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5, err_msg=what)


def _step_case(kind, rng, lengths):
    table, blocks = _table(kind, rng, SLOTS)
    lens = np.asarray(lengths)
    if kind == "partly_live":
        lens = np.where(lens > 0, lens - 37, 0)
    at = np.arange(L)[None, :]
    keep = (at < lens[:, None]) & (rng.random((SLOTS, L)) < 0.7)
    keep[np.arange(SLOTS), np.maximum(lens - 1, 0)] = lens > 0
    bias = jnp.asarray(np.where(keep, 0.0, -1e9)[:, None], jnp.float32)
    return table, blocks, lens, bias


def _run_case(kernel, kind, seed=0):
    """``(got, want)`` of ``kernel`` over a table of ``kind``, and the
    live blocks of each slot it had to copy."""
    rng = np.random.default_rng(seed)
    scale = 1 / np.sqrt(D)
    if kernel.startswith("paged") or kernel == "index_scores":
        table, blocks, lens, bias = _step_case(kind, rng, LENGTHS)
        rows = _rows(table)
        live = lens > 0
        if kernel == "index_scores":
            arena = _arena(rng, blocks, D)
            q = jnp.asarray(rng.standard_normal((SLOTS, 4 * D)), jnp.float32)
            w = jnp.asarray(rng.standard_normal((SLOTS, 4)), jnp.float32)
            got = S.index_scores(q, w, arena, rows, SLOTS, BS,
                                 jnp.asarray(lens, jnp.int32),
                                 interpret=True)[:, :L]
            want = S.index_scores_composite(q, w, arena, rows, SLOTS)
            seen = np.arange(L)[None, :] < lens[:, None]
            return (np.where(seen, got, 0.0), np.where(seen, want, 0.0),
                    table, lens)
        if kernel == "paged_latent":
            k, v, heads, vw = _arena(rng, blocks, H), None, 0, D
            q = jnp.asarray(rng.standard_normal((SLOTS, 4 * H)), jnp.float32)
        else:
            k, v = _arena(rng, blocks, H), _arena(rng, blocks, H)
            heads, vw = (G, 0) if kernel == "paged_grouped" else (0, 0)
            q = jnp.asarray(rng.standard_normal(
                (SLOTS, PER * H if heads else H)), jnp.float32)
        got = A.paged_attention(q, k, v, rows, bias, SLOTS, L, BS, scale,
                                interpret=True, kv_heads=heads, v_width=vw)
        want = A.paged_attention_composite(q, k, v, rows, bias, SLOTS, L,
                                           scale, kv_heads=heads, v_width=vw)
        return (np.where(live[:, None], got, 0.0),
                np.where(live[:, None], want, 0.0), table, lens)
    # a prompt chunk's C queries behind ``start`` rows of ONE sequence
    table, blocks = _table(kind, rng, 1)
    start = 300 - C if kind != "partly_live" else 263 - C
    rows = _rows(table)
    k, v = _arena(rng, blocks, H), _arena(rng, blocks, H)
    q = jnp.asarray(rng.standard_normal((C, PER * H)), jnp.float32)
    span = jnp.asarray([start, C], jnp.int32)
    lens = np.asarray([start + C])
    if kernel == "masked_chunk":
        mask = np.arange(L)[None, :] <= start + np.arange(C)[:, None]
        mask &= rng.random((C, L)) < 0.6
        mask[np.arange(C), start + np.arange(C)] = True
        mask = jnp.asarray(mask, jnp.int8)
        got = S.masked_chunk_attention(q, k, v, rows, span, mask, BS, scale,
                                       G, interpret=True)
        want = S.masked_chunk_composite(q, k, v, rows, mask, scale, G)
        return got, want, table, lens
    window = 150 if kernel == "chunk_windowed" else 0
    got = A.chunk_attention(q, k, v, rows, span, BS, scale, G,
                            interpret=True, window=window)
    want = A.chunk_attention_by_span(q, k, v, rows, span, scale, G,
                                     window=window)
    return got, want, table, lens


@pytest.mark.parametrize("kind", TABLES)
@pytest.mark.parametrize("kernel", KERNELS)
def test_a_kernel_over_any_table_is_its_composite(kernel, kind):
    got, want, _table_, _lens = _run_case(kernel, kind)
    _close(got, want, f"{kernel} over a {kind} table")


def _descriptors(table, lens, unit, run):
    """The rule: of a slot's live blocks, each aligned, wholly live,
    ascending group of ``run`` is one descriptor, every other block one."""
    runs = singles = 0
    for row, n in zip(table, lens):
        live = -(-int(n) // BS)
        whole = live // run if run and unit % run == 0 else 0
        groups = row[:whole * run].reshape(whole, max(run, 1))
        flagged = int(np.all(np.diff(groups, axis=1) == 1, axis=1).sum())
        runs += flagged
        singles += live - flagged * run
    return runs, singles


@pytest.mark.parametrize("kind", TABLES)
@pytest.mark.parametrize("kernel", ["paged_grouped", "paged_latent",
                                    "index_scores", "masked_chunk"])
def test_the_descriptors_a_call_issues_follow_the_rule(kernel, kind,
                                                       monkeypatch):
    """Every copy the kernel starts, by the rows it moves: ``_RUN_BLOCKS``
    blocks where the flags and the lengths allow it, one block anywhere
    else, each arena alike; over an arena of fewer than ``_RUN_BLOCKS``
    blocks no run is even traced."""
    started, mask_tiles = [], []
    make = A.pltpu.make_async_copy

    class Spy:
        def __init__(self, src, dst, sem):
            self.copy, self.rows = make(src, dst, sem), src.shape[0]
            # (the masked kernel's mask tile rides beside K and V)
            self.mask = src.shape[1] == 128

        def start(self):
            jax.debug.callback(
                lambda: (mask_tiles if self.mask else started).append(
                    self.rows))
            self.copy.start()

        def wait(self):
            self.copy.wait()

    monkeypatch.setattr(A.pltpu, "make_async_copy", Spy)
    got, want, table, lens = _run_case(kernel, kind, seed=3)
    jax.block_until_ready(got)
    jax.effects_barrier()
    _close(got, want, f"{kernel} over a {kind} table")
    arenas = 1 if kernel in ("paged_latent", "index_scores") else 2
    assert mask_tiles == [C] * (
        -(-int(lens[0]) // 128) if kernel == "masked_chunk" else 0)
    run = 0 if kind == "small_arena" else A._RUN_BLOCKS
    runs, singles = _descriptors(table, lens, 16, run)
    assert sorted(started) == sorted(
        [BS] * (singles * arenas) + [run * BS] * (runs * arenas))
    if kind == "ascending":
        assert runs == sum(-(-int(n) // BS) // 8 for n in lens)
    if kind in ("shuffled", "descending", "small_arena"):
        assert runs == 0
    if kind == "crossing":
        # entries 5..28 ascend: the groups at 8 and 16 are runs, the ones
        # the run only enters or leaves are not
        assert runs == sum(min(2, max(-(-int(n) // BS) // 8 - 1, 0))
                           for n in lens)


def test_the_flags_are_exact_for_any_table():
    """``_copy_runs`` flags a group iff all its differences are 1: never a
    first-and-last test (a group whose ends are 7 apart with a swap inside
    is no run), the padded last group of a table that is not whole groups
    never flagged beyond what its entries say."""
    table = np.arange(100, 124)[None, :].repeat(4, axis=0)
    table[1, [9, 10]] = table[1, [10, 9]]        # a swap inside a group
    table[2, 16:] = table[2, 16:][::-1]          # the last group descends
    table[3] = 0                                 # all the same block
    arena = jnp.zeros((200 * BS, 8))
    run, flags = A._copy_runs(jnp.asarray(table, jnp.int32), 16, arena, BS)
    assert run == 8
    assert np.asarray(flags).reshape(4, 3).tolist() == [
        [1, 1, 1], [1, 0, 1], [1, 1, 0], [0, 0, 0]]
    # 20 entries a slot: the third group is padded and never a run
    run, flags = A._copy_runs(jnp.asarray(table[:, :20], jnp.int32), 8,
                              arena, BS)
    assert np.asarray(flags).reshape(4, 3)[:, :2].tolist() == [
        [1, 1], [1, 0], [1, 1], [0, 0]]
    # a unit that is not whole groups, an arena with fewer blocks: no runs
    assert A._copy_runs(jnp.asarray(table, jnp.int32), 12, arena, BS)[0] == 0
    assert A._copy_runs(jnp.asarray(table, jnp.int32), 16,
                        jnp.zeros((7 * BS, 8)), BS)[0] == 0
    assert A.paged_run_blocks(16, 8) == 8 and A.paged_run_blocks(16, 7) == 0
