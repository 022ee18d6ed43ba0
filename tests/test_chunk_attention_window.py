"""The chunk kernel under a WINDOW (kernels/attention.py ``chunk_attention``
with ``window``; ``chunk_floor`` is the rule's lower edge): interpreted, it
gives what the composite gives under the same mask at every edge, it starts
a query tile at the copy tile that holds its first lower edge, and without a
window it is the kernel it was."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import attention as A

BS, G, PER, D = 8, 2, 2, 16
C = 16
NB = 24                                     # blocks of the arena


def _case(seed, blocks):
    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.normal(size=(NB * BS, G * D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(NB * BS, G * D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(C, G * PER * D)), jnp.float32)
    table = rng.permutation(NB)[:blocks]
    rows = (table[:, None] * BS + np.arange(BS)).reshape(-1)
    return q, k, v, jnp.asarray(rows, jnp.int32)


def _by_hand(q, k, v, rows, start, real, window):
    """Query c at ``start + c`` over keys ``[max(0, at - W + 1), at]``."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    rows = np.asarray(rows)
    out = np.zeros((C, G, PER, D))
    for c in range(real):
        at = start + c
        lo = max(at - window + 1, 0) if window else 0
        for g in range(G):
            ks = k[rows[lo:at + 1], g * D:(g + 1) * D]
            vs = v[rows[lo:at + 1], g * D:(g + 1) * D]
            for h in range(PER):
                qh = q[c, (g * PER + h) * D:(g * PER + h + 1) * D]
                s = ks @ qh / np.sqrt(D)
                p = np.exp(s - s.max())
                out[c, g, h] = (p / p.sum()) @ vs
    return out.reshape(C, -1)


@pytest.mark.parametrize("start,real,window,what", [
    (0, 16, 6, "the prompt's first chunk: the edge comes into it"),
    (0, 5, 6, "real < C, shorter than the window"),
    (32, 16, 48, "window equal to the context the last query sees"),
    (32, 16, 200, "window longer than the context"),
    (32, 16, 6, "window shorter than the chunk"),
    (40, 16, 24, "a chunk that straddles the edge, blocks under it"),
    (56, 9, 24, "real < C behind a long context"),
    (64, 16, 1, "a window of the query alone"),
    (61, 16, 17, "nothing aligned to a block"),
])
def test_the_windowed_kernel_is_the_rule_at_every_edge(start, real, window,
                                                       what):
    blocks = -(-(start + C) // BS) + 1
    q, k, v, rows = _case(start + window, blocks)
    span = jnp.asarray([start, real], jnp.int32)
    got = np.asarray(A.chunk_attention(
        q, k, v, rows, span, BS, 1 / np.sqrt(D), G, interpret=True,
        window=window))
    want = _by_hand(q, k, v, rows, start, real, window)
    np.testing.assert_allclose(got[:real], want[:real], rtol=2e-5, atol=2e-6,
                               err_msg=what)
    assert not got[real:].any()             # past the real ones: zeros
    composite = np.asarray(A.chunk_attention_by_span(
        q, k, v, rows, span, 1 / np.sqrt(D), G, window=window))
    np.testing.assert_allclose(got[:real], composite[:real], rtol=2e-5,
                               atol=2e-6, err_msg=what)


def test_the_floor_is_the_rules_lower_edge():
    span = jnp.asarray([10, 4], jnp.int32)
    assert np.asarray(A.chunk_floor(span, 6, 8)).tolist() == [3, 4, 5, 6, 7, 8]
    assert np.asarray(A.chunk_floor(span, 6, 64)).tolist() == [0] * 6
    bias = np.asarray(A.chunk_mask_bias(span, 6, 16, window=8))[0]
    sees = (bias == 0.0)
    assert [int(r.sum()) for r in sees] == [8, 8, 8, 8, 0, 0]
    assert sees[0].nonzero()[0].tolist() == list(range(3, 11))
    # no window: the mask it was
    assert (np.asarray(A.chunk_mask_bias(span, 6, 16))
            == np.asarray(A.chunk_mask_bias(span, 6, 16, window=0))).all()


def test_without_a_window_the_kernel_is_what_it_was():
    """Bit for bit: a window longer than everything opens the same pairs,
    and the two executables' numbers are one another's; the unwindowed call
    keeps its name."""
    q, k, v, rows = _case(3, 8)
    span = jnp.asarray([40, 16], jnp.int32)

    def call(window):
        return A.chunk_attention(q, k, v, rows, span, BS, 1 / np.sqrt(D), G,
                                 interpret=True, window=window)

    plain = np.asarray(call(0))
    assert plain.tobytes() == np.asarray(call(10 ** 6)).tobytes()
    text = str(jax.make_jaxpr(lambda: call(0))())
    assert "name=chunk_attention" in text and "windowed" not in text
    under = str(jax.make_jaxpr(lambda: call(24))())
    assert A.WINDOWED_CHUNK_KERNEL in under
    # a pattern for the one kernel's events misses the other's
    assert "chunk_attention" not in A.WINDOWED_CHUNK_KERNEL


def test_a_query_tile_starts_at_the_tile_of_its_first_edge(monkeypatch):
    """No copy for a tile wholly under the edge: with two queries a tile
    and one block a copy tile, the copies each query tile starts are the
    blocks from its first query's edge to its last query's position."""
    monkeypatch.setattr(A, "_CHUNK_QUERY_ROWS", 2 * PER)
    monkeypatch.setattr(A, "_CHUNK_TILE_ROWS", BS)
    started = []
    own = A._start_copies

    def spy(bt_ref, run_ref, len_ref, arenas, bufs, sem, slot, unit_no, half,
            **kw):
        jax.debug.callback(lambda t: started.append(int(t)), unit_no)
        return own(bt_ref, run_ref, len_ref, arenas, bufs, sem, slot,
                   unit_no, half, **kw)

    monkeypatch.setattr(A, "_start_copies", spy)
    start, window = 64, 20
    q, k, v, rows = _case(9, 11)
    span = jnp.asarray([start, C], jnp.int32)
    got = jax.block_until_ready(A.chunk_attention(
        q, k, v, rows, span, BS, 1 / np.sqrt(D), G, interpret=True,
        window=window))
    jax.effects_barrier()
    want = _by_hand(q, k, v, rows, start, C, window)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-6)
    tiles = sorted(set(started))
    # the first query's edge is 45 (block 5), the last position 79 (block 9)
    assert tiles == list(range((start - window + 1) // BS,
                               (start + C - 1) // BS + 1))
    per_tile = {}
    for i in range(C // 2):
        lo = (start + 2 * i - window + 1) // BS
        hi = (start + 2 * i + 1) // BS
        per_tile[i] = hi - lo + 1
    assert len(started) == sum(per_tile.values())
