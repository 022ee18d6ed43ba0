"""The ``ssm_scan`` kernel (``kernels/mamba.py``: the body of the chunked
scan's loop) through the Pallas interpreter, held to the recurrence itself:
``ssm_scan_sequential`` token by token in float64 on the host (the
reference ``tools/check_ssm_scan.py`` reads on the chip). The registry's
gates (tests/test_kernels.py, tests/test_kernels_tpu_aot.py) hold it to the
composite and to the chip's compiler."""

import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import kernels
from paddle_tpu.kernels import mamba

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location(
        "check_ssm_scan", os.path.join(REPO, "tools", "check_ssm_scan.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _scan(chunk, kernel=True):
    return jax.jit(lambda *a: mamba.ssm_scan_chunked(*a, chunk,
                                                     kernel=kernel))


def _error(got, want):
    return float(np.abs(np.asarray(got, np.float64) - want).max()
                 / np.abs(want).max())


#: (T, real, H, P, N, G, Q): one group and several (a head block of whole
#: groups, of part of one, a group a head), launches that are whole scan
#: chunks and ragged ones, one trip and several
GEOMETRIES = {
    "one_group": (48, 48, 8, 8, 16, 1, 16),
    "whole_groups_a_block": (32, 32, 32, 8, 16, 4, 16),
    "part_of_a_group_a_block": (32, 32, 32, 8, 16, 1, 8),
    "a_group_a_head": (24, 24, 6, 8, 16, 6, 8),
    "ragged_one_group": (40, 29, 8, 8, 16, 1, 16),
    "ragged_groups": (21, 21, 8, 8, 16, 2, 8),
    "lane_wide_heads": (16, 16, 4, 128, 128, 2, 8),
    "heads_of_the_cells": (32, 19, 16, 64, 128, 8, 16),
    "two_lane_tiles_a_chunk": (300, 300, 8, 8, 16, 2, 256),
}


@pytest.mark.parametrize("carried", [False, True], ids=["zero", "carried"])
@pytest.mark.parametrize("name", sorted(GEOMETRIES))
def test_kernel_is_the_recurrence(name, carried, tool):
    """``y`` and the last state within a few float32 roundings of the
    float64 recurrence, and no further from it than 4 x the composite's
    own distance (plus a rounding)."""
    t, real, heads, p, n_state, groups, chunk = GEOMETRIES[name]
    rng = np.random.default_rng(len(name) + 31 * carried)
    case = list(kernels._scan_case(rng, t, heads, p, n_state, groups,
                                   real))
    if not carried:
        case[5] = np.zeros_like(case[5])
    want = tool.sequential_float64(*case)
    before = kernels.fallback_counter().value
    got = _scan(chunk)(*case)
    assert kernels.fallback_counter().value == before
    ref = _scan(chunk, kernel=None)(*case)
    for g, r, w in zip(got, ref, want):
        assert _error(g, w) <= max(4 * _error(r, w), 0) + 2e-6


def test_a_masked_chunk_leaves_the_state_bit_for_bit(tool):
    """Every position masked (``dt = 0``): decay 1, no input; ``y`` is what
    the carried state alone gives."""
    rng = np.random.default_rng(5)
    x, _dt, a, b, c, h0 = kernels._scan_case(rng, 32, 8, 8, 16, 2, 32)
    dt = np.zeros((32, 8), "float32")
    y, h = _scan(16)(x, dt, a, b, c, h0)
    assert np.asarray(h).tobytes() == h0.tobytes()
    want, _h = tool.sequential_float64(x, dt, a, b, c, h0)
    assert _error(y, want) < 2e-6


def test_a_ragged_tail_moves_nothing():
    """The padding behind a ragged launch and the masked positions before
    it: the state after 19 real tokens of 32 is the state after a launch of
    those 19 alone."""
    rng = np.random.default_rng(6)
    case = kernels._scan_case(rng, 32, 8, 8, 16, 1, 19)
    y, h = _scan(16)(*case)
    short = tuple(v[:19] if i in (0, 1, 3, 4) else v
                  for i, v in enumerate(case))
    y19, h19 = _scan(16)(*short)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h19), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(y)[:19], np.asarray(y19),
                               rtol=1e-5, atol=1e-6)


def test_two_launches_over_a_boundary_are_one():
    """A prompt in two launches, the second continuing from the first one's
    state, against the prompt in one: the cut falls inside a scan chunk of
    the whole launch."""
    rng = np.random.default_rng(7)
    x, dt, a, b, c, h0 = kernels._scan_case(rng, 56, 8, 8, 16, 2, 56)
    y, h = _scan(16)(x, dt, a, b, c, h0)
    y1, h1 = _scan(16)(x[:24], dt[:24], a, b[:24], c[:24], h0)
    y2, h2 = _scan(16)(x[24:], dt[24:], a, b[24:], c[24:], h1)
    np.testing.assert_allclose(np.concatenate([y1, y2]), np.asarray(y),
                               rtol=1e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h), rtol=1e-5,
                               atol=2e-6)


def test_every_product_of_the_body_is_float32_at_highest(tool):
    """No bfloat16 operand and no reduced pass count: the configuration
    states a float32 state update."""
    case = kernels._scan_case(np.random.default_rng(8), 32, 8, 8, 16, 1)
    products = tool.kernel_products(
        lambda *a: mamba.ssm_scan_chunked(*a, 16, kernel=True), case)
    # b c^T a group; a head's (dt x) @ w; what the state gives and takes
    assert len(products) == 1 + 8 + 2
    for types, precision in products:
        assert set(types) == {"float32"} and "HIGHEST" in precision
    assert not tool.kernel_products(
        lambda *a: mamba.ssm_scan_chunked(*a, 16), case)


@pytest.mark.parametrize("heads,per_group,q,p,n_state,want", [
    (64, 64, 256, 64, 128, 16),      # granite_4_0_h_micro
    (64, 8, 128, 64, 128, 16),       # nemotron3_nano_30b_a3b: 2 groups
    (64, 1, 256, 64, 128, 16),       # a group a head
    (24, 24, 256, 128, 128, 8),      # wide heads: 10 fit, 8 divides
    (64, 64, 1024, 64, 128, 4),      # long scan chunks: fewer heads fit
    (64, 32, 256, 64, 128, 16),      # half a group
    (6, 2, 8, 8, 16, 6),
])
def test_head_block_follows_the_shapes(heads, per_group, q, p, n_state,
                                       want):
    assert mamba._scan_heads(heads, per_group, q, p, n_state) == want


def test_mixer_chunk_takes_the_kernel_without_a_fallback():
    """``mixer_chunk`` under the interpreter equals the composite and moves
    no fallback; a slot the launch does not name keeps its state."""
    H, P, N, G, T, S = 8, 8, 16, 2, 24, 2
    rng = np.random.RandomState(9)
    conv_dim = H * P + 2 * G * N
    width = 2 * H * P + 2 * G * N + H
    params = {k: jnp.asarray(0.3 * rng.randn(*s).astype("float32"))
              for k, s in (("conv_w", (4, conv_dim)), ("conv_b", (conv_dim,)),
                           ("dt_bias", (H,)), ("a_log", (H,)), ("d", (H,)),
                           ("norm_w", (H * P,)))}
    states = (rng.randn(S, 3, conv_dim).astype("float32"),
              rng.randn(S, H, P, N).astype("float32"))
    how = dict(heads=H, head_dim=P, groups=G, n_state=N, chunk=16, eps=1e-5,
               out_dtype="float32")
    z = rng.randn(T, width).astype("float32")
    mask = np.arange(T) < 21

    def run(kernel):
        return jax.jit(lambda z, m: mamba.mixer_chunk(
            z, params, *states, 1, m, False, kernel=kernel, **how))(z, mask)

    before = kernels.fallback_counter().value
    got = run(True)
    assert kernels.fallback_counter().value == before
    for g, r in zip(got, run(None)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-5,
                                   atol=2e-5)
    np.testing.assert_array_equal(np.asarray(got[2])[0], states[1][0])


def test_the_op_selects_the_kernel_by_mode(monkeypatch):
    """``mamba2_mixer``'s Pallas lowering asks the registry for ``ssm_scan``
    in chunk mode and ``ssm_update`` in step mode; ``off`` (and the CPU's
    ``auto``) run the composite."""
    from paddle_tpu.ops import mamba as op

    seen = []
    monkeypatch.setattr(op, "_mixer",
                        lambda ins, attrs, kernel: seen.append(kernel))
    for mode in ("interpret", "off", "auto"):
        with kernels.scoped_mode(mode):
            op._mixer_pallas({}, {"mode": "chunk"})
            op._mixer_pallas({}, {"mode": "step"})
    assert seen == [True, True, None, None, None, None]
    assert kernels.get("ssm_scan").op_types == ("mamba2_mixer",)
    assert ("ssm_scan", 1) in kernels.registry_fingerprint()


def test_a_geometry_mosaic_cannot_tile_gives_way_counted():
    """Compiled, a scan chunk that is no whole lane tiles runs the
    composite, bit for bit, and counts in ``kernel_fallbacks_total``; the
    interpreter has no such limit (every test above)."""
    case = kernels._scan_case(np.random.default_rng(10), 32, 8, 8, 16, 1)
    counter = kernels.fallback_counter()
    before = counter.value
    got = _scan(16, kernel=False)(*case)
    assert counter.value == before + 1
    for g, r in zip(got, _scan(16, kernel=None)(*case)):
        assert np.asarray(g).tobytes() == np.asarray(r).tobytes()
