"""The ``ssm_update`` kernel (``kernels/mamba.py``: the decode step's
one-token state update, a grid step as many heads of ONE stepping slot as
``_update_heads`` gives) through the Pallas interpreter, held to
``ssm_update_composite`` at every block the chooser gives or could give.
The registry's gates (tests/test_kernels.py, tests/test_kernels_tpu_aot.py)
hold it to the chip's compiler; ``tools/check_ssm_update.py`` times it on
the chip."""

import functools
import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import kernels
from paddle_tpu.kernels import mamba

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: (S, H, P, N) and the head blocks a grid step may carry there: None is
#: what the chooser gives, the others what it would give under a smaller
#: budget (divisors of the heads)
GEOMETRIES = {
    "s6_h32_p8_n128": ((6, 32, 8, 128), (None, 8, 16, 32)),
    "s4_h64_p64_n128": ((4, 64, 64, 128), (None, 16, 32, 64)),
}
CASES = [(name, block) for name, (_shape, blocks) in GEOMETRIES.items()
         for block in blocks]

#: which slots step, by the number of slots
MASKS = {
    "none": lambda s: [0] * s,
    "all": lambda s: [1] * s,
    "first_alone": lambda s: [1] + [0] * (s - 1),
    "last_alone": lambda s: [0] * (s - 1) + [1],
    "mixed": lambda s: [1, 0, 1, 1, 0, 0][:s],
}


def _operands(shape, seed):
    s, h, p, n = shape
    rng = np.random.RandomState(seed)
    draw = lambda *dims: jnp.asarray(rng.randn(*dims).astype("float32"))  # noqa: E731
    return (draw(s, h, p, n), draw(s, h, p),
            jnp.asarray(rng.rand(s, h).astype("float32")), draw(s, h, n),
            draw(s, h, n))


@functools.lru_cache(maxsize=None)
def _update(block):
    """One jitted kernel a block: the masks of a geometry share a trace."""
    return jax.jit(lambda *a: mamba.ssm_update(*a, interpret=True,
                                               block=block))


@pytest.mark.parametrize("steps", sorted(MASKS))
@pytest.mark.parametrize(
    "name,block", CASES,
    ids=[f"{name}-{'chosen' if block is None else block}"
         for name, block in CASES])
def test_kernel_is_the_composite(name, block, steps):
    """State and ``y`` at the parity gate's tolerances, an idle slot's
    state bit for bit and its ``y`` 0, no fallback."""
    shape = GEOMETRIES[name][0]
    case = _operands(shape, len(name) + len(steps))
    mask = jnp.asarray(MASKS[steps](shape[0]), bool)
    before = kernels.fallback_counter().value
    new, y = _update(block)(*case, mask)
    assert kernels.fallback_counter().value == before
    ref_new, ref_y = mamba.ssm_update_composite(*case, mask)
    np.testing.assert_allclose(new, ref_new, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y, ref_y, rtol=1e-5, atol=1e-4)
    idle = ~np.asarray(mask)
    assert (np.asarray(new)[idle].tobytes()
            == np.asarray(case[0])[idle].tobytes())
    assert not np.asarray(y)[idle].any()


def test_heads_the_block_does_not_divide_fall_back():
    """A block that is no divisor of the heads: the composite serves the
    call and the fallback counter moves by one."""
    case = _operands((3, 12, 8, 128), 3)
    mask = jnp.asarray([1, 0, 1], bool)
    before = kernels.fallback_counter().value
    new, y = mamba.ssm_update(*case, mask, interpret=True, block=8)
    assert kernels.fallback_counter().value == before + 1
    ref_new, ref_y = mamba.ssm_update_composite(*case, mask)
    assert np.asarray(new).tobytes() == np.asarray(ref_new).tobytes()
    assert np.asarray(y).tobytes() == np.asarray(ref_y).tobytes()


@pytest.mark.parametrize("heads,p,n_state,budget,block", [
    (64, 64, 128, None, 64),           # the hybrid cells': a slot a grid step
    (64, 64, 128, 4 * 2 ** 20, 32),    # half the budget: half a slot
    (64, 64, 128, 3 * 2 ** 20, 16),    # 24 heads would fit: a divisor does
    (64, 128, 256, None, 16),          # a state four times as wide
    (48, 64, 128, 4 * 2 ** 20, 24),
    (67, 64, 128, None, 1),            # a prime just over what fits
    (8, 64, 128, None, 8),             # never more than the heads
    (4, 1024, 1024, None, 1),          # at least one
], ids=lambda v: str(v))
def test_the_block_follows_the_shapes(heads, p, n_state, budget, block,
                                      monkeypatch):
    """``_update_heads`` is a pure function of the operands' shapes and the
    budget: the largest divisor of the heads whose four float32 state
    blocks fit."""
    if budget is not None:
        monkeypatch.setattr(mamba, "_UPDATE_BLOCK_BYTES", budget)
    got = mamba._update_heads(heads, p, n_state)
    assert got == block and heads % got == 0
    assert got == 1 or 16 * got * p * n_state <= mamba._UPDATE_BLOCK_BYTES


def test_the_tool_rehearses_through_the_interpreter(capsys):
    """``tools/check_ssm_update.py --interpret``: the chip tool's code at a
    toy size, every block at the composite's value, no fallback."""
    spec = importlib.util.spec_from_file_location(
        "check_ssm_update",
        os.path.join(REPO, "tools", "check_ssm_update.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    tool.main(["--interpret", "--seed", "5500000977", "--blocks", "8,32"])
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert report["fallbacks"] == 0
    assert report["chosen_block"] == 32
    assert set(report["errors"]) == {"8", "32"}
    for reading in report["errors"].values():
        assert reading["idle_slots_bit_for_bit"]
        assert reading["state_error"] < 1e-6 and reading["y_error"] < 1e-5
