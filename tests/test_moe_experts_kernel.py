"""The ``moe_experts`` kernel's grid (``kernels/moe.py``: the decode step's
held experts, a grid row for each expert some token chose and none for any
other): the traced call's expert axis is a dynamic bound at the serving
cells' geometries, and the bound the wrapper hands it is the touched count.
What the rows add up to is the registry's parity case
(``_parity_moe_experts``, tests/test_kernels.py); the chip's compiler is
tests/test_kernels_tpu_aot.py's; ``tools/check_moe_experts.py`` times the
kernel on the chip."""

import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import kernels
from paddle_tpu.kernels import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: tokens, hidden size, expert width, held experts
T, H, F, E = 16, 256, 40, 8


def _operands(touched, matrices, seed=62):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(T, H).astype("float32"))
    weights = [jnp.asarray(0.1 * rng.randn(E, F, H).astype("float32"))
               for _ in range(matrices)]
    c = np.zeros((T, E), "float32")
    for e in touched:
        rows = rng.permutation(T)[:1 + e % 3]
        c[rows, e] = 0.1 + rng.rand(len(rows))
    return x, jnp.asarray(c), weights


def _experts_call(fn, *args):
    """``fn``'s traced program and its one ``pallas_call``."""
    traced = jax.make_jaxpr(fn)(*args)
    (call,) = [e for e in traced.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    return traced, call


@pytest.mark.parametrize("cell", sorted(kernels.MOE_EXPERTS_STEPS))
def test_the_grids_expert_axis_is_a_traced_bound(cell):
    """The traced call's grid: its FIRST extent is a dynamic bound (no
    static ``held`` rows to walk whatever the step touched) and its second
    the static ``2 * tiles`` of one expert; the bound's operand is the first
    the call takes, before the two scalar prefetches."""
    tokens, hidden, ffn, held, matrices = kernels.MOE_EXPERTS_STEPS[cell]
    args = [jax.ShapeDtypeStruct((tokens, hidden), jnp.bfloat16),
            jax.ShapeDtypeStruct((tokens, held), jnp.float32)] + [
        jax.ShapeDtypeStruct((held, ffn, hidden), jnp.bfloat16)] * matrices
    _traced, call = _experts_call(moe.moe_experts, *args)
    mapping = call.params["grid_mapping"]
    tile = moe.hidden_tile(-(-tokens // 16) * 16, hidden, ffn, jnp.bfloat16,
                           matrices)
    rows, steps = mapping.grid
    assert not isinstance(rows, int), f"{cell}: a static expert axis: {rows}"
    assert mapping.num_dynamic_grid_bounds == 1
    assert steps == 2 * (hidden // tile)
    bound, order, count = call.invars[:3]
    assert bound.aval.shape == () and bound.aval.dtype == jnp.int32
    assert order.aval.shape == (held,) and count.aval.shape == (1,)


@pytest.mark.parametrize("touched", [0, 1, 3, E])
def test_the_bound_is_the_touched_count_and_one_for_none(touched):
    """What the wrapper hands the grid as its extent, evaluated: the count
    of touched experts, and ONE row for a step that touched none."""
    x, c, weights = _operands(list(range(E))[::-1][:touched], 3)
    jaxpr, call = _experts_call(
        lambda *a: moe.moe_experts(*a, interpret=True), x, c, *weights)
    upto = jaxpr.jaxpr.eqns.index(call)
    head = jaxpr.jaxpr.replace(eqns=jaxpr.jaxpr.eqns[:upto],
                               outvars=list(call.invars[:3]))
    bound, order, count = jax.core.eval_jaxpr(
        head, jaxpr.consts, x, c, *weights)
    assert int(bound) == max(touched, 1) and int(count[0]) == touched
    assert sorted(np.asarray(order)[:touched]) == sorted(
        range(E))[E - touched:]


def test_the_tool_rehearses_through_the_interpreter(capsys):
    """``tools/check_moe_experts.py --interpret``: the chip tool's code at
    a toy size: every program at the composite's value, a grid row a
    touched expert, no fallback."""
    spec = importlib.util.spec_from_file_location(
        "check_moe_experts",
        os.path.join(REPO, "tools", "check_moe_experts.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    tool.main(["--interpret", "--seed", "6200000977", "--touched", "1,3,all"])
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    held = report["geometry"][3]
    assert report["fallbacks"] == 0 and report["none_touched_is_zeros"]
    assert set(report["errors"]) == {
        f"{form}:{n}" for form in ("held", "touched_only")
        for n in (1, 3, held)}
    assert all(error < 1e-5 for error in report["errors"].values())
    assert report["grid_rows"] == {
        key: int(key.split(":")[1]) for key in report["errors"]}
