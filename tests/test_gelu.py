"""The gelu lowering (ops/common.py gelu: the `gelu` op and fc's fused
activation): the exact form goes to XLA as ``erf`` with its derivative
written out, the tanh form is jax's own, untouched. Values and gradients
are held to a float64 reference, the two call sites to one another, the
synthesised grad op to finite differences."""

import numpy as np
import pytest
from scipy import special

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import OpRegistry
from paddle_tpu.ops.common import gelu, gelu_lowering_counts

from op_test import OpTest

SEEDS = (0, 1, 2)


def _points():
    """A dense grid of [-12, 12] and the seeds' normal draws, float64."""
    draws = [np.random.RandomState(s).standard_normal(4096) for s in SEEDS]
    return np.concatenate([np.linspace(-12.0, 12.0, 24001)] + draws)


def _phi(x):
    return np.exp(-0.5 * x * x) / np.sqrt(2.0 * np.pi)


def _reference(x, approximate, order):
    """gelu (order 0) or its derivative (order 1) in float64."""
    if not approximate:
        cdf = 0.5 * special.erfc(-x / np.sqrt(2.0))
        return x * cdf if order == 0 else cdf + x * _phi(x)
    c = np.sqrt(2.0 / np.pi)
    t = np.tanh(c * (x + 0.044715 * x ** 3))
    if order == 0:
        return 0.5 * x * (1.0 + t)
    return 0.5 * (1.0 + t) + 0.5 * x * (1.0 - t * t) * c * (
        1.0 + 3 * 0.044715 * x * x)


def _evaluate(x, approximate, order):
    if order == 0:
        return jax.jit(lambda v: gelu(v, approximate))(x)
    return jax.jit(jax.grad(
        lambda v: jnp.sum(gelu(v, approximate).astype(jnp.float32))))(x)


def _bf16_steps(a, b):
    """How many bfloat16 values lie between a and b, element by element."""
    def ordered(v):
        bits = np.asarray(v, jnp.bfloat16).view(np.uint16).astype(np.int32)
        return np.where(bits & 0x8000, 0x8000 - bits, bits)
    return np.abs(ordered(a) - ordered(b))


@pytest.mark.parametrize("approximate, order, scale", [
    (False, 0, 4e-7), (False, 1, 4e-7), (True, 0, 4e-7),
    # jax's own rule for tanh, untouched: 1 - tanh^2 cancels near 1
    (True, 1, 4e-6),
], ids=["erf-value", "erf-grad", "tanh-value", "tanh-grad"])
def test_float32_against_float64(approximate, order, scale):
    """Absolute error within 4e-7 * max(1, |x|): a few float32 ulps of
    |x| everywhere, the far negative tail included, where ``1 + erf``
    has lost its RELATIVE precision and gelu itself is ~0."""
    x64 = _points().astype(np.float32).astype(np.float64)
    got = np.asarray(_evaluate(jnp.asarray(x64, jnp.float32),
                               approximate, order), np.float64)
    err = np.abs(got - _reference(x64, approximate, order))
    limit = scale * np.maximum(1.0, np.abs(x64))
    worst = int(np.argmax(err / limit))
    assert (err <= limit).all(), (x64[worst], got[worst], err[worst])


@pytest.mark.parametrize("order, floor", [(0, 1e-4), (1, 1e-5)],
                         ids=["value", "grad"])
def test_bfloat16_is_the_rounded_reference(order, floor):
    """A bfloat16 operand is evaluated in float32 and rounded once:
    within one bfloat16 step of the rounded float64 value wherever that
    is not ~0 (in bfloat16 arithmetic ``1 + erf`` cancels to nothing
    from x = -2 on). The value's floor is 1e-4, x > -4.1: below it the
    float32 ``1 + erf`` is off by ~5e-7 absolutely, which at x = -4.5,
    where gelu is -1.3e-5, is four bfloat16 steps of next to nothing."""
    x = jnp.asarray(_points(), jnp.bfloat16)
    got = _evaluate(x, False, order)
    assert got.dtype == jnp.bfloat16
    ref = _reference(np.asarray(x, np.float64), False, order)
    steps = _bf16_steps(got, jnp.asarray(ref, jnp.bfloat16))
    held = np.abs(ref) >= floor
    assert steps[held].max() <= 1, np.asarray(x)[held][np.argmax(steps[held])]
    assert np.abs(np.asarray(got, np.float64) - ref)[~held].max() <= 2e-6


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("order", [0, 1], ids=["value", "grad"])
def test_tanh_form_is_bit_for_bit_jax_nn_gelu(dtype, order):
    x = jnp.asarray(_points(), dtype)
    got = _evaluate(x, True, order)
    if order == 0:
        want = jax.jit(lambda v: jax.nn.gelu(v, approximate=True))(x)
    else:
        want = jax.jit(jax.grad(lambda v: jnp.sum(
            jax.nn.gelu(v, approximate=True).astype(jnp.float32))))(x)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_second_derivative_through_the_written_out_rule():
    """Reverse over reverse differentiates the rule's own backward:
    gelu'' = phi(x) * (2 - x^2)."""
    x64 = np.linspace(-6.0, 6.0, 1201)
    second = jax.jit(jax.vmap(jax.grad(jax.grad(gelu))))(
        jnp.asarray(x64, jnp.float32))
    np.testing.assert_allclose(np.asarray(second, np.float64),
                               _phi(x64) * (2.0 - x64 * x64), atol=2e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gelu_op_and_fc_activation_give_the_same_bits(dtype):
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.standard_normal((16, 24)), dtype)
    w = jnp.asarray(rng.standard_normal((24, 40)) * 0.3, dtype)
    b = jnp.asarray(rng.standard_normal((40,)), dtype)
    fc = OpRegistry.get("fc").lowering()
    act = OpRegistry.get("gelu").lowering()

    def fused(x, w, b):
        return fc({"Input": [x], "W": [w], "Bias": [b]},
                  {"activation_type": "gelu"})["Out"][0]

    def apart(x, w, b):
        pre = fc({"Input": [x], "W": [w], "Bias": [b]}, {})["Out"][0]
        return act({"X": [pre]}, {"approximate": False})["Out"][0]

    np.testing.assert_array_equal(
        np.asarray(jax.jit(fused)(x, w, b), np.float32),
        np.asarray(jax.jit(apart)(x, w, b), np.float32))


class _GeluOp(OpTest):
    op_type = "gelu"

    def __init__(self, approximate):
        x = np.random.RandomState(3).uniform(-3, 3, (4, 6)).astype("float32")
        self.inputs = {"X": x}
        self.outputs = {"Out": None}
        self.attrs = {"approximate": approximate}
        self.checked = ["X"]


class _FcGeluOp(OpTest):
    op_type = "fc"

    def __init__(self):
        rng = np.random.RandomState(5)
        self.inputs = {
            "Input": rng.uniform(-1, 1, (4, 6)).astype("float32"),
            "W": rng.uniform(-1, 1, (6, 5)).astype("float32"),
            "Bias": rng.uniform(-1, 1, (5,)).astype("float32"),
        }
        self.outputs = {"Out": None}
        self.attrs = {"activation_type": "gelu", "in_num_col_dims": 1}
        self.checked = ["Input", "W", "Bias"]


@pytest.mark.parametrize("case", [
    lambda: _GeluOp(False), lambda: _GeluOp(True), _FcGeluOp,
], ids=["gelu_erf", "gelu_tanh", "fc_gelu"])
def test_synthesised_grad_op_matches_finite_differences(case):
    """core/backward.py takes the grad op through ``jax.vjp`` of the
    lowering, so through the written-out rule."""
    op = case()
    op.check_grad(op.checked, "Out", max_relative_error=0.01)


@pytest.mark.parametrize("approximate, form", [(False, "erf"),
                                               (True, "tanh")])
def test_every_lowered_gelu_is_counted_by_form(approximate, form):
    before = gelu_lowering_counts()
    jax.eval_shape(lambda v: gelu(v, approximate),
                   jax.ShapeDtypeStruct((4,), jnp.float32))
    after = gelu_lowering_counts()
    other = "tanh" if form == "erf" else "erf"
    assert after[form] == before[form] + 1
    assert after[other] == before[other]


# ---------------------------------------------------------------------------
# round_dtype: the AMP rewrite's mark on a gelu that only casts read
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("order", [0, 1], ids=["value", "grad"])
def test_round_dtype_rounds_the_value_and_leaves_the_gradient(order):
    x = jnp.asarray(_points(), jnp.float32)
    if order == 0:
        got = jax.jit(lambda v: gelu(v, round_dtype="bfloat16"))(x)
        want = jax.jit(lambda v: gelu(v).astype(jnp.bfloat16))(x)
        assert got.dtype == jnp.float32
        want = want.astype(jnp.float32)
    else:
        got = jax.jit(jax.grad(
            lambda v: jnp.sum(gelu(v, round_dtype="bfloat16"))))(x)
        want = _evaluate(x, False, 1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _amp_ffn(approximate=False, also_normed=False):
    """x -> fc -> gelu -> fc -> loss under bf16 AMP; ``also_normed`` adds a
    float32 reader (a black-list op) of gelu's result."""
    import paddle_tpu as fluid
    from paddle_tpu.core.ir import Program, program_guard
    from paddle_tpu.utils import unique_name

    main, startup = Program(), Program()
    main.random_seed = startup.random_seed = 11
    with unique_name.guard(), program_guard(main, startup):
        x = fluid.data("x", shape=[-1, 16])
        h = fluid.layers.gelu(fluid.layers.fc(x, size=32),
                              approximate=approximate)
        out = fluid.layers.fc(h, size=16)
        if also_normed:
            out = out + fluid.layers.fc(fluid.layers.layer_norm(h), size=16)
        loss = fluid.layers.mean(out * out)
        fluid.amp.decorate(fluid.optimizer.SGD(learning_rate=0.1),
                           dest_dtype="bfloat16").minimize(loss)
    return main, startup, loss


@pytest.mark.parametrize("kwargs, marked", [
    ({}, True), ({"approximate": True}, False), ({"also_normed": True}, False),
], ids=["only_casts_read_it", "tanh_form", "a_float32_reader"])
def test_amp_marks_a_gelu_that_only_casts_read(kwargs, marked):
    main, _startup, _loss = _amp_ffn(**kwargs)
    (op,) = [op for op in main.global_block().ops if op.type == "gelu"]
    assert (op.attrs.get("round_dtype") == "bfloat16") is marked


def test_the_mark_changes_no_number_of_the_program():
    """The casts that read a marked gelu change no bit, so losses and
    updated weights are what the unmarked program gives."""
    import paddle_tpu as fluid

    x = np.random.RandomState(1).standard_normal((8, 16)).astype("float32")

    def train(strip):
        main, startup, loss = _amp_ffn()
        if strip:
            for op in main.global_block().ops:
                op.attrs.pop("round_dtype", None)
        weights = [p.name for p in main.all_parameters()]
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            losses = [exe.run(main, feed={"x": x}, fetch_list=[loss])[0]
                      for _ in range(3)]
            return losses, [np.asarray(scope.find_var(n)) for n in weights]

    (marked, w_marked), (plain, w_plain) = train(False), train(True)
    np.testing.assert_array_equal(np.asarray(marked), np.asarray(plain))
    for a, b in zip(w_marked, w_plain):
        np.testing.assert_array_equal(a, b)
