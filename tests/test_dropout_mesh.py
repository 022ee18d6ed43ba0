"""Dropout under ``CompiledProgram.with_parallel``: the mask's bits are drawn
per shard (``ops/common.py keep_mask``).

Four virtual CPU devices, both generators. What a mesh changes is WHICH bits
a device draws, never what a mask is: Bernoulli(1 - p) an element, saved for
the grad, a function of (seed, step, op, shard). Whether the TPU compiler's
``rng-bit-generator`` then has the per-shard shape is held chip-free by
``tests/test_kernels_tpu_aot.py``: the CPU backend expands the generator
before it partitions, so no virtual CPU mesh can show that.
"""

import itertools

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu.ops import common
from paddle_tpu.parallel.env import make_mesh
from paddle_tpu.utils import flags

B, S, H = 16, 6, 64
P_DROP = 0.25
SHARDS = 4


@pytest.fixture(params=["rbg", "threefry"])
def rng_impl(request):
    old, flags.rng_impl = flags.rng_impl, request.param
    yield request.param
    flags.rng_impl = old


def _data_mesh(shape=(SHARDS,), names=("data",)):
    return make_mesh(shape, names,
                     devices=jax.devices()[:int(np.prod(shape))])


def _dropout_program(seed, implementation="upscale_in_train",
                     batch_first=True):
    """mean(dropout(x)) and its gradient. Every test that reads the counter
    builds under a seed of its own: an equal program under an equal mesh is
    served from the process's compile cache and lowers nothing."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[B, S, H])
        x.stop_gradient = False
        h = x if batch_first else fluid.layers.transpose(x, [1, 0, 2])
        out = fluid.layers.dropout(
            h, P_DROP, dropout_implementation=implementation)
        loss = fluid.layers.mean(out)
        (gx,) = fluid.gradients(loss, x)
    (op,) = [o for o in main.global_block().ops if o.type == "dropout"]
    return main, [out.name, op.outputs["Mask"][0], gx.name]


def _x():
    return np.random.RandomState(3).randn(B, S, H).astype("float32") + 3.0


def _counts_moved(fn):
    before = common.rng_draw_counts()
    got = fn()
    after = common.rng_draw_counts()
    return got, {k: after[k] - before[k] for k in after}


def _run(main, fetch, mesh=None, steps=1):
    """`steps` runs of a fresh executor (so the step counter starts anew):
    a list of [out, mask, grad] per step."""
    prog = main if mesh is None else \
        fluid.CompiledProgram(main).with_parallel(mesh=mesh)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        return [[np.asarray(v) for v in
                 exe.run(prog, feed={"x": _x()}, fetch_list=fetch)]
                for _ in range(steps)]


def test_every_shard_keeps_at_the_rate_and_no_two_draw_the_same(rng_impl):
    main, fetch = _dropout_program(seed=11)
    ((_, mask, _),), moved = _counts_moved(
        lambda: _run(main, fetch, _data_mesh()))
    assert moved == {"per_shard": 1, "global": 0}
    assert set(np.unique(mask)) == {0.0, 1.0}
    shards = np.split(mask, SHARDS, axis=0)
    n = shards[0].size
    # five standard deviations of a binomial share
    tolerance = 5 * np.sqrt(P_DROP * (1 - P_DROP) / n)
    for shard in shards:
        assert abs(shard.mean() - (1 - P_DROP)) < tolerance
    for a, b in itertools.combinations(shards, 2):
        # two independent masks agree on p^2 + (1-p)^2 of their elements
        agree = (a == b).mean()
        assert abs(agree - (P_DROP ** 2 + (1 - P_DROP) ** 2)) < 0.05


def test_equal_seed_and_step_give_equal_masks_and_the_next_step_others(
        rng_impl):
    main, fetch = _dropout_program(seed=12)
    mesh = _data_mesh()
    first = _run(main, fetch, mesh, steps=2)
    again = _run(main, fetch, mesh, steps=2)
    for (_, m0, _), (_, m1, _) in zip(first, again):
        np.testing.assert_array_equal(m0, m1)
    assert (first[0][1] != first[1][1]).mean() > 0.2
    other_seed, fetch2 = _dropout_program(seed=13)
    ((_, m2, _),) = _run(other_seed, fetch2, mesh)
    assert (first[0][1] != m2).mean() > 0.2


@pytest.mark.parametrize("implementation",
                         ["upscale_in_train", "downgrade_in_infer"])
def test_out_and_grad_follow_the_forwards_mask(rng_impl, implementation):
    main, fetch = _dropout_program(seed=14, implementation=implementation)
    ((out, mask, grad),) = _run(main, fetch, _data_mesh())
    x = _x()
    scale = 1.0 / (1.0 - P_DROP) if implementation == "upscale_in_train" \
        else 1.0
    kept = mask == 1
    # x / (1 - p) to an ulp (XLA may multiply by the reciprocal), 0 elsewhere
    np.testing.assert_allclose(out[kept], x[kept] * scale, rtol=2e-7)
    assert not out[~kept].any()
    # dropout_grad against the saved Mask: d mean(out) / dx
    np.testing.assert_allclose(grad, mask * scale / x.size, rtol=1e-6)


def test_without_a_mesh_the_mask_is_the_one_jax_draws_from_the_key(rng_impl):
    """The parent's lowering bit for bit: no mesh, one call of
    ``jax.random.bernoulli`` with the op's key."""
    main, fetch = _dropout_program(seed=15)
    ((_, mask, _),), moved = _counts_moved(lambda: _run(main, fetch))
    assert moved == {"per_shard": 0, "global": 1}
    exe = fluid.Executor(fluid.CPUPlace())
    step_key = exe._next_rng_key(main)
    (op,) = [o for o in main.global_block().ops if o.type == "dropout"]
    rng_id = op.attrs.get("__rng_id__",
                          main.global_block().ops.index(op))
    golden = jax.random.bernoulli(
        jax.random.fold_in(step_key, rng_id), 1.0 - P_DROP, (B, S, H))
    np.testing.assert_array_equal(mask, np.asarray(golden, "float32"))
    # and under a mesh the same key draws other bits
    ((_, meshed, _),) = _run(main, fetch, _data_mesh())
    assert (meshed != mask).mean() > 0.2


@pytest.mark.parametrize("mesh_shape, names, batch_first, placement", [
    ((4,), ("data",), True, "per_shard"),
    ((2, 2), ("dcn", "data"), True, "per_shard"),
    ((2, 2), ("data", "model"), True, "per_shard"),
    ((4,), ("data",), False, "global"),     # dim 0 is S = 6: 4 divides it not
    ((4,), ("model",), True, "global"),     # no data axis
    ((1,), ("data",), True, "global"),      # one device
], ids=["dp4", "dcn2xdp2", "dp2xtp2", "ragged", "tp4", "dp1"])
def test_the_branch_follows_what_the_lowering_observes(
        rng_impl, mesh_shape, names, batch_first, placement):
    main, fetch = _dropout_program(seed=16, batch_first=batch_first)
    ((_, mask, _),), moved = _counts_moved(
        lambda: _run(main, fetch, _data_mesh(mesh_shape, names)))
    assert moved == {"per_shard": int(placement == "per_shard"),
                     "global": int(placement == "global")}
    assert abs(mask.mean() - (1 - P_DROP)) < 0.02
    if names == ("data", "model"):
        # the tensor-parallel ranks of a data shard saw ONE draw: the mask
        # is two halves, each a whole [batch / 2] draw
        top, bottom = np.split(mask, 2, axis=0)
        assert (top != bottom).mean() > 0.2


def test_building_a_program_counts_nothing():
    """Shape inference runs the lowering abstractly and lowers no draw."""
    _, moved = _counts_moved(lambda: _dropout_program(seed=17))
    assert moved == {"per_shard": 0, "global": 0}


def test_inside_the_dgc_per_shard_step_the_draw_is_the_shards_own(rng_impl):
    """The DGC step is a manual region already (``check_vma=False``, so no
    value carries its axes: the abstract mesh says it): today's draw, from
    the key the region folded the shard's index into."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [8, 16])
        y = fluid.data("y", [8, 1])
        h = fluid.layers.dropout(x, 0.25,
                                 dropout_implementation="upscale_in_train")
        pred = fluid.layers.fc(h, size=1, act=None)
        loss = fluid.layers.mean(
            fluid.layers.square(fluid.layers.elementwise_sub(pred, y)))
        fluid.optimizer.DGCMomentumOptimizer(
            learning_rate=0.1, momentum=0.9, rampup_begin_step=1,
            rampup_step=1, sparsity=[0.75]).minimize(loss)
    prog = fluid.CompiledProgram(main).with_parallel(
        mesh=_data_mesh(), loss_name=loss.name)
    exe = fluid.Executor(fluid.CPUPlace())
    rs = np.random.RandomState(5)
    feed = {"x": rs.randn(8, 16).astype("float32"),
            "y": rs.randn(8, 1).astype("float32")}

    def run():
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            return float(np.asarray(exe.run(
                prog, feed=feed, fetch_list=[loss])[0]).reshape(-1)[0])

    value, moved = _counts_moved(run)
    assert np.isfinite(value)
    assert moved == {"per_shard": 0, "global": 1}


def test_inside_a_pipeline_stack_body_the_draw_is_todays(rng_impl):
    B2, S2, H2 = 8, 4, 16
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[B2, S2, H2])
        y = fluid.data("y", shape=[B2, S2, H2])
        stack = fluid.layers.PipelinedStack(num_layers=2, num_microbatches=2)
        with stack.layer():
            h = stack.input(x)
            w = stack.layer_param([H2, H2])
            hp = fluid.layers.dropout(
                fluid.layers.matmul(h, w), 0.25,
                dropout_implementation="upscale_in_train")
            stack.output(hp)
        out = stack()
        loss = fluid.layers.mean(
            fluid.layers.square(fluid.layers.elementwise_sub(out, y)))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    mesh = _data_mesh((2, 2), ("data", "stage"))
    prog = fluid.CompiledProgram(main).with_parallel(
        mesh=mesh, loss_name=loss.name,
        param_specs=stack.param_spec_overrides())
    exe = fluid.Executor(fluid.CPUPlace())
    rs = np.random.RandomState(5)
    feed = {"x": rs.randn(B2, S2, H2).astype("float32"),
            "y": rs.randn(B2, S2, H2).astype("float32")}

    def run():
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            return float(np.asarray(exe.run(
                prog, feed=feed, fetch_list=[loss])[0]).reshape(-1)[0])

    value, moved = _counts_moved(run)
    assert np.isfinite(value)
    assert moved["per_shard"] == 0 and moved["global"] >= 1


def test_a_recomputed_segment_draws_the_forwards_mask_again(rng_impl):
    """Under a data mesh the recomputed dropout folds the same shard index
    into the same key: the curve is the plain program's."""
    rs = np.random.RandomState(9)
    x = rs.rand(16, 8).astype("float32")
    y = x.sum(axis=1, keepdims=True).astype("float32")

    def curve(recompute):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 19
        with fluid.program_guard(main, startup):
            h = fluid.data("x", shape=[16, 8])
            target = fluid.data("y", shape=[16, 1])
            checkpoints = []
            for _ in range(3):
                h = fluid.layers.dropout(
                    fluid.layers.fc(h, size=16, act="relu"), 0.3)
                checkpoints.append(h)
            loss = fluid.layers.mean(fluid.layers.square_error_cost(
                fluid.layers.fc(h, size=1), target))
            opt = fluid.optimizer.SGD(learning_rate=0.1)
            if recompute:
                opt = fluid.optimizer.RecomputeOptimizer(opt)
                opt._set_checkpoints(checkpoints)
            opt.minimize(loss)
        prog = fluid.CompiledProgram(main).with_parallel(
            mesh=_data_mesh(), loss_name=loss.name)
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            return [float(np.asarray(exe.run(
                prog, feed={"x": x, "y": y},
                fetch_list=[loss])[0]).reshape(-1)[0]) for _ in range(4)]

    (plain, moved_plain), (again, moved_again) = (
        _counts_moved(lambda: curve(False)), _counts_moved(lambda: curve(True)))
    np.testing.assert_allclose(plain, again, rtol=1e-5, atol=1e-6)
    assert moved_plain == {"per_shard": 3, "global": 0}
    assert moved_again["global"] == 0 and moved_again["per_shard"] >= 3
