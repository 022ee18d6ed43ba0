"""Shared helpers for op lowering rules."""

import contextlib
import functools
import math
import threading

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core.dtypes import to_numpy_dtype


def vma_names(x):
    """Manual-mesh-axis (shard_map 'vma') names of x's abstract value, as
    a frozenset: non-empty exactly when x is traced inside a shard_map
    region."""
    return jax.typeof(x).vma


def first(ins, slot):
    return ins[slot][0]


def maybe(ins, slot, default=None):
    vals = ins.get(slot)
    return vals[0] if vals else default


def np_dtype(attrs, key="dtype", default="float32"):
    return to_numpy_dtype(attrs.get(key, default))


def broadcast_y(x, y, axis):
    """Reference elementwise broadcast semantics: Y aligns into X starting at
    `axis` (reference: paddle/fluid/operators/elementwise/
    elementwise_op_function.h). axis=-1 aligns trailing dims (numpy rule)."""
    if axis is None or axis == -1 or x.ndim == y.ndim:
        return y
    trailing = x.ndim - axis - y.ndim
    if trailing < 0:
        return y
    return y.reshape((1,) * axis + y.shape + (1,) * trailing)


def rng_key(ins):
    key = ins.get("__rng_key__")
    if key is None:
        raise RuntimeError("stateful op executed without an rng key")
    return key[0]


def seeded_rng_key(ins, attrs):
    """Key honoring a fixed per-op `seed` attr while still advancing between
    executor runs (the reference's seeded generator semantics)."""
    import jax
    import jax.numpy as jnp

    seed = attrs.get("seed", 0)
    if not seed:
        return rng_key(ins)
    base = jax.random.PRNGKey(seed)
    injected = ins.get("__rng_key__")
    if injected is None:
        return base
    raw = jnp.asarray(injected[0]).astype(jnp.uint32)
    return jax.random.fold_in(base, raw[0] ^ raw[1])


#: the mesh axes a batch is split over (parallel/spec_layout.py names the
#: tensor-parallel ones)
DATA_AXIS_NAMES = ("dcn", "data")


def mesh_axes_dividing(mesh, names, dim):
    """Those of the axes `names` that `mesh` has with more than one device,
    as long as their product divides `dim`; None when none is left."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    got = [a for a in names if sizes.get(a, 1) > 1]
    while got and dim % math.prod(sizes[a] for a in got):
        got.pop(0)
    return tuple(got) or None


_lowering_for = threading.local()


@contextlib.contextmanager
def shape_inference():
    """Entered by ``layer_helper.infer_op_shapes`` around its abstract
    evaluation: a lowering run for its output shapes puts nothing into a
    step, so ``rng_draws_total`` stands still under it."""
    old = getattr(_lowering_for, "shapes", False)
    _lowering_for.shapes = True
    try:
        yield
    finally:
        _lowering_for.shapes = old


def _rng_draws(placement):
    from paddle_tpu.observability import metrics as obs_metrics

    return obs_metrics.registry().counter(
        "rng_draws_total",
        "dropout masks lowered into a step, by how much of the array a "
        "device draws",
        labels={"placement": placement},
    )


def rng_draw_counts():
    """``rng_draws_total`` by placement, as ``keep_mask`` left it."""
    return {placement: int(_rng_draws(placement).value)
            for placement in ("per_shard", "global")}


def keep_mask(key, keep_prob, shape):
    """A Bernoulli(`keep_prob`) mask of `shape`, each device drawing its own
    rows where the lowering can see that it may. GSPMD does not partition
    ``rng-bit-generator``: under ``with_parallel`` every device of a data
    mesh drew the GLOBAL batch's bits and kept its slice, four times the
    work on four chips (PERF.md section 6, PR 50). When a mesh is current,
    its data axes ('dcn', 'data') have more than one device between them
    and divide dim 0, and the draw is not inside a manual region already
    (the DGC per-shard step, a ``pipeline_stack`` body), the draw runs in
    a ``shard_map`` that only the key enters: each shard folds its linear
    index over the data axes into the key and draws `shape` with dim 0
    divided; every other axis sees the draw replicated, as before. Such
    masks are a function of (key, shard) and so of the data axes' size,
    not the masks one device draws from the key; anywhere else this is
    ``jax.random.bernoulli(key, keep_prob, shape)``.
    ``rng_draws_total{placement=per_shard|global}`` counts each draw
    lowered into a step."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.parallel import env as penv

    mesh = penv.current_mesh()
    axes = None
    if (mesh is not None and shape
            and not jax.sharding.get_abstract_mesh().manual_axes):
        axes = mesh_axes_dividing(mesh, DATA_AXIS_NAMES, shape[0])
    if not getattr(_lowering_for, "shapes", False):
        _rng_draws("per_shard" if axes else "global").inc()
    if not axes:
        return jax.random.bernoulli(key, keep_prob, shape)
    shards = math.prod(mesh.shape[a] for a in axes)
    local_shape = (shape[0] // shards, *shape[1:])

    def local(key):
        key = jax.random.fold_in(key, lax.axis_index(axes))
        return jax.random.bernoulli(key, keep_prob, local_shape)

    return jax.shard_map(
        local, mesh=mesh, in_specs=P(),
        out_specs=P(axes, *[None] * (len(shape) - 1)))(key)


def reduce_axes(attrs, ndim):
    if attrs.get("reduce_all", False):
        return tuple(range(ndim))
    dims = attrs.get("dim", [0])
    if isinstance(dims, int):
        dims = [dims]
    return tuple(d % ndim for d in dims)


def normalize_padding(attrs, spatial_dims, ksize, strides, in_shape):
    """Resolve the reference's padding attrs (explicit list / SAME / VALID)
    into lax-style ((lo, hi), ...) pairs."""
    algo = attrs.get("padding_algorithm", "EXPLICIT")
    pads = attrs.get("paddings", [0] * spatial_dims)
    if algo == "VALID":
        return ((0, 0),) * spatial_dims
    if algo == "SAME":
        out = []
        for i in range(spatial_dims):
            out_size = -(-in_shape[i] // strides[i])
            total = max(0, (out_size - 1) * strides[i] + ksize[i] - in_shape[i])
            out.append((total // 2, total - total // 2))
        return tuple(out)
    if len(pads) == spatial_dims:
        return tuple((p, p) for p in pads)
    return tuple((pads[2 * i], pads[2 * i + 1]) for i in range(spatial_dims))


def astype_like(g, ref):
    return g.astype(ref.dtype) if g.dtype != ref.dtype else g


def flat_float(x):
    return jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)


# ---------------------------------------------------------------------------
# gelu — the `gelu` op and fc's fused activation lower through this one
# ---------------------------------------------------------------------------

_SQRT_HALF = math.sqrt(0.5)
_INV_SQRT_2PI = 1.0 / math.sqrt(2.0 * math.pi)


def _gelu_lowerings(form):
    from paddle_tpu.observability import metrics as obs_metrics

    return obs_metrics.registry().counter(
        "gelu_lowerings_total",
        "gelu ops lowered, by the form handed to XLA",
        labels={"form": form},
    )


def gelu_lowering_counts():
    """``gelu_lowerings_total`` by form, as ``gelu`` left it."""
    return {form: int(_gelu_lowerings(form).value)
            for form in ("erf", "tanh")}


def _normal_cdf(x):
    return 0.5 * (1.0 + lax.erf(x * _SQRT_HALF))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _gelu_erf(x, round_dtype):
    y = x * _normal_cdf(x)
    if round_dtype is not None:
        # the barrier makes the rounded value a buffer of its own: without
        # it the TPU compiler writes gelu's result nowhere and evaluates it
        # again on the way into every product that reads it
        y = lax.optimization_barrier(y.astype(round_dtype)).astype(x.dtype)
    return y


def _gelu_erf_fwd(x, round_dtype):
    return _gelu_erf(x, round_dtype), x


def _gelu_erf_bwd(round_dtype, x, g):
    pdf = jnp.exp(-0.5 * x * x) * _INV_SQRT_2PI
    return (g * (_normal_cdf(x) + x * pdf),)


_gelu_erf.defvjp(_gelu_erf_fwd, _gelu_erf_bwd)


def gelu(x, approximate=False, round_dtype=None):
    """gelu(x) = x * Phi(x). The exact form goes through ``lax.erf``, which
    the TPU compiler keeps as ONE instruction, with the derivative
    ``Phi(x) + x * phi(x)`` written out from the saved x. ``jax.nn.gelu``
    writes it ``0.5 * x * erfc(-x / sqrt 2)``; XLA has no erfc and expands
    it into three polynomial branches, ~65 float32 operations an element
    forward and ~72 backward, which rode as the epilogue of BERT's FFN
    products and held the MXU back (PERF.md section 6, PR 49). ``1 + erf`` loses
    RELATIVE precision where gelu itself vanishes (x < -4); the absolute
    error stays a few float32 ulps of |x|. Operands narrower than float32
    are evaluated in float32 and rounded once: in bfloat16 ``1 + erf``
    would cancel to nothing from x = -2 on. The rule is reverse-mode only
    (core/backward.py takes every gradient through ``jax.vjp``).

    ``round_dtype`` (the exact form's alone; the AMP rewrite sets it where
    every reader of the result casts it to that dtype anyway,
    amp/decorator.py) rounds the result to it, still in the operand's
    dtype, and keeps the rounded value a buffer of its own.
    ``gelu_lowerings_total{form=}`` counts each lowered call."""
    _gelu_lowerings("tanh" if approximate else "erf").inc()
    if approximate:
        return jax.nn.gelu(x, approximate=True)
    if jnp.finfo(x.dtype).bits < 32:
        return _gelu_erf(x.astype(jnp.float32), None).astype(x.dtype)
    return _gelu_erf(x, round_dtype)
