"""Shared helpers for op lowering rules."""

import jax
import jax.numpy as jnp

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
