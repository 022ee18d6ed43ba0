"""On-device embedding admission: the PR-8 leftover, closed.

``embedding/store.py`` admits cache misses by mutating the device hot
slab. The original path round-tripped the ENTIRE ``[capacity, dim]`` slab
through host numpy per missing batch (``np.array(slab); slab[slots] =
rows; scope.set(...)``) — a capacity-sized device->host->device copy to
move a handful of rows. This module replaces it with device-side
gather/scatter:

* ``read_rows(slab, slots)``  — gather ONLY the eviction victims' rows
  for write-back (a ``[n_evicted, dim]`` transfer, not capacity-sized);
* ``admit_rows(slab, slots, rows)`` — scatter the pulled miss rows into
  their slots, DONATED (the slab updates in place on device; the scope
  keeps the result as a device array between steps).

Admission counts are padded to power-of-2 buckets (the dedup-gather
discipline, embedding/gather.py) with ``slot == capacity`` as the "write
nowhere" encoding — the paged-arena drop convention — so the jitted
update retraces O(log capacity) times, not per batch shape. Both jits go
through the ``core/lowering.py`` ``jit_compile`` chokepoint (compile
counts stay observable) and are cached here under a lockdep-named lock.

The scatter is ``slab.at[slots].set(rows, mode="drop")`` under a donating
jit — XLA updates the slab in place. Rows move byte-for-byte — admission is
bit-identical across capacities and ep counts (tools/bench_embedding.py
--smoke asserts it end to end).
"""

import numpy as np

import jax.numpy as jnp

from paddle_tpu.observability import lockdep

__all__ = ["read_rows", "admit_rows", "admit_bucket", "pad_slots",
           "admission_roundtrip_counter"]

_jit_cache = {}   # (kind, capacity, dim, bucket, dtype) -> fn
_jit_lock = lockdep.named_lock("kernels.cache")


def admission_roundtrip_counter():
    """Host capacity-slab round-trips (the legacy admission path);
    tests/test_kernels.py asserts this stays ZERO under device
    admission."""
    from paddle_tpu.observability import metrics as obs_metrics

    return obs_metrics.registry().counter(
        "embedding_host_slab_roundtrips_total",
        "miss admissions that copied the full [capacity, dim] slab "
        "through host numpy (legacy path; 0 under device admission)",
    )


def admit_bucket(n):
    """Power-of-2 admission bucket (>= 1) bounding jit retraces."""
    b = 1
    while b < n:
        b <<= 1
    return b


def pad_slots(slots, rows, capacity, dim, dtype):
    """Pad (slots, rows) to the bucket size; padded entries write
    NOWHERE (slot == capacity, dropped by every backend)."""
    n = len(slots)
    b = admit_bucket(max(n, 1))
    s = np.full((b,), capacity, dtype=np.int32)
    s[:n] = np.asarray(slots, dtype=np.int32)
    r = np.zeros((b, dim), dtype=dtype)
    if n:
        r[:n] = np.asarray(rows, dtype=dtype)
    return s, r


def _scatter_composite(slab, slots, rows):
    # mode="drop": the padded slot == capacity rows are skipped, the
    # exact analog of ops/tensor.py scatter's paged-decode encoding
    return slab.at[slots].set(rows, mode="drop")


def _get_jit(kind, capacity, dim, bucket, dtype):
    key = (kind, capacity, dim, bucket, str(dtype))
    with _jit_lock:
        fn = _jit_cache.get(key)
        if fn is not None:
            return fn
    from paddle_tpu.core.lowering import jit_compile

    if kind == "gather":
        fn = jit_compile(lambda slab, slots: jnp.take(slab, slots, axis=0))
    else:
        fn = jit_compile(_scatter_composite, donate_argnums=(0,))
    with _jit_lock:
        return _jit_cache.setdefault(key, fn)


def read_rows(slab, slots):
    """Gather ``slab[slots]`` on device; returns a host array (the
    write-back payload). Only the victims' rows cross the wire."""
    n = len(slots)
    b = admit_bucket(max(n, 1))
    # pad with slot 0 (sliced off below) so the gather shape is bucketed
    s = np.zeros((b,), dtype=np.int32)
    s[:n] = np.asarray(slots, dtype=np.int32)
    fn = _get_jit("gather", slab.shape[0], slab.shape[1], b, slab.dtype)
    return np.asarray(fn(jnp.asarray(slab), jnp.asarray(s)))[:n]


def admit_rows(slab, slots, rows):
    """Scatter the admitted rows into the slab ON DEVICE (donated).
    Returns the updated device slab."""
    slab = jnp.asarray(slab)   # device-commit so donation is real
    s, r = pad_slots(slots, rows, slab.shape[0], slab.shape[1], slab.dtype)
    fn = _get_jit("admit", slab.shape[0], slab.shape[1], len(s), slab.dtype)
    return fn(slab, jnp.asarray(s), jnp.asarray(r))
