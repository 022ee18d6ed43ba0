"""Interleaved schedule execution over shard_map + collective_permute.

`parallel/pipeline.py pipeline_apply` is the v=1 (gpipe) runtime: one
chunk per device, activations hop the stage ring once. This module is the
interleaved generalization the 1f1b slot tables (schedule.py) describe:
every device hosts ``interleave`` model chunks (virtual stages, Megatron
style), the stacked layer rows are pre-permuted so device d's shard holds
virtual stages d, d+s, ..., d+(v-1)s, and a microbatch laps the SAME
`lax.ppermute` ring v times — virtual stage k always hands off to device
(k+1) mod s, so the circular schedule needs no extra transfer pattern,
only a per-tick chunk selector. Reverse-mode AD transposes the ring into
the mirrored backward wave (the bwd half of the slot table).

The fill/drain edge of each lap is one CHUNK (1/v of a stage) deep, which
is where the bubble win comes from: 3/11 vs gpipe's 3/7 at s=4, m=4
(tests/test_pipeline_runtime.py::test_predicted_bubble_closed_forms).

The schedule override context here is how a RUN-time choice (
``with_parallel(pipeline_schedule=...)``) reaches the `pipeline_stack`
lowering without editing program attrs — the compiler joins the same
value into the compile-cache fingerprint, so the context and the cache
key can never disagree.
"""

import contextlib
import threading

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.parallel.pipeline import _vary
from paddle_tpu.utils.enforce import EnforceError

__all__ = ["pipeline_apply_interleaved", "interleave_permutation",
           "schedule_override", "current_schedule_override"]


def interleave_permutation(num_layers, num_stages, interleave):
    """Row order putting stacked layer rows into circular (virtual-stage)
    device assignment: under P(stage) sharding of the permuted array,
    device d's shard holds chunk d's rows then chunk (d+s)'s, ... —
    local chunk j == virtual stage j*s + d. Returns a list of original
    row indices; applying it is a gather the vjp scatters back through,
    so stacked parameter gradients land on the unpermuted rows."""
    s, v = int(num_stages), int(interleave)
    k_total = s * v
    if num_layers % k_total:
        raise EnforceError(
            f"1f1b interleave={v} over {s} stages needs num_layers "
            f"divisible by {k_total} (got {num_layers})")
    cs = num_layers // k_total
    perm = []
    for d in range(s):
        for j in range(v):
            k = j * s + d
            perm.extend(range(k * cs, (k + 1) * cs))
    return perm


def pipeline_apply_interleaved(block_fn, stacked_params, x_mb, stage_axis,
                               interleave, collect="broadcast"):
    """Runs INSIDE shard_map; same contract as pipeline_apply, plus
    ``interleave`` = chunks per device (v >= 2). stacked_params leaves
    are this device's [L_local, ...] shard in circular order
    (interleave_permutation applied to the global array beforehand).
    Requires num_microbatches <= num_stages — the contention-free window
    of the circular wave (schedule.compile_schedule enforces the same)."""
    v = int(interleave)
    n_stage = lax.psum(1, stage_axis)
    idx = lax.axis_index(stage_axis)
    tmap = jax.tree_util.tree_map
    n_mb = jax.tree_util.tree_leaves(x_mb)[0].shape[0]
    if n_mb > n_stage:
        raise EnforceError(
            f"1f1b needs num_microbatches <= num_stages "
            f"({n_mb} > {n_stage})")
    l_local = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if l_local % v:
        raise EnforceError(
            f"stage shard of {l_local} layers is not divisible by "
            f"interleave={v}")
    cs = l_local // v
    k_total = n_stage * v
    total = n_mb + k_total - 1
    perm = [(j, (j + 1) % n_stage) for j in range(n_stage)]

    def run_chunk(h, jj):
        chunk = tmap(
            lambda p: lax.dynamic_slice_in_dim(p, jj * cs, cs, axis=0),
            stacked_params,
        )

        def layer(h, p):
            return block_fn(p, h), None

        h, _ = lax.scan(layer, h, chunk)
        return h

    outs0 = tmap(lambda a: _vary(0.0 * a, stage_axis), x_mb)
    cur0 = tmap(lambda a: _vary(0.0 * a[0], stage_axis), x_mb)

    def tick(carry, t):
        cur, outs = carry
        # inject fresh microbatches at virtual stage 0 only (device 0
        # while t < m; afterwards device 0 serves later chunks and must
        # keep the carry arriving off the ring)
        inject = jnp.logical_and(idx == 0, t < n_mb)
        inp = tmap(
            lambda xa, ca: jnp.where(
                inject, xa[jnp.minimum(t, n_mb - 1)], ca),
            x_mb, cur,
        )
        # the chunk this device serves at tick t: the live microbatch
        # wave puts virtual stage k = d + j*s here with j = (t-d)//s
        jj = jnp.clip((t - idx) // n_stage, 0, v - 1)
        y = run_chunk(inp, jj)
        slot = jnp.clip(t - (k_total - 1), 0, n_mb - 1)
        is_out = jnp.logical_and(idx == n_stage - 1, t >= k_total - 1)
        outs = tmap(
            lambda oa, ya: jnp.where(is_out, oa.at[slot].set(ya), oa),
            outs, y,
        )
        cur = tmap(lambda ya: lax.ppermute(ya, stage_axis, perm), y)
        return (cur, outs), None

    (_, outs), _ = lax.scan(tick, (cur0, outs0), jnp.arange(total))
    if collect == "broadcast":
        outs = tmap(
            lambda oa: lax.psum(
                jnp.where(idx == n_stage - 1, oa, 0.0), stage_axis),
            outs,
        )
    return outs


# ---------------------------------------------------------------------------
# run-time schedule selection (CompiledProgram.with_parallel -> op lowering)
# ---------------------------------------------------------------------------

_TLS = threading.local()


@contextlib.contextmanager
def schedule_override(schedule=None, interleave=None):
    """Bind the step's pipeline schedule choice for the ops lowered under
    it. compiler.py wraps lowering+execution in this, the same way
    mesh_context carries the mesh; the identical (schedule, interleave)
    pair is joined into the compile-cache fingerprint."""
    prev = getattr(_TLS, "value", None)
    _TLS.value = (schedule, interleave)
    try:
        yield
    finally:
        _TLS.value = prev


def current_schedule_override():
    """(schedule, interleave) bound by the innermost schedule_override,
    (None, None) outside one."""
    return getattr(_TLS, "value", None) or (None, None)
