"""Pipeline parallelism: microbatched stage execution over a mesh axis.

TPU-native redesign of the reference's pipeline trainer (reference:
python/paddle/fluid/optimizer.py:3414 PipelineOptimizer cuts the program into
sections; paddle/fluid/framework/trainer.h:118 PipelineTrainer runs sections
as host threads passing Scopes through queues). Threads-and-queues cannot
express TPU pipelining — instead the schedule is a single differentiable
`lax.scan`: every device runs the SAME stage body (SPMD) on its shard of the
stacked layer parameters, activations hop to the next stage over ICI via
`lax.ppermute`, and stage 0 injects a fresh microbatch each tick. Reverse-mode
AD transposes the scan+ppermute into the backward pipeline automatically —
the GPipe schedule with no hand-built section workers.
"""

import jax
import jax.numpy as jnp
from jax import lax


def _vary(x, axis):
    """Mark x as varying over `axis` unless it already varies over it."""
    if axis in jax.typeof(x).vma:
        return x
    return lax.pcast(x, axis, to="varying")


def pipeline_apply(block_fn, stacked_params, x_mb, stage_axis,
                   collect="broadcast"):
    """Runs INSIDE shard_map.

    block_fn(layer_params, h) -> h : one layer; applied to the L_local layers
        of this stage's shard (leading dim of every leaf in stacked_params).
    stacked_params : pytree, leaves [L_local, ...] — the stage's layer shard.
    x_mb : pytree of [M, mb, ...] microbatched activations (only stage 0's
        copy is consumed). A pytree carry lets the model thread auxiliary
        state (e.g. the MoE load-balance loss) through the pipeline.
    collect : 'broadcast' psum-broadcasts the final outputs to every stage
        (so the caller can compute the head/loss SPMD with a stage mask);
        'last' leaves outputs valid on the last stage only, zeros elsewhere.

    Returns pytree of [M, mb, ...] outputs of the last stage.
    """
    n_stage = lax.psum(1, stage_axis)
    idx = lax.axis_index(stage_axis)
    tmap = jax.tree_util.tree_map
    n_mb = jax.tree_util.tree_leaves(x_mb)[0].shape[0]
    total = n_mb + n_stage - 1
    perm = [(j, (j + 1) % n_stage) for j in range(n_stage)]

    def run_stage(h):
        def layer(h, p):
            return block_fn(p, h), None

        h, _ = lax.scan(layer, h, stacked_params)
        return h

    # carries become stage-varying after the first ppermute/stage-masked
    # update; give them that type (plus x_mb's own vma) up front so the
    # scan carry type is stable under jax 0.9 vma checking
    outs0 = tmap(lambda a: _vary(0.0 * a, stage_axis), x_mb)
    cur0 = tmap(lambda a: _vary(0.0 * a[0], stage_axis), x_mb)

    def tick(carry, t):
        cur, outs = carry
        inp = tmap(
            lambda xa, ca: jnp.where(idx == 0, xa[jnp.minimum(t, n_mb - 1)], ca),
            x_mb,
            cur,
        )
        y = run_stage(inp)
        slot = jnp.clip(t - (n_stage - 1), 0, n_mb - 1)
        is_out = jnp.logical_and(idx == n_stage - 1, t >= n_stage - 1)
        outs = tmap(
            lambda oa, ya: jnp.where(is_out, oa.at[slot].set(ya), oa), outs, y
        )
        cur = tmap(lambda ya: lax.ppermute(ya, stage_axis, perm), y)
        return (cur, outs), None

    (_, outs), _ = lax.scan(tick, (cur0, outs0), jnp.arange(total))
    if collect == "broadcast":
        outs = tmap(
            lambda oa: lax.psum(jnp.where(idx == n_stage - 1, oa, 0.0), stage_axis),
            outs,
        )
    return outs


def split_microbatches(x, num_microbatches):
    """[B, ...] -> [M, B/M, ...]"""
    b = x.shape[0]
    assert b % num_microbatches == 0, (b, num_microbatches)
    return x.reshape((num_microbatches, b // num_microbatches) + x.shape[1:])
