"""LocalSGD: per-replica local steps with periodic parameter averaging.

reference: python/paddle/fluid/transpiler/collective.py:270 (LocalSGD
transpiler — it rewrites the program so each trainer applies its optimizer
locally and every k steps block-averages parameters over NCCL).

TPU-native redesign: under single-program GSPMD data parallelism the
compiler MUST insert a per-step gradient all-reduce (replicated params +
sharded batch leave it no choice), so LocalSGD cannot be expressed there.
The honest form gives each mesh slot its own parameter copy — params carry
a leading `dp` axis sharded over the data axis inside `shard_map` — steps
run with zero cross-device traffic, and every `sync_steps` steps one
`lax.pmean` averages the copies (1/k of the per-step allreduce bandwidth,
the point of the algorithm). This is the DCN-friendly schedule for
multi-slice / multi-host data parallelism (SURVEY §5.8: hierarchical
allreduce maps to the DCN axis).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map as _shard_map
from jax.sharding import PartitionSpec as P


def replicate_for_localsgd(params, n_replicas):
    """Stack per-replica parameter copies along a new leading axis (to be
    sharded over the data axis)."""
    return jax.tree.map(
        lambda p: jnp.broadcast_to(p[None], (n_replicas,) + p.shape), params
    )


def localsgd_step_fn(grad_fn, optimizer_update, axis_name="data",
                     sync_steps=4):
    """Build the per-shard LocalSGD step (runs INSIDE shard_map; params and
    opt state carry a leading replica axis of size 1 per shard).

    grad_fn(params, batch) -> (loss, grads); optimizer_update(params, grads,
    opt_state) -> (params, opt_state). Returns step(carry, batch) with
    carry = (params, opt_state, step_idx).
    """

    def step(carry, batch):
        params, opt_state, idx = carry
        squeezed = jax.tree.map(lambda p: p[0], params)
        loss, grads = grad_fn(squeezed, batch)
        new_p, new_s = optimizer_update(squeezed, grads, opt_state)
        idx = idx + 1

        do_sync = (idx % sync_steps) == 0
        # lax.cond, NOT jnp.where: where would run (and discard) the pmean
        # collective every step, erasing the 1/k bandwidth saving that is
        # the whole point; the predicate is replicated (derived from the
        # shared step counter) so all shards take the same branch
        # pcast re-marks the (replicated) mean as axis-varying so both
        # branches carry the same device-variance type under shard_map
        synced = lax.cond(
            do_sync,
            lambda ps: jax.tree.map(
                lambda p: lax.pcast(
                    lax.pmean(p, axis_name), axis_name, to="varying"),
                ps,
            ),
            lambda ps: ps,
            new_p,
        )
        return (
            jax.tree.map(lambda p: p[None], synced),
            new_s,
            idx,
        ), loss

    return step


def localsgd_train(mesh, params, opt_state, grad_fn, optimizer_update,
                   batches, axis_name="data", sync_steps=4):
    """Run len(batches) LocalSGD steps over `mesh`'s `axis_name`.

    params: pytree of replicated arrays (will be given per-replica copies).
    batches: pytree of arrays with leading [n_replicas, steps, ...] layout.
    Returns (averaged_params, per-step losses [steps, n_replicas]).
    """
    n = mesh.shape[axis_name]
    stacked = replicate_for_localsgd(params, n)
    step = localsgd_step_fn(grad_fn, optimizer_update, axis_name, sync_steps)

    def run(stacked_params, opt_state, batches):
        local_batches = jax.tree.map(lambda b: b[0], batches)  # [steps, ...]

        (p, _, _), losses = lax.scan(
            step, (stacked_params, opt_state, jnp.zeros((), jnp.int32)),
            local_batches,
        )
        # final average so the caller gets ONE parameter set
        p = jax.tree.map(lambda x: lax.pmean(x[0], axis_name)[None], p)
        return p, losses[:, None]

    spec_p = jax.tree.map(lambda _: P(axis_name), stacked)
    spec_b = jax.tree.map(lambda _: P(axis_name), batches)
    run_sharded = _shard_map(
        run,
        mesh=mesh,
        in_specs=(spec_p, P(), spec_b),
        out_specs=(spec_p, P(None, axis_name)),
    )
    out_p, losses = run_sharded(stacked, opt_state, batches)
    return jax.tree.map(lambda x: x[0], out_p), losses
