"""DGC sparse gradient exchange: top-k select + allgather under shard_map.

reference: paddle/fluid/framework/details/sparse_all_reduce_op_handle.h —
the reference sparsifies each gradient to its top-k entries and exchanges
only (index, value) pairs over NCCL, the actual communication saving of
Deep Gradient Compression (Lin et al.). The round-2 IR op masked AFTER a
dense allreduce (compression without savings); this module is the honest
exchange: each data-parallel shard

  1. adds its gradient into a local error-feedback residual,
  2. selects the top-k entries by magnitude (k static -> static shapes;
     jax.lax.top_k, no host sync),
  3. all-gathers the (index, value) pairs over the axis — 2*k*n values on
     the wire instead of the full dense gradient,
  4. scatter-adds the gathered contributions into a dense update and
     subtracts what it sent from its residual.

Wire cost per step: 2 * k * n_shards floats vs `size` floats for the dense
allreduce — a real > 100x reduction at DGC's 99.9% sparsity.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map as _shard_map
from jax.sharding import PartitionSpec as P


def dgc_exchange_local(grad, residual, k, axis_name):
    """Runs INSIDE shard_map. grad/residual: flat [size] per-shard arrays.
    Returns (dense_update [size] — the mean of all shards' sparse
    contributions — and the new residual)."""
    acc = residual + grad
    mag = jnp.abs(acc)
    _, idx = lax.top_k(mag, k)
    vals = acc[idx]
    # what we transmit leaves the residual; the rest accumulates
    new_residual = acc.at[idx].set(0.0)
    n = lax.psum(1, axis_name)
    all_idx = lax.all_gather(idx, axis_name)      # [n, k]
    all_vals = lax.all_gather(vals, axis_name)    # [n, k]
    update = jnp.zeros_like(grad).at[all_idx.reshape(-1)].add(
        all_vals.reshape(-1)
    ) / n
    return update, new_residual


def dgc_allreduce(mesh, grads, residuals, sparsity=0.999, axis_name="data"):
    """Sparse-allreduce a pytree of per-shard gradients.

    grads/residuals: pytrees with leading [n_shards, ...] axis sharded over
    `axis_name` (per-shard gradients, e.g. from per-shard microbatches).
    Returns (updates, new_residuals) with the same layout; `updates` is
    identical on every shard (it is the aggregated sparse gradient).
    """
    def one(g, r):
        def fn(g, r):
            g0 = g[0].reshape(-1)
            r0 = r[0].reshape(-1)
            k = max(1, int(round(g0.size * (1.0 - sparsity))))
            upd, new_r = dgc_exchange_local(g0, r0, k, axis_name)
            return (
                upd.reshape(g[0].shape)[None],
                new_r.reshape(r[0].shape)[None],
            )

        return _shard_map(
            fn,
            mesh=mesh,
            in_specs=(P(axis_name), P(axis_name)),
            out_specs=(P(axis_name), P(axis_name)),
        )(g, r)

    flat_g, tree = jax.tree.flatten(grads)
    flat_r, _ = jax.tree.flatten(residuals)
    outs = [one(g, r) for g, r in zip(flat_g, flat_r)]
    updates = jax.tree.unflatten(tree, [o[0] for o in outs])
    new_res = jax.tree.unflatten(tree, [o[1] for o in outs])
    return updates, new_res
