"""`pipeline_stack` op: GPipe pipeline parallelism on the Program/IR path.

The reference's pipeline cuts the program into sections run by host threads
passing scopes through queues (reference: python/paddle/fluid/
optimizer.py:3414 PipelineOptimizer, paddle/fluid/framework/
section_worker.cc:142). On TPU the schedule must live inside the compiled
computation, so the IR form mirrors the dominant pipelined workload — a
stack of identical layers: the per-layer body is a sub-block (built by
layers.pipeline.PipelinedStack), its parameters are STACKED with a leading
[num_layers] axis sharded over the mesh's `stage` axis, and the lowering
wraps parallel/pipeline.pipeline_apply (ppermute ring + microbatch ticks)
in a nested shard_map — real cross-stage overlap, differentiable through
the generic vjp path.

Off-mesh (no `stage` axis, single device, plain Executor) the same op
degrades to a lax.scan over the stacked layers — identical numerics, no
pipeline, which is what makes single-device parity tests possible.
"""

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

from paddle_tpu.core.registry import register_op
from paddle_tpu.utils.enforce import EnforceError


def _body_runner(sub, inner_x, inner_out, param_inner, ex, bindings, rng):
    """block_fn(layer_params, h) for pipeline_apply / the scan fallback.
    layer_params' first leaf is the per-layer index (for RNG folding)."""
    from paddle_tpu.core.executor import _interpret_block
    from paddle_tpu.parallel.env import collective_context

    def block_fn(layer_params, h):
        layer_idx = layer_params[0]
        env = dict(ex)
        env[inner_x] = h
        env.update(zip(param_inner, layer_params[1:]))
        key = jax.random.fold_in(rng, layer_idx.astype(jnp.uint32))
        with collective_context(bindings):
            _interpret_block(sub, env, key)
        return env[inner_out]

    return block_fn


@register_op("pipeline_stack", stateful=True, needs_block=True,
             nondiff_inputs=())
def _pipeline_stack(ins, attrs):
    block = attrs["_ctx_block"]
    sub = block.program.block(attrs["sub_block"])
    x = ins["X"][0]
    stacked = list(ins.get("StackedParams", []))
    ex_names = attrs.get("ex_vars", [])
    ex = dict(zip(ex_names, ins.get("Ex", [])))
    inner_x = attrs["inner_x"]
    inner_out = attrs["inner_out"]
    param_inner = attrs.get("param_inner_vars", [])
    num_mb = attrs.get("num_microbatches", 1)
    stage_axis = attrs.get("stage_axis", "stage")
    bindings = dict(attrs.get("ring_bindings", {}))
    rng = ins.get("__rng_key__", [jax.random.PRNGKey(0)])[0]
    if not stacked:
        raise EnforceError("pipeline_stack needs stacked layer params")
    L = stacked[0].shape[0]
    layer_ids = jnp.arange(L)

    # schedule choice: op attr (PipelinedStack(schedule=...)), overridden
    # by with_parallel(pipeline_schedule=...) via the thread-local the
    # compiler binds around lowering — the same value it joined into the
    # compile-cache fingerprint
    from paddle_tpu.parallel.pipeline_runtime.runtime import (
        current_schedule_override,
    )

    schedule_kind = attrs.get("schedule") or "gpipe"
    interleave = attrs.get("interleave")
    ov_kind, ov_v = current_schedule_override()
    if ov_kind is not None:
        schedule_kind = ov_kind
        interleave = ov_v if ov_v is not None else None
    elif ov_v is not None:
        interleave = ov_v

    from paddle_tpu.parallel.env import current_mesh

    mesh = current_mesh()
    on_mesh = (
        mesh is not None
        and stage_axis in mesh.axis_names
        and mesh.shape[stage_axis] > 1
    )

    if not on_mesh:
        # degenerate path: the SAME microbatch loop, minus the ring — per
        # microbatch, scan the stacked layers. Looping microbatches (not
        # scanning the full batch) keeps the per-gemm shapes identical to
        # the pipelined arms, so single-device parity is BITWISE, not
        # just allclose (the no-pipeline reference of tests/test_pipeline_runtime.py).
        body = _body_runner(
            sub, inner_x, inner_out, param_inner, ex, bindings, rng
        )

        def layer(h, p):
            return body(p, h), None

        if num_mb > 1 and x.shape[0] % num_mb == 0:
            from paddle_tpu.parallel.pipeline import split_microbatches

            def run_mb(_, xm):
                out, __ = lax.scan(layer, xm, (layer_ids, *stacked))
                return _, out

            _, outs = lax.scan(run_mb, 0, split_microbatches(x, num_mb))
            return {"Out": [outs.reshape(x.shape)]}
        out, _ = lax.scan(layer, x, (layer_ids, *stacked))
        return {"Out": [out]}

    from paddle_tpu.parallel.pipeline import (
        pipeline_apply,
        split_microbatches,
    )
    from paddle_tpu.parallel.pipeline_runtime.runtime import (
        interleave_permutation,
        pipeline_apply_interleaved,
    )
    from paddle_tpu.parallel.pipeline_runtime.schedule import (
        compile_schedule,
    )

    n_stage = mesh.shape[stage_axis]
    # validates the (kind, stages, microbatches, interleave) tuple — a
    # contention-ful 1f1b config fails HERE, pre-trace, with the why
    sched = compile_schedule(schedule_kind, n_stage, num_mb, interleave)
    if sched.kind == "1f1b":
        # circular virtual-stage assignment: permute stacked rows (and
        # layer_ids with them, so per-layer RNG folds follow the layer)
        # BEFORE the P(stage) shard — device d holds chunks d, d+s, ...
        perm = jnp.asarray(
            interleave_permutation(L, n_stage, sched.interleave)
        )
        stacked = [p[perm] for p in stacked]
        layer_ids = layer_ids[perm]

    # per-param specs for the non-stage dims (TP etc.), leading dim 'stage'
    extra_specs = attrs.get("param_specs") or [()] * len(stacked)
    in_param_specs = tuple(
        P(stage_axis, *spec) for spec in extra_specs
    )
    # resolve the batch axis the way CompiledProgram does ('data' if
    # present, else the mesh's first axis) so the activation stays batch-
    # sharded instead of silently replicating onto every device
    if "data" in mesh.axis_names:
        data_axis = "data"
    elif mesh.axis_names[0] != stage_axis:
        data_axis = mesh.axis_names[0]
    else:
        data_axis = None
    x_spec = P(data_axis) if data_axis else P()
    ex_specs = tuple(P() for _ in ex_names)

    def sharded_fn(x, layer_ids, stacked, ex_vals):
        ex_local = dict(zip(ex_names, ex_vals))
        body = _body_runner(
            sub, inner_x, inner_out, param_inner, ex_local, bindings, rng
        )
        x_mb = split_microbatches(x, num_mb)
        if sched.kind == "1f1b":
            outs = pipeline_apply_interleaved(
                body, (layer_ids, *stacked), x_mb, stage_axis,
                sched.interleave, collect="broadcast",
            )
        else:
            outs = pipeline_apply(
                body, (layer_ids, *stacked), x_mb, stage_axis,
                collect="broadcast",
            )
        return outs.reshape(x.shape)

    out = _shard_map(
        sharded_fn,
        mesh=mesh,
        in_specs=(x_spec, P(stage_axis), in_param_specs, ex_specs),
        out_specs=x_spec,
    )(x, layer_ids, tuple(stacked), tuple(ex.values()))
    return {"Out": [out]}
