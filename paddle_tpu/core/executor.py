"""Executor: runs Programs by compiling whole blocks to XLA.

This replaces the reference's per-op interpretive executors
(reference: paddle/fluid/framework/executor.cc:195 Executor::Run — a loop
dispatching one kernel per op) with the design the TPU demands: the entire
block is traced through each op's jax lowering rule into ONE XLA computation,
compiled once per (program version, feed signature) and cached — the analog of
the reference's ExecutorPrepareContext cache (executor.cc) but at whole-graph
granularity, letting XLA fuse elementwise chains into matmuls and schedule the
MXU instead of a host hot-loop dispatching kernels.

State threading: a Scope maps names to jax.Arrays. The compiled step takes
(feeds, scope-resident inputs, rng key) and returns (fetches, updated
persistables); parameter buffers are donated so optimizer updates are
in-place at the XLA level — the donation discipline replaces the reference's
inplace/eager-deletion passes (paddle/fluid/framework/ir/memory_optimize_pass/).

A per-op interpretive mode remains as the debug path (FLAGS_check_nan_inf),
mirroring the reference's NaN/Inf sanitizer hooked into op dispatch
(reference: paddle/fluid/framework/operator.cc:1029).
"""

import warnings

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.core.dtypes import to_numpy_dtype
from paddle_tpu.core.ir import Program
from paddle_tpu.core.places import CPUPlace, TPUPlace
from paddle_tpu.core.backward import resolve_op_def as get_op_def
from paddle_tpu.core.scope import global_scope
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.observability import sanitizer as obs_sanitizer
from paddle_tpu.observability.tracer import trace_scope
from paddle_tpu.profiler import RecordEvent
from paddle_tpu.utils.enforce import EnforceError
from paddle_tpu.utils.flags import flags

# always-on executor telemetry (one scrape shows compile-cache behavior
# next to serving stats and supervisor events); counter inc is the only
# per-step registry cost on the hot compiled path
_CACHE_HITS = obs_metrics.registry().counter(
    "executor_cache_hits_total", "compiled-step cache hits"
)
_CACHE_MISSES = obs_metrics.registry().counter(
    "executor_cache_misses_total", "compiled-step cache misses (traces)"
)
_COMPILE_SECONDS = obs_metrics.registry().histogram(
    "executor_compile_seconds", "trace+compile latency on cache miss"
)

# op types handled structurally by the interpreter (they run sub-blocks);
# `recurrent` is NOT here: it is a regular op whose lowering scans its
# sub-block (ops/rnn.py), so autodiff works through the generic vjp path
CONTROL_FLOW_OPS = {"while", "conditional_block"}
# pseudo-ops that the executor elides (feed/fetch are direct env access here)
ELIDED_OPS = {"feed", "fetch"}


# the use-def/liveness computation lives in the shared static-analysis
# layer (one control-flow-aware implementation for the executor's planner,
# the DCE/fusion passes, and the verifier); re-exported here because this
# module is its historical home
from paddle_tpu.analysis.usedef import live_ops  # noqa: E402


class _OpStep:
    """One op's pre-resolved execution plan: op-def lookup, baked attrs
    (with `_ctx_block`/`__out_counts__` already applied — lowerings only
    read attrs), and the non-empty input/output slot lists. Resolving
    these once per (program version, op list) instead of every `run()`
    call removes the dominant per-op Python dispatch cost the PR-4
    observability spans showed on the interpreted path, and shrinks trace
    time on the compiled path the same way."""

    __slots__ = ("op", "op_def", "attrs", "inputs", "outputs",
                 "control_flow", "rng_id")

    def __init__(self, op, op_def, attrs, inputs, outputs, control_flow,
                 rng_id):
        self.op = op
        self.op_def = op_def
        self.attrs = attrs
        self.inputs = inputs
        self.outputs = outputs
        self.control_flow = control_flow
        self.rng_id = rng_id


# (program uid, program version, block idx, op-list identity) -> [_OpStep];
# version bumps on every program mutation, so stale plans can't be served.
# Bounded: cleared wholesale at the cap (plans are cheap to rebuild).
_PLAN_CACHE = {}
_PLAN_CACHE_CAP = 256


def _block_plan(block, ops=None):
    prog = block.program
    key = (prog._uid, prog._version, block.idx,
           None if ops is None else tuple(map(id, ops)))
    plan = _PLAN_CACHE.get(key)
    if plan is not None:
        return plan
    plan = []
    for op_index, op in enumerate(block.ops if ops is None else ops):
        if op.type in ELIDED_OPS:
            continue
        if op.type in CONTROL_FLOW_OPS:
            plan.append(_OpStep(op, None, None, None, None, True, 0))
            continue
        op_def = get_op_def(op.type)
        attrs = op.attrs
        if op_def.needs_block:
            attrs = dict(attrs)
            attrs["_ctx_block"] = block
        if op_def.needs_out_counts:
            if attrs is op.attrs:
                attrs = dict(attrs)
            attrs["__out_counts__"] = {
                s: len(ns) for s, ns in op.outputs.items()
            }
        plan.append(_OpStep(
            op, op_def, attrs,
            [(slot, names) for slot, names in op.inputs.items() if names],
            list(op.outputs.items()),
            False,
            op.attrs.get("__rng_id__", op_index),
        ))
    if len(_PLAN_CACHE) >= _PLAN_CACHE_CAP:
        _PLAN_CACHE.clear()
    _PLAN_CACHE[key] = plan
    return plan


def _run_op_step(step, env, rng_key, use_pallas):
    """Execute one planned op against `env` (shared by the tracing and
    interpretive paths)."""
    op_def = step.op_def
    ins = {
        slot: [env[n] for n in names]
        for slot, names in step.inputs
        if all(n in env for n in names)
    }
    if op_def.stateful:
        ins["__rng_key__"] = [jax.random.fold_in(rng_key, step.rng_id)]
    if op_def.needs_base_rng:
        ins["__base_rng__"] = [rng_key]
    try:
        outs = op_def.lowering(use_pallas)(ins, step.attrs)
    except EnforceError:
        raise
    except Exception as e:
        raise EnforceError(
            f"lowering failed: {e}",
            op_type=step.op.type,
            op_callstack=step.op.attrs.get("op_callstack"),
        ) from e
    return outs


def _store_outputs(step, outs, env):
    for slot, names in step.outputs:
        if slot not in outs:
            continue
        vals = outs[slot]
        if not isinstance(vals, (list, tuple)):
            vals = [vals]
        for name, val in zip(names, vals):
            if val is not None:
                env[name] = val


def _interpret_block(block, env, rng_key, use_pallas=True, ops=None):
    """Trace every op in `block` through its lowering rule, mutating `env`.

    Called under jax tracing for the compiled path, or with concrete arrays
    for the interpretive debug path. Per-op resolution comes from the
    cached block plan, so repeated traces (and every interpreted step)
    skip the op-def/attrs re-resolution work.
    """
    from paddle_tpu.ops import control_flow as cf  # late import, avoids cycle

    for step in _block_plan(block, ops):
        if step.control_flow:
            cf.run_control_flow_op(step.op, block, env, rng_key,
                                   _interpret_block)
            continue
        outs = _run_op_step(step, env, rng_key, use_pallas)
        _store_outputs(step, outs, env)
    return env


def plan_step(block, feed_names, fetch_names, scope, use_donation):
    """Classify step I/O: validate fetches, split scope-resident inputs into
    donated (rewritten by the step — donation makes the update in-place at
    the XLA level) and read-only. Dead ops are pruned first (live_ops).
    Shared by Executor and CompiledProgram."""
    produced = set(feed_names)
    for op in block.ops:
        produced.update(op.output_names())
    bad_fetch = [
        n for n in fetch_names if n not in produced and not scope.has_var(n)
    ]
    if bad_fetch:
        raise EnforceError(
            f"fetch variables {bad_fetch} are not produced by the program, "
            f"fed, or present in scope"
        )
    ops = live_ops(block, fetch_names)
    scope_inputs, written_persistable = _block_io(block, feed_names, ops)
    # fetching a scope-resident var the block never reads (e.g. a parameter)
    # still needs that var as a step input
    for n in fetch_names:
        if n not in produced and n not in scope_inputs:
            scope_inputs.append(n)
    missing = [n for n in scope_inputs if not scope.has_var(n)]
    if missing:
        raise EnforceError(
            f"variables {missing} are read by the program but not "
            f"initialized in scope (run the startup program first?)"
        )
    overwritten = set(written_persistable) - set(fetch_names)
    donated = (
        [n for n in scope_inputs if n in overwritten] if use_donation else []
    )
    readonly = [n for n in scope_inputs if n not in set(donated)]
    return donated, readonly, written_persistable, ops


def _block_io(block, feed_names, ops=None):
    """Statically classify variables: which must come from the scope, which
    persistables get written back."""
    if ops is None:
        ops = block.ops
    produced = set(feed_names)
    scope_inputs = []
    for op in ops:
        if op.type in ELIDED_OPS:
            continue
        for name in op.input_names():
            if name not in produced and name not in scope_inputs:
                scope_inputs.append(name)
        # conservatively pull sub-block reads from scope too
        if op.type in CONTROL_FLOW_OPS and "sub_block" in op.attrs:
            sub = block.program.block(op.attrs["sub_block"])
            sub_produced = set()
            for sop in sub.ops:
                for n in sop.input_names():
                    if (
                        n not in produced
                        and n not in sub_produced
                        and n not in scope_inputs
                        and sub._find_var_recursive(n) is not None
                    ):
                        scope_inputs.append(n)
                sub_produced.update(sop.output_names())
        produced.update(op.output_names())
    written_persistable = []
    for op in ops:
        for name in op.output_names():
            v = block._find_var_recursive(name)
            if v is not None and v.persistable and name not in written_persistable:
                written_persistable.append(name)
    return scope_inputs, written_persistable


_OP_ROLE_OPTIMIZE = 2


def _make_microbatched_step(block, ops, feed_names, donated, readonly,
                            written_persistable, fetch_names, num_mb):
    """Microbatched step for PipelineOptimizer: the forward+backward region
    runs once per microbatch (feed dim 0 split into num_mb chunks) with
    gradients averaged across microbatches, then the optimizer region runs
    once. The TPU analog of the reference's section pipeline
    (reference: python/paddle/fluid/optimizer.py:3414 PipelineOptimizer +
    section_worker.cc:142 — there microbatches flow through scope queues
    between device sections; here the schedule is unrolled into one XLA
    computation, and with stage-sharded params under with_parallel the
    per-stage overlap is GSPMD's to exploit)."""
    fwd_ops = [
        op for op in ops if op.attrs.get("op_role", 0) != _OP_ROLE_OPTIMIZE
    ]
    opt_ops = [
        op for op in ops if op.attrs.get("op_role", 0) == _OP_ROLE_OPTIMIZE
    ]
    # gradients consumed by optimizer ops get accumulated across microbatches
    fwd_produced = {n for op in fwd_ops for n in op.output_names()}
    acc_names = sorted(
        {
            n
            for op in opt_ops
            for n in op.input_names()
            if n.endswith("@GRAD") and n in fwd_produced
        }
    )

    # float fetches produced per-microbatch (losses/metrics) are averaged
    # across microbatches so they describe the WHOLE fed batch
    fwd_fetches = [n for n in fetch_names if n in fwd_produced]

    def step(feed_vals, donated_vals, readonly_vals, rng_key):
        base_env = dict(zip(donated, donated_vals))
        base_env.update(zip(readonly, readonly_vals))
        feeds = dict(zip(feed_names, feed_vals))
        for n, v in feeds.items():
            if hasattr(v, "ndim") and v.ndim and v.shape[0] % num_mb != 0:
                raise EnforceError(
                    f"feed '{n}' batch dim {v.shape[0]} is not divisible by "
                    f"num_microbatches={num_mb} — remainder rows would be "
                    f"silently dropped"
                )
        acc = {}
        fetch_parts = {n: [] for n in fwd_fetches}
        last_env = None
        mb_size = 0
        for m in range(num_mb):
            env = dict(base_env)
            for n, v in feeds.items():
                mb = v.shape[0] // num_mb if hasattr(v, "ndim") and v.ndim else 0
                env[n] = v[m * mb:(m + 1) * mb] if mb else v
                mb_size = mb or mb_size
            _interpret_block(
                block, env, jax.random.fold_in(rng_key, m), ops=fwd_ops
            )
            for n in acc_names:
                g = env[n]
                acc[n] = g if m == 0 else acc[n] + g
            for n in fwd_fetches:
                fetch_parts[n].append(env[n])
            # forward-written persistables (batch-norm moving stats, streaming
            # metric accumulators) must chain across microbatches, not reset
            # to base_env each time — the reference's section pipeline updates
            # shared-scope persistables every microbatch
            for n in written_persistable:
                if n in env:
                    base_env[n] = env[n]
            last_env = env
        env = last_env
        for n in acc_names:
            env[n] = acc[n] / num_mb
        _interpret_block(block, env, rng_key, ops=opt_ops)
        for n, parts in fetch_parts.items():
            v0 = jnp.asarray(parts[0])
            if v0.ndim and mb_size and v0.shape[0] == mb_size:
                env[n] = jnp.concatenate(parts, axis=0)  # per-example fetch
            elif jnp.issubdtype(v0.dtype, jnp.floating):
                env[n] = sum(parts) / num_mb  # scalar metric: batch mean
        fetches = [env[n] for n in fetch_names]
        updates = [env.get(n) for n in written_persistable]
        return fetches, updates

    return step


class Executor:
    """Feed/fetch driver (reference: python/paddle/fluid/executor.py:432)."""

    def __init__(self, place=None):
        self.place = place if place is not None else TPUPlace(0)
        self._cache = {}
        self._rng_counter = 0

    # ------------------------------------------------------------------
    def run(
        self,
        program=None,
        feed=None,
        fetch_list=None,
        scope=None,
        return_numpy=True,
        use_program_cache=True,
    ):
        from paddle_tpu.compiler import CompiledProgram

        if program is None:
            from paddle_tpu.core.ir import default_main_program

            program = default_main_program()
        if isinstance(program, CompiledProgram):
            return program._run(self, feed, fetch_list, scope, return_numpy)
        feed = feed or {}
        fetch_list = fetch_list or []
        scope = scope or global_scope()
        fetch_names = [
            f.name if not isinstance(f, str) else f for f in fetch_list
        ]

        block = program.global_block()
        with trace_scope("executor::feed", nfeeds=len(feed)):
            feed_arrays = {
                name: self._to_device(value, block, name)
                for name, value in feed.items()
            }

        if flags.check_nan_inf or flags.benchmark:
            return self._run_interpreted(
                program, feed_arrays, fetch_names, scope, return_numpy
            )
        return self._run_compiled(
            program, feed_arrays, fetch_names, scope, return_numpy
        )

    # ------------------------------------------------------------------
    def train_from_dataset(
        self,
        program=None,
        dataset=None,
        scope=None,
        thread=0,
        debug=False,
        fetch_list=None,
        fetch_info=None,
        print_period=100,
        fetch_handler=None,
        is_infer=False,
    ):
        """Dataset-mode training loop (reference: python/paddle/fluid/
        executor.py:1124 train_from_dataset -> C++ Executor::RunFromDataset
        with thread-per-core DeviceWorkers). TPU-native: the whole step is
        one XLA computation, so the worker-thread pool collapses into the
        native data-feed producing batches (csrc/datafeed) while the chip
        runs the compiled step. The per-batch driver comes from the
        program's `_fleet_opt` via TrainerFactory (device_worker.py):
        Hogwild = plain step, DownpourSGD = the PS pull/step/push loop;
        its run configuration (fetch/debug/infer) travels on the
        TrainerDesc."""
        from paddle_tpu.utils.enforce import enforce as _enforce

        _enforce(dataset is not None, "dataset is required")
        import time as _time

        from paddle_tpu.device_worker import TrainerFactory

        prog_obj = getattr(program, "program", program)
        if is_infer:
            # evaluation must not update state: a program still carrying
            # optimizer ops (or in-graph grad pushes) would train on the
            # eval data — demand the test clone, like the reference's
            # infer-trainer contract
            bad = [
                op.type
                for op in prog_obj.global_block().ops
                if op.attrs.get("op_role", 0) == _OP_ROLE_OPTIMIZE
                or op.type == "distributed_push_sparse"
            ]
            _enforce(
                not bad,
                "infer_from_dataset got a TRAINING program (contains "
                f"{sorted(set(bad))[:3]}...): pass the "
                "clone(for_test=True) inference program instead",
            )
        trainer = TrainerFactory()._create_trainer(
            getattr(prog_obj, "_fleet_opt", None)
        )
        trainer._set_thread(thread)
        trainer._set_debug(debug)
        trainer._set_infer(is_infer)
        trainer._set_fetch_var_and_info(fetch_list, fetch_info, print_period)
        trainer._set_program(prog_obj)
        worker = trainer._device_worker
        worker.prepare(self, prog_obj, scope)

        fetch_list = trainer._fetch_vars
        fetch_info = trainer._fetch_info or [str(f) for f in fetch_list]
        print_period = trainer._print_period
        debug = trainer._debug
        step = 0
        last = None
        last_handled = _time.monotonic()
        # background=True on the FetchHandler moves delivery off the
        # training loop onto a period-driven monitor thread (reference:
        # FetchHandlerMonitor) — a long epoch reports on schedule even
        # when single steps are slow
        monitor = None
        if fetch_list and fetch_handler is not None and getattr(
                fetch_handler, "background", False):
            from paddle_tpu.observability.fetcher import FetchHandlerMonitor

            monitor = FetchHandlerMonitor(fetch_handler).start()
        # lookahead iteration ONLY for programs with in-graph remote tables
        # (distributed_embedding): the NEXT batch's ids are announced before
        # the current step runs, so the PS pull overlaps device compute —
        # the dataset-mode analog of the reference's prefetch thread
        # (reference: paddle/fluid/operators/distributed/parameter_prefetch.cc).
        # Other programs keep strict one-batch-at-a-time iteration: eagerly
        # demanding batch N+1 from a streaming producer would stall batch N.
        lookahead = bool(
            getattr(getattr(program, "program", program), "_remote_tables", None)
        )
        host_feed = lookahead or bool(
            getattr(prog_obj, "_sparse_tables", None)
        )
        if host_feed:
            # PS paths read feed ids on the HOST (PSWorker.run / the
            # lookahead pull): keep the raw iterator — device-staging
            # first would force a device->host copy per batch
            it = iter(dataset._iter_batches())
        else:
            # dataio double-buffer: batch N+1 is device_put while batch N
            # computes (the buffered_reader.cc overlap)
            from paddle_tpu.dataio.prefetch import DevicePrefetcher

            it = iter(DevicePrefetcher(dataset._iter_batches(), depth=2,
                                       device=self.place.jax_device(),
                                       name="train_from_dataset"))
        feed = next(it, None)
        nxt = None
        try:
            while feed is not None:
                if lookahead:
                    nxt = next(it, None)
                    if nxt is not None:
                        from paddle_tpu.distributed import lookup as _rl

                        _rl.prefetch_for_program(program, nxt)
                out = worker.run_batch(
                    self, program, feed, fetch_list=fetch_list, scope=scope
                )
                last = out
                if fetch_list and fetch_handler is not None:
                    names = [
                        f if isinstance(f, str) else f.name
                        for f in fetch_list
                    ]
                    if monitor is not None:
                        # background monitor owns the cadence; the loop
                        # only publishes the newest values (one dict swap)
                        monitor.update(dict(zip(names, out)))
                    else:
                        # in-loop cadence (reference: FetchHandlerMonitor
                        # wakes every period_secs, executor.py:406) with a
                        # step fallback so short runs still observe fetches
                        now = _time.monotonic()
                        if (
                            now - last_handled >= fetch_handler.period_secs
                            or step % print_period == 0
                        ):
                            fetch_handler.handler(dict(zip(names, out)))
                            last_handled = now
                elif fetch_list and (debug or step % print_period == 0):
                    msgs = [
                        f"{info}={np.asarray(v).reshape(-1)[:1][0]:.6f}"
                        for info, v in zip(fetch_info, out)
                    ]
                    print(f"step {step}: " + ", ".join(msgs))
                step += 1
                feed = nxt if lookahead else next(it, None)
        finally:
            # a mid-epoch raise must not leak the monitor's daemon thread;
            # the final tick delivers the last published fetch either way
            if monitor is not None:
                monitor.stop()
        worker.finish()
        return last

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           fetch_handler=None):
        return self.train_from_dataset(
            program, dataset, scope, thread, debug, fetch_list, fetch_info,
            print_period, fetch_handler, is_infer=True,
        )

    # ------------------------------------------------------------------
    def _to_device(self, value, block, name):
        if isinstance(value, jax.Array):
            return value
        return jax.device_put(np.asarray(value), self.place.jax_device())

    @staticmethod
    def _committed(scope, name, dev, store=True):
        """Scope value as a device-committed array, verifying at most once:
        steady-state training steps hand back the arrays the previous step
        produced (written back via _set_verified, already on `dev`), so the
        common path is ONE dict lookup — not a device_put (the round-2
        profile's biggest host-side line item) and not even a per-step
        `.devices()` call (~5 us x ~600 scope entries on BERT).
        User-facing scope.set invalidates
        the verification.

        `store=False` for DONATED inputs: their buffer is consumed by the
        step, so storing the committed copy would leave a deleted array in
        the scope whenever the step fails — the post-step write-back is
        their only legitimate store."""
        owner = scope._find_owner(name)
        v = owner._vars[name] if owner is not None else None
        if isinstance(v, jax.Array):
            ver = owner._device_verified.get(name)
            if ver is not None and dev in ver:
                return v
            devs = v.devices()
            if dev in devs or len(devs) > 1:  # right chip, or sharded: keep
                owner._device_verified.setdefault(name, set()).add(dev)
                return v
        arr = jax.device_put(v, dev)
        if store:
            scope._set_verified(name, arr, dev)
        return arr

    def _next_rng_key(self, program):
        seed = program.random_seed or 0
        self._rng_counter += 1
        if flags.rng_impl != "threefry":
            # rbg: hardware-RNG-backed bits on TPU - dropout-heavy steps
            # stop paying threefry's ALU cost. Streams differ from threefry
            # but the distribution is identical.
            return jax.random.fold_in(
                jax.random.key(seed, impl=flags.rng_impl), self._rng_counter
            )
        return jax.random.fold_in(jax.random.PRNGKey(seed), self._rng_counter)

    # ------------------------------------------------------------------
    def _run_compiled(self, program, feed_arrays, fetch_names, scope, return_numpy):
        from paddle_tpu.passes import (
            apply_deferred_sharded_embedding_rewrite,
            apply_deferred_sparse_rewrite,
            resolve_tensor_array_indices,
        )

        apply_deferred_sparse_rewrite(program)
        apply_deferred_sharded_embedding_rewrite(program)
        resolve_tensor_array_indices(program)
        block = program.global_block()
        feed_names = sorted(feed_arrays)
        feed_sig = tuple(
            (n, tuple(feed_arrays[n].shape), str(feed_arrays[n].dtype))
            for n in feed_names
        )
        # per-executor cheap key: steady-state steps never pay the
        # content-addressed fingerprint (which serializes the program);
        # on a miss the shared lowering consults the process-wide and
        # persistent tiers before tracing. The RESOLVED kernel mode
        # (paddle_tpu/kernels/) joins the cheap key — flipping
        # PADDLE_TPU_KERNELS must not serve a stale executable from this
        # per-object tier when the content-addressed one would miss
        from paddle_tpu.kernels import registry as _kernel_registry

        key = (program._uid, program._version, feed_sig,
               tuple(fetch_names), _kernel_registry.resolved_mode())
        entry = self._cache.get(key)
        if entry is None:
            from paddle_tpu.core import lowering

            num_mb = getattr(program, "_num_microbatches", 0)
            make_step = None
            extra = ()
            if num_mb and num_mb > 1:
                if any(op.type == "sgd_sparse" for op in block.ops):
                    raise EnforceError(
                        "sgd_sparse cannot run microbatched: Ids differ per "
                        "microbatch while grads accumulate across them. "
                        "Build the program with "
                        "FLAGS_sparse_embedding_update=0, or apply "
                        "PipelineOptimizer before minimize"
                    )
                extra = (("mb", num_mb),)

                def make_step(blk, plan):
                    f_names, f_fetch, donated, readonly, written, ops = plan
                    return _make_microbatched_step(
                        blk, ops, f_names, donated, readonly, written,
                        f_fetch, num_mb,
                    )

            with trace_scope("executor::plan", ops=len(block.ops)):
                entry, source = lowering.lower_step(
                    program, scope, feed_sig, fetch_names,
                    donate=flags.use_donation, make_step=make_step,
                    extra_fingerprint=extra, label="executor",
                )
            if source == "trace":
                _CACHE_MISSES.inc()
            self._cache[key] = entry
        else:
            _CACHE_HITS.inc()

        compiled = entry.fn
        donated, readonly = entry.donated, entry.readonly
        written_persistable = entry.written
        missing = [n for n in donated + readonly if not scope.has_var(n)]
        if missing:
            raise EnforceError(
                f"variables {missing} are read by the program but not "
                f"initialized in scope (run the startup program first?)"
            )
        # Commit every input to the executor's device: mixing committed and
        # uncommitted arrays makes XLA compile one executable per layout
        # combination (first step vs steady state), doubling compile time.
        # The commit is sticky (written back to the scope) so steady-state
        # steps skip the per-param device_put loop entirely — the step outputs
        # written back below are already committed device arrays.
        dev = self.place.jax_device()
        with trace_scope("executor::commit_inputs"):
            feed_vals = tuple(feed_arrays[n] for n in sorted(feed_arrays))
            donated_vals = tuple(
                self._committed(scope, n, dev, store=False) for n in donated
            )
            readonly_vals = tuple(
                self._committed(scope, n, dev) for n in readonly
            )
        rng_key = self._next_rng_key(program)
        # first call on a freshly traced entry runs the XLA compile; a
        # separate span name keeps compile time out of the execute track,
        # and a persistent-cache load gets its own span (it compiles the
        # deserialized module, it does not retrace)
        if not entry.executed and entry.source == "trace":
            import time as _time

            t0 = _time.perf_counter()
            with trace_scope("executor::trace_compile_execute"), \
                    warnings.catch_warnings():
                warnings.simplefilter("ignore")
                fetches, updates = compiled(
                    feed_vals, donated_vals, readonly_vals, rng_key
                )
            _COMPILE_SECONDS.observe(
                entry.build_seconds + _time.perf_counter() - t0
            )
        elif not entry.executed:
            with trace_scope("executor::persistent_load_execute"), \
                    warnings.catch_warnings():
                warnings.simplefilter("ignore")
                fetches, updates = compiled(
                    feed_vals, donated_vals, readonly_vals, rng_key
                )
        else:
            with trace_scope("executor::execute"), warnings.catch_warnings():
                warnings.simplefilter("ignore")  # donation warnings on CPU
                fetches, updates = compiled(
                    feed_vals, donated_vals, readonly_vals, rng_key
                )
        entry.executed = True
        for name, val in zip(written_persistable, updates):
            if val is not None:
                # write back to the scope the variable LIVES in (reference
                # semantics: persistables update in place through child
                # scopes — and the owner's buffer was donated, so leaving
                # it unreplaced would strand a deleted array there). Step
                # outputs are on `dev` by construction: mark verified so
                # the next dispatch skips the devices() probe.
                target = scope._find_owner(name) or scope
                target._set_verified(name, val, dev)
        if return_numpy:
            with trace_scope("executor::fetch", nfetch=len(fetches)):
                return [np.asarray(f) for f in fetches]
        return list(fetches)

    # ------------------------------------------------------------------
    def _run_interpreted(self, program, feed_arrays, fetch_names, scope, return_numpy):
        """Per-op debug path with NaN/Inf checking
        (reference: paddle/fluid/framework/details/nan_inf_utils_detail.cc)."""
        from paddle_tpu.passes import (
            apply_deferred_sharded_embedding_rewrite,
            resolve_tensor_array_indices,
        )

        apply_deferred_sharded_embedding_rewrite(program)
        resolve_tensor_array_indices(program)
        block = program.global_block()
        env = dict(feed_arrays)
        for name in block.vars:
            v = scope.find_var(name)
            if v is not None and name not in env:
                env[name] = v
        rng_key = self._next_rng_key(program)
        from paddle_tpu.ops import control_flow as cf

        # per-op resolution comes from the cached block plan (shared with
        # the compiled path's tracer): repeated debug/benchmark steps skip
        # the op-def/attrs re-resolution entirely
        for step in _block_plan(block):
            op = step.op
            if step.control_flow:
                cf.run_control_flow_op(op, block, env, rng_key, _interpret_block)
                continue
            if flags.benchmark:
                # per-op timing: block on the op's outputs so device time is
                # attributed to the op (reference: FLAGS_benchmark serializes
                # with dev_ctx->Wait, operator.cc:1006)
                with RecordEvent(op.type):
                    outs = _run_op_step(step, env, rng_key, True)
                    for vals in outs.values():
                        for v in vals if isinstance(vals, (list, tuple)) else [vals]:
                            if hasattr(v, "block_until_ready"):
                                v.block_until_ready()
            else:
                with trace_scope("op::" + op.type, cat="op"):
                    outs = _run_op_step(step, env, rng_key, True)
            for slot, names in step.outputs:
                if slot not in outs:
                    continue
                vals = outs[slot]
                if not isinstance(vals, (list, tuple)):
                    vals = [vals]
                for name, val in zip(names, vals):
                    if val is None:
                        continue
                    env[name] = val
                    if flags.check_nan_inf:
                        # sanitizer mode (reference: nan_inf_utils_detail.cc):
                        # names the op, the output var, value stats, and the
                        # user callstack that built the op
                        obs_sanitizer.check_output(op, name, val)
        for name, val in env.items():
            var = block._find_var_recursive(name)
            if var is not None and var.persistable:
                (scope._find_owner(name) or scope).set(name, val)
        fetches = [env[n] for n in fetch_names]
        if return_numpy:
            return [np.asarray(f) for f in fetches]
        return fetches

    # ------------------------------------------------------------------
    def close(self):
        self._cache.clear()
