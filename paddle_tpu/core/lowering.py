"""ONE program->XLA lowering path for the whole framework.

Before this module, three subsystems each carried their own
plan/trace/compile/cache logic — ``Executor._run_compiled``,
``CompiledProgram._run``, and ``Predictor._compiled`` — the reproduction's
analog of the reference's per-executor ExecutorPrepareContext cache
(reference: paddle/fluid/framework/executor.cc), grown three times. Every
hardening PR had to touch all three (ROADMAP open item 5). This module
collapses them: plan (``executor.plan_step``) -> mandatory verifier pass
(analysis/verify.py) -> step closure -> ``jax.jit`` with donation and
shardings -> the content-addressed compile cache (core/compile_cache.py),
with ``jax.export`` serialization to the persistent tier where the
installed jax supports it and a graceful trace-on-miss fallback where it
does not.

The contract every caller shares: a lowered step is a function

    (feed_vals, donated_vals, readonly_vals, rng_key)
        -> (fetches, written_persistable_updates)

Executor, CompiledProgram (with mesh shardings), Predictor (donation off,
fixed rng), and utils/hlo.py (lower-only, no cache) all route through
``lower_step``; ``jit_compile`` is the repo-wide chokepoint for the few
remaining free-function jits (models/, tools/), so compile counts stay
observable from one place.
"""

import re
import time

import numpy as np

from paddle_tpu.observability import lockdep
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.utils.enforce import EnforceError

__all__ = ["LoweredStep", "lower_step", "jit_compile", "verify_for_lowering",
           "abstract_signature", "zero_rng_key", "LOWERING_VERSION"]

#: joins every program fingerprint (compile_cache.program_fingerprint).
#: Bump it in the PR that changes what an op lowering under ops/ or the
#: step closure here EMITS for an unchanged Program: the persistent tier
#: would otherwise load the executable of the old lowering. A Pallas
#: kernel's body has its own ``KernelSpec(version=)``.
LOWERING_VERSION = 4

_JITS = obs_metrics.registry().counter(
    "lowering_jit_total", "jax.jit computations created via the chokepoint"
)
_PERSIST_HITS = obs_metrics.registry().counter(
    "compile_cache_persistent_hits_total",
    "lowered steps loaded from the persistent cache (no retrace)",
)
_PERSIST_LOAD_SECONDS = obs_metrics.registry().histogram(
    "executor_persistent_load_seconds",
    "deserialize latency for persistent compile-cache hits",
)
_SHARED_HITS = obs_metrics.registry().counter(
    "compile_cache_memory_hits_total",
    "lowered steps served from the process-wide memory cache",
)


def jit_compile(fn, **jit_kwargs):
    """The one place outside ops/ that calls ``jax.jit``: every compiled
    computation in the repo is countable from this chokepoint."""
    import jax

    _JITS.inc()
    return jax.jit(fn, **jit_kwargs)


# ---------------------------------------------------------------------------
# mandatory pre-lowering verification
# ---------------------------------------------------------------------------

_VERIFIED = {}  # (uid, version, feeds, fetches) -> True (errors raise)
_VERIFIED_CAP = 512


def verify_for_lowering(program, feed_names, fetch_names, scope=None):
    """Run the analysis/ verifier before any lowering; error-severity
    diagnostics raise (a malformed program must fail loudly at compile
    time, not trace into a wrong computation). Memoized per program
    version so steady-state steps pay one dict lookup.

    Fetch names are screened against the program's declared vars first:
    fetching a scope-resident var the program never mentions is legal
    executor behavior (plan_step validates it against the scope), not a
    dangling fetch."""
    key = (program._uid, program._version, tuple(feed_names),
           tuple(fetch_names))
    if key in _VERIFIED:
        return
    from paddle_tpu.analysis.verify import verify_program

    declared = {n for b in program.blocks for n in b.vars}
    diags = verify_program(
        program,
        feed_names=feed_names,
        fetch_names=[n for n in fetch_names if n in declared],
        scope=scope,
    )
    errors = [d for d in diags if d.severity == "error"]
    if errors:
        lines = [f"[{d.code}] {d.message}" for d in errors[:5]]
        raise EnforceError(
            "program failed pre-lowering verification "
            f"({len(errors)} error(s)):\n  " + "\n  ".join(lines),
            op_type=errors[0].op_type,
            op_callstack=errors[0].callstack,
        )
    if len(_VERIFIED) >= _VERIFIED_CAP:
        _VERIFIED.clear()
    _VERIFIED[key] = True


# ---------------------------------------------------------------------------
# opt-in static diagnostic stages (FLAGS_static_diagnostics) — run ahead of
# the mandatory verifier so a program with a statically-decidable defect
# (shape mismatch, over-budget collective) fails with op attribution
# before any tracing
# ---------------------------------------------------------------------------

_STATIC_STAGE_NAMES = ("shapes", "sharding", "memory", "cost")


def _static_stages():
    from paddle_tpu.utils.flags import flags

    raw = (flags.static_diagnostics or "").strip().lower()
    if not raw:
        return ()
    if raw == "all":
        return _STATIC_STAGE_NAMES
    parts = tuple(p.strip() for p in raw.split(",") if p.strip())
    unknown = [p for p in parts if p not in _STATIC_STAGE_NAMES]
    if unknown:
        # a silently-dropped typo ("shape") would disarm a gate the
        # operator believes is on — refuse instead
        raise EnforceError(
            f"FLAGS_static_diagnostics: unknown stage(s) {unknown}; "
            f"valid: {', '.join(_STATIC_STAGE_NAMES)} or 'all'"
        )
    return tuple(s for s in _STATIC_STAGE_NAMES if s in parts)


_diag_log = None


def _stage_log():
    global _diag_log
    if _diag_log is None:
        from paddle_tpu.observability.logger import RateLimitedLogger

        _diag_log = RateLimitedLogger("paddle_tpu.static_diagnostics",
                                      max_records=32)
    return _diag_log


def run_static_diagnostics(program, feed_sig, fetch_names, stages, *,
                           mesh=None, placement=None, label=""):
    """Run the requested analysis stages; error diagnostics raise, warnings
    go through the rate-limited logger. ``placement`` carries the
    CompiledProgram's parameter-placement inputs (spec_layout /
    param_rules / param_specs / input_specs) so the sharding stage lints
    the layout the compile will actually use."""
    from paddle_tpu.analysis import shapes as a_shapes
    from paddle_tpu.utils.flags import flags

    feed_shapes = {n: s for n, s, _d in feed_sig}
    feed_dtypes = {n: d for n, _s, d in feed_sig}
    shape_report = None
    errors = []
    if "shapes" in stages or "memory" in stages or "sharding" in stages \
            or "cost" in stages:
        shape_report = a_shapes.infer_shapes(
            program, feed_shapes=feed_shapes, feed_dtypes=feed_dtypes,
        )
    if shape_report is not None:
        for d in shape_report.diagnostics:
            if d.severity == "error":
                # every stage consumes the shape report — a broken shape
                # poisons sharding bytes and HBM estimates, so shape
                # errors gate no matter which stage was armed
                errors.append(d)
            elif "shapes" in stages:
                _stage_log().warning("static[%s]: %s", label, d)
    sharding_report = None
    if ("sharding" in stages or "cost" in stages) and mesh is not None:
        from paddle_tpu.analysis import sharding as a_sharding

        placement = placement or {}
        sharding_report = a_sharding.analyze_sharding(
            program, mesh,
            spec_layout=placement.get("spec_layout"),
            param_rules=placement.get("param_rules"),
            param_specs=placement.get("param_specs"),
            input_specs=placement.get("input_specs"),
            feed_shapes=feed_shapes,
            shape_report=shape_report,
        )
        budget_kb = flags.collective_budget_kb
        if budget_kb and "sharding" in stages:
            from paddle_tpu.analysis.sharding import (
                collective_budget_diagnostics,
            )

            errors.extend(collective_budget_diagnostics(
                sharding_report, budget_kb * 1024,
            ))
    if "memory" in stages:
        from paddle_tpu.analysis.memory import estimate_peak_hbm

        mem = estimate_peak_hbm(
            program, feed_shapes=feed_shapes, fetch_names=fetch_names,
            shape_report=shape_report, sharding_report=sharding_report,
        )
        _stage_log().info(
            "static[%s]: peak HBM estimate %.2f MiB per device "
            "(persistent %.2f MiB + intermediates %.2f MiB at op "
            "#%s <%s>)",
            label, mem.peak_total_bytes / 2**20,
            mem.persistent_bytes / 2**20,
            mem.peak_intermediate_bytes / 2**20,
            mem.peak_op_index, mem.peak_op_type,
        )
    if "cost" in stages:
        from paddle_tpu.analysis.cost import (
            analyze_cost,
            hierarchical_collective_diagnostics,
        )

        placement = placement or {}
        axis_tags = placement.get("axis_tags")
        cost = analyze_cost(
            program, machine=flags.cost_machine or "tpu-v4-8",
            mesh=mesh, axis_tags=axis_tags, feed_shapes=feed_shapes,
            feed_dtypes=feed_dtypes, fetch_names=fetch_names,
            shape_report=shape_report, sharding_report=sharding_report,
        )
        _stage_log().info(
            "static[%s]: predicted step %.3f ms on %s (roofline %.3f ms "
            "+ collectives %.3f ms), MFU %.4f, %d/%d ops compute-bound",
            label, cost.step_seconds * 1e3, cost.cost_model.machine.name,
            cost.roofline_seconds * 1e3, cost.collective_seconds * 1e3,
            cost.mfu, cost.bound_counts()["compute"], len(cost.ops),
        )
        hier = hierarchical_collective_diagnostics(cost)
        if axis_tags and any(t == "dcn" for t in axis_tags.values()):
            # the caller has DECLARED the slow tier — a full-payload
            # all-reduce across it is a layout bug, not a maybe
            errors.extend(hier)
        else:
            for d in hier:
                _stage_log().warning("static[%s]: %s", label, d)
    if errors:
        lines = [f"[{d.code}] {d.message}" for d in errors[:5]]
        raise EnforceError(
            f"static diagnostics failed before lowering ({len(errors)} "
            "error(s)):\n  " + "\n  ".join(lines),
            op_type=errors[0].op_type,
            op_callstack=errors[0].callstack,
        )


# ---------------------------------------------------------------------------
# the lowered-step entry
# ---------------------------------------------------------------------------


class LoweredStep:
    """One compiled step + its I/O plan. ``fn`` has the shared 4-arg
    signature; ``source`` records where it came from ("trace" | "disk" —
    tier-1 memory hits return the same object). ``meta`` carries
    caller-specific extras (CompiledProgram stores shardings there)."""

    __slots__ = (
        "fn", "feed_names", "fetch_names", "donated", "readonly", "written",
        "ops", "fingerprint", "source", "build_seconds", "executed", "meta",
        "_aot", "_aot_lock",
    )

    def __init__(self, fn, plan, fingerprint, source, build_seconds):
        (self.feed_names, self.fetch_names, self.donated, self.readonly,
         self.written, self.ops) = plan
        self.fn = fn
        self.fingerprint = fingerprint
        self.source = source
        self.build_seconds = build_seconds
        self.executed = False
        self.meta = {}
        self._aot = None
        self._aot_lock = lockdep.named_lock("compile.aot")

    @property
    def scope_names(self):
        return self.donated + self.readonly

    def lower(self, *abstract_args):
        """jax ``Lowered`` for HLO evidence (utils/hlo.py)."""
        return self.fn.lower(*abstract_args)

    def aot_compile(self, abstract_args):
        """AOT executable for the serving hot path (Predictor): committed
        same-layout args, no per-call jit dispatch. Cached on the entry —
        clones warming the same bucket share one executable (the lock
        keeps concurrent warmups from compiling it twice)."""
        with self._aot_lock:
            if self._aot is None:
                self._aot = self.fn.lower(*abstract_args).compile()
            return self._aot


def _sds(value):
    import jax

    if hasattr(value, "shape") and hasattr(value, "dtype"):
        return jax.ShapeDtypeStruct(tuple(value.shape), value.dtype)
    arr = np.asarray(value)
    return jax.ShapeDtypeStruct(arr.shape, arr.dtype)


def zero_rng_key(device=None):
    """The fixed zero rng key deterministic (inference/decode) steps pass
    for the shared 4-arg contract's rng slot. MUST be built flags-aware —
    under ``FLAGS_rng_impl != threefry`` a plain PRNGKey would be a dtype
    mismatch against ``_rng_abstract`` on every call. One definition
    (Predictor and the decode engine both commit this key once)."""
    import jax

    from paddle_tpu.utils.flags import flags

    if flags.rng_impl != "threefry":
        key = jax.random.key(0, impl=flags.rng_impl)
    else:
        key = jax.random.PRNGKey(0)
    return jax.device_put(key, device) if device is not None else key


def _rng_abstract():
    """Abstract value of the rng key argument, matching the construction
    in ``Executor._next_rng_key`` (impl-dependent dtype)."""
    import jax

    key = zero_rng_key()
    return jax.ShapeDtypeStruct(key.shape, key.dtype)


def _named(fn, label):
    """``fn`` (a lowered step's four-argument contract) under a name made
    of ``label``: what ``jax.jit`` is handed, so that the device module is
    ``jit_decode_<model>_step`` in a profiler's trace and not the
    ``jit_call`` that every ``exported.call`` would be. The wrapper is
    outside whatever is exported: the persisted module's bytes and the
    fingerprint do not carry it. An entry shared through the memory tier
    (one fingerprint under two labels) keeps its first label's name."""
    name = re.sub(r"\W", "_", label)
    if not name or name[0].isdigit():
        name = "_" + name

    def call(feed_vals, donated_vals, readonly_vals, rng_key):
        return fn(feed_vals, donated_vals, readonly_vals, rng_key)

    call.__name__ = call.__qualname__ = name
    return call


def _default_step(block, plan):
    feed_names, fetch_names, donated, readonly, written, ops = plan
    from paddle_tpu.core.executor import _interpret_block

    def step(feed_vals, donated_vals, readonly_vals, rng_key):
        env = dict(zip(feed_names, feed_vals))
        env.update(zip(donated, donated_vals))
        env.update(zip(readonly, readonly_vals))
        _interpret_block(block, env, rng_key, ops=ops)
        fetches = [env[n] for n in fetch_names]
        updates = [env.get(n) for n in written]
        return fetches, updates

    return step


def lower_step(
    program,
    scope,
    feed_sig,
    fetch_names,
    *,
    donate=True,
    make_step=None,
    plan=None,
    mesh=None,
    in_shardings=None,
    out_shardings=None,
    layout_sig=None,
    placement=None,
    extra_fingerprint=(),
    use_cache=True,
    persist=None,
    label="executor",
):
    """The one lowering entrypoint.

    ``feed_sig`` is the ordered tuple of (name, shape, dtype-str) for the
    step's feeds. ``make_step(block, plan) -> step`` overrides the default
    step body (microbatching, DGC shard_map). ``plan`` is an optional
    precomputed ``plan_step`` result ``(donated, readonly, written, ops)``
    — callers that already planned (CompiledProgram derives its shardings
    from the plan) pass it so the ONE plan that ordered their
    in/out_shardings is the one the entry records. ``persist`` defaults to
    single-device lowerings (mesh entries stay in the memory tier: the
    serialized-module format does not carry multi-device sharding safely
    across processes). Returns ``(LoweredStep, source)`` where source says
    how THIS call obtained the entry — "trace" (this call compiled),
    "disk" (persistent-cache load), or "memory" (process-wide tier, incl.
    waiting out another thread's in-flight build) — so callers count
    compiles exactly once. Concurrent calls for the same fingerprint share
    one build (compile_cache single-flight).
    """
    from paddle_tpu.core import compile_cache
    from paddle_tpu.core.executor import plan_step

    block = program.global_block()
    feed_names = [n for n, _s, _d in feed_sig]

    # opt-in static diagnostic stages run FIRST: statically-decidable
    # defects (shape mismatch, over-budget collective) fail with op
    # attribution before the verifier and long before any tracing
    stages = _static_stages()
    if stages:
        run_static_diagnostics(
            program, feed_sig, fetch_names, stages,
            mesh=mesh, placement=placement, label=label,
        )

    # mandatory pre-lowering pass: a program that fails verification never
    # reaches tracing (and never poisons the content-addressed cache)
    verify_for_lowering(program, feed_names, fetch_names, scope=scope)

    with_donation = donate
    if plan is None:
        plan = plan_step(block, feed_names, fetch_names, scope,
                         with_donation)
    donated, readonly, written, ops = plan

    # donation safety is always-on and cheap (O(ops)): a plan that
    # fetches a donated buffer, aliases it twice, or reads it after its
    # in-place update must never reach tracing
    if with_donation and donated:
        from paddle_tpu.analysis.memory import check_donation_safety

        unsafe = check_donation_safety(
            program, donated, readonly, fetch_names, block=block,
        )
        if unsafe:
            lines = [f"[{d.code}] {d.message}" for d in unsafe[:5]]
            raise EnforceError(
                f"donation-safety check failed ({len(unsafe)} error(s)):"
                "\n  " + "\n  ".join(lines),
                op_type=unsafe[0].op_type,
                op_callstack=unsafe[0].callstack,
            )
    plan = (list(feed_names), list(fetch_names), donated, readonly,
            written, ops)

    scope_sig = tuple(
        (n, tuple(np.shape(scope.find_var(n))), _dtype_str(scope.find_var(n)))
        for n in donated + readonly
    )
    sharding_sig = None
    if in_shardings is not None:
        sharding_sig = _sharding_sig(in_shardings, out_shardings)
    # the Pallas kernel registry's selection joins the fingerprint here
    # (the layout_sig pattern): op lowerings consult the registry at
    # trace time, so a mode/registry change MUST miss the cache
    from paddle_tpu.kernels import registry as kernel_registry

    fingerprint = compile_cache.program_fingerprint(
        program, feed_sig, fetch_names, scope_sig,
        donate=with_donation, mesh=mesh, sharding_sig=sharding_sig,
        layout_sig=layout_sig, kernel_sig=kernel_registry.kernel_sig(),
        extra=(label.split(":", 1)[0],) + tuple(extra_fingerprint),
    )

    if persist is None:
        persist = mesh is None and in_shardings is None
    # with persistence switched off, skip the export/serialize work and
    # trace straight into a plain jit
    persist = persist and compile_cache.enabled()
    step_factory = make_step if make_step is not None else _default_step

    def build():
        import jax

        jit_kwargs = {}
        if donated:
            jit_kwargs["donate_argnums"] = (1,)
        if in_shardings is not None:
            jit_kwargs["in_shardings"] = in_shardings
        if out_shardings is not None:
            jit_kwargs["out_shardings"] = out_shardings

        if persist:
            rec = compile_cache.load_persistent(fingerprint)
            if rec is not None:
                header, payload = rec
                t0 = time.perf_counter()
                entry = _entry_from_payload(header, payload, plan,
                                            fingerprint, jit_kwargs, label)
                if entry is not None:
                    _PERSIST_HITS.inc()
                    _PERSIST_LOAD_SECONDS.observe(time.perf_counter() - t0)
                    return entry
                # plan drift against the stored header: stale entry —
                # fall through to a fresh trace (never a wrong answer)

        t0 = time.perf_counter()
        step = step_factory(block, plan)
        fn = None
        if persist:
            fn = _trace_and_persist(
                step, plan, _abstract_args(plan, feed_sig, scope),
                fingerprint, jit_kwargs, label,
            )
        if fn is None:
            _JITS.inc()
            fn = jax.jit(_named(step, label), **jit_kwargs)
        return LoweredStep(fn, plan, fingerprint, "trace",
                           time.perf_counter() - t0)

    if not use_cache:
        entry = build()
        return entry, entry.source
    entry, source = compile_cache.get_or_build(fingerprint, build)
    if source == "memory":
        _SHARED_HITS.inc()
    return entry, source


def _dtype_str(v):
    return str(getattr(v, "dtype", np.asarray(v).dtype))


def _sharding_sig(in_shardings, out_shardings):
    def spec_of(s):
        if s is None:
            return None
        spec = getattr(s, "spec", s)
        return str(spec)

    import jax

    return [
        [spec_of(s) for s in jax.tree_util.tree_leaves(in_shardings)],
        [spec_of(s) for s in jax.tree_util.tree_leaves(
            out_shardings, is_leaf=lambda x: x is None)],
    ]


def abstract_signature(entry, feed_sig, scope):
    """Abstract (ShapeDtypeStruct) argument tuple for a LoweredStep —
    what ``aot_compile`` wants (Predictor warms buckets without data)."""
    plan = (entry.feed_names, entry.fetch_names, entry.donated,
            entry.readonly, entry.written, entry.ops)
    return _abstract_args(plan, feed_sig, scope)


def _abstract_args(plan, feed_sig, scope):
    import jax

    _f, _F, donated, readonly, _w, _ops = plan
    feed_sds = tuple(
        jax.ShapeDtypeStruct(tuple(s), np.dtype(d)) for _n, s, d in feed_sig
    )
    donated_sds = tuple(_sds(scope.find_var(n)) for n in donated)
    readonly_sds = tuple(_sds(scope.find_var(n)) for n in readonly)
    return (feed_sds, donated_sds, readonly_sds, _rng_abstract())


def _trace_and_persist(step, plan, abstract_sig, fingerprint, jit_kwargs,
                       label):
    """Trace once through ``jax.export``, persist the serialized module,
    and return a jitted wrapper around the exported call — the EXACT
    module a later process will deserialize, so cache-cold and cache-warm
    runs execute identical StableHLO (bit-identical fetches). Any
    unsupported construct (extended-dtype rng keys, callbacks, version
    skew) returns None and the caller falls back to a plain jit."""
    import jax

    from paddle_tpu.core import compile_cache

    try:
        from jax import export as jax_export
    except ImportError:
        return None
    try:
        _JITS.inc()
        exported = jax_export.export(jax.jit(step, **jit_kwargs))(
            *abstract_sig
        )
        payload = exported.serialize()
    except Exception:
        return None
    feed_names, fetch_names, donated, readonly, written, _ops = plan
    compile_cache.store_persistent(
        fingerprint,
        {
            "feed_names": feed_names,
            "fetch_names": fetch_names,
            "donated": donated,
            "readonly": readonly,
            "written": written,
            "jax": jax.__version__,
        },
        payload,
    )
    _JITS.inc()
    return jax.jit(_named(exported.call, label),
                   **_wrapper_jit_kwargs(jit_kwargs))


def _wrapper_jit_kwargs(jit_kwargs):
    """The exported module already carries sharding + aliasing attrs;
    the wrapper jit only re-applies donation so caller buffers are
    actually released."""
    out = {}
    if "donate_argnums" in jit_kwargs:
        out["donate_argnums"] = jit_kwargs["donate_argnums"]
    return out


def _entry_from_payload(header, payload, plan, fingerprint, jit_kwargs,
                        label):
    """Wrap a persisted module for execution, cross-checking the stored
    I/O plan against the freshly computed one — a mismatch means the
    planner or program changed without changing the fingerprint inputs
    (should be impossible; treated as a miss, not trusted)."""
    import jax

    try:
        from jax import export as jax_export
    except ImportError:
        return None
    feed_names, fetch_names, donated, readonly, written, _ops = plan
    if (header.get("feed_names") != feed_names
            or header.get("fetch_names") != fetch_names
            or header.get("donated") != donated
            or header.get("readonly") != readonly
            or header.get("written") != written
            or header.get("jax") != jax.__version__):
        return None
    try:
        exported = jax_export.deserialize(payload)
        _JITS.inc()
        fn = jax.jit(_named(exported.call, label),
                     **_wrapper_jit_kwargs(jit_kwargs))
    except Exception:
        return None
    return LoweredStep(fn, plan, fingerprint, "disk", 0.0)
