"""AnalysisPredictor analog: load → analyze → AOT-compile → zero-copy serve.

reference: paddle/fluid/inference/api/analysis_predictor.h:47 (class
AnalysisPredictor), paddle_api.h (PaddlePredictor/ZeroCopyTensor),
paddle_analysis_config.h (AnalysisConfig). The reference pipeline was
load → 30+ ir fusion passes → NaiveExecutor op loop with zero-copy scope
tensors. The TPU-native pipeline is load → semantic passes (passes.py) →
jax.jit AOT lowering of the WHOLE pruned program into one XLA executable per
input-shape bucket; weights live on device across calls, feeds are
device_put once, outputs stay on device until copy_to_cpu.
"""

import json
import os
import threading

import numpy as np

from paddle_tpu.core.scope import Scope
from paddle_tpu.utils.enforce import EnforceError, enforce

__all__ = ["Config", "PrecisionType", "Predictor", "Tensor", "create_predictor"]


class PrecisionType:
    """reference: paddle_api.h PaddleDType/Precision. kHalf maps to bf16 —
    the TPU's native low-precision dtype."""

    Float32 = "float32"
    Bfloat16 = "bfloat16"
    Half = "bfloat16"
    Int8 = "int8"  # accepted; executed as bf16 (no TPU int8 matmul path here)


class Config:
    """reference: paddle/fluid/inference/api/paddle_analysis_config.h:61
    (AnalysisConfig). Construction mirrors the reference: Config(model_dir)
    for the __model__/__params__ layout, or Config(prog_file, params_file)."""

    def __init__(self, model_dir=None, params_file=None):
        if model_dir is not None and params_file is not None:
            self._prog_file = model_dir
            self._params_file = params_file
            self._model_dir = os.path.dirname(model_dir)
        else:
            self._model_dir = model_dir
            self._prog_file = None
            self._params_file = None
        self._use_tpu = True
        self._device_id = 0
        self._ir_optim = True
        self._memory_optim = True
        self._precision = PrecisionType.Float32
        self._passes = None  # None = default pipeline
        self._deleted_passes = set()
        self._verify_each_pass = False
        self._options = {}
        self._serving_buckets = None

    # -- model location (reference: AnalysisConfig::SetModel — updates only
    # the paths; previously configured options must survive) ---------------
    def set_model(self, model_dir_or_prog, params_file=None):
        if model_dir_or_prog is not None and params_file is not None:
            self._prog_file = model_dir_or_prog
            self._params_file = params_file
            self._model_dir = os.path.dirname(model_dir_or_prog)
        else:
            self._model_dir = model_dir_or_prog
            self._prog_file = None
            self._params_file = None

    def model_dir(self):
        return self._model_dir

    # -- device (reference: EnableUseGpu/DisableGpu — re-targeted to TPU) --
    def enable_tpu(self, device_id=0):
        self._use_tpu = True
        self._device_id = device_id

    def disable_tpu(self):
        self._use_tpu = False

    def use_tpu(self):
        return self._use_tpu

    # GPU-era spellings kept callable for porting ease
    def enable_use_gpu(self, memory_pool_init_size_mb=0, device_id=0):
        self.enable_tpu(device_id)

    def disable_gpu(self):
        self.disable_tpu()

    # -- analysis (reference: SwitchIrOptim / pass_builder) ----------------
    def switch_ir_optim(self, x=True):
        self._ir_optim = x

    def enable_program_verification(self, x=True):
        """Run the IR verifier (analysis/verify.py) after every analysis
        pass; a pass that breaks a program invariant raises naming the
        pass instead of serving a silently-corrupted model."""
        self._verify_each_pass = x

    def ir_optim(self):
        return self._ir_optim

    def enable_memory_optim(self, x=True):
        """Donation-based buffer reuse inside the executable (XLA owns the
        actual memory plan; reference: EnableMemoryOptim)."""
        self._memory_optim = x

    def enable_bf16(self):
        """Serve matmul/conv regions in bfloat16 (the reference's
        EnableMkldnnBfloat16/TensorRT-fp16 analog on TPU)."""
        self._precision = PrecisionType.Bfloat16

    def set_precision(self, precision):
        if precision == PrecisionType.Int8:
            # no silent mode degradation: the caller asked for an int8
            # engine (reference: TensorRT int8 calibration path) and gets
            # bf16 execution instead — say so loudly
            import warnings

            warnings.warn(
                "PrecisionType.Int8 requested but this build serves bf16: "
                "there is no int8 matmul path here (weights are not "
                "quantized). Use contrib.quantize QAT for int8-simulated "
                "training, or set Bfloat16 to silence this warning.",
                stacklevel=2,
            )
        self._precision = precision

    def precision(self):
        return self._precision

    def delete_pass(self, name):
        """reference: pass_builder()->DeletePass."""
        self._deleted_passes.add(name)

    def set_passes(self, names):
        self._passes = list(names)

    def analysis_passes(self):
        if self._passes is not None:
            names = list(self._passes)
        else:
            # pattern fusions run AFTER test-mode flip (multihead matching
            # needs is_test dropout) and BEFORE the precision cast (the
            # fused fc/sdpa ops are AMP-white-listed)
            names = ["strip_debug_ops", "flip_test_mode",
                     "dead_code_elimination", "fold_constants",
                     "conv_bn_fuse", "fc_fuse", "multihead_matmul_fuse"]
            if self._precision == PrecisionType.Float32:
                pass
            else:
                names.append("bf16_cast")
        return [n for n in names if n not in self._deleted_passes]

    # -- serving (paddle_tpu/serving: bucket lattice + warmup) -------------
    def set_serving_buckets(self, batch_sizes, seq_lens=None, pad_axis=1):
        """Declare the serving shape lattice: every served batch will be
        one of (batch, seq) with batch from `batch_sizes` and seq from
        `seq_lens` (None = the model has no variable-length axis).
        Predictor.warmup() pre-compiles every lattice point so first-
        request latency never includes a trace, and ServingEngine batches
        only onto these shapes so the compile cache never misses."""
        self._serving_buckets = {
            "batch_sizes": tuple(sorted(int(b) for b in batch_sizes)),
            "seq_lens": (tuple(sorted(int(s) for s in seq_lens))
                         if seq_lens else None),
            "pad_axis": int(pad_axis),
        }

    def serving_buckets(self):
        return self._serving_buckets

    # -- parity shims (accepted, no TPU meaning) ---------------------------
    def set_cpu_math_library_num_threads(self, n):
        self._options["cpu_math_threads"] = n

    def switch_use_feed_fetch_ops(self, x=False):
        self._options["use_feed_fetch_ops"] = x

    def switch_specify_input_names(self, x=True):
        self._options["specify_input_names"] = x


class Tensor:
    """Zero-copy I/O handle (reference: paddle_api.h ZeroCopyTensor:
    copy_from_cpu/copy_to_cpu/Reshape). Input handles hold the next feed;
    output handles hold the last run's device array (fetched lazily)."""

    def __init__(self, name, var, place):
        self.name = name
        self._var = var
        self._place = place
        self._value = None  # np array (pending feed) or jax array (output)
        self._declared_shape = None  # set by reshape()

    def shape(self):
        if self._value is not None:
            return list(np.shape(self._value))
        return list(self._var.shape) if self._var is not None else []

    def reshape(self, shape):
        """Declare the upcoming feed's shape (reference: ZeroCopyTensor::
        Reshape): the next copy_from_cpu may then pass a flat buffer, which
        is viewed through this shape (and thereby selects the compile
        bucket, since buckets key on the concrete feed shapes)."""
        self._declared_shape = list(shape)

    def copy_from_cpu(self, data):
        arr = np.ascontiguousarray(data)
        if self._declared_shape is not None and (
            list(arr.shape) != self._declared_shape
        ):
            arr = arr.reshape(self._declared_shape)
        self._value = arr

    def share_external_data(self, data):
        """Zero-copy variant: keep the caller's buffer (no copy here; the
        single host→device transfer happens inside run())."""
        self._value = np.asarray(data)

    def copy_to_cpu(self):
        enforce(self._value is not None, f"tensor '{self.name}' has no value")
        return np.asarray(self._value)

    def value(self):
        return self._value


class Predictor:
    """reference: analysis_predictor.h:47. Loads the inference program,
    runs the analysis pipeline, and serves through AOT-compiled XLA
    executables keyed on input shapes. clone() shares weights and the
    compile cache (reference: AnalysisPredictor::Clone shares params via the
    parent scope)."""

    def __init__(self, config, _shared=None):
        import jax

        from paddle_tpu.core.places import CPUPlace, TPUPlace

        self._config = config
        self._place = (
            TPUPlace(config._device_id) if config._use_tpu else CPUPlace()
        )
        if _shared is not None:
            # clone: share scope (weights), program, compiled cache, and
            # the cache hit/miss counters (serving replicas report one
            # compile-cache hit rate, not per-clone fragments)
            (self._program, self._feed_names, self._fetch_names,
             self._scope, self._cache, self._analysis_stats,
             self._cache_stats, self._cache_lock) = _shared
        else:
            self._scope = Scope()
            self._program, self._feed_names, self._fetch_names = self._load()
            self._analysis_stats = {}
            if config.ir_optim():
                self._analyze()
            self._cache = {}
            self._cache_stats = {"hits": 0, "misses": 0, "compile_s": 0.0,
                                 "persistent_hits": 0}
            # clones run in concurrent serving workers; counter updates
            # and cache writes need the shared lock (compiles run outside
            # it — the shared lowering single-flights duplicate compiles
            # for the same signature instead of serializing everything)
            self._cache_lock = threading.Lock()
        self._rng0 = None
        self._inputs = {}
        self._outputs = {}
        block = self._program.global_block()
        for n in self._feed_names:
            self._inputs[n] = Tensor(n, block._find_var_recursive(n), self._place)
        for n in self._fetch_names:
            self._outputs[n] = Tensor(n, block._find_var_recursive(n), self._place)

    # -- loading (reference: AnalysisPredictor::LoadProgramDesc/Parameters) -
    def _load(self):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.core.ir import Program
        from paddle_tpu.io import _read_combined

        cfg = self._config
        if cfg._prog_file:
            model_path, params_path = cfg._prog_file, cfg._params_file
        else:
            enforce(cfg._model_dir, "Config has no model location")
            model_path = os.path.join(cfg._model_dir, "__model__")
            params_path = os.path.join(cfg._model_dir, "__params__")
        enforce(os.path.exists(model_path), f"{model_path} not found")
        with open(model_path, "rb") as f:
            desc = json.loads(f.read().decode("utf-8"))
        program = Program.from_bytes(
            json.dumps(
                {k: v for k, v in desc.items()
                 if k not in ("feed_var_names", "fetch_var_names")}
            ).encode()
        )
        feed_names = desc.get("feed_var_names", [])
        fetch_names = desc.get("fetch_var_names", [])
        dev = self._place.jax_device()
        for name, arr in _read_combined(params_path).items():
            # weights go device-resident ONCE; every run() reuses them
            self._scope.set(name, jax.device_put(jnp.asarray(arr), dev))
        return program, feed_names, fetch_names

    # -- analysis (reference: AnalysisPredictor::OptimizeInferenceProgram) -
    def _analyze(self):
        from paddle_tpu.passes import PassContext, PassManager

        ctx = PassContext(
            scope=self._scope,
            feed_names=self._feed_names,
            fetch_names=self._fetch_names,
            bf16_white_list=self._config._options.get("bf16_white_list"),
            bf16_black_list=self._config._options.get("bf16_black_list"),
        )
        pm = PassManager(
            self._config.analysis_passes(),
            verify_each_pass=self._config._verify_each_pass,
        )
        self._program = pm.run(self._program, ctx)
        if self._config.precision() != PrecisionType.Float32:
            self._fold_param_casts()
        self._analysis_stats = ctx.stats

    def _fold_param_casts(self):
        """Pre-cast device weights that only flow through a leading cast op,
        deleting the cast from the program — bf16 weights then live on
        device at half the HBM footprint and no per-call cast runs."""
        import jax.numpy as jnp

        block = self._program.global_block()
        kept = []
        folded_srcs = []
        for op in block.ops:
            if op.type == "cast":
                src = op.inputs.get("X", [None])[0]
                dst = op.outputs.get("Out", [None])[0]
                var = block._find_var_recursive(src) if src else None
                if (
                    var is not None
                    and var.persistable
                    and self._scope.has_var(src)
                    and src not in self._feed_names
                ):
                    w = self._scope.find_var(src)
                    self._scope.set(
                        dst, jnp.asarray(w).astype(op.attrs.get("out_dtype"))
                    )
                    dvar = block._find_var_recursive(dst)
                    if dvar is not None:
                        dvar.persistable = True
                    folded_srcs.append(src)
                    continue
            kept.append(op)
        if len(kept) != len(block.ops):
            block.ops = kept
            # drop an original-precision weight only when NOTHING still reads
            # it (tied weights may feed another op directly, e.g. a lookup
            # table shared with an MLM output matmul)
            still_read = {
                n
                for b in self._program.blocks
                for op in b.ops
                for n in op.input_names()
            } | set(self._fetch_names)
            self._scope.erase([n for n in folded_srcs if n not in still_read])
            self._program._bump_version()

    # -- surface (reference: GetInputNames/GetOutputNames/GetInputTensor) --
    def get_input_names(self):
        return list(self._feed_names)

    def get_output_names(self):
        return list(self._fetch_names)

    def get_input_handle(self, name):
        enforce(name in self._inputs, f"no input named '{name}'")
        return self._inputs[name]

    def get_output_handle(self, name):
        enforce(name in self._outputs, f"no output named '{name}'")
        return self._outputs[name]

    # reference spellings
    get_input_tensor = get_input_handle
    get_output_tensor = get_output_handle

    def get_input_tensor_shape(self):
        block = self._program.global_block()
        out = {}
        for n in self._feed_names:
            v = block._find_var_recursive(n)
            out[n] = list(v.shape) if v is not None else []
        return out

    # -- execution (reference: AnalysisPredictor::ZeroCopyRun) -------------
    def run(self, inputs=None):
        """Run one inference. Either set input handles first (zero-copy
        style) and call run(), or pass `inputs` as {name: np.ndarray} /
        [np.ndarray, ...] (reference: PaddlePredictor::Run). Returns the
        list of output np.ndarrays AND fills the output handles."""
        if inputs is not None:
            if isinstance(inputs, dict):
                for n, v in inputs.items():
                    self.get_input_handle(n).copy_from_cpu(v)
            else:
                enforce(
                    len(inputs) == len(self._feed_names),
                    f"expected {len(self._feed_names)} inputs, "
                    f"got {len(inputs)}",
                )
                for n, v in zip(self._feed_names, inputs):
                    self._inputs[n].copy_from_cpu(v)
        feed_vals = []
        for n in self._feed_names:
            v = self._inputs[n].value()
            enforce(v is not None, f"input '{n}' was never set")
            feed_vals.append(np.asarray(v))
        outs = self._execute_feeds(feed_vals)
        results = []
        for n, o in zip(self._fetch_names, outs):
            self._outputs[n]._value = o
            results.append(np.asarray(o))
        return results

    # compatibility alias (reference: ZeroCopyRun)
    def zero_copy_run(self):
        self.run()
        return True

    @staticmethod
    def _cache_key(sig):
        """Cheap bucket key: the resolved kernel mode
        (paddle_tpu/kernels/) joins it (see core/executor.py) — a
        PADDLE_TPU_KERNELS flip must reach the content-addressed tier,
        never a stale per-object executable."""
        from paddle_tpu.kernels import registry as _kernel_registry

        return (sig, _kernel_registry.resolved_mode())

    def _compiled(self, sig):
        """AOT-compile the pruned program for one input-shape bucket,
        through the shared lowering (core/lowering.py): mandatory verifier
        pass, process-wide + persistent compile cache, and single-flight
        dedupe — N clones warming the same bucket concurrently share ONE
        compile instead of racing N duplicate traces (the lock-free
        duplicate-compile window this replaces multiplied under replica
        warmup). The serving hot path still calls a fixed AOT executable:
        committed same-layout args, no per-call jit dispatch."""
        from paddle_tpu.observability import metrics as obs_metrics

        cache_key = self._cache_key(sig)
        reg = obs_metrics.registry()
        with self._cache_lock:
            hit = self._cache.get(cache_key)
            if hit is not None:
                self._cache_stats["hits"] += 1
                reg.counter("predictor_cache_hits_total",
                            "AOT executable cache hits").inc()
                return hit
            self._cache_stats["misses"] += 1
            reg.counter("predictor_cache_misses_total",
                        "AOT executable cache misses (bucket lookups that "
                        "went to the shared lowering)").inc()
        import time as _time

        from paddle_tpu import profiler
        from paddle_tpu.core import lowering

        feed_sig = tuple(
            (n, tuple(s), str(d)) for n, (s, d) in zip(self._feed_names, sig)
        )
        t0 = _time.perf_counter()
        with profiler.RecordEvent("predictor::aot_compile"):
            entry, source = lowering.lower_step(
                self._program, self._scope, feed_sig, self._fetch_names,
                donate=False, label="predictor",
            )
            executable = entry.aot_compile(
                lowering.abstract_signature(entry, feed_sig, self._scope)
            )
        dt = _time.perf_counter() - t0
        if source == "trace":
            # only the single-flight leader counts a compile; waiters,
            # memory-tier hits, and persistent-cache loads don't
            profiler.incr_counter("predictor.aot_compiles")
            reg.histogram("predictor_compile_seconds",
                          "AOT bucket compile latency").observe(dt)
        elif source == "disk":
            profiler.incr_counter("predictor.persistent_cache_hits")
        with self._cache_lock:
            if source == "trace":
                self._cache_stats["compile_s"] += dt
            elif source == "disk":
                self._cache_stats["persistent_hits"] += 1
            self._cache[cache_key] = (executable, entry.scope_names)
        return self._cache[cache_key]

    def cache_stats(self):
        """Compile-cache counters, shared across clones: {hits, misses,
        compile_s, persistent_hits}. A warmed serving fleet holds misses
        constant while hits grow — the hit-rate metric
        ServingEngine.stats() reports; persistent_hits counts buckets a
        cold replica loaded from the compile-cache directory instead of
        compiling."""
        with self._cache_lock:
            return dict(self._cache_stats)

    def _rng_arg(self):
        # the lowered step takes the rng key as an argument (shared 4-arg
        # contract); inference programs are deterministic, so one
        # committed zero key serves every call (lowering.zero_rng_key is
        # flags-aware so the dtype matches the AOT executable's rng aval)
        if self._rng0 is None:
            from paddle_tpu.core.lowering import zero_rng_key

            self._rng0 = zero_rng_key(self._place.jax_device())
        return self._rng0

    def _execute_feeds(self, feed_vals):
        """Shared execution tail for run()/run_batch(): signature,
        compile-cache lookup, device transfer, call. ONE place defines
        the cache-signature format the warmup/bucket machinery matches."""
        import jax

        from paddle_tpu.observability.tracer import trace_scope

        sig = tuple((v.shape, str(v.dtype)) for v in feed_vals)
        executable, scope_names = self._compiled(sig)
        dev = self._place.jax_device()
        with trace_scope("predictor::execute", cat="serving"):
            feed_dev = [jax.device_put(v, dev) for v in feed_vals]
            weights = [self._scope.find_var(n) for n in scope_names]
            fetches, _updates = executable(
                tuple(feed_dev), (), tuple(weights), self._rng_arg()
            )
            return fetches

    # -- batched serving (paddle_tpu/serving drives these) -----------------
    def run_batch(self, feeds):
        """Dict-in/dict-out single-shot run that bypasses the zero-copy
        handles — the serving hot path. Each engine worker owns a clone,
        so nothing here touches shared mutable state (the compile cache
        dict is append-only and shared deliberately)."""
        feed_vals = []
        for n in self._feed_names:
            enforce(n in feeds, f"run_batch feed missing input '{n}'")
            feed_vals.append(np.ascontiguousarray(feeds[n]))
        outs = self._execute_feeds(feed_vals)
        return {n: np.asarray(o) for n, o in zip(self._fetch_names, outs)}

    def _bucket_signature(self, batch, seq):
        """Concrete feed signature for one lattice point: each feed var's
        first -1 dim takes the batch bucket, every later -1 takes the
        length bucket (a fixed-shape var serves as declared)."""
        block = self._program.global_block()
        sig = []
        for n in self._feed_names:
            v = block._find_var_recursive(n)
            enforce(v is not None, f"feed var '{n}' not in program")
            shape, saw_batch = [], False
            for d in v.shape:
                if int(d) != -1:
                    shape.append(int(d))
                elif not saw_batch:
                    shape.append(int(batch))
                    saw_batch = True
                else:
                    enforce(
                        seq is not None,
                        f"feed '{n}' has a variable non-batch dim "
                        f"{list(v.shape)}: set_serving_buckets needs "
                        "seq_lens to warm it",
                    )
                    shape.append(int(seq))
            sig.append((tuple(shape), str(v.dtype)))
        return tuple(sig)

    def warmup(self, buckets=None):
        """Pre-compile every serving bucket so no request ever pays a
        trace (reference: the engine-build-on-first-run latency cliff
        this removes). `buckets` overrides Config.set_serving_buckets.
        Returns [(signature, seconds)] per newly compiled bucket; each
        compile is logged through the profiler event machinery."""
        import time as _time

        from paddle_tpu import profiler

        spec = buckets if buckets is not None else \
            self._config.serving_buckets()
        enforce(
            spec is not None,
            "warmup needs buckets: call Config.set_serving_buckets first",
        )
        seqs = spec["seq_lens"] or (None,)
        compiled = []
        for b in spec["batch_sizes"]:
            for s in seqs:
                sig = self._bucket_signature(b, s)
                if self._cache_key(sig) in self._cache:
                    continue
                t0 = _time.perf_counter()
                with profiler.RecordEvent("predictor::warmup_bucket"):
                    self._compiled(sig)
                compiled.append((sig, _time.perf_counter() - t0))
                profiler.incr_counter("predictor.warmup_buckets")
        return compiled

    # -- management --------------------------------------------------------
    def clone(self):
        """Share weights + compiled executables; independent I/O handles
        (reference: AnalysisPredictor::Clone — thread-per-predictor
        serving)."""
        return Predictor(
            self._config,
            _shared=(self._program, self._feed_names, self._fetch_names,
                     self._scope, self._cache, self._analysis_stats,
                     self._cache_stats, self._cache_lock),
        )

    def get_serialized_program(self):
        """reference: AnalysisPredictor::GetSerializedProgram."""
        return self._program.to_bytes()

    def save_optim_model(self, dirname):
        """Persist the analyzed program + (possibly precision-cast) weights
        (reference: AnalysisPredictor::SaveOptimModel)."""
        os.makedirs(dirname, exist_ok=True)
        desc = json.loads(self._program.to_bytes().decode("utf-8"))
        desc["feed_var_names"] = self._feed_names
        desc["fetch_var_names"] = self._fetch_names
        with open(os.path.join(dirname, "__model__"), "wb") as f:
            f.write(json.dumps(desc).encode("utf-8"))
        from paddle_tpu.io import _write_combined

        block = self._program.global_block()
        arrays = {}
        for n in sorted(self._scope.var_names()):
            v = block._find_var_recursive(n)
            if v is not None and v.persistable:
                arrays[n] = np.asarray(self._scope.find_var(n))
        _write_combined(os.path.join(dirname, "__params__"), arrays)

    def analysis_stats(self):
        """Per-pass statistics from the analysis pipeline (debugging aid)."""
        return dict(self._analysis_stats)

    def clear_intermediate_tensor(self):
        """reference: AnalysisPredictor::ClearIntermediateTensor. XLA owns
        intermediates inside the executable; nothing survives a run."""

    def try_shrink_memory(self):
        """Drop compiled executables for unused shape buckets."""
        self._cache.clear()
        return True


def create_predictor(config):
    """reference: CreatePaddlePredictor<AnalysisConfig> /
    paddle_infer::CreatePredictor."""
    return Predictor(config)
