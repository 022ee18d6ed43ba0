"""Global flag registry with environment-variable bridge.

TPU-native analog of the reference's gflags registry
(reference: paddle/fluid/platform/flags.cc:33-470) and the Python
``__bootstrap__`` env bridge (reference: python/paddle/fluid/__init__.py:136).
Flags may be set via ``FLAGS_<name>`` environment variables or at runtime via
``flags.<name> = value`` / ``set_flags({...})``.
"""

import os


class _FlagRegistry:
    def __init__(self):
        object.__setattr__(self, "_defs", {})
        object.__setattr__(self, "_values", {})

    def define(self, name, default, help=""):
        self._defs[name] = (type(default), default, help)
        env = os.environ.get("FLAGS_" + name)
        if env is not None:
            self._values[name] = _parse(type(default), env)
        else:
            self._values[name] = default

    def __getattr__(self, name):
        try:
            return self._values[name]
        except KeyError:
            raise AttributeError(f"undefined flag FLAGS_{name}")

    def __setattr__(self, name, value):
        if name not in self._defs:
            raise AttributeError(f"undefined flag FLAGS_{name}")
        ty = self._defs[name][0]
        self._values[name] = _parse(ty, value) if isinstance(value, str) else ty(value)

    def get_all(self):
        return dict(self._values)


def _parse(ty, s):
    if ty is bool:
        return s if isinstance(s, bool) else str(s).lower() in ("1", "true", "yes")
    return ty(s)


flags = _FlagRegistry()


def define_flag(name, default, help=""):
    flags.define(name, default, help)


def set_flags(d):
    for k, v in d.items():
        setattr(flags, k.replace("FLAGS_", ""), v)


def get_flags(names=None):
    all_flags = flags.get_all()
    if names is None:
        return all_flags
    if isinstance(names, str):
        names = [names]
    return {n: all_flags[n.replace("FLAGS_", "")] for n in names}


# Core flags, mirroring the categories in the reference's flags.cc.
define_flag("check_nan_inf", False, "check every op output for NaN/Inf")
define_flag("benchmark", False, "block after each op for timing")
define_flag("use_donation", True, "donate parameter buffers into compiled steps")
define_flag("amp_dtype", "bfloat16", "low-precision dtype for AMP on TPU")
define_flag(
    "rng_impl", "threefry",
    "PRNG implementation for stateful ops (dropout etc.): 'threefry' is "
    "jax's default splittable generator; 'rbg' uses the TPU's hardware RNG "
    "path - much cheaper bits, same distribution, different stream",
)
define_flag(
    "dgc_sparse_exchange", True,
    "DGCMomentumOptimizer + data-parallel CompiledProgram: run the block "
    "per-shard and exchange top-k (index, value) pairs instead of dense "
    "gradients; 0 keeps the fused dense form",
)
define_flag(
    "sparse_embedding_update", True,
    "fuse lookup_table_grad + sgd into a row-sparse update (SelectedRows "
    "analog): the [V, D] dense embedding gradient never materializes",
)
define_flag(
    "static_diagnostics", "",
    "opt-in static-analysis stages run ahead of the mandatory verifier "
    "in core/lowering.py: comma list of 'shapes', 'sharding', 'memory', "
    "'cost' (or 'all'). Shape/dtype errors then fail at lowering time "
    "with op attribution instead of exploding inside jit; sharding adds "
    "the collective-cost report, memory the peak-HBM estimate, cost the "
    "roofline step-time/MFU prediction plus the hierarchical-collective "
    "linter (errors when axis_tags declare a 'dcn' axis)",
)
define_flag(
    "collective_budget_kb", 0,
    "per-collective byte budget (KB) for the static sharding linter "
    "when the 'sharding' diagnostic stage is on; 0 disables the budget "
    "gate (the report still runs)",
)
define_flag(
    "cost_machine", "tpu-v4-8",
    "machine model for the 'cost' static diagnostic stage "
    "(analysis/cost.py MACHINES: tpu-v4-8, tpu-v5e-8, tpu-v5p-8, "
    "tpu-v6e-8, cpu-host)",
)
