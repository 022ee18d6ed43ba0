"""Counts what was compiled or loaded, from jax's own monitoring events and
the repo's counters (the arithmetic of ``chip_smoke.py Compiles``, copied):
inside a measured window all of them must stand still."""

import collections


class Compiles:
    REPO_COUNTERS = ("lowering_jit_total", "executor_cache_misses_total")
    JAX_EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self._events = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, secs, **kw: self._events.update([name]))

    def snapshot(self):
        from paddle_tpu.observability import metrics

        reg = metrics.registry()
        out = {}
        for name in self.REPO_COUNTERS:
            m = reg.get(name)
            out[name] = int(m.value) if m is not None else 0
        out["xla_backend_compiles_or_loads"] = self._events[self.JAX_EVENT]
        return out

    @staticmethod
    def moved(before, after):
        """How many compilations, traces or loads fell between two
        snapshots; 0 in a steady window."""
        return sum(after[k] - before[k] for k in after)
