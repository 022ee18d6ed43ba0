"""Kernel registry: op/composite patterns -> optional Pallas TPU kernels.

The reference ships hand-fused CUDA kernels for the ops its framework
fuses poorly (reference: paddle/fluid/operators/fused/ — multihead
attention, fused embedding+seqpool, fused adam). SURVEY §7 maps that
capability onto this stack as "Pallas kernels behind the op registry":
every op keeps its XLA-composite lowering as the MANDATORY fallback, and
may additionally register a hand-written Pallas kernel here. Selection is
env-gated and joins the compile-cache fingerprint at the
``core/lowering.py`` chokepoint (``kernel_sig()``, the ``layout_sig``
pattern from PR 7), so flipping kernels on or off can never serve a stale
executable.

``PADDLE_TPU_KERNELS`` modes:

* ``auto`` (default) — Pallas kernels compiled for the MXU when the
  backend is a real TPU; the composite fallback everywhere else (Pallas
  interpret mode is a correctness tool, not a production path: on CPU the
  composite IS the fast path).
* ``off`` — composite fallback everywhere, even on TPU (the opt-out; also
  the reference side of every parity gate).
* ``interpret`` — kernels run through the Pallas interpreter on any
  backend. This is how a CPU-only container proves kernel semantics: an
  interpret-mode kernel body traces to plain jax ops, so a kernel written
  as the exact composite primitive sequence is BIT-identical to its
  fallback, and the parity tests assert exactly that.

Registration is the CI contract: every ``KernelSpec`` MUST carry a
``parity_check`` callable — ``register()`` refuses one without it, and
``tests/test_kernels.py`` parametrizes over ``all_specs()``, so a new
kernel cannot land without an interpret-mode parity test (the gate is
enumerated from the registry, not from a hand-maintained list). A Pallas
kernel must also carry ``tpu_cases``: interpret mode accepts programs
Mosaic refuses, so ``tests/test_kernels_tpu_aot.py`` AOT-compiles each
one's compiled path for the v5e, chip-free.

The mode is PROCESS-global (it mirrors an environment variable);
``scoped_mode()`` swaps it for a ``with`` block — tests that lower under
a non-default mode must also clear the compile cache or vary program
content, exactly like the layout_sig landmine.
"""

import os
import threading
from collections import namedtuple

__all__ = [
    "KernelSpec", "register", "get", "all_specs", "has",
    "mode", "resolved_mode", "selected", "probe", "scoped_mode",
    "kernel_sig", "registry_fingerprint", "fallback_counter",
    "latent_chunk_counter", "flash_grid_metrics", "flash_grid_snapshot", "FLASH_KERNELS", "MODE_ENV",
]

MODE_ENV = "PADDLE_TPU_KERNELS"
_MODES = ("auto", "off", "interpret")

#: what a lowering gets back from ``selected()``: whether to run the
#: Pallas body through the interpreter (CPU parity) or compiled (TPU)
Selection = namedtuple("Selection", ["name", "interpret"])


class KernelSpec:
    """One registered kernel (or remat policy) behind the op registry.

    ``op_types``     — op/composite types this kernel can serve (bench
                       probes and the parity gate enumerate these).
    ``parity``       — "bit" (interpret mode must be bit-identical to the
                       composite fallback) or "tolerance" (documented
                       summation-order difference, embedding-dedup-style;
                       the parity check asserts the tolerance both ways).
    ``parity_check`` — zero-arg-plus-rng callable running the interpret
                       parity assertion; REQUIRED (see module docstring).
    ``kind``         — "kernel" (a Pallas lowering) or "policy" (an
                       IR-keyed remat policy: no Pallas body, still
                       enumerated so its bit-identity test is mandatory).
    ``tpu_cases``    — zero-arg callable returning ``[(label, fn,
                       [(shape, dtype), ...]), ...]``: the COMPILED
                       (``interpret=False``) path at realistic shapes.
                       REQUIRED for kind "kernel":
                       tests/test_kernels_tpu_aot.py AOT-compiles every
                       case for a device-less v5e topology, so a kernel
                       that interprets but does not lower for the chip
                       cannot register.
    ``version``      — content version mixed into ``kernel_sig()``:
                       bump when the kernel's numerics change so cached
                       executables retrace.
    """

    __slots__ = ("name", "op_types", "doc", "parity", "parity_check",
                 "kind", "tpu_cases", "version")

    def __init__(self, name, op_types, parity, parity_check, doc="",
                 kind="kernel", tpu_cases=None, version=1):
        if parity not in ("bit", "tolerance"):
            raise ValueError(f"kernel {name}: parity must be 'bit' or "
                             f"'tolerance', got {parity!r}")
        if not callable(parity_check):
            raise ValueError(
                f"kernel {name}: a parity_check callable is required — "
                "every registered kernel must have an interpret-mode "
                "parity test (the CI gate enumerates the registry)"
            )
        if kind == "kernel" and not callable(tpu_cases):
            raise ValueError(
                f"kernel {name}: a tpu_cases callable is required — every "
                "registered Pallas kernel must AOT-compile for the chip "
                "(tests/test_kernels_tpu_aot.py enumerates the registry)"
            )
        self.name = name
        self.op_types = tuple(op_types)
        self.doc = doc
        self.parity = parity
        self.parity_check = parity_check
        self.kind = kind
        self.tpu_cases = tpu_cases
        self.version = int(version)


_specs = {}
_mode_stack = []          # scoped_mode overrides (innermost last)
_mode_lock = threading.Lock()


def register(spec):
    if spec.name in _specs:
        from paddle_tpu.utils.enforce import EnforceError

        raise EnforceError(f"kernel {spec.name} registered twice")
    _specs[spec.name] = spec
    return spec


def get(name):
    return _specs[name]


def has(name):
    return name in _specs


def all_specs():
    return [v for _k, v in sorted(_specs.items())]


def mode():
    """The raw requested mode: innermost ``scoped_mode`` override, else
    the ``PADDLE_TPU_KERNELS`` env var, else ``auto``. Unknown values
    raise — a typo'd mode must not silently disarm (or arm) kernels."""
    with _mode_lock:
        if _mode_stack:
            return _mode_stack[-1]
    raw = os.environ.get(MODE_ENV, "").strip().lower() or "auto"
    if raw not in _MODES:
        from paddle_tpu.utils.enforce import EnforceError

        raise EnforceError(
            f"{MODE_ENV}={raw!r}: unknown mode (want one of {_MODES})"
        )
    return raw


def _on_tpu():
    import jax

    return jax.default_backend() == "tpu"


def resolved_mode():
    """The effective selection for THIS process/backend: "off"
    (composites everywhere), "interpret" (Pallas interpreter), or "tpu"
    (compiled Pallas kernels)."""
    m = mode()
    if m == "off":
        return "off"
    if m == "interpret":
        return "interpret"
    return "tpu" if _on_tpu() else "off"


def selected(name):
    """Selection for one registered kernel under the current mode, or
    None when its composite fallback should run."""
    spec = _specs.get(name)
    if spec is None or spec.kind != "kernel":
        return None
    rm = resolved_mode()
    if rm == "off":
        return None
    return Selection(name, rm == "interpret")


def fallback_counter():
    """``kernel_fallbacks_total``: selected kernels that gave way to their
    composite by geometry (a workset Mosaic cannot tile or VMEM cannot
    hold) or inside a manual-mesh region. chip_smoke.py prints it next to
    the custom-call census so a run cannot pass on composites while
    claiming kernels."""
    from paddle_tpu.observability import metrics as obs_metrics

    return obs_metrics.registry().counter(
        "kernel_fallbacks_total",
        "kernel-eligible ops that ran the composite fallback "
        "(untileable/VMEM-oversized geometry or manual-mesh region)",
    )


def latent_chunk_counter():
    """``latent_chunk_kernel_lowerings_total``: lowered calls of the latent
    chunk kernel (kernels/attention.py ``latent_chunk_attention``), beside
    the fallbacks: one a layer of a compiled chunk program of a latent
    model, none where the loops serve."""
    from paddle_tpu.observability import metrics as obs_metrics

    return obs_metrics.registry().counter(
        "latent_chunk_kernel_lowerings_total",
        "lowered calls of the latent chunk attention kernel (the expanded "
        "form of a prompt chunk's latent attention as one Pallas kernel)",
    )


#: the flash kernels by the ``kernel`` label of their two series
FLASH_KERNELS = ("fwd", "bwd_dkdv", "bwd_dq")


def flash_grid_metrics(kernel, grid_steps, heads):
    """Counted at every lowered call of a flash kernel (``kernel`` is one
    of ``FLASH_KERNELS``), beside the fallbacks:
    ``flash_grid_steps_total{kernel=}`` adds the call's grid size and
    ``flash_heads_per_step{kernel=}`` holds the (batch, head) pairs a grid
    step of the last such call serves (``flash_attention._heads_per_step``)
    — a step whose heads-per-step reads 1 at short sequences is paying for
    its grid, not for its work."""
    from paddle_tpu.observability import metrics as obs_metrics

    reg = obs_metrics.registry()
    labels = {"kernel": kernel}
    reg.counter(
        "flash_grid_steps_total",
        "grid steps of the flash-attention kernels' lowered calls",
        labels=labels,
    ).inc(grid_steps)
    reg.gauge(
        "flash_heads_per_step",
        "(batch, head) pairs one grid step of the last lowered "
        "flash-attention call serves",
        labels=labels,
    ).set(heads)


def flash_grid_snapshot():
    """``{kernel: {"grid_steps", "heads_per_step"}}`` as
    ``flash_grid_metrics`` left them (zeros for a kernel never lowered)."""
    from paddle_tpu.observability import metrics as obs_metrics

    reg = obs_metrics.registry()
    out = {}
    for kernel in FLASH_KERNELS:
        steps = reg.get("flash_grid_steps_total", {"kernel": kernel})
        heads = reg.get("flash_heads_per_step", {"kernel": kernel})
        out[kernel] = {
            "grid_steps": int(steps.value) if steps is not None else 0,
            "heads_per_step": int(heads.value) if heads is not None else 0,
        }
    return out


def probe(name):
    """Would this kernel serve its op right now?"""
    return selected(name) is not None


class scoped_mode:
    """Swap the PROCESS-global kernel mode for a ``with`` block (the env
    var analog for tests). Nestable; restores on exit. NOT thread-local
    by design: engine scheduler threads must observe the same mode as
    the thread that entered the scope."""

    def __init__(self, m):
        if m not in _MODES:
            raise ValueError(f"unknown kernel mode {m!r} (want {_MODES})")
        self._m = m

    def __enter__(self):
        with _mode_lock:
            _mode_stack.append(self._m)
        return self

    def __exit__(self, *exc):
        with _mode_lock:
            _mode_stack.pop()
        return False


def registry_fingerprint():
    """Pure content hash of the mode-selectable kernel set — which
    kernels exist and their numeric versions."""
    return sorted(
        (s.name, s.version) for s in _specs.values() if s.kind == "kernel"
    )


def kernel_sig():
    """What ``core/lowering.py`` joins into the compile-cache program
    fingerprint. None whenever every mode-selectable kernel resolves to
    its composite fallback ("off", or "auto" off-TPU) — so fingerprints
    of kernel-less lowerings stay byte-identical to pre-registry
    revisions and an existing compile-cache directory does not cold-miss
    on deploy (the layout_sig discipline)."""
    rm = resolved_mode()
    if rm == "off":
        return None
    return [rm, registry_fingerprint()]
