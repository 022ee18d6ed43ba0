"""Content-addressed compile cache: one fingerprint, three tiers.

The reference keeps one ExecutorPrepareContext cache per executor
(reference: paddle/fluid/framework/executor.cc) — in-memory, per-object,
gone on restart. Here the unit of caching is the LOWERED STEP (the whole
block compiled to one XLA computation), keyed by a content-addressed
**program fingerprint** so train (Executor), data-parallel train
(CompiledProgram), and serving (Predictor) share entries, and a restarted
process re-enters its step without a retrace:

- tier 1: a process-wide in-memory map fingerprint -> LoweredStep, shared
  by every Executor/Predictor/CompiledProgram in the process;
- tier 2: an on-disk persistent cache (``<cache_dir()>/ptcc``) holding
  ``jax.export``-serialized StableHLO, written atomically with a CRC32
  like incubate/checkpoint.py — a corrupt or truncated entry is
  quarantined and silently falls back to a fresh trace, never a crash or
  a wrong answer;
- tier 3: XLA's own persistent compilation cache (``cache_dir()`` itself)
  so even the StableHLO->executable compile is reused across processes.

Placement is decided from outside, by the standard variable:
``JAX_COMPILATION_CACHE_DIR`` set -> that directory holds everything (jax
reads it natively; this module never sets ``jax_compilation_cache_dir``
on that path); unset -> ``<checkout>/.jax_cache``, a fixed path computed
from ``__file__`` (the directory is part of XLA's cache key, so one that
moves never hits). On the CPU backend the default directory is not
used: see ``enabled()``.

The fingerprint covers everything that can change the compiled artifact:
the serialized block desc, feed/fetch signature, scope-input
shapes/dtypes, the donation plan, the lowering-relevant flags, the mesh
and sharding specs, and the jax version + backend — so a jax upgrade or a
backend switch misses cleanly instead of deserializing a stale module.

Concurrent lowerings of the SAME fingerprint are single-flighted: the
first caller traces (or loads), the rest wait and share the result — the
replica-warmup compile storm (N clones x same bucket) collapses to one
compile.
"""

import hashlib
import json
import os
import struct
import threading
import time
import zlib

from paddle_tpu.observability import lockdep as _lockdep

__all__ = [
    "program_fingerprint",
    "cache_dir",
    "enabled",
    "get_or_build",
    "load_persistent",
    "store_persistent",
    "clear_memory_cache",
    "stats",
]

_MAGIC = b"PTCC1\n"
_ENTRY_SUFFIX = ".ptcc"

# tier-1 memory cache + single-flight registry (process-wide). LRU with
# a cap: unlike the old per-Executor/Predictor caches (freed with their
# owner), this map outlives every caller — a model-cycling server must
# not accumulate executables forever. Eviction only costs a recompile
# (or a disk-tier reload).
_MEM_CAP = 512
_mem = {}  # insertion/use-ordered: dict move-to-end via pop+reinsert
_inflight = {}
_lock = _lockdep.named_lock("compile.cache")

# lazily-created metric handles: avoid registering registry series in
# processes that never build an entry (the lockdep import above pulls
# the observability package at module import, so availability is no
# longer the concern — series hygiene is)
_counters = {}


def _counter(name, help_):
    c = _counters.get(name)
    if c is None:
        from paddle_tpu.observability import metrics as obs_metrics

        c = obs_metrics.registry().counter(name, help_)
        _counters[name] = c
    return c


_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def _placed_dir():
    """Where the environment placed the cache ('' when it did not). Read
    per call (not latched at import) so the answer is the environment's,
    not this module's."""
    return os.environ.get(_DIR_ENV, "").strip()


def cache_dir():
    """The persistent cache directory: ``$JAX_COMPILATION_CACHE_DIR`` when
    set, else ``<checkout>/.jax_cache``."""
    return _placed_dir() or _DEFAULT_DIR


def enabled():
    """Whether anything is persisted. jax's own switch
    (``JAX_ENABLE_COMPILATION_CACHE``) governs tiers 2 and 3 alike. Past
    it, an accelerator backend always persists (compiles there take
    minutes), while the CPU backend persists only where
    ``JAX_COMPILATION_CACHE_DIR`` places the cache: CPU compiles are
    short, this jaxlib's XLA:CPU loader logs kilobytes on every load, and
    tier-1's trace-count assertions must not meet a warm checkout."""
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return False
    return bool(_placed_dir()) or jax.default_backend() != "cpu"


_xla_cache_wired = False


def _wire_xla_cache():
    """Let XLA's persistent cache keep every executable (its defaults skip
    sub-second compiles), and — only when the environment did not place
    it — point it at the default directory."""
    global _xla_cache_wired
    if _xla_cache_wired:
        return
    _xla_cache_wired = True
    import jax

    if not _placed_dir():
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ---------------------------------------------------------------------------
# fingerprint
# ---------------------------------------------------------------------------

#: flags that change the emitted computation (ops/ lowering rules read
#: these); check_nan_inf/benchmark route to the interpreted path and never
#: reach the compiled cache
_LOWERING_FLAGS = (
    "use_donation",
    "amp_dtype",
    "rng_impl",
    "sparse_embedding_update",
    "dgc_sparse_exchange",
)


def _mesh_desc(mesh):
    if mesh is None:
        return None
    return {
        "axis_names": list(mesh.axis_names),
        "shape": list(mesh.devices.shape),
        "device_kinds": sorted(
            {getattr(d, "device_kind", str(d.platform)) for d in mesh.devices.flat}
        ),
    }


def program_fingerprint(
    program,
    feed_sig,
    fetch_names,
    scope_sig=(),
    *,
    donate=True,
    mesh=None,
    sharding_sig=None,
    layout_sig=None,
    kernel_sig=None,
    extra=(),
):
    """Content-addressed identity of one lowered step.

    ``feed_sig``/``scope_sig`` are (name, shape, dtype) tuples;
    ``sharding_sig`` any JSON-able description of the partition specs;
    ``layout_sig`` the SpecLayout registry fingerprint when placement
    came from the canonical sharding layer (parallel/spec_layout.py) —
    editing a role's spec must retrace even though the per-step
    sharding_sig already covers the RESOLVED specs (the layout also owns
    future placement of vars this step does not touch, and two processes
    with the same layout must agree on the fingerprint without resolving
    first). The jax version, the backend and ``lowering.LOWERING_VERSION``
    (the op lowerings' hand-kept version: the Program's bytes do not say
    what a lowering emits for them) are always mixed in: a version bump
    or a backend switch invalidates every persisted entry (fall back to
    retrace — never a wrong answer from a stale module)."""
    import jax

    from paddle_tpu.core import lowering
    from paddle_tpu.utils.flags import flags

    payload = {
        "ir": None,  # filled below as raw bytes, hashed separately
        "lowering": lowering.LOWERING_VERSION,
        "feed_sig": [[n, list(s), str(d)] for n, s, d in feed_sig],
        "fetch": list(fetch_names),
        "scope_sig": [[n, list(s), str(d)] for n, s, d in scope_sig],
        "donate": bool(donate),
        "flags": {f: getattr(flags, f) for f in _LOWERING_FLAGS},
        "mesh": _mesh_desc(mesh),
        "shardings": sharding_sig,
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "extra": list(extra),
    }
    if layout_sig is not None:
        # added only when a registry drives placement, so fingerprints of
        # layout-less lowerings (everything the persistent tier holds)
        # are byte-identical to pre-registry revisions — a deploy of this
        # code does not cold-miss an existing cache directory
        payload["layout"] = layout_sig
    if kernel_sig is not None:
        # same discipline for the Pallas kernel registry
        # (paddle_tpu/kernels/): None whenever every kernel resolves to
        # its composite fallback, so kernel-less fingerprints stay
        # byte-identical to pre-registry revisions; any active kernel
        # selection (mode x registry content) retraces cleanly
        payload["kernels"] = kernel_sig
    h = hashlib.sha256()
    h.update(program.to_bytes())
    h.update(b"\0")
    h.update(json.dumps(payload, sort_keys=True, default=str).encode("utf-8"))
    return h.hexdigest()


# ---------------------------------------------------------------------------
# tier 2: on-disk entries (atomic write + CRC, checkpoint.py discipline)
# ---------------------------------------------------------------------------


def _entry_path(fingerprint):
    return os.path.join(cache_dir(), "ptcc", fingerprint + _ENTRY_SUFFIX)


def store_persistent(fingerprint, header, payload):
    """Atomically persist one serialized executable. ``header`` is a
    JSON-able dict (plan lists, versions); ``payload`` the jax.export
    bytes. Layout: MAGIC | u32 header_len | header JSON | payload, with
    the payload CRC32 + length recorded in the header so truncation and
    bit-rot are detected before deserialization. Best effort: any IO
    failure leaves the cache cold, never breaks the step."""
    try:
        final = _entry_path(fingerprint)
        os.makedirs(os.path.dirname(final), exist_ok=True)
        header = dict(header)
        header["fingerprint"] = fingerprint
        header["payload_crc32"] = zlib.crc32(payload) & 0xFFFFFFFF
        header["payload_len"] = len(payload)
        header["created"] = time.time()
        hbytes = json.dumps(header, sort_keys=True).encode("utf-8")
        tmp = final + f".tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(_MAGIC)
            f.write(struct.pack(">I", len(hbytes)))
            f.write(hbytes)
            f.write(payload)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, final)
        _counter("compile_cache_persistent_stores_total",
                 "persisted compile-cache entries written").inc()
        return True
    except OSError:
        _counter("compile_cache_persistent_errors_total",
                 "persistent compile-cache IO/corruption events").inc()
        return False


def _quarantine(path):
    """Keep the bad bytes for forensics, out of the lookup path (the
    checkpoint.py ``*.corrupt`` convention)."""
    try:
        os.replace(path, path + ".corrupt")
    except OSError:
        pass


def load_persistent(fingerprint):
    """Load one entry; returns (header, payload) or None. A missing file
    is a plain miss; a corrupt/truncated/mismatched one is quarantined
    and reported as a miss — the caller falls back to a fresh trace."""
    path = _entry_path(fingerprint)
    if not os.path.exists(path):
        return None
    try:
        with open(path, "rb") as f:
            magic = f.read(len(_MAGIC))
            if magic != _MAGIC:
                raise ValueError("bad magic")
            (hlen,) = struct.unpack(">I", f.read(4))
            header = json.loads(f.read(hlen).decode("utf-8"))
            payload = f.read()
        if header.get("fingerprint") != fingerprint:
            raise ValueError("fingerprint mismatch")
        if len(payload) != header.get("payload_len"):
            raise ValueError(
                f"payload is {len(payload)} bytes, header says "
                f"{header.get('payload_len')} (torn write)"
            )
        if (zlib.crc32(payload) & 0xFFFFFFFF) != header.get("payload_crc32"):
            raise ValueError("payload CRC mismatch")
        return header, payload
    except (OSError, ValueError, KeyError, struct.error,
            json.JSONDecodeError) as e:
        _counter("compile_cache_persistent_errors_total",
                 "persistent compile-cache IO/corruption events").inc()
        import logging

        logging.getLogger("paddle_tpu.compile_cache").warning(
            "quarantining corrupt compile-cache entry %s (%s); retracing",
            path, e,
        )
        _quarantine(path)
        return None


# ---------------------------------------------------------------------------
# tier 1 + single-flight
# ---------------------------------------------------------------------------


class _Flight:
    __slots__ = ("event", "result", "exc")

    def __init__(self):
        self.event = threading.Event()
        self.result = None
        self.exc = None


def get_or_build(fingerprint, build):
    """Memory-cache lookup with single-flight build.

    Returns (entry, source) where source is "memory" or whatever
    ``build()`` reported for the entry it produced ("disk"/"trace" — the
    entry's own ``source`` attribute). Concurrent callers with the same
    fingerprint share ONE ``build()``; distinct fingerprints build in
    parallel. A failed build propagates its exception to every waiter and
    leaves the cache cold (the next call retries)."""
    if enabled():
        _wire_xla_cache()
    while True:
        with _lock:
            entry = _mem.pop(fingerprint, None)
            if entry is not None:
                _mem[fingerprint] = entry  # LRU touch: newest position
                return entry, "memory"
            flight = _inflight.get(fingerprint)
            if flight is None:
                flight = _Flight()
                _inflight[fingerprint] = flight
                leader = True
            else:
                leader = False
        if leader:
            try:
                entry = build()
            except BaseException as e:
                with _lock:
                    _inflight.pop(fingerprint, None)
                flight.exc = e
                flight.event.set()
                raise
            with _lock:
                _mem[fingerprint] = entry
                while len(_mem) > _MEM_CAP:
                    _mem.pop(next(iter(_mem)))  # evict least recently used
                _inflight.pop(fingerprint, None)
            flight.result = entry
            flight.event.set()
            return entry, getattr(entry, "source", "trace")
        flight.event.wait()
        if flight.exc is not None:
            raise flight.exc
        if flight.result is not None:
            return flight.result, "memory"
        # leader failed between registry pop and event set: retry


def clear_memory_cache():
    """Drop tier 1 (tests; also frees executables for long-lived
    processes that served many shapes)."""
    with _lock:
        _mem.clear()


def stats():
    with _lock:
        return {"memory_entries": len(_mem), "inflight": len(_inflight)}
