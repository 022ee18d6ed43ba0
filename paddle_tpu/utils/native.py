"""Native-library loader: compile-on-first-use C++ components.

The reference ships its native runtime prebuilt (pybind11 `core` module,
reference: paddle/fluid/pybind/pybind.cc); here each native component under
csrc/ is a single translation unit compiled to a shared library on first use
with the system toolchain and cached next to its source. Bindings are ctypes
(no pybind11 in this image). Callers must degrade gracefully if no compiler
is present — every native component keeps a pure-Python fallback.
"""

import ctypes
import hashlib
import os
import subprocess
import threading

_CSRC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "csrc",
)
_lock = threading.Lock()
_cache = {}


class NativeBuildError(RuntimeError):
    pass


def load_native(component, source=None, extra_flags=()):
    """Build (if stale) and dlopen csrc/<component>/lib<component>.so.
    Returns a ctypes.CDLL, or raises NativeBuildError."""
    with _lock:
        if component in _cache:
            return _cache[component]
        src = source or os.path.join(_CSRC, component, f"{component}.cc")
        out = os.path.join(_CSRC, component, f"lib{component}.so")
        if not os.path.exists(src):
            raise NativeBuildError(f"no source for native component {component}")
        # staleness is keyed on a hash of the source and flags stored
        # beside the .so, not on mtimes: a copy of the tree (the chip
        # tool's, a fresh checkout over old binaries) resets mtimes and
        # would make a stale binary look fresh
        with open(src, "rb") as f:
            want = hashlib.sha256(
                f.read() + "\0".join(extra_flags).encode()).hexdigest()
        stamp = out + ".srchash"
        have = None
        if os.path.exists(out) and os.path.exists(stamp):
            with open(stamp) as f:
                have = f.read().strip()
        if have != want:
            # compile to a per-process temp and rename atomically: concurrent
            # launch_procs workers may race to build the same component, and
            # dlopen of a half-written .so is a crash
            tmp = f"{out}.{os.getpid()}.tmp"
            # extra_flags go AFTER the source so -l libraries resolve
            # symbols the object actually references (link order matters)
            cmd = [
                "g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
                "-o", tmp, src, *extra_flags,
            ]
            try:
                proc = subprocess.run(
                    cmd, capture_output=True, text=True, timeout=300
                )
            except (OSError, subprocess.TimeoutExpired) as e:
                raise NativeBuildError(f"g++ unavailable: {e}") from e
            if proc.returncode != 0:
                raise NativeBuildError(
                    f"native build of {component} failed:\n{proc.stderr[-2000:]}"
                )
            os.replace(tmp, out)
            with open(tmp, "w") as f:
                f.write(want)
            os.replace(tmp, stamp)
        lib = ctypes.CDLL(out)
        _cache[component] = lib
        return lib
