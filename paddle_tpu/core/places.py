"""Device identity ("Place") and device discovery.

TPU-native analog of the reference's Place variant
(reference: paddle/fluid/platform/place.h:79 — CUDAPlace/CPUPlace/
CUDAPinnedPlace) with TPUPlace replacing CUDAPlace, and of device discovery in
``InitDevices`` (reference: paddle/fluid/platform/init.cc:116). Discovery here
goes through the PJRT client that jax exposes rather than the CUDA driver.
"""


class Place:
    _kind = "undefined"

    def __init__(self, device_id=0):
        self.device_id = device_id

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((self._kind, self.device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"


class CPUPlace(Place):
    _kind = "cpu"

    def __init__(self):
        super().__init__(0)

    def jax_device(self):
        import jax

        # local_devices: in a multi-controller job jax.devices() lists every
        # process's devices; an executor must target one THIS process owns
        return jax.local_devices(backend="cpu")[0]


class TPUPlace(Place):
    """One TPU chip, identified by its index in the local PJRT device list.

    The platform is never degraded: with no local TPU, ``jax_device()``
    raises — unless the process was explicitly pinned to the CPU
    (``JAX_PLATFORMS=cpu``, the one way to ask for it; tier-1 and the
    virtual 8-device mesh tests do), where ``TPUPlace(i)`` is virtual CPU
    device ``i``."""

    _kind = "tpu"

    def jax_device(self):
        devs = _tpu_devices()
        if self.device_id >= len(devs):
            raise RuntimeError(
                f"{self!r}: this process has {len(devs)} local "
                f"{devs[0].platform} device(s)"
            )
        return devs[self.device_id]


def _tpu_devices():
    """Local devices a ``TPUPlace`` may name (see its docstring)."""
    import jax

    # local only: a multi-controller peer's devices are not device_put
    # targets here
    devs = jax.local_devices()
    tpus = [d for d in devs if d.platform == "tpu"]
    if tpus:
        return tpus
    if jax.config.jax_platforms == "cpu":
        return devs
    raise RuntimeError(
        "no TPU among this process's devices "
        f"({sorted({d.platform for d in devs})}) and it is not pinned to "
        "the CPU: set JAX_PLATFORMS=cpu to run on the CPU on purpose"
    )


def is_compiled_with_tpu():
    return True


def tpu_device_count():
    return len(_tpu_devices())


def get_all_places():
    return [TPUPlace(i) for i in range(tpu_device_count())]
