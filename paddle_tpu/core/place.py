"""Device places.

Parity: paddle/fluid/platform/place.h — Place/CPUPlace/CUDAPlace. On TPU the
native place is TPUPlace; CUDAPlace is accepted as an alias so reference
recipes run unchanged with place=TPUPlace(0) (or even CUDAPlace(0), which we
map onto the available accelerator).

Unlike the reference there are no per-place DeviceContexts with streams:
XLA owns scheduling. A Place here just selects a jax.Device.
"""

import jax


class Place:
    _kind = "undefined"

    def __init__(self, device_id=0):
        self.device_id = int(device_id)

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def jax_device(self):
        """Resolve to the concrete jax.Device this place names. An id
        past the last device raises — a clamp would quietly run
        everything on one chip."""
        devs = jax.devices("cpu") if self._kind == "cpu" else jax.devices()
        if not 0 <= self.device_id < len(devs):
            raise ValueError(
                f"{self!r} names device {self.device_id} but jax has "
                f"{len(devs)} {devs[0].platform} device(s)")
        return devs[self.device_id]


class CPUPlace(Place):
    _kind = "cpu"

    def __init__(self):
        super().__init__(0)

    def __repr__(self):
        return "CPUPlace"


class TPUPlace(Place):
    _kind = "accelerator"


class CUDAPlace(TPUPlace):
    """Alias: reference recipes using CUDAPlace(0) get the accelerator."""


class CUDAPinnedPlace(CPUPlace):
    pass


def is_compiled_with_cuda():
    return False


def is_compiled_with_tpu():
    return any(d.platform == "tpu" for d in jax.devices())


def tpu_places(device_ids=None):
    """Parity with fluid.cuda_places(): list of accelerator places."""
    n = len(jax.devices())
    ids = range(n) if device_ids is None else device_ids
    return [TPUPlace(i) for i in ids]


cuda_places = tpu_places


def cpu_places(device_count=None):
    """Parity: fluid.cpu_places — None reads CPU_NUM env (default 1)."""
    import os
    if device_count is None:
        device_count = int(os.environ.get("CPU_NUM", 1))
    return [CPUPlace() for _ in range(device_count)]


def cuda_pinned_places(device_count=None):
    """Parity: fluid.cuda_pinned_places. Pinned host staging is managed by
    the runtime (the C++ prefetch ring + XLA's transfer manager), so these
    are plain host places."""
    n = device_count if device_count else 1
    return [CUDAPinnedPlace() for _ in range(n)]
