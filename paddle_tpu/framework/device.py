"""Device / Place abstraction.

Reference: paddle/fluid/platform/place.h (CPUPlace/CUDAPlace/...),
python/paddle/device/__init__.py:276 (set_device). TPU-native: a Place wraps a
jax.Device; there are no streams or per-device contexts to manage — XLA/PJRT owns
scheduling. We keep a process-global current place used by creation ops.
"""
from __future__ import annotations

import functools
import warnings

import jax


@functools.lru_cache(maxsize=None)
def _devices_of(kind: str):
    """This process's devices of one platform (its backends do not change
    once initialised, so the answer is cached).

    An accelerator place on a machine without one (CPU-only CI, reference
    scripts that say set_device("gpu")) still resolves, to the default
    backend — with one loud warning per kind. Code that must be on a chip
    (chip_smoke.py, benchmark/) checks the arrays' devices, never this."""
    try:
        return tuple(jax.devices(kind))
    except RuntimeError:   # "Unknown backend tpu. Available backends ..."
        warnings.warn(
            f"no {kind!r} device in this process: places of that kind "
            f"resolve to the default backend ({jax.default_backend()!r})",
            RuntimeWarning, stacklevel=3)
        return tuple(jax.devices())


class Place:
    """Tagged device identity. Compares by (kind, index)."""

    kind = "unknown"

    def __init__(self, index: int = 0):
        self.index = int(index)

    @property
    def jax_device(self) -> jax.Device:
        devs = _devices_of(self.kind)
        if self.index >= len(devs):
            raise ValueError(
                f"{self!r}: this process has {len(devs)} such device(s)")
        return devs[self.index]

    def __eq__(self, other):
        return isinstance(other, Place) and (self.kind, self.index) == (other.kind, other.index)

    def __hash__(self):
        return hash((self.kind, self.index))

    def __repr__(self):
        return f"Place({self.kind}:{self.index})"


class CPUPlace(Place):
    kind = "cpu"


class TPUPlace(Place):
    kind = "tpu"


class CUDAPlace(Place):
    """Accepted for API compatibility; resolves to the accelerator backend."""

    kind = "tpu"


class XPUPlace(Place):
    """Accepted for API compatibility; resolves to the accelerator backend."""

    kind = "tpu"


@functools.lru_cache(maxsize=None)
def _default_place() -> Place:
    """The place creation ops use until set_device(): the default
    backend's first device, named for what it is."""
    return TPUPlace(0) if jax.default_backend() == "tpu" else CPUPlace(0)


_CURRENT: list = []


def set_device(device) -> Place:
    """paddle.set_device('tpu') / 'cpu' / 'tpu:0'."""
    place = _parse(device)
    _CURRENT[:] = [place]
    return place


def get_device() -> str:
    p = current_place()
    return f"{p.kind}:{p.index}"


def current_place() -> Place:
    if _CURRENT:
        return _CURRENT[0]
    return _default_place()


def _parse(device) -> Place:
    if isinstance(device, Place):
        return device
    if isinstance(device, jax.Device):
        cls = TPUPlace if device.platform == "tpu" else CPUPlace
        return cls(device.id)
    if not isinstance(device, str):
        raise ValueError(f"Cannot parse device {device!r}")
    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    name = name.lower()
    if name in ("tpu", "gpu", "cuda", "xpu", "npu", "ipu", "mlu"):
        return TPUPlace(idx)
    if name == "cpu":
        return CPUPlace(idx)
    raise ValueError(f"Unknown device {device!r}")


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def device_count() -> int:
    return len(jax.devices())
