"""Device / place management.

TPU-native analog of the reference's Place / DeviceContextPool
(paddle/fluid/platform/place.h, device_context.h).  On TPU+XLA there are no
streams or contexts to manage — this module owns device discovery, the
current-device notion, and host/device transfer helpers.
"""

from __future__ import annotations

import functools
from typing import List, Optional

import jax


def local_devices(platform: Optional[str] = None):
    """Devices of ``platform``, or of jax's default platform (which
    ``JAX_PLATFORMS`` chooses: the test suite and the CPU simulation set it
    to ``cpu``).  A platform that was asked for and is not there is an
    error (``RuntimeError`` from jax) — never a quiet move to whatever the
    default platform happens to be.
    """
    return jax.devices(platform) if platform else jax.devices()


class Place:
    """String-y device handle (``paddle.CUDAPlace``-family parity).

    Accepts ``"tpu"``, ``"tpu:0"``, ``"cpu"``, ``"gpu:1"``.
    """

    def __init__(self, spec: str = "tpu:0"):
        if ":" in spec:
            kind, idx = spec.split(":")
            self.kind, self.index = kind, int(idx)
        else:
            self.kind, self.index = spec, 0

    def __repr__(self):
        return f"Place({self.kind}:{self.index})"

    def __eq__(self, other):
        other = Place(other) if isinstance(other, str) else other
        return (self.kind, self.index) == (other.kind, other.index)

    def __hash__(self):
        return hash((self.kind, self.index))

    def is_cpu_place(self):
        return self.kind == "cpu"

    def jax_device(self):
        devs = _devices_of_kind(self.kind)
        return devs[self.index % len(devs)]


_current: Optional[Place] = None


@functools.lru_cache(maxsize=None)
def _platform_names() -> List[str]:
    return [d.platform for d in jax.devices()]


def _devices_of_kind(kind: str):
    if kind == "cpu":
        return local_devices("cpu")
    # "tpu"/"gpu"/"xpu" → default platform accelerators
    return jax.devices()


def set_device(spec: str) -> Place:
    """``paddle.set_device`` parity."""
    global _current
    _current = Place(spec) if isinstance(spec, str) else spec
    return _current


def get_device() -> str:
    """``paddle.get_device`` parity — returns e.g. ``"tpu:0"``."""
    p = _get_place()
    return f"{p.kind}:{p.index}"


def _get_place() -> Place:
    global _current
    if _current is None:
        plat = jax.default_backend()
        _current = Place(f"{plat}:0")
    return _current


def device_count() -> int:
    """Number of local accelerator devices (``paddle.device.cuda.device_count`` parity)."""
    return jax.local_device_count()


def is_compiled_with_cuda() -> bool:  # API parity; always False on TPU builds
    return False


def is_compiled_with_tpu() -> bool:
    return jax.default_backend() == "tpu"


def synchronize() -> None:
    """Block until all dispatched work completes (``paddle.device.synchronize``)."""
    (jax.device_put(0.0) + 0).block_until_ready()
