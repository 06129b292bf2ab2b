"""Device placement (ref: paddle/phi/common/place.h).

The reference keys kernels and allocations by ``phi::Place`` (CPUPlace/GPUPlace/...).
On TPU the device runtime is PJRT behind jax; a Place here names a jax device and
``set_device`` steers where eager ops place their outputs via jax's default-device.
"""
from __future__ import annotations

import jax


class Place:
    """Base place. Identifies a device type and an index."""

    device_type: str = "undefined"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (isinstance(other, Place)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def jax_device(self):
        """The jax device this place names. A place whose backend is not
        on this machine (a TPUPlace without a TPU) or whose index is past
        the last device raises: no other device stands in for it."""
        try:
            devs = jax.devices(self.device_type)
        except RuntimeError as e:
            raise RuntimeError(
                f"{self!r}: this machine has no {self.device_type!r} "
                f"backend (jax: {e})") from e
        if not 0 <= self.device_id < len(devs):
            raise RuntimeError(
                f"{self!r}: only {len(devs)} {self.device_type!r} "
                f"device(s) here")
        return devs[self.device_id]


class CPUPlace(Place):
    device_type = "cpu"


class TPUPlace(Place):
    device_type = "tpu"


class CustomPlace(Place):
    def __init__(self, device_type: str, device_id: int = 0):
        super().__init__(device_id)
        self.device_type = device_type


# GPU alias for API parity: scripts that say "gpu" run on the accelerator present.
class GPUPlace(Place):
    device_type = "tpu"


CUDAPlace = GPUPlace

_current_place: Place | None = None


def _dev_kind(d) -> str:
    """The device's platform string: "tpu" or "cpu" on this installation."""
    return d.platform.lower()


def _default_place() -> Place:
    """A place on the default backend's first device."""
    return (CPUPlace if jax.default_backend() == "cpu" else TPUPlace)(0)


def get_device() -> str:
    p = _current_expected_place()
    return f"{p.device_type}:{p.device_id}"


def set_device(device: str) -> Place:
    """Set the global default device, e.g. 'tpu', 'tpu:0', 'cpu', 'gpu:0'."""
    global _current_place
    if isinstance(device, Place):
        _current_place = device
        return device
    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    name = name.lower()
    if name in ("tpu", "gpu", "cuda", "xpu", "npu"):
        _current_place = TPUPlace(idx)
    elif name == "cpu":
        _current_place = CPUPlace(idx)
    else:
        _current_place = CustomPlace(name, idx)
    return _current_place


def _current_expected_place() -> Place:
    global _current_place
    if _current_place is None:
        _current_place = _default_place()
    return _current_place


def is_compiled_with_cuda() -> bool:  # parity shim
    return False


def is_compiled_with_tpu() -> bool:
    return jax.default_backend() == "tpu"


def device_count() -> int:
    return jax.device_count()


def is_compiled_with_xpu() -> bool:  # parity shim
    return False


def is_compiled_with_rocm() -> bool:  # parity shim
    return False


def is_compiled_with_custom_device(device_type: str = None) -> bool:
    """The TPU backend registers through PJRT — the plugin mechanism the
    reference's custom-device API describes."""
    if device_type is None:
        return True
    return device_type.lower() == "tpu"
