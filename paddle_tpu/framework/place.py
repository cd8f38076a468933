"""Places (devices).

TPU-native analog of the reference's Place hierarchy
(ref: paddle/phi/common/place.h, python/paddle/device/__init__.py).
A Place wraps a jax.Device; TPUPlace is the first-class accelerator.
"""
import warnings

import jax


class Place:
    """Base place. Compares by device kind + index."""

    _kind = "undefined"

    def __init__(self, device_id=0):
        self._device_id = int(device_id)

    def get_device_id(self):
        return self._device_id

    @property
    def jax_device(self):
        devs = [d for d in jax.devices() if d.platform == self._kind]
        if not devs:
            # No device of this kind: the default backend's devices stand
            # in, so reference code that names an accelerator place still
            # runs in CPU tests — said out loud, because get_device() and
            # chip.require_tpu() tell where the program runs; this does
            # not.
            devs = jax.devices()
            if self._kind == "tpu":
                warnings.warn(
                    f"{self!r} named but jax has no tpu device; using "
                    f"{devs[0].platform}:{self._device_id % len(devs)}",
                    RuntimeWarning, stacklevel=2)
        return devs[self._device_id % len(devs)]

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self._kind == other._kind
            and self._device_id == other._device_id
        )

    def __hash__(self):
        return hash((self._kind, self._device_id))

    def __repr__(self):
        return f"Place({self._kind}:{self._device_id})"


class CPUPlace(Place):
    _kind = "cpu"

    def __init__(self):
        super().__init__(0)


class TPUPlace(Place):
    """The accelerator place. Analog of CUDAPlace in the reference
    (ref: paddle/phi/common/place.h:CUDAPlace)."""

    _kind = "tpu"


# Aliases for source compatibility with reference user code: every
# accelerator place maps to the TPU place; pinned host memory maps to CPU.
CUDAPlace = TPUPlace
XPUPlace = TPUPlace
NPUPlace = TPUPlace
MLUPlace = TPUPlace
IPUPlace = TPUPlace


class CUDAPinnedPlace(CPUPlace):
    """Pinned host memory place (ref: phi/common/place.h CUDAPinnedPlace).
    jax host arrays are already page-locked-transfer-friendly; behaves as
    CPUPlace."""
    _kind = "cuda_pinned"

_current_place = None


def _best_place():
    backend = jax.default_backend()
    if backend == "cpu":
        return CPUPlace()
    return TPUPlace(0)


def set_device(device):
    """paddle.set_device analog. Accepts 'cpu', 'tpu', 'tpu:0', 'gpu'(alias)."""
    global _current_place
    if isinstance(device, Place):
        _current_place = device
        return _current_place
    name = str(device).lower()
    if name.startswith("cpu"):
        _current_place = CPUPlace()
    elif name.startswith(("tpu", "gpu", "cuda", "xpu")):
        idx = int(name.split(":")[1]) if ":" in name else 0
        _current_place = TPUPlace(idx)
    else:
        raise ValueError(f"Unknown device {device!r}")
    return _current_place


def get_device():
    p = _get_current_place()
    return f"{p._kind}:{p.get_device_id()}" if not isinstance(p, CPUPlace) else "cpu"


def _get_current_place():
    global _current_place
    if _current_place is None:
        _current_place = _best_place()
    return _current_place


def is_compiled_with_tpu():
    return any(d.platform != "cpu" for d in jax.devices())


def is_compiled_with_cuda():
    # Source-compat shim: reference user code gates on this; on TPU builds it
    # answers whether an accelerator is present.
    return is_compiled_with_tpu()
