"""paddle.device analog (ref: python/paddle/device/__init__.py)."""
import jax

from ..framework.place import (set_device, get_device, is_compiled_with_tpu,
                               is_compiled_with_cuda, CPUPlace, TPUPlace)


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def device_count():
    return len([d for d in jax.devices() if d.platform != "cpu"]) or len(jax.devices())


class cuda:
    """Source-compat shim for paddle.device.cuda."""

    @staticmethod
    def device_count():
        return device_count()

    @staticmethod
    def synchronize(device=None):
        # XLA dispatch is async; block on a trivial computation.
        jax.block_until_ready(jax.numpy.zeros(()))

    @staticmethod
    def empty_cache():
        pass

    @staticmethod
    def max_memory_allocated(device=None):
        try:
            d = jax.devices()[0]
            return d.memory_stats().get("peak_bytes_in_use", 0)
        except Exception:
            return 0

    @staticmethod
    def memory_allocated(device=None):
        try:
            d = jax.devices()[0]
            return d.memory_stats().get("bytes_in_use", 0)
        except Exception:
            return 0


def synchronize(device=None):
    cuda.synchronize(device)


def _device_for(device=None):
    if device is None:
        return jax.devices()[0]
    if hasattr(device, "platform"):
        return device
    name = str(device)
    idx = int(name.split(":")[1]) if ":" in name else 0
    return jax.devices()[idx]


def memory_stats(device=None):
    """Full allocator statistics for a device (TPU: bytes_in_use,
    peak_bytes_in_use, bytes_limit, num_allocs, ...; CPU backends report
    {}). The observability analog of the reference's memory/stats.cc
    (ref: paddle/fluid/memory/stats.cc, memory/allocation/
    allocator_facade.cc) — XLA owns allocation, this surfaces its stats."""
    try:
        return dict(_device_for(device).memory_stats() or {})
    except Exception:
        return {}


def max_memory_allocated(device=None):
    return memory_stats(device).get("peak_bytes_in_use", 0)


def max_memory_reserved(device=None):
    st = memory_stats(device)
    return st.get("bytes_reserved", st.get("peak_bytes_in_use", 0))


def memory_allocated(device=None):
    return memory_stats(device).get("bytes_in_use", 0)


def memory_reserved(device=None):
    st = memory_stats(device)
    return st.get("bytes_reserved", st.get("bytes_in_use", 0))


def reset_peak_memory_stats(device=None):
    # XLA exposes no reset; callers should diff successive readings.
    return None


# --- platform predicates + stream compat (ref: python/paddle/device/
# __init__.py) ---------------------------------------------------------------
# The is_compiled_with_* family reports build capabilities; this build
# targets XLA/TPU only, so every vendor-specific predicate is honestly
# False (same pattern as the cuda.* shims above).

def get_cudnn_version():
    """ref: device/__init__.py get_cudnn_version — None: no cuDNN in an
    XLA/TPU build."""
    return None


def is_compiled_with_xpu():
    return False


def is_compiled_with_ipu():
    return False


def is_compiled_with_cinn():
    """False by name; the XLA compiler IS this build's compiler tier
    (BASELINE.md descope ledger)."""
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_npu():
    return False


def is_compiled_with_mlu():
    return False


def is_compiled_with_custom_device(device_type=None):
    """TPU rides the jax plugin mechanism — report True for 'tpu'."""
    return device_type == "tpu"


def get_all_custom_device_type():
    import jax
    try:
        return sorted({d.platform for d in jax.devices()
                       if d.platform not in ("cpu", "gpu")})
    except RuntimeError:
        return []


def get_available_custom_device():
    import jax
    try:
        return [str(d) for d in jax.devices()
                if d.platform not in ("cpu", "gpu")]
    except RuntimeError:
        return []


# Vendor places alias the accelerator place, matching the top-level
# paddle.XPUPlace/MLUPlace/IPUPlace aliases (framework/place.py:68-72):
# "the accelerator" on this build is the TPU, and a script that places on
# its vendor device must get the same object from either import path.
from ..framework.place import (XPUPlace, IPUPlace,  # noqa: E402,F401
                               MLUPlace)


class Stream:
    """ref: device/__init__.py Stream. XLA owns scheduling: a Stream is a
    labeled synchronization scope — record/synchronize map to
    block-until-ready on the tracked work."""

    def __init__(self, device=None, priority=2, blocking=False):
        self.device = device
        self.priority = priority

    def synchronize(self):
        synchronize(self.device)

    def wait_event(self, event):
        event.synchronize()

    def wait_stream(self, stream):
        stream.synchronize()

    def record_event(self, event=None):
        event = event or Event()
        event.record(self)
        return event


class Event:
    """ref: device/__init__.py Event — device-sync marker."""

    def __init__(self, device=None, enable_timing=False, blocking=False,
                 interprocess=False):
        import time as _time
        self._time = _time
        self._stamp = None
        self.device = device
        self.enable_timing = enable_timing

    def record(self, stream=None):
        (stream or current_stream()).synchronize()
        self._stamp = self._time.perf_counter()

    def query(self):
        return True  # synchronous record: always complete

    def synchronize(self):
        pass

    def elapsed_time(self, end_event):
        if self._stamp is None or end_event._stamp is None:
            raise RuntimeError("elapsed_time needs both events recorded")
        return (end_event._stamp - self._stamp) * 1000.0


_current_stream = [None]


def current_stream(device=None):
    if _current_stream[0] is None:
        _current_stream[0] = Stream(device)
    return _current_stream[0]


def set_stream(stream):
    prev = current_stream()
    _current_stream[0] = stream
    return prev


class stream_guard:
    """ref: device/__init__.py stream_guard context manager."""

    def __init__(self, stream):
        self._stream = stream
        self._prev = None

    def __enter__(self):
        self._prev = set_stream(self._stream)
        return self._stream

    def __exit__(self, *exc):
        set_stream(self._prev)
        return False
