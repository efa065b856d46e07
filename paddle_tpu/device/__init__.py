"""Device management (reference: `python/paddle/device/__init__.py:265`
``set_device`` and the phi DeviceManager, `phi/backends/device_manager.h:134`).

TPU-native: devices are PJRT devices enumerated by JAX; there is no manual
stream/event surface because XLA schedules asynchronously — the stream-like
knobs are kept as no-op shims for API parity.
"""

from __future__ import annotations

import jax

__all__ = ["set_device", "get_device", "get_all_devices", "device_count",
           "is_compiled_with_cuda", "is_compiled_with_rocm",
           "is_compiled_with_xpu", "is_compiled_with_ipu",
           "is_compiled_with_custom_device", "synchronize", "Stream", "Event",
           "current_stream", "cuda"]

_current_device = None

_DEVICE_NAMES = ("cpu", "gpu", "tpu", "cuda")


def _platform():
    return jax.default_backend()


def _looks_like_device(spec) -> bool:
    """True if ``spec`` is a device string like 'tpu' / 'cpu:0' / 'cuda:1'."""
    if not isinstance(spec, str):
        return False
    return spec.lower().partition(":")[0] in _DEVICE_NAMES


def _resolve_device(spec: str):
    """Resolve a device string to a concrete JAX device (shared by
    ``set_device`` and ``Tensor.to``)."""
    name, _, idx = spec.lower().partition(":")
    if name == "cuda":
        name = "gpu"
    idx = int(idx) if idx else 0
    devs = [d for d in jax.devices()
            if d.platform == name
            or (name == "gpu" and d.platform in ("cuda", "rocm"))]
    if not devs and name == "cpu":
        # CPU devices exist even when an accelerator is the default backend;
        # ask the CPU backend explicitly.
        devs = jax.devices("cpu")
    if not devs:
        raise ValueError(
            f"no '{name}' device available; platforms present: "
            f"{sorted({d.platform for d in jax.devices()})}")
    if idx >= len(devs):
        raise ValueError(
            f"device index {idx} out of range: only {len(devs)} '{name}' "
            "device(s) present")
    return devs[idx]


def set_device(device: str):
    """Select default device: 'tpu', 'cpu', 'tpu:0' etc."""
    global _current_device
    _current_device = _resolve_device(device)
    jax.config.update("jax_default_device", _current_device)
    return _current_device


def get_device() -> str:
    d = _current_device or jax.devices()[0]
    return f"{d.platform}:{getattr(d, 'id', 0)}"


def get_all_devices():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def device_count():
    return jax.device_count()


def is_compiled_with_cuda():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_ipu():
    return False


def is_compiled_with_custom_device(name="tpu"):
    return True


def synchronize(device=None):
    """Block until all dispatched work completes (stream sync analog)."""
    try:
        (jax.device_put(0) + 0).block_until_ready()
    except Exception:
        pass


class Stream:
    """No-op shim: XLA owns scheduling; kept for API parity with
    ``paddle.device.Stream``."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()

    def wait_event(self, event):
        pass

    def wait_stream(self, stream):
        pass

    def record_event(self, event=None):
        return event or Event()


class Event:
    def __init__(self, device=None, enable_timing=False, blocking=False):
        pass

    def record(self, stream=None):
        pass

    def query(self):
        return True

    def synchronize(self):
        synchronize()


def current_stream(device=None):
    return Stream(device)


class _CudaShim:
    """``paddle.device.cuda`` compatibility namespace (no CUDA on TPU)."""

    @staticmethod
    def device_count():
        return 0

    @staticmethod
    def is_available():
        return False

    @staticmethod
    def synchronize(device=None):
        synchronize()

    @staticmethod
    def empty_cache():
        pass

    @staticmethod
    def max_memory_allocated(device=None):
        return 0

    @staticmethod
    def memory_allocated(device=None):
        return 0


cuda = _CudaShim()


# ---------------------------------------------------------------------------
# memory statistics (reference: `fluid/memory/stats.cc` — allocated/reserved
# current + peak per device; `paddle.device.cuda.max_memory_allocated`)
# ---------------------------------------------------------------------------
_peak_allocated: dict = {}


def _device_obj(device=None):
    if device is None:
        return jax.devices()[0]
    if isinstance(device, int):
        return jax.devices()[device]
    return device


def memory_stats(device=None, live_arrays=None):
    """Raw allocator statistics for a device. On real TPU/GPU backends
    this is the PJRT allocator report (``bytes_in_use``,
    ``peak_bytes_in_use``, ``bytes_limit``, ...); where the backend does
    not report (CPU), live on-device arrays are summed
    instead and the dict carries ``{"bytes_in_use": ..., "source":
    "live_arrays"}``. ``live_arrays`` optionally supplies an already-
    fetched ``jax.live_arrays()`` list so callers that walk it anyway
    (the observability memory sampler) don't pay the enumeration
    twice."""
    d = _device_obj(device)
    stats = None
    try:
        stats = d.memory_stats()
    except Exception:
        stats = None
    if stats:
        out = dict(stats)
        # tag the provenance on BOTH paths so consumers (the
        # observability memory sampler, dashboards) can tell an
        # allocator-reported figure from a live-array estimate
        out.setdefault("source", "allocator")
        return out
    live = jax.live_arrays() if live_arrays is None else live_arrays
    in_use = sum(
        x.nbytes for x in live
        if any(dd == d for dd in x.devices()))
    return {"bytes_in_use": in_use, "source": "live_arrays"}


def memory_allocated(device=None):
    """Bytes currently allocated on the device (reference
    `paddle.device.cuda.memory_allocated`)."""
    n = int(memory_stats(device).get("bytes_in_use", 0))
    key = str(_device_obj(device))
    _peak_allocated[key] = max(_peak_allocated.get(key, 0), n)
    return n


def max_memory_allocated(device=None):
    """Peak allocated bytes: the allocator's own peak when reported,
    else the running max over this process's ``memory_allocated`` calls."""
    stats = memory_stats(device)
    if "peak_bytes_in_use" in stats:
        return int(stats["peak_bytes_in_use"])
    key = str(_device_obj(device))
    current = int(stats.get("bytes_in_use", 0))
    _peak_allocated[key] = max(_peak_allocated.get(key, 0), current)
    return _peak_allocated[key]


def memory_reserved(device=None):
    """Bytes reserved by the allocator (``bytes_limit`` when reported —
    XLA preallocates; else equals allocated)."""
    stats = memory_stats(device)
    return int(stats.get("bytes_limit", stats.get("bytes_in_use", 0)))


def reset_max_memory_allocated(device=None):
    _peak_allocated[str(_device_obj(device))] = 0


def empty_cache():
    """Reference `paddle.device.cuda.empty_cache`. XLA's BFC allocator
    serves frees internally; deleting dangling host references is the
    only lever, so this triggers a GC pass."""
    import gc
    gc.collect()


__all__ += ["memory_stats", "memory_allocated", "max_memory_allocated",
            "memory_reserved", "reset_max_memory_allocated", "empty_cache"]
