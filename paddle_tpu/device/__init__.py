"""Device API — analog of ``paddle.device`` / ``phi::Place``
(upstream: paddle/phi/common/place.h, python/paddle/device/__init__.py).

On TPU there is one device kind per process; ``set_device`` selects the
jax default device. 'gpu'/'cuda' strings are accepted and mapped to the
accelerator (TPU) for script compatibility.
"""
from __future__ import annotations

import jax


class Place:
    def __init__(self, kind: str, device_id: int = 0):
        self._kind = kind
        self._id = device_id

    def is_cpu_place(self):
        return self._kind == "cpu"

    def is_gpu_place(self):
        return False

    def is_tpu_place(self):
        return self._kind == "tpu"

    def is_custom_place(self):
        return self._kind not in ("cpu",)

    def get_device_id(self):
        return self._id

    def __repr__(self):
        return f"Place({self._kind}:{self._id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self._kind == other._kind
            and self._id == other._id
        )


class CPUPlace(Place):
    def __init__(self):
        super().__init__("cpu", 0)


class TPUPlace(Place):
    def __init__(self, device_id=0):
        super().__init__("tpu", device_id)


# 'CUDAPlace' accepted for script parity; maps to the accelerator.
CUDAPlace = TPUPlace
CustomPlace = Place

_current = None


def _accelerator_kind():
    plat = jax.default_backend()
    return "cpu" if plat == "cpu" else "tpu"


def _current_place() -> Place:
    global _current
    if _current is None:
        _current = Place(_accelerator_kind(), 0)
    return _current


def set_device(device: str):
    """paddle.set_device('tpu'|'tpu:0'|'cpu'|'gpu:0'→tpu)."""
    global _current
    if isinstance(device, Place):
        _current = device
        return _current
    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    if name in ("gpu", "cuda", "tpu", "xpu", "npu"):
        kind = _accelerator_kind()
        if kind == "cpu":
            raise RuntimeError(
                f"set_device({device!r}): JAX's default backend is 'cpu' "
                "— no accelerator is attached to this process")
    elif name == "cpu":
        kind = "cpu"
    else:
        raise ValueError(f"unknown device {device!r}")
    devs = jax.devices("cpu" if kind == "cpu" else None)
    if idx >= len(devs):
        raise ValueError(
            f"set_device({device!r}): index {idx} out of range, "
            f"{len(devs)} {kind} device(s) visible")
    if kind != "cpu":
        jax.config.update("jax_default_device", devs[idx])
    _current = Place(kind, idx)
    return _current


def get_device() -> str:
    p = _current_place()
    return f"{p._kind}:{p._id}"


def device_count() -> int:
    return jax.device_count()


def is_compiled_with_cuda():
    return False


def is_compiled_with_tpu():
    return True


def is_compiled_with_xpu():
    return False


def is_compiled_with_custom_device(name: str = "tpu"):
    return name in ("tpu",)


def synchronize(device=None):
    """Block until all dispatched work completes (stream sync analog)."""
    (jax.device_put(0) + 0).block_until_ready()


def get_available_device():
    """All visible devices as place strings (upstream
    paddle.device.get_available_device)."""
    kind = "tpu" if is_compiled_with_tpu() and any(
        d.platform not in ("cpu",) for d in jax.devices()
    ) else "cpu"
    return [f"{kind}:{i}" for i in range(jax.device_count())]


def get_available_custom_device():
    """Custom-device places (upstream analog; TPU is this framework's
    first-class device, not a custom plugin — empty list)."""
    return []


class _XPUShim:
    """paddle.device.xpu parity veneer: XPU (Kunlun) hardware is out of
    scope on TPU (SURVEY §7); every query reports absence."""

    @staticmethod
    def device_count():
        return 0

    @staticmethod
    def synchronize(device=None):
        return None


xpu = _XPUShim()


# -- memory observability (upstream: paddle/fluid/memory/stats.h) ----------
def memory_allocated(device=None) -> int:
    try:
        d = jax.devices()[0]
        stats = d.memory_stats()
        return int(stats.get("bytes_in_use", 0)) if stats else 0
    except Exception:
        return 0


def max_memory_allocated(device=None) -> int:
    try:
        d = jax.devices()[0]
        stats = d.memory_stats()
        return int(stats.get("peak_bytes_in_use", 0)) if stats else 0
    except Exception:
        return 0


def max_memory_reserved(device=None) -> int:
    return max_memory_allocated(device)


def memory_reserved(device=None) -> int:
    return memory_allocated(device)


class Stream:
    """Execution-stream handle (upstream: phi::GPUContext streams).

    On TPU, XLA/PJRT owns stream scheduling — all compute is issued on
    the runtime's single logical stream and ordering across programs is
    data-dependency-driven. The handle exists for API parity: wait/
    synchronize map to real dispatch barriers; there is no user-visible
    concurrent-stream model to configure."""

    def __init__(self, device=None, priority=None):
        self.device = device
        self.priority = priority

    def synchronize(self):
        synchronize(self.device)

    def wait_stream(self, stream):
        synchronize(self.device)

    def wait_event(self, event):
        event.synchronize()

    def record_event(self, event=None):
        event = event or Event()
        event.record(self)
        return event

    def __repr__(self):
        return f"Stream(device={self.device})"


class Event:
    """Event marker (upstream: cudaEvent). Records a point in the
    dispatch order; synchronize() drains outstanding work (PJRT has no
    finer-grained user fence). elapsed_time uses host wall-clock
    between two drained records."""

    def __init__(self, enable_timing=True, blocking=False,
                 interprocess=False):
        import time as _time

        self._time = _time
        self._stamp = None

    def record(self, stream=None):
        synchronize()
        self._stamp = self._time.perf_counter()

    def query(self):
        return True

    def synchronize(self):
        synchronize()

    def elapsed_time(self, end_event):
        if self._stamp is None or end_event._stamp is None:
            raise RuntimeError("both events must be recorded")
        return (end_event._stamp - self._stamp) * 1000.0


_current_stream = Stream()


def current_stream(device=None):
    return _current_stream


import contextlib as _contextlib


@_contextlib.contextmanager
def stream_guard(stream):
    """API parity: all work already rides PJRT's stream; the guard is
    an ordering no-op (XLA schedules overlap itself)."""
    yield stream


class cuda:
    """Namespace shim: paddle.device.cuda.* parity, backed by TPU stats."""

    Stream = Stream
    Event = Event
    current_stream = staticmethod(current_stream)
    stream_guard = staticmethod(stream_guard)

    memory_allocated = staticmethod(memory_allocated)
    max_memory_allocated = staticmethod(max_memory_allocated)
    max_memory_reserved = staticmethod(max_memory_reserved)
    memory_reserved = staticmethod(memory_reserved)
    synchronize = staticmethod(synchronize)

    @staticmethod
    def device_count():
        return jax.device_count()

    @staticmethod
    def empty_cache():
        pass
