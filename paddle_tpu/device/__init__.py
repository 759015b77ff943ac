"""Device / Place API.

Ref design: paddle/phi/common/place.h (phi::Place, CPUPlace/CUDAPlace/
XPUPlace/CustomPlace — the fork adds TPUPlace) and python/paddle/device/.
On TPU the device runtime is PJRT; Places are lightweight descriptors
that resolve to ``jax.Device`` objects.
"""
from __future__ import annotations

from typing import Optional

import jax

__all__ = [
    "Place", "CPUPlace", "TPUPlace", "CUDAPlace", "XPUPlace", "CustomPlace",
    "set_device", "get_device", "get_all_devices", "device_count",
    "is_compiled_with_cuda", "is_compiled_with_xpu", "is_compiled_with_rocm",
    "is_compiled_with_tpu", "is_compiled_with_cinn", "is_compiled_with_distribute",
    "synchronize", "cuda", "jax_device",
]


class Place:
    """Base place: a named device slot resolving to a jax.Device."""

    _kind = "unknown"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    def __repr__(self):
        return f"Place({self._kind}:{self._device_id})"

    def __eq__(self, other):
        return (isinstance(other, Place) and self._kind == other._kind
                and self._device_id == other._device_id)

    def __hash__(self):
        return hash((self._kind, self._device_id))


class CPUPlace(Place):
    _kind = "cpu"

    def __init__(self):
        super().__init__(0)

    def __repr__(self):
        return "Place(cpu)"


class TPUPlace(Place):
    _kind = "tpu"


class CUDAPlace(Place):  # accepted for API parity; resolves to accelerator 0
    _kind = "gpu"


class XPUPlace(Place):
    _kind = "xpu"


class CUDAPinnedPlace(Place):
    _kind = "cuda_pinned"


class CustomPlace(Place):
    def __init__(self, dev_type: str, device_id: int = 0):
        super().__init__(device_id)
        self._kind = dev_type


_current_place: Optional[Place] = None


def _default_place() -> Place:
    backend = jax.default_backend()
    if backend == "cpu":
        return CPUPlace()
    return TPUPlace(0)


def _parse(device: str) -> Place:
    device = device.lower()
    if device in ("cpu",):
        return CPUPlace()
    for prefix, cls in (("tpu", TPUPlace), ("gpu", CUDAPlace), ("xpu", XPUPlace)):
        if device == prefix:
            return cls(0)
        if device.startswith(prefix + ":"):
            return cls(int(device.split(":")[1]))
    if ":" in device:
        kind, idx = device.split(":")
        return CustomPlace(kind, int(idx))
    raise ValueError(f"cannot parse device string {device!r}")


def set_device(device) -> Place:
    """paddle.device.set_device — selects the default placement target."""
    global _current_place
    _current_place = device if isinstance(device, Place) else _parse(device)
    return _current_place


def get_device() -> str:
    p = _current_place or _default_place()
    if isinstance(p, CPUPlace):
        return "cpu"
    return f"{p._kind}:{p.get_device_id()}"


def current_place() -> Place:
    return _current_place or _default_place()


def jax_device(place: Optional[Place] = None):
    """Resolve a Place to a jax.Device (None → framework default).  An
    id past the attached devices raises."""
    place = place or current_place()
    devs = jax.devices("cpu") if isinstance(place, CPUPlace) \
        else jax.devices()
    i = place.get_device_id()
    if not 0 <= i < len(devs):
        raise ValueError(f"{place!r}: device id {i} out of range "
                         f"({len(devs)} {devs[0].platform} device(s))")
    return devs[i]


def get_all_devices():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def device_count() -> int:
    return jax.device_count()


def get_device_topology():
    """ICI/DCN topology query (ref: phi/backends device topology — the
    reference exposes NVLink/PCIe topology; here it's the TPU
    coords/slice layout PJRT reports per device).

    Returns a list of dicts: id, process_index, platform, device_kind,
    coords (ICI mesh coordinates when the runtime exposes them),
    core_on_chip, slice_index (DCN: which slice in a multi-slice job).
    """
    import jax
    out = []
    for d in jax.devices():
        info = {
            "id": d.id,
            "process_index": d.process_index,
            "platform": d.platform,
            "device_kind": getattr(d, "device_kind", ""),
        }
        for attr in ("coords", "core_on_chip", "slice_index"):
            v = getattr(d, attr, None)
            if v is not None:
                info[attr] = tuple(v) if isinstance(v, (list, tuple)) \
                    else v
        out.append(info)
    return out


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def is_compiled_with_cinn() -> bool:
    # XLA plays CINN's role and is always on.
    return True


def is_compiled_with_distribute() -> bool:
    return True


def synchronize(device=None):
    """Block until all queued work is done (ref: device synchronize)."""
    # jax dispatch is async; the strongest barrier is a tiny blocking transfer.
    import jax.numpy as jnp
    jnp.zeros(()).block_until_ready()


# bf16 peak FLOPs per chip by TPU generation (public spec sheets) —
# the single source for Engine.cost's MFU numbers
TPU_PEAK_BF16 = {
    "v2": 46e12, "v3": 123e12, "v4": 275e12,
    "v5lite": 197e12, "v5e": 197e12, "v5p": 459e12, "v6e": 918e12,
}


def chip_peak_flops(device=None) -> float:
    """Peak bf16 FLOPs of the attached chip, keyed on device_kind.  A
    kind that is not in the table raises: a utilization computed from a
    guessed peak is worse than none."""
    d = device if device is not None else jax.devices()[0]
    kind = getattr(d, "device_kind", "").lower().replace(" ", "")
    for key, peak in sorted(TPU_PEAK_BF16.items(),
                            key=lambda kv: -len(kv[0])):
        if key in kind:
            return peak
    raise ValueError(f"no peak FLOPs on record for device_kind "
                     f"{getattr(d, 'device_kind', None)!r} "
                     f"(known: {sorted(TPU_PEAK_BF16)})")


class _CudaNamespace:
    """paddle.device.cuda parity shims (memory stats come from PJRT)."""

    @staticmethod
    def device_count():
        return jax.device_count()

    @staticmethod
    def synchronize(device=None):
        synchronize(device)

    @staticmethod
    def max_memory_allocated(device=None):
        d = jax.devices()[0]
        stats = getattr(d, "memory_stats", lambda: None)()
        return (stats or {}).get("peak_bytes_in_use", 0)

    @staticmethod
    def max_memory_reserved(device=None):
        d = jax.devices()[0]
        stats = getattr(d, "memory_stats", lambda: None)()
        return (stats or {}).get("peak_bytes_in_use", 0)

    @staticmethod
    def memory_allocated(device=None):
        d = jax.devices()[0]
        stats = getattr(d, "memory_stats", lambda: None)()
        return (stats or {}).get("bytes_in_use", 0)

    @staticmethod
    def memory_reserved(device=None):
        d = jax.devices()[0]
        stats = getattr(d, "memory_stats", lambda: None)()
        return (stats or {}).get("bytes_in_use", 0)

    @staticmethod
    def empty_cache():
        pass


class Event:
    """ref: paddle.device.cuda.Event — timestamp semantics over the
    XLA queue: record() synchronizes-and-stamps (XLA has no user-visible
    stream timeline; kernel-level timing belongs to paddle.profiler)."""

    def __init__(self, enable_timing: bool = True, blocking: bool = False,
                 interprocess: bool = False):
        self._t = None

    def record(self, stream=None):
        import time
        synchronize()
        self._t = time.perf_counter()

    def query(self) -> bool:
        return self._t is not None

    def synchronize(self):
        synchronize()

    def elapsed_time(self, end: "Event") -> float:
        """Milliseconds between two recorded events."""
        if self._t is None or end._t is None:
            raise RuntimeError("both events must be recorded first")
        return (end._t - self._t) * 1000.0


class Stream:
    """ref: paddle.device.cuda.Stream — XLA owns scheduling; the API
    surface is preserved so stream-annotated code runs unchanged."""

    def __init__(self, device=None, priority=None):
        self.device = device

    def synchronize(self):
        synchronize()

    def query(self) -> bool:
        return True

    def wait_event(self, event):
        return None

    def wait_stream(self, stream):
        return None

    def record_event(self, event=None):
        ev = event or Event()
        ev.record(self)
        return ev


_current_stream = Stream()


def current_stream(device=None) -> Stream:
    return _current_stream


class stream_guard:
    """ref: paddle.device.cuda.stream_guard — a no-op scope (XLA
    schedules; kept so guarded code is portable)."""

    def __init__(self, stream: Stream):
        self.stream = stream

    def __enter__(self):
        return self.stream

    def __exit__(self, *exc):
        return False


def get_device_properties(device=None):
    """ref: cuda.get_device_properties — TPU chip properties."""
    d = jax.devices()[0]
    stats = getattr(d, "memory_stats", lambda: None)() or {}

    class _Props:
        name = getattr(d, "device_kind", "TPU")
        major, minor = 0, 0
        total_memory = stats.get("bytes_limit", 0)
        multi_processor_count = 1

        def __repr__(self):
            return (f"_gpuDeviceProperties(name='{self.name}', "
                    f"total_memory={self.total_memory})")

    return _Props()


cuda = _CudaNamespace()
cuda.Event = Event
cuda.Stream = Stream
cuda.current_stream = current_stream
cuda.stream_guard = stream_guard
cuda.get_device_properties = get_device_properties

from . import graphs as _graphs  # noqa: E402
cuda.graphs = _graphs
cuda.CUDAGraph = _graphs.CUDAGraph
