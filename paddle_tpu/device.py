"""Device / Place taxonomy.

Parity surface: ``phi::Place`` (upstream: paddle/phi/common/place.h) and
``paddle.device.set_device`` (python/paddle/device/__init__.py). TPU-native
design: a Place names a jax device; ``set_device`` selects the default
placement used by tensor factories; cross-place copies are ``jax.device_put``.
No DeviceContext/stream pool is needed — XLA/PJRT owns streams and events.
"""

from __future__ import annotations

import functools
from typing import Optional, Union

import jax

__all__ = [
    "Place", "CPUPlace", "TPUPlace", "CUDAPlace", "CustomPlace",
    "XPUPlace", "MLUPlace", "IPUPlace", "CUDAPinnedPlace",
    "set_device", "get_device", "get_all_devices", "device_count",
    "is_compiled_with_cuda", "is_compiled_with_tpu", "current_place",
    "device_put", "describe",
]


class Place:
    """Identity of a physical device: (device_type, device_id)."""

    __slots__ = ("device_type", "device_id")

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = int(device_id)

    # -- mapping to jax ------------------------------------------------------
    def jax_device(self) -> jax.Device:
        devs = _devices_of_type(self.device_type)
        if not devs:
            raise RuntimeError(f"no {self.device_type!r} devices visible to jax")
        return devs[self.device_id % len(devs)]

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"


def CPUPlace(device_id: int = 0) -> Place:
    return Place("cpu", device_id)


def TPUPlace(device_id: int = 0) -> Place:
    return Place("tpu", device_id)


def CUDAPlace(device_id: int = 0) -> Place:
    # Parity alias: there is no CUDA on TPU systems; accepted so reference
    # scripts run, mapped to the accelerator if present else CPU.
    return Place("tpu", device_id) if _accelerator_type() == "tpu" else Place("cpu", device_id)


def CustomPlace(device_type: str, device_id: int = 0) -> Place:
    return Place(device_type, device_id)


def XPUPlace(device_id: int = 0) -> Place:
    # Parity alias (Kunlun XPU in the reference): maps to the accelerator.
    return CUDAPlace(device_id)


def MLUPlace(device_id: int = 0) -> Place:
    return CUDAPlace(device_id)


def IPUPlace(device_id: int = 0) -> Place:
    return CUDAPlace(device_id)


def CUDAPinnedPlace() -> Place:
    # Pinned host memory: on TPU the host side is plain CPU memory (PJRT
    # stages transfers itself), so this is the cpu place.
    return Place("cpu", 0)


@functools.lru_cache(maxsize=None)
def _devices_of_type(device_type: str):
    """Devices of one place type. The ``tpu`` place is ``platform ==
    "tpu"`` and nothing else (``gpu``/``xpu`` are the reference scripts'
    spellings of "the accelerator"); a backend that fails to initialise
    raises here — it is never read as "no such devices"."""
    if device_type == "cpu":
        return tuple(jax.devices("cpu"))
    if device_type in ("gpu", "xpu"):
        device_type = "tpu"
    return tuple(d for d in jax.devices() if d.platform == device_type)


@functools.lru_cache(maxsize=1)
def _accelerator_type() -> str:
    """``"tpu"`` when jax's default backend is the TPU, else ``"cpu"``. A
    failed backend probe propagates: a process that asked for the chip and
    could not get it must not carry on as a CPU process."""
    return "tpu" if any(d.platform == "tpu" for d in jax.devices()) \
        else "cpu"


_current_place: Optional[Place] = None


def set_device(device: Union[str, Place]) -> Place:
    """``paddle.device.set_device('tpu')`` / ``('tpu:0')`` / ``('cpu')``."""
    global _current_place
    if isinstance(device, Place):
        _current_place = device
        return _current_place
    dev = device.lower()
    if dev in ("gpu", "cuda", "xpu"):
        dev = "tpu" if _accelerator_type() == "tpu" else "cpu"
    if ":" in dev:
        kind, _, idx = dev.partition(":")
        place = Place(kind, int(idx))
    else:
        place = Place(dev, 0)
    place.jax_device()  # validate before it becomes the default
    _current_place = place
    return _current_place


def current_place() -> Place:
    global _current_place
    if _current_place is None:
        _current_place = Place(_accelerator_type(), 0)
    return _current_place


def get_device() -> str:
    p = current_place()
    return f"{p.device_type}:{p.device_id}"


def get_all_devices():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def device_count(device_type: Optional[str] = None) -> int:
    return len(_devices_of_type(device_type or current_place().device_type))


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return _accelerator_type() == "tpu"


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_ipu() -> bool:
    return False


def is_compiled_with_mlu() -> bool:
    return False


def is_compiled_with_cinn() -> bool:
    # the graph compiler role is filled by XLA itself (SURVEY §2.5.7)
    return True


def is_compiled_with_distribute() -> bool:
    return True


def is_compiled_with_custom_device(device_type: str = "") -> bool:
    # any non-cpu PJRT backend is a "custom device" in reference terms
    return _accelerator_type() != "cpu"


def default_jax_device() -> jax.Device:
    return current_place().jax_device()


def describe() -> dict:
    """The device as JAX reports it — ``platform``, ``kind``
    (``device_kind``) and ``count`` — the label every benchmark result
    carries, and the key of the peaks table
    (``observability.cost.DEVICE_PEAKS``)."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def device_put(x, place: Union[str, Place, jax.Device, None] = None):
    """The sanctioned single-device transfer: ``jax.device_put`` with the
    target resolved through the Place taxonomy (``None`` → the current
    default device). Every non-distributed transfer in the framework
    routes through here or through ``core/fallback.py`` — enforced by the
    ``device-access`` lint rule; the distributed layer's mesh-sharded
    ``device_put(x, NamedSharding(...))`` calls are a different API and
    stay in that layer (baselined)."""
    if place is None:
        dev = default_jax_device()
    elif isinstance(place, Place):
        dev = place.jax_device()
    elif isinstance(place, jax.Device):
        dev = place
    else:
        dev = Place(*_parse_device_str(str(place).lower())).jax_device()
    return jax.device_put(x, dev)


# ---------------------------------------------------------------------------
# Device memory stats (parity: paddle.device.cuda.max_memory_allocated & co,
# backed by the allocator StatAllocator counters in the reference — here by
# PJRT per-device memory_stats(), which libtpu/XLA maintain natively).
# ---------------------------------------------------------------------------

def _memory_stats(device: Union[str, Place, None] = None) -> dict:
    if device is None:
        dev = default_jax_device()
    elif isinstance(device, Place):
        dev = device.jax_device()
    else:
        dev = Place(*_parse_device_str(device)).jax_device() if isinstance(
            device, str) else device
    try:
        return dev.memory_stats() or {}
    except Exception:
        return {}


def _parse_device_str(s: str):
    if ":" in s:
        kind, idx = s.split(":", 1)
        return kind, int(idx)
    return s, 0


def memory_allocated(device=None) -> int:
    """Bytes currently in use on the device (PJRT ``bytes_in_use``)."""
    return int(_memory_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    """Peak bytes in use (PJRT ``peak_bytes_in_use``)."""
    return int(_memory_stats(device).get("peak_bytes_in_use", 0))


def memory_reserved(device=None) -> int:
    """Bytes reserved by the allocator pool (``bytes_reserved`` /
    ``pool_bytes`` when the backend reports it; falls back to in-use)."""
    st = _memory_stats(device)
    return int(st.get("bytes_reserved", st.get("pool_bytes",
                                               st.get("bytes_in_use", 0))))


def max_memory_reserved(device=None) -> int:
    st = _memory_stats(device)
    return int(st.get("peak_bytes_reserved", st.get(
        "largest_alloc_size", st.get("peak_bytes_in_use", 0))))


def empty_cache() -> None:
    """Parity no-op: PJRT owns its BFC pool; there is no user-facing cache
    flush on TPU (documented divergence)."""


class _DeviceStatsNS:
    """Namespace so both ``paddle.device.tpu.*`` and ``paddle.device.cuda.*``
    spellings resolve (model-zoo code calls the latter unconditionally)."""

    memory_allocated = staticmethod(memory_allocated)
    max_memory_allocated = staticmethod(max_memory_allocated)
    memory_reserved = staticmethod(memory_reserved)
    max_memory_reserved = staticmethod(max_memory_reserved)
    empty_cache = staticmethod(empty_cache)

    @staticmethod
    def device_count() -> int:
        return device_count()

    @staticmethod
    def synchronize(device=None) -> None:
        # XLA dispatch is async. TPU executes enqueued programs in order per
        # core, so enqueueing a trivial program on each local device and
        # blocking on its result drains the pipeline (effects_barrier alone
        # only waits for side-effecting computations).
        import jax.numpy as jnp

        jax.effects_barrier()
        d = device.jax_device() if isinstance(device, Place) \
            else default_jax_device()
        # the committed input places the program on ``d``
        jax.block_until_ready(
            jax.jit(lambda x: x + 1)(jax.device_put(jnp.zeros(()), d)))


tpu = _DeviceStatsNS()
cuda = _DeviceStatsNS()
xpu = _DeviceStatsNS()


def synchronize(device=None) -> None:
    _DeviceStatsNS.synchronize(device)
