"""``paddle.utils.dlpack`` — zero-copy tensor exchange via the DLPack
protocol (reference: python/paddle/utils/dlpack.py). jax arrays implement
``__dlpack__`` natively, so this is a thin seam."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, to_tensor

__all__ = ["to_dlpack", "from_dlpack"]


def to_dlpack(x: Tensor):
    """Export a Tensor as a DLPack capsule. Zero-copy from the jax buffer
    when the PJRT backend supports external references; otherwise stages
    through host memory."""
    arr = x._data if isinstance(x, Tensor) else jnp.asarray(x)
    try:
        return arr.__dlpack__()
    except Exception:
        import numpy as np
        return np.array(jax.device_get(arr)).__dlpack__()  # writable host copy


class _CapsuleHolder:
    """Adapter giving a raw DLPack capsule the array-API protocol surface
    (consumers now expect ``__dlpack__``/``__dlpack_device__``, not bare
    capsules). Host capsules only — device is kDLCPU."""

    def __init__(self, capsule):
        self._capsule = capsule

    def __dlpack__(self, stream=None, **kwargs):
        return self._capsule

    def __dlpack_device__(self):
        return (1, 0)  # (kDLCPU, 0)


def from_dlpack(capsule) -> Tensor:
    """Import a DLPack capsule (or any object with ``__dlpack__``)."""
    if hasattr(capsule, "__dlpack__"):
        return to_tensor(jnp.from_dlpack(capsule))
    return to_tensor(jnp.from_dlpack(_CapsuleHolder(capsule)))
