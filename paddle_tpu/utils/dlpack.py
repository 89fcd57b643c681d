"""``paddle.utils.dlpack`` — zero-copy tensor interchange (reference
``python/paddle/utils/dlpack.py``). jax arrays speak dlpack natively."""
from __future__ import annotations

from ..core.tensor import Tensor


def to_dlpack(x: Tensor):
    import jax
    import numpy as np
    v = x._read() if isinstance(x, Tensor) else x
    try:
        if hasattr(v, "__dlpack__"):
            return v.__dlpack__()
        return jax.dlpack.to_dlpack(v)
    except Exception:
        # device buffers that can't be externally referenced:
        # export a host copy's capsule (zero-copy only host-side)
        return np.asarray(v).__dlpack__()


def from_dlpack(capsule) -> Tensor:
    import jax.numpy as jnp
    import numpy as np
    if hasattr(capsule, "__dlpack__"):  # modern protocol object
        return Tensor(jnp.asarray(np.from_dlpack(capsule)))
    return Tensor(jnp.asarray(np.from_dlpack(_CapsuleHolder(capsule))))


class _CapsuleHolder:
    """Adapts a raw PyCapsule to the __dlpack__ protocol numpy expects."""

    def __init__(self, capsule):
        self._capsule = capsule

    def __dlpack__(self, stream=None):
        return self._capsule

    def __dlpack_device__(self):
        return (1, 0)  # kDLCPU


__all__ = ["to_dlpack", "from_dlpack"]
