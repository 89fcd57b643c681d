"""Dtype and place abstractions.

Capability analog of the reference's ``paddle/phi/common/`` scalar/dtype/place
layer (SURVEY C3; reference ``paddle/phi/common/place.h``, ``data_type.h``),
re-expressed for a JAX/XLA runtime: dtypes are jnp dtypes, a Place names an
XLA device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Canonical dtype table: paddle-style name -> jnp dtype.
_DTYPE_TABLE = {
    "float64": jnp.float64,
    "float32": jnp.float32,
    "float16": jnp.float16,
    "bfloat16": jnp.bfloat16,
    "int64": jnp.int64,
    "int32": jnp.int32,
    "int16": jnp.int16,
    "int8": jnp.int8,
    "uint8": jnp.uint8,
    "bool": jnp.bool_,
    "complex64": jnp.complex64,
    "complex128": jnp.complex128,
}

_ALIASES = {
    "float": "float32",
    "double": "float64",
    "half": "float16",
    "int": "int32",
    "long": "int64",
}

float32 = jnp.float32
float64 = jnp.float64
float16 = jnp.float16
bfloat16 = jnp.bfloat16
int64 = jnp.int64
int32 = jnp.int32
int16 = jnp.int16
int8 = jnp.int8
uint8 = jnp.uint8
bool_ = jnp.bool_
complex64 = jnp.complex64
complex128 = jnp.complex128


def convert_dtype(dtype):
    """Normalize any dtype spec (str, np/jnp dtype, None) to a numpy dtype."""
    if dtype is None:
        return None
    if isinstance(dtype, str):
        name = _ALIASES.get(dtype, dtype)
        if name in _DTYPE_TABLE:
            return np.dtype(_DTYPE_TABLE[name])
        return np.dtype(name)
    try:
        return np.dtype(dtype)
    except TypeError:
        # jnp scalar types like jnp.float32
        return np.dtype(np.dtype(dtype).name)


def dtype_name(dtype) -> str:
    d = np.dtype(dtype)
    return d.name


def is_floating(dtype) -> bool:
    d = np.dtype(dtype)
    return jnp.issubdtype(d, jnp.floating)


def is_integer(dtype) -> bool:
    d = np.dtype(dtype)
    return jnp.issubdtype(d, jnp.integer)


def is_complex(dtype) -> bool:
    d = np.dtype(dtype)
    return jnp.issubdtype(d, jnp.complexfloating)


class Place:
    """Device identity. Analog of ``phi::Place`` (reference
    ``paddle/phi/common/place.h``) over jax devices."""

    def __init__(self, device: "jax.Device | str | Place | None" = None):
        if isinstance(device, Place):
            self._device = device._device
        elif isinstance(device, str):
            kind, _, idx = device.partition(":")
            idx = int(idx) if idx else 0
            devs = [d for d in jax.devices() if d.platform == _platform(kind)]
            if not devs:
                devs = jax.devices()
            if not 0 <= idx < len(devs):
                raise ValueError(
                    f"Place({device!r}): index {idx} is past the "
                    f"{len(devs)} {devs[0].platform} device(s) there are")
            self._device = devs[idx]
        elif device is None:
            self._device = jax.devices()[0]
        else:
            self._device = device

    @property
    def device(self):
        return self._device

    @property
    def platform(self) -> str:
        return self._device.platform

    def is_tpu_place(self) -> bool:
        return self._device.platform == "tpu"

    def is_cpu_place(self) -> bool:
        return self._device.platform == "cpu"

    def __eq__(self, other):
        return isinstance(other, Place) and self._device == other._device

    def __hash__(self):
        return hash(self._device)

    def __repr__(self):
        return f"Place({self._device.platform}:{self._device.id})"


def _platform(kind: str) -> str:
    kind = kind.lower()
    if kind in ("tpu", "xla"):
        return "tpu"
    if kind in ("gpu", "cuda"):
        return "gpu"
    if kind not in ("cpu", ""):
        try:  # registered custom device types resolve to their platform
            from ..device.custom import resolve_type
            r = resolve_type(kind)
            if r is not None:
                return r
        except ImportError:
            pass
    return "cpu"


def TPUPlace(idx: int = 0) -> Place:
    return Place(f"tpu:{idx}")


def CPUPlace(idx: int = 0) -> Place:
    return Place(f"cpu:{idx}")


def get_default_dtype() -> np.dtype:
    from . import state

    return state.DEFAULT_DTYPE


def set_default_dtype(dtype) -> None:
    from . import state

    state.DEFAULT_DTYPE = convert_dtype(dtype)
