"""paddle_tpu — a TPU-native deep-learning framework with the capabilities of
PaddlePaddle (reference surveyed in SURVEY.md), built on jax/XLA/pallas/pjit.

Top-level namespace mirrors ``import paddle``: tensor factories and ops live
here, subpackages ``nn``, ``optimizer``, ``amp``, ``io``, ``jit``,
``distributed``, ``static`` mirror paddle's.
"""
from __future__ import annotations

from .core import state as _state
from .core.tensor import Tensor, Parameter, to_tensor  # noqa: F401
from .core.dtype import (  # noqa: F401
    Place, TPUPlace, CPUPlace, set_default_dtype, get_default_dtype,
    float64, float32, float16, bfloat16, int64, int32, int16, int8, uint8,
    bool_, complex64, complex128,
)
from .core.autograd import no_grad, enable_grad, set_grad_enabled, grad  # noqa: F401
from .core import autograd as _autograd_mod
from .ops import *  # noqa: F401,F403
from .ops import creation as _creation

# framework-level helpers (paddle.* parity)
from .core.state import seed, get_flags, set_flags  # noqa: F401
from .core.lazy import LazyGuard  # noqa: F401

from . import ops  # noqa: F401
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import amp  # noqa: F401
from . import io  # noqa: F401
from . import jit  # noqa: F401
from . import autograd  # noqa: F401
from . import distributed  # noqa: F401
from . import incubate  # noqa: F401
from . import static  # noqa: F401
from . import metric  # noqa: F401
from . import fft  # noqa: F401
from . import signal  # noqa: F401
from . import distribution  # noqa: F401
from . import observability  # noqa: F401
from . import profiler  # noqa: F401
from . import device  # noqa: F401
from .device import set_device, get_device  # noqa: F401
from .device.custom import CustomPlace  # noqa: F401
from . import quantization  # noqa: F401
from . import text  # noqa: F401
from . import audio  # noqa: F401
from . import sparse  # noqa: F401
from .core import errors  # noqa: F401
from . import inference  # noqa: F401
from . import utils  # noqa: F401
from . import regularizer  # noqa: F401
from . import version  # noqa: F401
from . import vision  # noqa: F401
from . import hapi  # noqa: F401
from . import analysis  # noqa: F401
from . import resilience  # noqa: F401
from .hapi import Model  # noqa: F401
from .hapi import callbacks  # noqa: F401
from .distributed.parallel import DataParallel  # noqa: F401
from . import framework  # noqa: F401
from .framework import save, load  # noqa: F401
from .jit import to_static  # noqa: F401
from . import geometric  # noqa: F401
from . import sysconfig  # noqa: F401
from . import reader  # noqa: F401
from . import dataset  # noqa: F401
from . import onnx  # noqa: F401
from . import cost_model  # noqa: F401
from .hapi import hub  # noqa: F401
from . import tensor  # noqa: F401  (compat: paddle.tensor op namespace)
from . import base  # noqa: F401

import numpy as _np


def is_grad_enabled():
    return _state.is_grad_enabled()


def in_dynamic_mode():
    return True


def device_count():
    import jax
    return len(jax.devices())


def get_device():
    from .device import get_device as _gd
    return _gd()


def set_device(device):
    # route through device.set_device: it resolves registered custom
    # device types and raises on unknown ones (a bare Place(str) would
    # silently map them to cpu); reference returns the Place — a
    # CustomPlace (keeping the registered type name) for custom types
    from .device import set_device as _sd
    from .device.custom import CustomPlace, registered_types
    resolved = _sd(device)
    dtype_name = str(device).split(":", 1)[0].lower()
    if dtype_name in registered_types():
        idx = int(str(device).split(":", 1)[1]) if ":" in str(device) else 0
        return CustomPlace(dtype_name, idx)
    return Place(resolved)


def is_compiled_with_cuda():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_tpu():
    import jax
    return jax.devices()[0].platform == "tpu"


def iinfo(dtype):
    """Reference ``paddle.iinfo``."""
    from .core.dtype import convert_dtype
    return _np.iinfo(_np.dtype(convert_dtype(dtype)))


def finfo(dtype):
    """Reference ``paddle.finfo`` (works for bfloat16 via ml_dtypes)."""
    import ml_dtypes
    from .core.dtype import convert_dtype
    d = convert_dtype(dtype)
    try:
        return _np.finfo(_np.dtype(d))
    except Exception:
        return ml_dtypes.finfo(d)


def batch(reader, batch_size, drop_last=False):
    """Reference ``paddle.batch`` (legacy reader combinator)."""
    def batched():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf
    return batched


def summary(layer, input_size=None, dtypes=None):
    n_params = sum(p.size for p in layer.parameters())
    trainable = sum(p.size for p in layer.parameters() if not p.stop_gradient)
    print(f"Total params: {n_params}\nTrainable params: {trainable}")
    return {"total_params": n_params, "trainable_params": trainable}


def flops(net, input_size, custom_ops=None, print_detail=False):
    """Reference ``paddle.flops`` (``hapi/dynamic_flops.py``) — but exact,
    not per-layer-formula: the forward is lowered and XLA's cost analysis
    reports the compiled program's FLOPs (fusion-aware, the number the MXU
    will actually execute)."""
    import jax

    import numpy as _np

    x = to_tensor(_np.zeros(input_size, _np.float32))

    def fwd(v):
        from .core import tensor as _tm
        old = _tm.set_tracker(None)
        try:
            with no_grad():
                out = net(Tensor(v))
        finally:
            _tm.set_tracker(old)
        return out._data if isinstance(out, Tensor) else out

    compiled = jax.jit(fwd).lower(x._read()).compile()
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, list):  # some backends return [dict]
        cost = cost[0] if cost else {}
    total = int(cost.get("flops", 0))
    if print_detail:
        print(f"FLOPs (XLA cost analysis): {total:,}")
    return total


__version__ = "0.1.0"
