"""paddle_tpu.nn.Layer — module base class.

Capability analog of ``paddle.nn.Layer`` (reference
``python/paddle/nn/layer/layers.py:334``): parameter/buffer/sublayer
registries, forward hooks, state_dict round-trip, train/eval mode, dtype/
device movement. TPU-native storage: parameters are ``Parameter`` facades over
jax.Arrays; ``state_dict`` yields host-transferable tensors for orbax-style
checkpointing in ``paddle_tpu.framework.save``.
"""
from __future__ import annotations

import collections
from typing import Iterator, Optional

import jax.numpy as jnp
import numpy as np

from ..core import scope as _scope
from ..core.dtype import convert_dtype
from ..core.tensor import Parameter, Tensor
from . import initializer as I


class ParamAttr:
    """Analog of ``paddle.ParamAttr`` (reference python/paddle/base/param_attr.py)."""

    def __init__(self, name=None, initializer=None, learning_rate=1.0,
                 regularizer=None, trainable=True, need_clip=True):
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.need_clip = need_clip


_layer_counters: dict[str, int] = collections.defaultdict(int)


class HookRemoveHelper:
    def __init__(self, hooks, hook_id):
        self._hooks = hooks
        self._hook_id = hook_id

    def remove(self):
        self._hooks.pop(self._hook_id, None)


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        cls = type(self).__name__.lower()
        _layer_counters[cls] += 1
        self._full_name = f"{name_scope or cls}_{_layer_counters[cls] - 1}"
        self._dtype = convert_dtype(dtype) or np.dtype("float32")
        self._parameters: dict[str, Optional[Parameter]] = \
            collections.OrderedDict()
        self._buffers: dict[str, Optional[Tensor]] = collections.OrderedDict()
        self._non_persistable_buffer_names = set()
        self._sub_layers: dict[str, "Layer"] = collections.OrderedDict()
        self._forward_pre_hooks = collections.OrderedDict()
        self._forward_post_hooks = collections.OrderedDict()
        self.training = True

    # --- construction helpers -------------------------------------------
    def create_parameter(self, shape, attr=None, dtype=None,
                         is_bias=False, default_initializer=None):
        """Reference ``layers.py`` create_parameter: resolve ParamAttr +
        initializer, build a Parameter. ``attr=False`` -> no parameter."""
        if attr is False:
            return None
        if attr is None:
            attr = ParamAttr()
        elif isinstance(attr, str):
            attr = ParamAttr(name=attr)
        elif isinstance(attr, I.Initializer):
            attr = ParamAttr(initializer=attr)
        dtype = convert_dtype(dtype) or self._dtype
        init = attr.initializer or default_initializer
        if init is None:
            init = I.Constant(0.0) if is_bias else I._default_weight_init
        elif not isinstance(init, I.Initializer) and not callable(init):
            init = I.to_initializer(init)
        from ..core import lazy as _lazy
        if _lazy.in_lazy_mode():
            # LazyGuard: no storage — abstract shape/dtype only
            import jax
            data = jax.ShapeDtypeStruct(
                tuple(int(s) for s in shape), jnp.dtype(dtype))
        else:
            data = init(tuple(int(s) for s in shape), jnp.dtype(dtype))
        p = Parameter(data, trainable=attr.trainable, name=attr.name)
        p.optimize_attr["learning_rate"] = attr.learning_rate
        p.regularizer = attr.regularizer
        p.need_clip = attr.need_clip
        return p

    def add_parameter(self, name, parameter):
        if parameter is not None and not isinstance(parameter, Parameter):
            raise TypeError(f"expected Parameter, got {type(parameter)}")
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer):
        if sublayer is not None and not isinstance(sublayer, Layer):
            raise TypeError(f"expected Layer, got {type(sublayer)}")
        self._sub_layers[str(name)] = sublayer
        if sublayer is not None:
            sublayer._scope_name = str(name)
        return sublayer

    def register_buffer(self, name, tensor, persistable=True):
        if tensor is not None and not isinstance(tensor, Tensor):
            tensor = Tensor(tensor)
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    # --- attribute protocol ---------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        sublayers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ before assigning params")
            for d in (sublayers, buffers):
                if d is not None:
                    d.pop(name, None)
            params[name] = value
        elif isinstance(value, Layer):
            if sublayers is None:
                raise RuntimeError("call Layer.__init__ before assigning sublayers")
            for d in (params, buffers):
                if d is not None:
                    d.pop(name, None)
            sublayers[name] = value
            value._scope_name = name
        elif buffers is not None and name in buffers:
            if value is not None and not isinstance(value, Tensor):
                value = Tensor(value)
            buffers[name] = value
        else:
            if params is not None and name in params:
                if value is None:
                    params[name] = None
                    return
                del params[name]
            if sublayers is not None and name in sublayers:
                if value is None:
                    sublayers[name] = None
                    return
                del sublayers[name]
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        return list(super().__dir__()) + list(self._parameters) + \
            list(self._buffers) + list(self._sub_layers)

    # --- iteration ------------------------------------------------------
    def named_members(self, get_members_fn, prefix="", include_self=True,
                      layers_set=None):
        layers_set = layers_set if layers_set is not None else set()
        for lname, layer in self.named_sublayers(
                prefix=prefix, include_self=include_self):
            if id(layer) in layers_set:
                continue
            layers_set.add(id(layer))
            for k, v in get_members_fn(layer):
                if v is None:
                    continue
                yield (lname + "." + k if lname else k), v

    def parameters(self, include_sublayers=True) -> list:
        return [p for _, p in self.named_parameters(
            include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True):
        if not include_sublayers:
            for k, v in self._parameters.items():
                if v is not None:
                    yield k, v
            return
        seen = set()
        for name, p in self.named_members(
                lambda l: l._parameters.items(), prefix=prefix):
            if id(p) in seen:
                continue
            seen.add(id(p))
            yield name, p

    def buffers(self, include_sublayers=True) -> list:
        return [b for _, b in self.named_buffers(
            include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        if not include_sublayers:
            for k, v in self._buffers.items():
                if v is not None:
                    yield k, v
            return
        for name, b in self.named_members(
                lambda l: l._buffers.items(), prefix=prefix):
            yield name, b

    def children(self) -> Iterator["Layer"]:
        for _, l in self.named_children():
            yield l

    def named_children(self):
        seen = set()
        for name, l in self._sub_layers.items():
            if l is not None and id(l) not in seen:
                seen.add(id(l))
                yield name, l

    def sublayers(self, include_self=False) -> list:
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def named_sublayers(self, prefix="", include_self=False, layers_set=None):
        layers_set = layers_set if layers_set is not None else set()
        if id(self) in layers_set:
            return
        layers_set.add(id(self))
        if include_self:
            yield prefix, self
        for name, l in self._sub_layers.items():
            if l is None:
                continue
            sub_prefix = prefix + ("." if prefix else "") + name
            yield from l.named_sublayers(
                prefix=sub_prefix, include_self=True, layers_set=layers_set)

    def apply(self, fn):
        for l in self.children():
            l.apply(fn)
        fn(self)
        return self

    # --- mode / dtype / device -----------------------------------------
    def train(self):
        self.training = True
        for l in self.sublayers():
            l.training = True
        return self

    def eval(self):
        self.training = False
        for l in self.sublayers():
            l.training = False
        return self

    def to(self, device=None, dtype=None, blocking=None):
        dtype = convert_dtype(dtype)

        def move(t):
            if t is None:
                return None
            val = t._read()
            if dtype is not None and jnp.issubdtype(val.dtype, jnp.floating):
                val = val.astype(dtype)
            t._write(val)
            return t

        for l in self.sublayers(include_self=True):
            for k in l._parameters:
                move(l._parameters[k])
            for k in l._buffers:
                move(l._buffers[k])
            if dtype is not None:
                l._dtype = np.dtype(str(jnp.dtype(dtype)))
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    # --- state dict -----------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True):
        dest = destination if destination is not None \
            else collections.OrderedDict()
        for name, p in self.named_parameters(
                include_sublayers=include_sublayers):
            dest[structured_name_prefix + name] = p
        for name, b in self.named_buffers(
                include_sublayers=include_sublayers):
            leaf = name.rsplit(".", 1)[-1]
            if leaf in self._find_owner(name)._non_persistable_buffer_names:
                continue
            dest[structured_name_prefix + name] = b
        return dest

    def _find_owner(self, dotted_name):
        layer = self
        parts = dotted_name.split(".")[:-1]
        for p in parts:
            nxt = layer._sub_layers.get(p)
            if nxt is None:
                return layer
            layer = nxt
        return layer

    def set_state_dict(self, state_dict, use_structured_name=True):
        missing, unexpected = [], []
        own = dict(self.state_dict())
        matched = set()
        for name, value in state_dict.items():
            target = own.get(name)
            if target is None:
                unexpected.append(name)
                continue
            matched.add(name)
            val = value._read() if isinstance(value, Tensor) else \
                jnp.asarray(np.asarray(value))
            if tuple(val.shape) != tuple(target._read().shape):
                raise ValueError(
                    f"shape mismatch for {name}: checkpoint {tuple(val.shape)}"
                    f" vs model {tuple(target._read().shape)}")
            target._write(val.astype(target._read().dtype))
        missing = [k for k in own if k not in matched]
        return missing, unexpected

    load_dict = set_state_dict

    # --- hooks ----------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        hid = id(hook)
        self._forward_pre_hooks[hid] = hook
        return HookRemoveHelper(self._forward_pre_hooks, hid)

    def register_forward_post_hook(self, hook):
        hid = id(hook)
        self._forward_post_hooks[hid] = hook
        return HookRemoveHelper(self._forward_post_hooks, hid)

    # --- call -----------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    # the key under which the parent registered this layer: its name in
    # the compiled program's phase scopes (``gpt/block_3/attn``).  Not
    # ``_full_name``, whose counter differs from process to process.
    _scope_name = None

    def __call__(self, *inputs, **kwargs):
        with _scope.phase(self._scope_name or type(self).__name__):
            for hook in list(self._forward_pre_hooks.values()):
                out = hook(self, inputs)
                if out is not None:
                    inputs = out if isinstance(out, tuple) else (out,)
            outputs = self.forward(*inputs, **kwargs)
            for hook in list(self._forward_post_hooks.values()):
                res = hook(self, inputs, outputs)
                if res is not None:
                    outputs = res
            return outputs

    def full_name(self):
        return self._full_name

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, l in self._sub_layers.items():
            if l is None:
                continue
            sub = repr(l).split("\n")
            sub = [sub[0]] + ["  " + s for s in sub[1:]]
            lines.append(f"({name}): " + "\n".join(sub))
        main = f"{type(self).__name__}({extra}"
        if lines:
            main += "\n" + "\n".join("  " + ln for ln in lines) + "\n"
        return main + ")"

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()
