"""Layer base class (ref: python/paddle/nn/layer/layers.py:412 `class Layer`).

Stateful shell over a functional core: parameters are `Parameter` Tensors
owned by the layer; `paddle_tpu.jit.functional_state`/`functional_call`
swap their `.data` with traced arrays so any Layer is a pure function for
jit/grad/pjit — the TPU-native answer to the reference's dygraph/static split.
"""
from __future__ import annotations

import contextlib
from collections import OrderedDict
from typing import Callable, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...framework import core
from ...tensor import Parameter, Tensor
from .. import initializer as I

# Monotonic counter bumped on ANY structural mutation of ANY layer
# (param/sublayer/buffer added, removed, or replaced). Callers that
# cache a layer's state_dict STRUCTURE (e.g. the SOT guard layer's
# per-call param map) key their cache on this; .data updates
# (optimizer steps, set_state_dict) mutate Tensor objects in place and
# deliberately do NOT bump it.
_STRUCT_VERSION = [0]


def struct_version() -> int:
    return _STRUCT_VERSION[0]


def bump_struct_version() -> None:
    _STRUCT_VERSION[0] += 1


class ParamAttr:
    """ref: python/paddle/base/param_attr.py."""

    def __init__(self, name=None, initializer=None, learning_rate=1.0,
                 regularizer=None, trainable=True, need_clip=True):
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.need_clip = need_clip

    @staticmethod
    def _to_attr(attr):
        if attr is None:
            return ParamAttr()
        if isinstance(attr, ParamAttr):
            return attr
        if isinstance(attr, str):
            return ParamAttr(name=attr)
        if isinstance(attr, I.Initializer):
            return ParamAttr(initializer=attr)
        if attr is False:
            return False
        return ParamAttr()


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        object.__setattr__(self, "_parameters", OrderedDict())
        object.__setattr__(self, "_sub_layers", OrderedDict())
        object.__setattr__(self, "_buffers", OrderedDict())
        self._non_persistable_buffer_names = set()
        self.training = True
        self._dtype = core.convert_dtype(dtype)
        self._name_scope = name_scope or self.__class__.__name__.lower()
        self._forward_pre_hooks = OrderedDict()
        self._forward_post_hooks = OrderedDict()
        self._casted_dtype = None  # set by .to(dtype)/amp decorate

    # -- attribute routing (ref: layers.py __setattr__) ---------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call super().__init__() first")
            for d in (layers, buffers):
                if d is not None and name in d:
                    del d[name]
            params[name] = value
            self.__dict__.pop(name, None)
            bump_struct_version()
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call super().__init__() first")
            for d in (params, buffers):
                if d is not None and name in d:
                    del d[name]
            layers[name] = value
            value._scope_name = name
            self.__dict__.pop(name, None)
            bump_struct_version()
        else:
            if params is not None and name in params:
                bump_struct_version()
                if value is None:
                    params[name] = None
                    return
                del params[name]
            if layers is not None and name in layers:
                del layers[name]
                bump_struct_version()
            if buffers is not None and name in buffers:
                bump_struct_version()
                if value is None or isinstance(value, Tensor):
                    buffers[name] = value
                    return
                del buffers[name]
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                bump_struct_version()
                return
        object.__delattr__(self, name)

    # -- parameter/buffer creation -----------------------------------------
    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        attr = ParamAttr._to_attr(attr)
        if attr is False:
            return None
        dtype = core.convert_dtype(dtype) or self._dtype or core.get_default_dtype()
        init = default_initializer or attr.initializer
        if init is None:
            init = I.Constant(0.0) if is_bias else I.XavierUniform()
        data = init(tuple(int(s) for s in shape), dtype)
        p = Parameter(data, name=attr.name or "")
        p.trainable = attr.trainable
        if not attr.trainable:
            p.stop_gradient = True
        p.optimize_attr = {"learning_rate": attr.learning_rate}
        p.regularizer = attr.regularizer
        return p

    def add_parameter(self, name, parameter):
        if parameter is None:
            self._parameters[name] = None
        else:
            self._parameters[name] = parameter
        bump_struct_version()
        return parameter

    def add_sublayer(self, name, sublayer):
        self._sub_layers[str(name)] = sublayer
        if isinstance(sublayer, Layer):
            sublayer._scope_name = str(name)
        bump_struct_version()
        return sublayer

    def register_buffer(self, name, tensor, persistable=True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        bump_struct_version()
        return tensor

    # -- traversal ----------------------------------------------------------
    def named_parameters(self, prefix="", include_sublayers=True
                         ) -> Iterator[Tuple[str, Parameter]]:
        seen = set()
        for name, layer in self.named_sublayers(prefix=prefix, include_self=True):
            if not include_sublayers and layer is not self:
                continue
            for pname, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield (f"{name}.{pname}" if name else pname), p

    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(
            include_sublayers=include_sublayers)]

    def named_sublayers(self, prefix="", include_self=False, layers_set=None):
        if layers_set is None:
            layers_set = set()
        if id(self) in layers_set:
            return
        layers_set.add(id(self))
        if include_self:
            yield prefix, self
        for name, layer in self._sub_layers.items():
            if layer is None:
                continue
            sub_prefix = f"{prefix}.{name}" if prefix else name
            yield from layer.named_sublayers(prefix=sub_prefix, include_self=True,
                                             layers_set=layers_set)

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def children(self):
        return iter(l for l in self._sub_layers.values() if l is not None)

    def named_children(self):
        return iter((n, l) for n, l in self._sub_layers.items() if l is not None)

    def named_buffers(self, prefix="", include_sublayers=True):
        seen = set()
        if not include_sublayers:
            for bname, b in self._buffers.items():
                if b is not None:
                    yield (f"{prefix}.{bname}" if prefix else bname), b
            return
        for name, layer in self.named_sublayers(prefix=prefix, include_self=True):
            for bname, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                yield (f"{name}.{bname}" if name else bname), b

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def apply(self, fn):
        for layer in self.children():
            layer.apply(fn)
        fn(self)
        return self

    def full_name(self):
        return self._name_scope

    # -- mode ---------------------------------------------------------------
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

    # -- dtype / device -----------------------------------------------------
    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            dtype = core.convert_dtype(dtype)
            for p in self.parameters():
                if jnp.issubdtype(p.dtype, jnp.floating):
                    p.data = p.data.astype(dtype)
            for b in self.buffers():
                if b is not None and jnp.issubdtype(b.dtype, jnp.floating):
                    b.data = b.data.astype(dtype)
            self._casted_dtype = dtype
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    # -- state dict ---------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True):
        dest = destination if destination is not None else OrderedDict()
        if not include_sublayers:
            # own parameters/buffers only (ref state_dict semantics)
            pre = structured_name_prefix
            if pre and not pre.endswith("."):
                pre += "."
            for name, p in self._parameters.items():
                if p is not None:
                    dest[pre + name] = p
            for name, b in self._buffers.items():
                if b is not None and \
                        name not in self._non_persistable_buffer_names:
                    dest[pre + name] = b
            return dest
        for name, p in self.named_parameters(prefix=structured_name_prefix.rstrip(".")):
            dest[name] = p
        for name, b in self.named_buffers(prefix=structured_name_prefix.rstrip(".")):
            short = name.rsplit(".", 1)[-1]
            if short not in self._non_persistable_buffer_names:
                dest[name] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing, unexpected = [], []
        for name, t in own.items():
            if name in state_dict:
                v = state_dict[name]
                arr = v.data if isinstance(v, Tensor) else jnp.asarray(np.asarray(v))
                t.data = arr.reshape(t.data.shape).astype(t.dtype)
            else:
                missing.append(name)
        for name in state_dict:
            if name not in own:
                unexpected.append(name)
        return missing, unexpected

    load_dict = set_state_dict
    set_dict = set_state_dict

    # -- hooks --------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        handle = _HookHandle(self._forward_pre_hooks)
        self._forward_pre_hooks[handle.id] = hook
        return handle

    def register_forward_post_hook(self, hook):
        handle = _HookHandle(self._forward_post_hooks)
        self._forward_post_hooks[handle.id] = hook
        return handle

    # -- call ---------------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        if jax.core.trace_ctx.is_top_level():
            return self._call(inputs, kwargs)
        # while a trace is in progress every device operation of this
        # layer carries its name (op_name in XProf and compiled text):
        # the name the parent registered it under; the class name at the
        # root and for the numbered entries of a list (a scanned stack
        # runs ONE body: the scope names the kind of layer, not an index)
        name = self.__dict__.get("_scope_name")
        if name is None or name.isdigit():
            name = type(self).__name__
        with jax.named_scope(name):
            return self._call(inputs, kwargs)

    def _call(self, inputs, kwargs):
        for hook in self._forward_pre_hooks.values():
            out = hook(self, inputs)
            if out is not None:
                inputs = out if isinstance(out, tuple) else (out,)
        outputs = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            out = hook(self, inputs, outputs)
            if out is not None:
                outputs = out
        return outputs

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, layer in self._sub_layers.items():
            body = repr(layer).split("\n")
            body = [body[0]] + ["  " + b for b in body[1:]]
            lines.append(f"  ({name}): " + "\n".join(body))
        main = f"{type(self).__name__}({extra}"
        if lines:
            return main + "\n" + "\n".join(lines) + "\n)"
        return main + ")"

    # -- functional bridge (TPU-native; no reference analog) ----------------
    def raw_state(self):
        """dict name -> jax array for all params + persistable buffers."""
        return {k: v.data for k, v in self.state_dict().items()}

    @contextlib.contextmanager
    def use_state(self, arrays: dict):
        """Temporarily swap state arrays (tracers OK) — makes the layer a
        pure function of `arrays` for jit/grad/pjit."""
        sd = self.state_dict()
        saved = {k: sd[k].data for k in sd}
        try:
            for k, v in arrays.items():
                if k in sd:
                    sd[k].data = v
            yield self
        finally:
            for k, v in saved.items():
                sd[k].data = v


class _HookHandle:
    _next = [0]

    def __init__(self, store):
        self.id = _HookHandle._next[0]
        _HookHandle._next[0] += 1
        self._store = store

    def remove(self):
        self._store.pop(self.id, None)
