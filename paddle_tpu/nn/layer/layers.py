"""nn.Layer — the module system
(upstream: python/paddle/nn/layer/layers.py, ~same public surface)."""
from __future__ import annotations

import collections
from typing import Iterator, Optional, Tuple

import numpy as np

from ...framework import state as _state_registry
from ...framework.core import EagerParamBase, Parameter, Tensor, no_grad
from ...framework.dtype import convert_dtype, to_np_dtype


class LazyGuard:
    """Layers constructed under it create their parameters with shape
    and dtype and no array (upstream: paddle.LazyGuard): the payload is
    a ``jax.ShapeDtypeStruct`` until the caller hands each parameter its
    array (``p._data = leaf``). Nothing is allocated, no initializer
    runs, and ``p.shape`` / ``p.dtype`` work, so a model larger than
    the float32-then-cast build could hold is filled leaf by leaf in
    the type it is served in."""

    _depth = 0

    def __enter__(self):
        LazyGuard._depth += 1
        return self

    def __exit__(self, *exc):
        LazyGuard._depth -= 1
        return False


class HookRemoveHelper:
    def __init__(self, hooks, hook_id):
        self._hooks = hooks
        self._hook_id = hook_id

    def remove(self):
        self._hooks.pop(self._hook_id, None)


def make_parameter(shape, dtype="float32", name=None, attr=None,
                   is_bias=False, default_initializer=None):
    """Single definition of the ParamAttr/initializer wiring behind
    both ``Layer.create_parameter`` and the standalone
    ``paddle.create_parameter``."""
    from .. import initializer as I
    from ..param_attr import ParamAttr

    attr = ParamAttr._to_attr(attr)
    if attr is False:
        return None
    if attr is not None and attr.initializer is not None:
        init = attr.initializer
    elif default_initializer is not None:
        init = default_initializer
    else:
        init = I.Constant(0.0) if is_bias else I.XavierUniform()
    if LazyGuard._depth:
        import jax

        data = jax.ShapeDtypeStruct(tuple(int(d) for d in shape),
                                    to_np_dtype(dtype))
    else:
        data = init(list(shape), to_np_dtype(dtype))
    p = Parameter(data, name=name or (attr.name if attr else None))
    if attr is not None:
        p.optimize_attr["learning_rate"] = attr.learning_rate
        p.regularizer = attr.regularizer
        p.trainable = attr.trainable
        p.stop_gradient = not attr.trainable
    return p


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        self.training = True
        self._dtype = dtype
        self._parameters = collections.OrderedDict()
        self._sub_layers = collections.OrderedDict()
        self._buffers = collections.OrderedDict()
        self._non_persistable_buffer_names_set = set()
        self._forward_pre_hooks = collections.OrderedDict()
        self._forward_post_hooks = collections.OrderedDict()
        self._hook_id = 0
        self._name_scope = name_scope or self.__class__.__name__.lower()
        self._casted_by_pure_fp16 = False
        _state_registry.register_layer(self)

    # -- attribute interception -------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, EagerParamBase):
            if params is None:
                raise RuntimeError("call Layer.__init__() first")
            params[name] = value
            for d in (layers, buffers):
                if d is not None:
                    d.pop(name, None)
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call Layer.__init__() first")
            layers[name] = value
            for d in (params, buffers):
                if d is not None:
                    d.pop(name, None)
            self.__dict__.pop(name, None)
        elif isinstance(value, Tensor) and buffers is not None and (
            name in buffers
        ):
            buffers[name] = value
        else:
            if params is not None:
                params.pop(name, None)
            if layers is not None:
                layers.pop(name, None)
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute '{name}'"
        )

    def __delattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        return (
            list(super().__dir__())
            + list(self._parameters)
            + list(self._sub_layers)
            + list(self._buffers)
        )

    # -- forward -----------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in list(self._forward_pre_hooks.values()):
            result = hook(self, inputs)
            if result is not None:
                inputs = result if isinstance(result, tuple) else (result,)
        outputs = self.forward(*inputs, **kwargs)
        for hook in list(self._forward_post_hooks.values()):
            result = hook(self, inputs, outputs)
            if result is not None:
                outputs = result
        return outputs

    def register_forward_pre_hook(self, hook):
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_pre_hooks, self._hook_id)

    def register_forward_post_hook(self, hook):
        self._hook_id += 1
        self._forward_post_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_post_hooks, self._hook_id)

    # -- parameter management ---------------------------------------------
    def create_parameter(self, shape, attr=None, dtype=None,
                         is_bias=False, default_initializer=None):
        return make_parameter(
            shape, dtype or self._dtype, attr=attr, is_bias=is_bias,
            default_initializer=default_initializer)

    def add_parameter(self, name, parameter):
        if parameter is None:
            self._parameters[name] = None
        else:
            self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer):
        self._sub_layers[str(name)] = sublayer
        return sublayer

    def register_buffer(self, name, tensor, persistable=True):
        self._buffers[name] = tensor
        if tensor is not None:
            tensor.persistable = persistable
        if not persistable:
            self._non_persistable_buffer_names_set.add(name)
        return tensor

    # -- traversal ---------------------------------------------------------
    def children(self) -> Iterator["Layer"]:
        for _, l in self.named_children():
            yield l

    def named_children(self):
        seen = set()
        for name, l in self._sub_layers.items():
            if l is not None and id(l) not in seen:
                seen.add(id(l))
                yield name, l

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def named_sublayers(self, prefix="", include_self=False, layers_set=None):
        if layers_set is None:
            layers_set = set()
        if include_self and id(self) not in layers_set:
            layers_set.add(id(self))
            yield prefix, self
        for name, l in self.named_children():
            if l is None or id(l) in layers_set:
                continue
            layers_set.add(id(l))
            sub_prefix = prefix + ("." if prefix else "") + name
            yield sub_prefix, l
            yield from l.named_sublayers(
                prefix=sub_prefix, include_self=False, layers_set=layers_set
            )

    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(
            include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        layers = (
            [(prefix, self)]
            + [
                (prefix + ("." if prefix else "") + n, l)
                for n, l in self.named_sublayers(prefix="")
            ]
            if include_sublayers
            else [(prefix, self)]
        )
        # rebuild names properly
        seen = set()

        def walk(layer, pfx):
            for name, p in layer._parameters.items():
                if p is not None and id(p) not in seen:
                    seen.add(id(p))
                    yield (pfx + ("." if pfx else "") + name, p)
            if include_sublayers:
                for name, l in layer.named_children():
                    yield from walk(l, pfx + ("." if pfx else "") + name)

        yield from walk(self, prefix)

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(
            include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        seen = set()

        def walk(layer, pfx):
            for name, b in layer._buffers.items():
                if b is not None and id(b) not in seen:
                    seen.add(id(b))
                    yield (pfx + ("." if pfx else "") + name, b)
            if include_sublayers:
                for name, l in layer.named_children():
                    yield from walk(l, pfx + ("." if pfx else "") + name)

        yield from walk(self, prefix)

    def _state_tensors(self):
        """All mutable tensors (params + buffers) — for the compiled step."""
        out = [p for p in self.parameters()]
        out += [b for b in self.buffers()]
        return out

    # -- state dict --------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True):
        dest = destination if destination is not None else collections.OrderedDict()
        for name, p in self.named_parameters(
            prefix=structured_name_prefix.rstrip("."),
            include_sublayers=include_sublayers,
        ):
            dest[name] = p
        for name, b in self.named_buffers(
            prefix=structured_name_prefix.rstrip("."),
            include_sublayers=include_sublayers,
        ):
            # skip non-persistable buffers (matches reference behavior)
            leaf = name.split(".")[-1]
            owner = self
            parts = name.split(".")[:-1]
            try:
                for part in parts:
                    owner = owner._sub_layers[part]
                if leaf in owner._non_persistable_buffer_names_set:
                    continue
            except (KeyError, AttributeError):
                pass
            dest[name] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        missing, unexpected = [], list(state_dict.keys())
        own = self.state_dict()
        for name, target in own.items():
            if name in state_dict:
                unexpected.remove(name)
                src = state_dict[name]
                data = src._data if isinstance(src, Tensor) else np.asarray(src)
                if tuple(np.shape(data)) != tuple(target.shape):
                    raise ValueError(
                        f"shape mismatch for {name}: "
                        f"{np.shape(data)} vs {tuple(target.shape)}"
                    )
                target.set_value(data)
            else:
                missing.append(name)
        return missing, unexpected

    load_dict = set_state_dict
    set_dict = set_state_dict

    # -- mode / dtype / device --------------------------------------------
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

    def apply(self, fn):
        for l in self.children():
            l.apply(fn)
        fn(self)
        return self

    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            self._convert_dtype(dtype)
        return self

    def astype(self, dtype):
        self._convert_dtype(dtype)
        return self

    def float(self):
        return self.astype("float32")

    def half(self):
        return self.astype("float16")

    def bfloat16(self):
        return self.astype("bfloat16")

    def _convert_dtype(self, dtype):
        d = to_np_dtype(dtype)
        for p in self.parameters():
            if p.dtype.is_floating_point:
                p._data = p._data.astype(d)
        for b in self.buffers():
            if b is not None and b.dtype.is_floating_point:
                b._data = b._data.astype(d)
        self._dtype = convert_dtype(dtype).name

    def full_name(self):
        return self._name_scope

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = [extra] if extra else []
        for name, l in self.named_children():
            mod_str = repr(l)
            mod_str = "\n".join(
                "  " + line for line in mod_str.split("\n")
            )
            lines.append(f"({name}): " + mod_str.lstrip())
        main = self.__class__.__name__ + "("
        if lines:
            main += "\n  " + "\n  ".join(lines) + "\n"
        return main + ")"


class Sequential(Layer):
    def __init__(self, *layers):
        super().__init__()
        if len(layers) == 1 and isinstance(layers[0], collections.OrderedDict):
            for name, l in layers[0].items():
                self.add_sublayer(name, l)
        else:
            for i, l in enumerate(layers):
                if isinstance(l, (tuple, list)):
                    self.add_sublayer(l[0], l[1])
                else:
                    self.add_sublayer(str(i), l)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return Sequential(*list(self._sub_layers.values())[idx])
        return list(self._sub_layers.values())[idx]

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())

    def forward(self, x):
        for layer in self._sub_layers.values():
            x = layer(x)
        return x


class LayerList(Layer):
    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            for i, l in enumerate(sublayers):
                self.add_sublayer(str(i), l)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return LayerList(list(self._sub_layers.values())[idx])
        return list(self._sub_layers.values())[idx]

    def __setitem__(self, idx, layer):
        self._sub_layers[str(idx)] = layer

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())

    def append(self, layer):
        self.add_sublayer(str(len(self._sub_layers)), layer)
        return self

    def insert(self, index, layer):
        layers = list(self._sub_layers.values())
        layers.insert(index, layer)
        self._sub_layers.clear()
        for i, l in enumerate(layers):
            self._sub_layers[str(i)] = l

    def extend(self, layers):
        for l in layers:
            self.append(l)
        return self

    def forward(self, *args, **kwargs):
        raise NotImplementedError("LayerList is a container")


class ParameterList(Layer):
    def __init__(self, parameters=None):
        super().__init__()
        if parameters is not None:
            for i, p in enumerate(parameters):
                self.add_parameter(str(i), p)

    def __getitem__(self, idx):
        return list(self._parameters.values())[idx]

    def __len__(self):
        return len(self._parameters)

    def __iter__(self):
        return iter(self._parameters.values())

    def append(self, parameter):
        self.add_parameter(str(len(self._parameters)), parameter)
        return self


class LayerDict(Layer):
    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            self.update(sublayers)

    def __getitem__(self, key):
        return self._sub_layers[key]

    def __setitem__(self, key, layer):
        self.add_sublayer(key, layer)

    def __delitem__(self, key):
        del self._sub_layers[key]

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers)

    def __contains__(self, key):
        return key in self._sub_layers

    def keys(self):
        return self._sub_layers.keys()

    def values(self):
        return self._sub_layers.values()

    def items(self):
        return self._sub_layers.items()

    def update(self, sublayers):
        items = sublayers.items() if isinstance(sublayers, dict) else sublayers
        for k, v in items:
            self.add_sublayer(k, v)


class Identity(Layer):
    def __init__(self, *args, **kwargs):
        super().__init__()

    def forward(self, x):
        return x
