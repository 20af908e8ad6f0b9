"""nn.Layer: eager module base class.

Analog of /root/reference/python/paddle/fluid/dygraph/layers.py `Layer`
(parameters/sublayers registry, train/eval, forward hooks, state_dict) —
parameters are eager Tensors living on device; state_dict moves to host
numpy for checkpointing (dygraph/checkpoint.py analog).
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dtypes import to_jax_dtype
from ..dygraph import tape
from ..dygraph.tape import Tensor


class Layer:
    def __init__(self, name_scope: Optional[str] = None, dtype="float32"):
        self._parameters: "OrderedDict[str, Tensor]" = OrderedDict()
        self._sub_layers: "OrderedDict[str, Layer]" = OrderedDict()
        self._buffers: "OrderedDict[str, Tensor]" = OrderedDict()
        self.training = True
        self._dtype = dtype
        self._forward_pre_hooks: List[Callable] = []
        self._forward_post_hooks: List[Callable] = []

    # --- parameter management -------------------------------------------
    def create_parameter(self, shape, dtype=None, is_bias=False,
                         default_initializer=None, attr=None) -> Tensor:
        from ..layers.helper import Constant, ParamAttr, Xavier, _init_desc
        from ..core.registry import REGISTRY, LowerCtx
        dtype = dtype or self._dtype
        attr = ParamAttr.to_attr(attr)
        if attr is False:
            return None
        default = default_initializer or \
            (Constant(0.0) if is_bias else Xavier())
        init = _init_desc(attr.initializer, shape, dtype, default)
        ctx = LowerCtx(tape._state.next_key(), is_test=True)
        val = REGISTRY.get(init["type"]).lower(ctx, {}, init["attrs"])["Out"][0]
        t = Tensor(val, stop_gradient=not attr.trainable,
                   name=attr.name, trainable=attr.trainable)
        t.is_param = True  # __setattr__ registers by this flag, so frozen
        # (trainable=False) parameters still land in state_dict like the
        # reference's Parameter class
        return t

    def add_parameter(self, name: str, param: Optional[Tensor]):
        if param is not None:
            self._parameters[name] = param
        return param

    def add_sublayer(self, name: str, layer: "Layer"):
        self._sub_layers[name] = layer
        return layer

    def register_buffer(self, name: str, value: Tensor):
        value.stop_gradient = True
        self._buffers[name] = value
        return value

    def __setattr__(self, name, value):
        if isinstance(value, Tensor) and getattr(value, "is_param", False):
            self.__dict__.setdefault("_parameters", OrderedDict())
            self._parameters[name] = value
            object.__setattr__(self, name, value)
        elif isinstance(value, Layer):
            self.__dict__.setdefault("_sub_layers", OrderedDict())
            self._sub_layers[name] = value
            object.__setattr__(self, name, value)
        else:
            object.__setattr__(self, name, value)

    # --- traversal ------------------------------------------------------
    def named_parameters(self, prefix: str = "") -> Iterator[Tuple[str, Tensor]]:
        for name, p in self._parameters.items():
            yield (f"{prefix}.{name}" if prefix else name), p
        for lname, layer in self._sub_layers.items():
            sub_prefix = f"{prefix}.{lname}" if prefix else lname
            yield from layer.named_parameters(sub_prefix)

    def parameters(self) -> List[Tensor]:
        return [p for _, p in self.named_parameters()]

    def named_buffers(self, prefix: str = ""):
        for name, b in self._buffers.items():
            yield (f"{prefix}.{name}" if prefix else name), b
        for lname, layer in self._sub_layers.items():
            sub_prefix = f"{prefix}.{lname}" if prefix else lname
            yield from layer.named_buffers(sub_prefix)

    def sublayers(self, include_self: bool = False) -> List["Layer"]:
        out = [self] if include_self else []
        for layer in self._sub_layers.values():
            out.append(layer)
            out.extend(layer.sublayers())
        return out

    def named_sublayers(self, prefix: str = ""):
        for lname, layer in self._sub_layers.items():
            sub_prefix = f"{prefix}.{lname}" if prefix else lname
            yield sub_prefix, layer
            yield from layer.named_sublayers(sub_prefix)

    def children(self):
        return iter(self._sub_layers.values())

    # --- modes ----------------------------------------------------------
    def train(self):
        self.training = True
        tape._state.is_test = False
        for layer in self.sublayers():
            layer.training = True
        return self

    def eval(self):
        self.training = False
        tape._state.is_test = True
        for layer in self.sublayers():
            layer.training = False
        return self

    # --- state dict -----------------------------------------------------
    def state_dict(self, destination=None, prefix: str = "") -> Dict[str, np.ndarray]:
        dest = destination if destination is not None else OrderedDict()
        for name, p in self.named_parameters(prefix):
            dest[name] = np.asarray(p.value)
        for name, b in self.named_buffers(prefix):
            dest[name] = np.asarray(b.value)
        return dest

    def set_state_dict(self, state_dict: Dict[str, np.ndarray],
                       use_structured_name: bool = True):
        params = dict(self.named_parameters())
        buffers = dict(self.named_buffers())
        missing = []
        for name, value in state_dict.items():
            if name in params:
                params[name].set_value(value)
            elif name in buffers:
                buffers[name].set_value(value)
            else:
                missing.append(name)
        return missing

    load_dict = set_state_dict

    # --- hooks ----------------------------------------------------------
    def register_forward_pre_hook(self, hook: Callable):
        self._forward_pre_hooks.append(hook)
        return hook

    def register_forward_post_hook(self, hook: Callable):
        self._forward_post_hooks.append(hook)
        return hook

    # --- call -----------------------------------------------------------
    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks:
            res = hook(self, args)
            if res is not None:
                args = res
        # device-trace name of everything this layer computes: metadata
        # of the compiled program, free at run time (telemetry.py)
        with jax.named_scope(type(self).__name__):
            out = self.forward(*args, **kwargs)
        for hook in self._forward_post_hooks:
            res = hook(self, args, out)
            if res is not None:
                out = res
        return out

    def apply(self, fn: Callable):
        for layer in self.sublayers(include_self=True):
            fn(layer)
        return self

    def to(self, dtype=None):
        if dtype is not None:
            jdt = to_jax_dtype(dtype)
            for p in self.parameters():
                if jnp.issubdtype(p.value.dtype, jnp.floating):
                    p.value = p.value.astype(jdt)
        return self

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_gradient()


class Sequential(Layer):
    def __init__(self, *layers):
        super().__init__()
        if len(layers) == 1 and isinstance(layers[0], (list, tuple)) and \
                layers[0] and isinstance(layers[0][0], (list, tuple)):
            for name, layer in layers[0]:
                self.add_sublayer(name, layer)
        else:
            for i, layer in enumerate(layers):
                self.add_sublayer(str(i), layer)

    def forward(self, x):
        for layer in self._sub_layers.values():
            x = layer(x)
        return x

    def __getitem__(self, idx):
        return list(self._sub_layers.values())[idx]

    def __len__(self):
        return len(self._sub_layers)


class LayerList(Layer):
    def __init__(self, sublayers=None):
        super().__init__()
        for i, layer in enumerate(sublayers or []):
            self.add_sublayer(str(i), layer)

    def append(self, layer):
        self.add_sublayer(str(len(self._sub_layers)), layer)
        return self

    def __getitem__(self, idx):
        return list(self._sub_layers.values())[idx]

    def __iter__(self):
        return iter(self._sub_layers.values())

    def __len__(self):
        return len(self._sub_layers)


class ParameterList(Layer):
    def __init__(self, parameters=None):
        super().__init__()
        for i, p in enumerate(parameters or []):
            self.add_parameter(str(i), p)

    def append(self, p):
        self.add_parameter(str(len(self._parameters)), p)
        return self

    def __getitem__(self, idx):
        return list(self._parameters.values())[idx]

    def __iter__(self):
        return iter(self._parameters.values())

    def __len__(self):
        return len(self._parameters)
