"""Layer: the module base class.

Reference: `python/paddle/fluid/dygraph/layers.py:81` (Layer) — named
parameters/buffers/sublayers, train/eval mode, state_dict, hooks. Buffers are
registered as framework state so BN running stats thread through compiled
training steps.
"""
from collections import OrderedDict

import numpy as np

from ...core.tensor import Parameter, Tensor
from ...observability import scopes as _scopes
from .. import initializer as I


def _check_trace_stash(layer_name, attr_name, value):
    """Reject stashing a traced Tensor on a plain Layer attribute.

    Inside a @to_static trace, a Tensor assigned to an unregistered
    attribute would hold a dead tracer after compilation (the value is
    never threaded through the compiled program). Registered buffers ARE
    threaded — point the user there."""
    import jax

    if not isinstance(getattr(value, "_value", None), jax.core.Tracer):
        return
    from ...jit.to_static import in_tracing
    if in_tracing():
        raise RuntimeError(
            f"cannot assign a traced Tensor to plain attribute "
            f"'{layer_name}.{attr_name}' inside a @to_static trace: the "
            f"value would be a dead tracer after compilation. Register it "
            f"first (self.register_buffer({attr_name!r}, paddle.zeros(...), "
            f"persistable=False) in __init__) so assignments thread "
            f"through the compiled step, or return it from forward().")


class ParamAttr:
    """Mirror of `paddle.ParamAttr` — name/initializer/trainable/regularizer."""

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
        if isinstance(attr, I.Initializer):
            return ParamAttr(initializer=attr)
        if isinstance(attr, str):
            return ParamAttr(name=attr)
        if attr is False:
            return False
        raise TypeError(f"bad ParamAttr: {attr!r}")


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        self.training = True
        self._dtype = dtype
        self._parameters = OrderedDict()
        self._buffers = OrderedDict()
        self._sub_layers = OrderedDict()
        self._forward_pre_hooks = OrderedDict()
        self._forward_post_hooks = OrderedDict()
        self._name_scope = name_scope or type(self).__name__.lower()

    # ---------------------------------------------------------- attributes
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ first")
            params[name] = value
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            subs = self.__dict__.get("_sub_layers")
            if subs is None:
                raise RuntimeError("call Layer.__init__ first")
            subs[name] = value
            self.__dict__.pop(name, None)
        else:
            if params is not None and name in params and value is None:
                del params[name]
            buffers = self.__dict__.get("_buffers")
            if buffers is not None and name in buffers:
                if isinstance(value, Tensor):
                    cur = buffers[name]
                    if cur is not None and cur is not value:
                        # in-place update keeps the registered state entry
                        # alive so writes inside a @to_static trace thread
                        # through the compiled program (the Scope-Variable
                        # in-place semantics of the reference); replacing
                        # the object would strand a tracer after the trace.
                        # Tape linkage must follow wholesale or gradients
                        # through the buffer are silently dropped/misseeded.
                        cur._value = value._value
                        cur._tape_node = value._tape_node
                        cur._tape_index = value._tape_index
                        cur.stop_gradient = value.stop_gradient
                        return
                    if cur is None:
                        _check_trace_stash(type(self).__name__, name, value)
                        value._mark_stateful()
                    buffers[name] = value
                    return
                del buffers[name]
            if isinstance(value, Tensor):
                _check_trace_stash(type(self).__name__, name, value)
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

    # ------------------------------------------------------------ creation
    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        attr = ParamAttr._to_attr(attr)
        if attr is False:
            return None
        dtype = dtype or self._dtype
        init = (attr.initializer or default_initializer
                or (I._default_bias_init() if is_bias else I._default_weight_init()))
        value = init(shape, dtype)
        p = Parameter(value, name=attr.name, trainable=attr.trainable)
        p.optimize_attr = {"learning_rate": attr.learning_rate}
        p.regularizer = attr.regularizer
        p.need_clip = attr.need_clip
        return p

    def register_buffer(self, name, tensor, persistable=True):
        if tensor is not None:
            tensor.persistable = persistable
            tensor._mark_stateful()
        self._buffers[name] = tensor
        self.__dict__.pop(name, None)
        return tensor

    def add_parameter(self, name, parameter):
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer):
        self._sub_layers[name] = sublayer
        return sublayer

    # ----------------------------------------------------------- traversal
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
            p = f"{prefix}.{name}" if prefix else name
            yield from layer.named_sublayers(prefix=p, include_self=True,
                                             layers_set=layers_set)

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        for name, layer in self.named_sublayers(prefix=prefix, include_self=True):
            for pname, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield (f"{name}.{pname}" if name else pname), p
            if not include_sublayers:
                break

    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix=""):
        seen = set()
        for name, layer in self.named_sublayers(prefix=prefix, include_self=True):
            for bname, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                yield (f"{name}.{bname}" if name else bname), b

    def buffers(self):
        return [b for _, b in self.named_buffers()]

    def children(self):
        return [l for l in self._sub_layers.values() if l is not None]

    def named_children(self):
        return [(n, l) for n, l in self._sub_layers.items() if l is not None]

    # ----------------------------------------------------- recompute seam
    def enable_recompute(self, policy="full"):
        """Run this layer's forward as an activation-recompute segment
        (``paddle_tpu.recompute``): activations inside are dropped per
        ``policy`` (``full`` / ``selective`` / ``kernels`` / ``offload``) and
        rematerialized in backward — dropout replays bitwise via the
        threaded RNG state. Applies in train mode while gradients are
        enabled; eval/no-grad calls run the plain forward. Returns
        ``self`` for chaining."""
        from ...recompute import resolve_policy
        if not callable(policy):
            resolve_policy(policy)  # validate the name loudly, up front
        object.__setattr__(self, "_recompute_policy", policy)
        return self

    def disable_recompute(self):
        object.__setattr__(self, "_recompute_policy", None)
        return self

    # ---------------------------------------------------------------- mode
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
        for l in self.sublayers(include_self=True):
            fn(l)
        return self

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    # ----------------------------------------------------------- state i/o
    def state_dict(self, include_sublayers=True, structured_name_prefix=""):
        out = OrderedDict()
        for name, p in self.named_parameters(prefix=structured_name_prefix):
            out[name] = p
        for name, b in self.named_buffers(prefix=structured_name_prefix):
            if b is not None and b.persistable:
                out[name] = b
        return out

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing, unexpected = [], []
        for name, t in own.items():
            if name in state_dict:
                v = state_dict[name]
                arr = v.numpy() if isinstance(v, Tensor) else np.asarray(v)
                t.set_value(arr.astype(np.dtype(t.dtype)))
            else:
                missing.append(name)
        for name in state_dict:
            if name not in own:
                unexpected.append(name)
        return missing, unexpected

    load_dict = set_state_dict

    def to(self, dtype=None):
        if dtype is not None:
            from ...core.dtype import convert_dtype, is_floating
            dt = convert_dtype(dtype)
            for p in self.parameters():
                if is_floating(p.dtype):
                    p._value = p._value.astype(dt)
            for b in self.buffers():
                if b is not None and is_floating(b.dtype):
                    b._value = b._value.astype(dt)
            self._dtype = np.dtype(dt).name
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    # ----------------------------------------------------------- hooks
    def register_forward_pre_hook(self, hook):
        handle = _HookHandle(self._forward_pre_hooks)
        self._forward_pre_hooks[handle.id] = hook
        return handle

    def register_forward_post_hook(self, hook):
        handle = _HookHandle(self._forward_post_hooks)
        self._forward_post_hooks[handle.id] = hook
        return handle

    # ----------------------------------------------------------- __call__
    def __call__(self, *inputs, **kwargs):
        if _scopes.tracing():
            # device time gets this layer's name (observability.scopes)
            with _scopes.layer_scope(self):
                return self._run_forward(inputs, kwargs)
        return self._run_forward(inputs, kwargs)

    def _run_forward(self, inputs, kwargs):
        for hook in self._forward_pre_hooks.values():
            result = hook(self, inputs)
            if result is not None:
                inputs = result if isinstance(result, tuple) else (result,)
        rc_policy = self.__dict__.get("_recompute_policy")
        if rc_policy is not None and self.training:
            from ...core.autograd import differentiated
            if differentiated():
                # always-immediate call shape: the public recompute()
                # returns a WRAPPER for no-arg calls, and a forward
                # taking zero inputs must still run here
                from ...recompute import _segment_call
                outputs = _segment_call(self.forward, inputs, kwargs,
                                        rc_policy)
            else:
                outputs = self.forward(*inputs, **kwargs)
        else:
            outputs = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            result = hook(self, inputs, outputs)
            if result is not None:
                outputs = result
        return outputs

    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, layer in self._sub_layers.items():
            mod_str = repr(layer)
            mod_str = "\n  ".join(mod_str.split("\n"))
            lines.append(f"({name}): {mod_str}")
        main = type(self).__name__ + "(" + extra
        if lines:
            main += "\n  " + "\n  ".join(lines) + "\n"
        return main + ")"

    def full_name(self):
        return self._name_scope


class _HookHandle:
    _next_id = 0

    def __init__(self, store):
        self.store = store
        self.id = _HookHandle._next_id
        _HookHandle._next_id += 1

    def remove(self):
        self.store.pop(self.id, None)
