"""Op dispatch: the eager/traced execution seam.

TPU-native analog of the reference's `imperative::Tracer::TraceOp`
(`paddle/fluid/imperative/tracer.cc:144`) + `PreparedOp`
(`prepared_operator.cc:161`): an op is a pure jnp function; `call_op` unwraps
Tensor arguments, runs the function (through `jax.vjp` when any input needs
grad, recording a TapeNode), and wraps outputs. There is no kernel registry —
XLA is the kernel library; the same dispatch path works eagerly on device
arrays and under `to_static` tracing on tracers.
"""
import jax
import jax.numpy as jnp

from ..observability import scopes as _scopes
from . import autograd
from .dtype import is_inexact

__all__ = ["call_op", "call_op_nograd", "wrap", "unwrap", "_STATIC_HOOK",
           "add_observer", "remove_observer", "OpCapture", "capture_ops",
           "op_display_name"]


def op_display_name(fn, op_name=None):
    """Canonical op name — the ONE naming scheme shared by program
    records, the sampled dispatch telemetry, and the static analyzer's
    lint, so a hot op flagged by analysis is the same string a runtime
    profile shows."""
    return op_name or getattr(fn, "__name__", None) or "op"

# When paddle.static program_guard is active, this holds Program.record and
# every op call is captured into the program instead of the autograd tape.
_STATIC_HOOK = [None]

# Op observers (profiler RecordEvent, FLAGS_check_nan_inf checker): each has
# begin(name)->token and end(token, name, outputs). Kept in a dict keyed by
# observer name; _OBSERVER_LIST is the flat fast-path view (None when empty so
# the hot path is a single truthiness check). Reference analog: every
# OperatorBase::Run wrapping itself in RecordEvent (platform/profiler.h:127)
# and the nan_inf_utils post-op hook (framework/details/nan_inf_utils.h:29).
_OBSERVERS = {}
_OBSERVER_LIST = None


def add_observer(key, obs):
    global _OBSERVER_LIST
    _OBSERVERS[key] = obs
    _OBSERVER_LIST = list(_OBSERVERS.values())


def remove_observer(key):
    global _OBSERVER_LIST
    _OBSERVERS.pop(key, None)
    _OBSERVER_LIST = list(_OBSERVERS.values()) or None


def _is_tensor(x):
    from .tensor import Tensor

    return isinstance(x, Tensor)


# Closure-capture for control flow: while a capture is active, every
# differentiated Tensor that an op reads and that was NOT created inside the
# captured region is recorded as an external operand. Control-flow lowering
# (nn/control_flow.py) uses this to turn closure-captured parameters (e.g. RNN
# weights read inside a while_loop body) into explicit lax.cond/scan operands
# so the tape can differentiate through the XLA construct. The reference gets
# the same information from sub-block var scoping
# (paddle/fluid/operators/controlflow/while_op.cc external-var analysis).
# Thread-local like _GradState: a DataLoader worker thread running ops must
# not pollute a capture active on the tracing thread.
import threading as _threading


class _CaptureState(_threading.local):
    def __init__(self):
        self.stack = []


_CAPTURE = _CaptureState()


class OpCapture:
    def __init__(self):
        self._created = set()
        self._ext_ids = set()
        self.external = []  # external diff Tensors, in first-read order

    def mark_created(self, tensors):
        for t in tensors:
            self._created.add(id(t))

    def note_inputs(self, tensors):
        for t in tensors:
            i = id(t)
            if i not in self._created and i not in self._ext_ids:
                self._ext_ids.add(i)
                self.external.append(t)


class capture_ops:
    def __init__(self, cap):
        self._cap = cap

    def __enter__(self):
        _CAPTURE.stack.append(self._cap)
        return self._cap

    def __exit__(self, *exc):
        _CAPTURE.stack.pop()
        return False


class bind_values:
    """Temporarily rebind Tensors' values (e.g. to traced operands) while a
    closure re-runs functionally. Used by control-flow lowering and the
    StableHLO exporter."""

    def __init__(self, tensors, values):
        self._tensors = tensors
        self._values = values
        self._saved = None

    def __enter__(self):
        self._saved = [(t._value, t._tape_node) for t in self._tensors]
        for t, v in zip(self._tensors, self._values):
            t._value = v
            t._tape_node = None
        return self

    def __exit__(self, *exc):
        for t, (v, node) in zip(self._tensors, self._saved):
            t._value = v
            t._tape_node = node
        return False


def unwrap(x):
    return x._value if _is_tensor(x) else x


def wrap(value, stop_gradient=True):
    from .tensor import Tensor

    return Tensor(value, stop_gradient=stop_gradient)


def _amp_cast(op_name, values):
    """AMP hook: bf16-cast inputs of allow-listed ops (see amp/auto_cast.py).
    In a compiled step the casts are the autocast layer's only device
    cost: they go under the scope `cast`."""
    from ..amp.auto_cast import _state, amp_cast_inputs
    if not _state.enabled:
        return values
    if _scopes.tracing():
        with _scopes.scope("cast"):
            return amp_cast_inputs(op_name, values)
    return amp_cast_inputs(op_name, values)


def _amp_wrap_fn(fn, op_name, args):
    """fp32-compute ops in a bf16 stream cast their outputs back down
    (amp.downcast_out_list); the cast lives inside the traced fn so jax.vjp
    upcasts cotangents symmetrically."""
    from ..amp.auto_cast import _state, amp_output_downcast
    if not _state.enabled:
        return fn
    dt = amp_output_downcast(op_name, [unwrap(a) for a in args])
    if dt is None:
        return fn

    def down(out):
        if isinstance(out, tuple):
            return tuple(o.astype(dt) if hasattr(o, "astype") else o
                         for o in out)
        return out.astype(dt) if hasattr(out, "astype") else out

    def wrapped(*a, **k):
        out = fn(*a, **k)
        if _scopes.tracing():
            with _scopes.scope("cast"):
                return down(out)
        return down(out)

    return wrapped


def _substitute(args, kwargs, positions, values, op_name=None):
    """Rebuild (args, kwargs) with Tensors replaced by raw values; the tensors
    at `positions` (path keys) get `values`, the rest are closed-over consts."""
    flat_args = list(args)
    new_kwargs = dict(kwargs)
    for (where, key), val in zip(positions, values):
        if where == "a":
            flat_args[key] = val
        else:
            new_kwargs[key] = val
    flat_args = _amp_cast(op_name, [unwrap(a) for a in flat_args])
    new_kwargs = {k: unwrap(v) for k, v in new_kwargs.items()}
    return flat_args, new_kwargs


def _observed(name, run):
    """Run `run()` under the registered op observers."""
    obs = _OBSERVER_LIST
    if obs is None:
        return run()
    pairs = [(o, o.begin(name)) for o in obs]
    out = run()
    flat = out if isinstance(out, tuple) else (out,)
    for o, tok in pairs:
        o.end(tok, name, flat)
    return out


def call_op(fn, *args, op_name=None, **kwargs):
    """Run `fn(*arrays, **kwargs)` with autograd recording.

    Tensor args participate in differentiation when grad is enabled, they are
    floating point, and `stop_gradient` is False. Everything else is closed
    over as a constant. Multi-output fns must return only floating-point
    outputs (mixed-dtype ops are built as composites in the ops library).
    """
    if _scopes.tracing():
        # a compiled step: the op's device time gets the op's name
        with _scopes.scope(op_display_name(fn, op_name)):
            return _dispatch(_call_op_impl, fn, args, op_name, kwargs)
    return _dispatch(_call_op_impl, fn, args, op_name, kwargs)


def _dispatch(impl, fn, args, op_name, kwargs):
    if _OBSERVER_LIST is not None and _STATIC_HOOK[0] is None:
        name = op_display_name(fn, op_name)
        return _observed(
            name, lambda: impl(fn, *args, op_name=op_name, **kwargs))
    return impl(fn, *args, op_name=op_name, **kwargs)


def _call_op_impl(fn, *args, op_name=None, **kwargs):
    if _STATIC_HOOK[0] is not None:
        return _STATIC_HOOK[0](fn, args, kwargs, op_name)

    diff_positions, diff_tensors = [], []
    if autograd.grad_enabled():
        for i, a in enumerate(args):
            if _is_tensor(a) and not a.stop_gradient and is_inexact(a.dtype):
                diff_positions.append(("a", i))
                diff_tensors.append(a)
        for k, v in kwargs.items():
            if _is_tensor(v) and not v.stop_gradient and is_inexact(v.dtype):
                diff_positions.append(("k", k))
                diff_tensors.append(v)

    if not diff_tensors:
        return _call_op_nograd_impl(fn, *args, op_name=op_name, **kwargs)

    if _CAPTURE.stack:
        _note_capture_inputs(args, kwargs)

    name = op_display_name(fn, op_name)
    fn = _amp_wrap_fn(fn, name, args)

    def g(*diff_vals):
        a, k = _substitute(args, kwargs, diff_positions, diff_vals, op_name=name)
        out = fn(*a, **k)
        return out if isinstance(out, tuple) else (out,)

    diff_vals = _amp_cast(name, [t._value for t in diff_tensors])
    outs, vjp_fn = jax.vjp(g, *diff_vals)
    out_meta = [(jnp.shape(o), o.dtype) for o in outs]
    node = autograd.TapeNode(vjp_fn, list(diff_tensors), out_meta,
                             name=name,
                             pure_fn=g,
                             in_dtypes=[v.dtype for v in diff_vals])

    tensors = []
    for i, o in enumerate(outs):
        t = wrap(o, stop_gradient=False)
        t._tape_node = node
        t._tape_index = i
        tensors.append(t)
    if _CAPTURE.stack:
        _CAPTURE.stack[-1].mark_created(tensors)
    if len(tensors) == 1:
        return tensors[0]
    return tuple(tensors)


def call_op_nograd(fn, *args, op_name=None, **kwargs):
    """Run without recording (non-diff inputs, no_grad scope, or int ops)."""
    if _scopes.tracing():
        with _scopes.scope(op_display_name(fn, op_name)):
            return _dispatch(_call_op_nograd_impl, fn, args, op_name, kwargs)
    return _dispatch(_call_op_nograd_impl, fn, args, op_name, kwargs)


def _note_capture_inputs(args, kwargs):
    # capture every Tensor input: diff tensors need gradient operands,
    # non-diff ones (feeds, int tensors, frozen weights) still need to be
    # operands so static-program replay and re-tracing see live values,
    # not the values baked at capture time
    _CAPTURE.stack[-1].note_inputs(
        [a for a in args if _is_tensor(a)]
        + [v for v in kwargs.values() if _is_tensor(v)])


def _call_op_nograd_impl(fn, *args, op_name=None, **kwargs):
    if _STATIC_HOOK[0] is not None:
        return _STATIC_HOOK[0](fn, args, kwargs, op_name)
    capturing = bool(_CAPTURE.stack)
    if capturing:
        _note_capture_inputs(args, kwargs)
    name = op_display_name(fn, op_name)
    fn = _amp_wrap_fn(fn, name, args)
    a = _amp_cast(name, [unwrap(x) for x in args])
    k = {key: unwrap(v) for key, v in kwargs.items()}
    out = fn(*a, **k)
    if isinstance(out, tuple):
        out = tuple(wrap(o) for o in out)
        if capturing:
            _CAPTURE.stack[-1].mark_created(out)
        return out
    out = wrap(out)
    if capturing:
        _CAPTURE.stack[-1].mark_created((out,))
    return out
