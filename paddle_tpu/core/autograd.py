"""Eager autograd engine.

The TPU-native analog of the reference dygraph engine
(`paddle/fluid/imperative/basic_engine.cc:39/235/305` + `tracer.cc:144` +
`gradient_accumulator.cc`): every differentiable op call records a TapeNode
holding a `jax.vjp` closure; `backward()` walks nodes in reverse topological
order and accumulates cotangents. Because the closures are pure jax functions,
the same tape works on concrete arrays (eager) and on tracers (inside
`to_static`), which is what lets the whole imperative training step compile to
one XLA computation.
"""
import threading
from contextlib import contextmanager

import numpy as np
from jax import dtypes as _jax_dtypes
import jax.numpy as jnp

from ..observability import scopes as _scopes

__all__ = [
    "TapeNode",
    "grad_enabled",
    "differentiated",
    "functional_region",
    "no_grad",
    "enable_grad",
    "backward",
    "grad",
]


class _GradState(threading.local):
    def __init__(self):
        self.enabled = True
        self.functional = False


_state = _GradState()


def grad_enabled() -> bool:
    return _state.enabled


def differentiated() -> bool:
    """Will what runs now be differentiated? True while the tape
    records, and inside a :func:`functional_region`."""
    return _state.enabled or _state.functional


@contextmanager
def no_grad():
    prev = (_state.enabled, _state.functional)
    _state.enabled = _state.functional = False
    try:
        yield
    finally:
        _state.enabled, _state.functional = prev


@contextmanager
def functional_region():
    """The tape is off because ONE enclosing op differentiates the whole
    closure (``jax.vjp`` over a rolled loop's body): no node is
    recorded, but the seams that shape the backward still apply — a
    layer with ``enable_recompute`` stages its ``jax.checkpoint``. Where
    nothing is being differentiated this is plain :func:`no_grad`."""
    prev = (_state.enabled, _state.functional)
    _state.functional = differentiated()
    _state.enabled = False
    try:
        yield
    finally:
        _state.enabled, _state.functional = prev


@contextmanager
def enable_grad():
    prev = _state.enabled
    _state.enabled = True
    try:
        yield
    finally:
        _state.enabled = prev


class TapeNode:
    """One recorded op: vjp closure + graph edges.

    ``inputs``: the differentiated input Tensors (strong refs — the eager graph
    lives until backward, as with the reference's GradOpNode chain).
    ``out_meta``: (shape, dtype) per output so missing cotangents can be zeros.
    ``scope``: inside a `to_static` trace, the scope path the op was
    recorded under; the backward re-enters it, so the transposed
    instructions carry their forward's name on the device.
    """

    __slots__ = ("vjp_fn", "inputs", "out_meta", "name", "cotangents",
                 "pending", "pure_fn", "in_dtypes", "scope", "__weakref__")

    def __init__(self, vjp_fn, inputs, out_meta, name="", pure_fn=None,
                 in_dtypes=None):
        self.vjp_fn = vjp_fn
        self.inputs = inputs
        self.out_meta = out_meta
        self.name = name
        self.cotangents = None  # filled during backward
        self.pending = 0
        # the pure forward closure (dispatch's `g`): create_graph re-derives
        # the VJP from it as a differentiable function of the LIVE inputs
        # (the recorded vjp_fn bakes primals in as constants). in_dtypes are
        # the dtypes the op was TRACED with (post-AMP cast) so the replay
        # matches even outside the original auto_cast scope.
        self.pure_fn = pure_fn
        self.in_dtypes = in_dtypes
        self.scope = _scopes.current_path()

    def scoped(self):
        """The context the node's backward runs in: the scope its
        forward was recorded under (nothing outside a trace)."""
        return _scopes.reenter(self.scope)

    def seed(self, index, value):
        if self.cotangents is None:
            self.cotangents = [None] * len(self.out_meta)
        cur = self.cotangents[index]
        self.cotangents[index] = value if cur is None else cur + value

    def materialized_cotangents(self):
        cots = self.cotangents or [None] * len(self.out_meta)
        out = []
        for c, (shape, dtype) in zip(cots, self.out_meta):
            if c is None:
                if jnp.issubdtype(dtype, jnp.inexact):
                    c = jnp.zeros(shape, dtype)
                else:
                    # integer/bool outputs (e.g. loop counters carried through
                    # a control-flow op): jax.vjp expects float0 cotangents
                    c = np.zeros(shape, _jax_dtypes.float0)
            elif c.dtype != dtype:
                # AMP boundary: downstream ran in a different precision
                with _scopes.scope("cast"):
                    c = c.astype(dtype)
            out.append(c)
        return tuple(out)


def _topo_order(roots):
    """Reverse topological order over the tape graph reachable from the
    root node(s)."""
    if not isinstance(roots, (list, tuple)):
        roots = [roots]
    order, visited = [], set()
    stack = [(r, False) for r in roots]
    while stack:
        node, processed = stack.pop()
        if processed:
            order.append(node)
            continue
        if id(node) in visited:
            continue
        visited.add(id(node))
        stack.append((node, True))
        for t in node.inputs:
            if t._tape_node is not None and id(t._tape_node) not in visited:
                stack.append((t._tape_node, False))
    order.reverse()
    return order


def backward(tensor, grad_tensor=None, retain_graph=False):
    """Run reverse accumulation from `tensor` (reference: basic_engine.cc:305)."""
    from .tensor import Tensor

    node = tensor._tape_node
    if node is None:
        return
    if grad_tensor is None:
        seed = jnp.ones(tensor.shape, dtype=tensor.dtype)
    else:
        seed = grad_tensor._value if isinstance(grad_tensor, Tensor) else jnp.asarray(grad_tensor)
    node.seed(tensor._tape_index, seed)

    for n in _topo_order(node):
        if n.cotangents is None or all(c is None for c in n.cotangents):
            continue
        if n.vjp_fn is None:
            raise RuntimeError(
                "autograd graph has been freed (backward already ran); "
                "pass retain_graph=True to keep it")
        with n.scoped():
            in_cots = n.vjp_fn(n.materialized_cotangents())
            for t, cot in zip(n.inputs, in_cots):
                if cot is None:
                    continue
                child = t._tape_node
                if child is not None:
                    child.seed(t._tape_index, cot)
                if child is None or t._retain_grads:
                    t._accumulate_grad(cot)
        n.cotangents = None
        if not retain_graph:
            n.vjp_fn = None
            n.inputs = ()
            n.pure_fn = None  # its closure holds the op's args alive

    if not retain_graph:
        tensor._tape_node = None


def grad(outputs, inputs, grad_outputs=None, retain_graph=None, create_graph=False,
         allow_unused=False):
    """`paddle.grad` analog (reference: imperative/partial_grad_engine.cc).

    Computes d(outputs)/d(inputs) without touching `.grad` on other leaves.
    With `create_graph=True` the backward itself runs through the op
    dispatch seam (each node's vjp closure is a pure function, so it is
    itself an op), producing differentiable grads — double backward /
    gradient-penalty training works (reference: partial_grad_engine's
    create_graph path).
    """
    from .tensor import Tensor

    if create_graph:
        return _grad_create_graph(outputs, inputs, grad_outputs,
                                  retain_graph, allow_unused)
    if retain_graph is None:
        retain_graph = True  # repeated paddle.grad calls over the same graph
    outs = outputs if isinstance(outputs, (list, tuple)) else [outputs]
    ins = inputs if isinstance(inputs, (list, tuple)) else [inputs]
    if grad_outputs is None:
        grad_outputs = [None] * len(outs)

    # Seed output cotangents.
    roots = []
    for o, g in zip(outs, grad_outputs):
        if o._tape_node is None:
            continue
        seed = (
            jnp.ones(o.shape, o.dtype)
            if g is None
            else (g._value if isinstance(g, Tensor) else jnp.asarray(g))
        )
        o._tape_node.seed(o._tape_index, seed)
        roots.append(o._tape_node)

    # Collect per-input grads (not into .grad — into a side table).
    table = {id(t): None for t in ins}
    wanted = {id(t): t for t in ins}

    for n in _topo_order(roots):
        if n.cotangents is None or all(c is None for c in n.cotangents):
            continue
        if n.vjp_fn is None:
            raise RuntimeError(
                "autograd graph has been freed (backward/grad already ran); "
                "pass retain_graph=True to keep it")
        with n.scoped():
            in_cots = n.vjp_fn(n.materialized_cotangents())
            for t, cot in zip(n.inputs, in_cots):
                if cot is None:
                    continue
                if id(t) in wanted:
                    table[id(t)] = (cot if table[id(t)] is None
                                    else table[id(t)] + cot)
                child = t._tape_node
                if child is not None:
                    child.seed(t._tape_index, cot)
        n.cotangents = None
        if not retain_graph:
            n.vjp_fn = None
            n.inputs = ()
            n.pure_fn = None

    results = []
    for t in ins:
        g = table[id(t)]
        if g is None:
            if not allow_unused:
                raise RuntimeError(
                    "One of the differentiated tensors appears unused; "
                    "pass allow_unused=True to return None for it."
                )
            results.append(None)
        else:
            results.append(Tensor(g, stop_gradient=True))
    if isinstance(inputs, (list, tuple)):
        return results
    return results[0]


def _grad_create_graph(outputs, inputs, grad_outputs, retain_graph,
                       allow_unused):
    """Differentiable backward: cotangents travel as Tensors, and every
    node's vjp closure runs through call_op so the computed grads carry
    their own tape (second and higher orders compose)."""
    from .dispatch import call_op
    from .tensor import Tensor

    outs = outputs if isinstance(outputs, (list, tuple)) else [outputs]
    ins = inputs if isinstance(inputs, (list, tuple)) else [inputs]
    if grad_outputs is None:
        grad_outputs = [None] * len(outs)

    if retain_graph is None:
        retain_graph = True  # paddle default: retain when create_graph

    # cotangent accumulation per (node, out_index) as Tensors
    node_cots = {}  # id(node) -> [Tensor|None per output]
    roots = []
    for o, g in zip(outs, grad_outputs):
        n = o._tape_node
        if n is None:
            continue
        seed = (Tensor(jnp.ones(o.shape, o.dtype), stop_gradient=True)
                if g is None else
                (g if isinstance(g, Tensor) else Tensor(jnp.asarray(g))))
        slot = node_cots.setdefault(id(n), [None] * len(n.out_meta))
        cur = slot[o._tape_index]
        slot[o._tape_index] = seed if cur is None else cur + seed
        roots.append(n)

    order = _topo_order(roots)
    table = {id(t): None for t in ins}
    wanted = {id(t): t for t in ins}

    for n in order:
        cots = node_cots.get(id(n))
        if cots is None or all(c is None for c in cots):
            continue
        if n.vjp_fn is None:
            raise RuntimeError(
                "autograd graph has been freed; create_graph needs the "
                "forward graph intact")
        if n.pure_fn is None:
            raise RuntimeError(
                f"node {n.name!r} has no recorded forward closure; "
                "create_graph needs nodes recorded by call_op")
        # materialize missing output cotangents as zero Tensors
        full = []
        for c, (shape, dtype) in zip(cots, n.out_meta):
            if c is None:
                if jnp.issubdtype(dtype, jnp.inexact):
                    c = Tensor(jnp.zeros(shape, dtype), stop_gradient=True)
                else:
                    c = np.zeros(shape, _jax_dtypes.float0)
            full.append(c)
        def regrad(*vals, _k=len(n.inputs), _fn=n.pure_fn,
                   _in_dt=tuple(n.in_dtypes or ()),
                   _out_dt=tuple(d for _, d in n.out_meta)):
            # _k/_fn/... bound at definition: regrad is replayed by later
            # grad levels, after the loop variables have moved on. Primals
            # and cotangents are cast to the dtypes the op was TRACED with
            # (post-AMP), so the replay matches outside the original
            # auto_cast scope; grads cast back to the live input dtypes.
            import jax as _jax
            primals, cs = list(vals[:_k]), list(vals[_k:])
            orig_dt = [p.dtype for p in primals]
            if _in_dt:
                primals = [p.astype(d) for p, d in zip(primals, _in_dt)]
            cs = [c.astype(d) if hasattr(c, "astype")
                  and jnp.issubdtype(d, jnp.inexact) else c
                  for c, d in zip(cs, _out_dt)]
            _, vjp_fn = _jax.vjp(_fn, *primals)
            gs = vjp_fn(tuple(cs))
            return tuple(g.astype(d) if hasattr(g, "astype") else g
                         for g, d in zip(gs, orig_dt))

        # differentiable wrt BOTH the original inputs and the cotangents:
        # re-derive the VJP from the pure closure at the live input values
        with n.scoped():
            in_cots = call_op(regrad, *n.inputs, *full,
                              op_name=f"grad_{n.name}")
        in_cots = in_cots if isinstance(in_cots, tuple) else (in_cots,)
        for t, cot in zip(n.inputs, in_cots):
            if cot is None:
                continue
            if id(t) in wanted:
                cur = table[id(t)]
                table[id(t)] = cot if cur is None else cur + cot
            child = t._tape_node
            if child is not None:
                slot = node_cots.setdefault(id(child),
                                            [None] * len(child.out_meta))
                cur = slot[t._tape_index]
                slot[t._tape_index] = cot if cur is None else cur + cot
        node_cots[id(n)] = None

    if not retain_graph:
        for n in order:  # the NEW grad graph survives; the old one frees
            n.vjp_fn = None
            n.inputs = ()
            n.pure_fn = None

    results = []
    for t in ins:
        g = table[id(t)]
        if g is None:
            if not allow_unused:
                raise RuntimeError(
                    "One of the differentiated tensors appears unused; "
                    "pass allow_unused=True to return None for it.")
            results.append(None)
        else:
            g.stop_gradient = False  # differentiable output
            results.append(g)
    if isinstance(inputs, (list, tuple)):
        return results
    return results[0]
