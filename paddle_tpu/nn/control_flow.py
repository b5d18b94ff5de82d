"""Control flow: while_loop / fixed_loop / cond / case / switch_case
(+ TensorArray ops).

TPU-native redesign of the reference's control-flow operators
(`/root/reference/paddle/fluid/operators/controlflow/while_op.cc`,
`conditional_block_op.cc`) and their Python front-end
(`/root/reference/python/paddle/fluid/layers/control_flow.py` —
`while_loop:1075`, `cond:2298`, `case:2712`, `switch_case:3007`).

The reference executes protobuf sub-blocks against scope snapshots. Here
there are two regimes:

- **Concrete predicate** (eager / dygraph): plain Python — run the taken
  branch; the autograd tape differentiates it like any other code. This
  matches the reference's dygraph short-circuit.
- **Traced predicate** (under `@to_static` or any jax transform): lower to
  XLA control flow — `lax.cond` / `lax.switch` / `lax.while_loop`, or a
  masked `lax.scan` when gradients must flow through a bounded loop.
  Tensors read from enclosing scope inside a branch (RNN weights, biases)
  are discovered with `core.dispatch.OpCapture` and passed as explicit
  operands so `jax.vjp` differentiates the whole construct; the reference
  obtains the same operand set from sub-block external-variable analysis.

`fixed_loop(body, loop_vars, trips)` is the reference's `while_op` with a
static bound and no predicate: eagerly a python loop, under tracing ONE
`lax.scan(length=trips)` whose body is captured once. It returns every
trip's loop vars stacked `[trips, ...]`, and the gradients of the
parameters the body reads are the sum over trips — a stack of layers
applied several times over the same weights (models/ouro.py).

Branch bodies must be side-effect free (no state mutation), matching XLA
semantics; the capture pass runs each branch once at trace time.
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..core import autograd, dispatch
from ..core import state as state_mod
from ..core.dispatch import call_op, call_op_nograd, unwrap, bind_values
from ..core.tensor import Tensor
from ..observability.scopes import scope

__all__ = ["while_loop", "fixed_loop", "cond", "case", "switch_case",
           "create_array", "array_write", "array_read", "array_length"]


def _is_traced(v):
    return isinstance(v, jax.core.Tracer)


def _static_recording():
    """True under static.program_guard: the predicate holds a build-time
    placeholder value, so the construct must be recorded as one data-dependent
    op (the reference records a conditional_block/while sub-block) rather than
    frozen to the placeholder's branch."""
    return dispatch._STATIC_HOOK[0] is not None


class _suspend_static_hook:
    """Run capture passes outside program recording so branch-probe ops don't
    leak into the Program; only the fused control-flow op is recorded."""

    def __enter__(self):
        self._saved = dispatch._STATIC_HOOK[0]
        dispatch._STATIC_HOOK[0] = None
        return self

    def __exit__(self, *exc):
        dispatch._STATIC_HOOK[0] = self._saved
        return False


def _as_pred(v):
    return jnp.reshape(jnp.asarray(v).astype(bool), ())


def _flatten_out(out):
    leaves, treedef = jax.tree_util.tree_flatten(
        out, is_leaf=lambda x: isinstance(x, Tensor))
    return [unwrap(l) for l in leaves], treedef


def _capture(branch, *args):
    """Run `branch(*args)` once, recording external diff Tensors it reads.
    `args` (the loop vars) are parameters, not closures — excluded."""
    cap = dispatch.OpCapture()
    arg_leaves, _ = jax.tree_util.tree_flatten(
        args, is_leaf=lambda x: isinstance(x, Tensor))
    created = {id(a) for a in arg_leaves if isinstance(a, Tensor)}
    cap.mark_created([a for a in arg_leaves if isinstance(a, Tensor)])
    from ..jit.to_static import capture_pass
    with dispatch.capture_ops(cap), _suspend_static_hook(), capture_pass():
        out = branch(*args)
    # a branch may return an external tensor *directly* (no op reads it);
    # it must still become an operand — diff or not — or its value at
    # capture time (a build placeholder, a stale weight) would bake in as a
    # constant and any gradient through it would silently drop
    out_leaves, _ = jax.tree_util.tree_flatten(
        out, is_leaf=lambda x: isinstance(x, Tensor))
    direct = [t for t in out_leaves
              if isinstance(t, Tensor) and id(t) not in created]
    cap.note_inputs(direct)
    return cap.external, out


def _merge_ext(*ext_lists):
    seen, merged = set(), []
    for ext in ext_lists:
        for t in ext:
            if id(t) not in seen:
                seen.add(id(t))
                merged.append(t)
    return merged


def _functional(branch, ext, ext_vals, *args, tape_off=autograd.no_grad):
    """Re-run a branch with captured externals bound to functional values,
    tape recording off (the enclosing call_op owns differentiation)."""
    with bind_values(ext, ext_vals), tape_off(), \
            _suspend_static_hook():
        out = branch(*args)
        # flatten INSIDE the bind scope: a branch may return a bound tensor
        # directly, and its value must be read before restore
        vals, treedef = _flatten_out(out)
    return vals, treedef


# ---------------------------------------------------------------------------
# cond / case / switch_case
# ---------------------------------------------------------------------------

def cond(pred, true_fn=None, false_fn=None, name=None):
    """Run `true_fn()` if `pred` else `false_fn()`.

    Reference: `fluid/layers/control_flow.py:cond` → conditional_block ops.
    Concrete predicate: Python dispatch (dygraph semantics). Traced
    predicate: `lax.cond` with closure tensors as differentiated operands.
    """
    pred_v = unwrap(pred) if isinstance(pred, Tensor) else pred
    if not _is_traced(pred_v) and not _static_recording():
        taken = true_fn if bool(np.asarray(pred_v).reshape(())) else false_fn
        return taken() if taken is not None else None
    if true_fn is None or false_fn is None:
        raise ValueError(
            "cond with a traced predicate requires both true_fn and false_fn")

    ext_t, t_out = _capture(true_fn)
    ext_f, f_out = _capture(false_fn)
    ext = _merge_ext(ext_t, ext_f)
    _, t_def = _flatten_out(t_out)
    _, f_def = _flatten_out(f_out)
    if t_def != f_def:
        raise ValueError(
            f"cond branches returned different structures: {t_def} vs {f_def}")

    def run(pv, *ext_vals):
        def make(branch):
            def f(ev):
                vals, _ = _functional(branch, ext, ev)
                return tuple(vals)
            return f
        return lax.cond(_as_pred(pv), make(true_fn), make(false_fn),
                        tuple(ext_vals))

    outs = call_op(run, pred, *ext, op_name="conditional_block")
    outs = outs if isinstance(outs, tuple) else (outs,)
    return jax.tree_util.tree_unflatten(t_def, list(outs))


def _switch_on_position(pos_tensor, fns, name):
    """Shared lax.switch lowering: `fns[pos]()` with captured externals."""
    captures = [_capture(fn) for fn in fns]
    ext = _merge_ext(*[c[0] for c in captures])
    treedefs = [_flatten_out(c[1])[1] for c in captures]
    if any(td != treedefs[0] for td in treedefs[1:]):
        raise ValueError(
            f"{name} branches returned different structures: {treedefs}")

    def run(pos, *ext_vals):
        def make(branch):
            def f(ev):
                vals, _ = _functional(branch, ext, ev)
                return tuple(vals)
            return f
        idx = jnp.clip(jnp.reshape(pos, ()).astype(jnp.int32), 0, len(fns) - 1)
        return lax.switch(idx, [make(fn) for fn in fns], tuple(ext_vals))

    outs = call_op(run, pos_tensor, *ext, op_name="switch")
    outs = outs if isinstance(outs, tuple) else (outs,)
    return jax.tree_util.tree_unflatten(treedefs[0], list(outs))


def switch_case(branch_index, branch_fns, default=None, name=None):
    """Run the branch whose key equals `branch_index`, else `default`.

    Reference: `fluid/layers/control_flow.py:switch_case:3007`.
    `branch_fns`: dict {int: callable}, list of (int, callable), or list of
    callables (keys = positions). `default=None` falls back to the
    highest-key branch (reference semantics).
    """
    if isinstance(branch_fns, dict):
        table = dict(branch_fns)
    else:
        fns = list(branch_fns)
        if fns and isinstance(fns[0], (list, tuple)):
            table = {int(k): fn for k, fn in fns}
        else:
            table = {i: fn for i, fn in enumerate(fns)}
    keys = sorted(table)
    if default is None:
        default = table[keys[-1]]

    idx_v = unwrap(branch_index) if isinstance(branch_index, Tensor) \
        else branch_index
    if not _is_traced(idx_v) and not _static_recording():
        k = int(np.asarray(idx_v).reshape(()))
        return table.get(k, default)()

    # position i selects table[keys[i]]; position len(keys) = default.
    # The index mapping is itself an op (recorded under program_guard so the
    # data dependency on branch_index survives into the Program).
    fns = [table[k] for k in keys] + [default]

    def _pos_fn(iv):
        flat_idx = jnp.reshape(iv, ()).astype(jnp.int32)
        pos = jnp.int32(len(keys))
        for i, k in enumerate(keys):
            pos = jnp.where(flat_idx == k, jnp.int32(i), pos)
        return pos

    idx_t = branch_index if isinstance(branch_index, Tensor) \
        else Tensor(idx_v)
    pos_t = call_op_nograd(_pos_fn, idx_t, op_name="switch_index")
    return _switch_on_position(pos_t, fns, "switch_case")


def case(pred_fn_pairs, default=None, name=None):
    """Run the fn of the first true predicate; else `default`.

    Reference: `fluid/layers/control_flow.py:case:2712`. `default=None`
    falls back to the last pair's fn (reference semantics).
    """
    pairs = list(pred_fn_pairs)
    if default is None:
        default = pairs[-1][1]
    preds = [unwrap(p) if isinstance(p, Tensor) else p for p, _ in pairs]
    if not any(_is_traced(p) for p in preds) and not _static_recording():
        for p, fn in zip(preds, (fn for _, fn in pairs)):
            if bool(np.asarray(p).reshape(())):
                return fn()
        return default()

    pred_tensors = [p if isinstance(p, Tensor) else Tensor(p)
                    for p, _ in pairs]

    def _pos_fn(*ps):
        stacked = jnp.stack([_as_pred(p) for p in ps])
        first_true = jnp.argmax(stacked).astype(jnp.int32)  # first True wins
        return jnp.where(jnp.any(stacked), first_true, jnp.int32(len(pairs)))

    pos_t = call_op_nograd(_pos_fn, *pred_tensors, op_name="case_index")
    fns = [fn for _, fn in pairs] + [default]
    return _switch_on_position(pos_t, fns, "case")


# ---------------------------------------------------------------------------
# while_loop
# ---------------------------------------------------------------------------

def _as_var_list(out):
    return list(out) if isinstance(out, (list, tuple)) else [out]


def while_loop(cond, body, loop_vars, is_test=False, name=None,
               maximum_trip_count=None):
    """`while cond(*vars): vars = body(*vars)`; returns the final vars list.

    Reference: `fluid/layers/control_flow.py:while_loop:1075` → while_op
    (`operators/controlflow/while_op.cc`). Concrete predicate: Python loop
    (tape-differentiable). Traced predicate: `lax.while_loop` when no
    gradient is needed; when loop vars or captured closures require grad,
    XLA's static-shape model needs a bound — pass `maximum_trip_count` and
    the loop lowers to a masked, reverse-differentiable `lax.scan` (the
    reference instead re-executes the sub-block a recorded number of times,
    `while_op.cc` grad maker).
    """
    if not isinstance(loop_vars, (list, tuple)) or not loop_vars:
        raise ValueError("loop_vars must be a non-empty list/tuple")
    vars_ = list(loop_vars)

    if _static_recording():
        first_v = None  # placeholder values must not pick the path
    else:
        first = cond(*vars_)
        first_v = unwrap(first) if isinstance(first, Tensor) else first
    if first_v is not None and not _is_traced(first_v):
        while bool(np.asarray(
                unwrap(c) if isinstance((c := cond(*vars_)), Tensor) else c
                ).reshape(())):
            vars_ = _as_var_list(body(*vars_))
        return vars_

    flat, treedef = jax.tree_util.tree_flatten(
        vars_, is_leaf=lambda x: isinstance(x, Tensor))
    ext_c, _ = _capture(cond, *vars_)
    ext_b, body_out = _capture(body, *vars_)
    ext = _merge_ext(ext_c, ext_b)
    _, out_def = _flatten_out(_as_var_list(body_out))
    if out_def != treedef:
        raise ValueError(
            f"body must return the loop_vars structure: {treedef}, "
            f"got {out_def}")
    n_ext = len(ext)

    def rebuild(carry):
        return jax.tree_util.tree_unflatten(
            treedef, [v if isinstance(v, Tensor) else Tensor(v)
                      for v in carry])

    needs_grad = autograd.grad_enabled() and any(
        isinstance(t, Tensor) and not t.stop_gradient
        and jnp.issubdtype(jnp.asarray(unwrap(t)).dtype, jnp.inexact)
        for t in list(ext) + flat)

    if not needs_grad:
        def run(*vals):
            ext_vals, var_vals = vals[:n_ext], vals[n_ext:]

            def c_fn(carry):
                vals2, _ = _functional(cond, ext, ext_vals, *rebuild(carry))
                return _as_pred(vals2[0])

            def b_fn(carry):
                vals2, _ = _functional(body, ext, ext_vals, *rebuild(carry))
                return tuple(vals2)

            return lax.while_loop(c_fn, b_fn, tuple(var_vals))

        outs = call_op_nograd(run, *ext, *flat, op_name="while")
    else:
        if maximum_trip_count is None:
            raise ValueError(
                "while_loop under tracing with gradients needs a static "
                "bound: pass maximum_trip_count=N (XLA cannot "
                "reverse-differentiate an unbounded loop), or wrap the loop "
                "in paddle.no_grad()")

        def run(*vals):
            ext_vals, var_vals = vals[:n_ext], vals[n_ext:]

            def step(carry, _):
                done, cur = carry[0], carry[1:]
                cvals, _ = _functional(cond, ext, ext_vals, *rebuild(cur))
                bvals, _ = _functional(body, ext, ext_vals, *rebuild(cur))
                c = _as_pred(cvals[0])
                active = jnp.logical_and(jnp.logical_not(done), c)
                new = tuple(jnp.where(active, n, v)
                            for n, v in zip(bvals, cur))
                return (jnp.logical_or(done, jnp.logical_not(c)),) + new, None

            carry0 = (jnp.asarray(False),) + tuple(var_vals)
            final, _ = lax.scan(step, carry0, None,
                                length=int(maximum_trip_count))
            out = final[1:]
            # If the loop still wanted more iterations after the bound, the
            # result would be a silent truncation (the reference while_op runs
            # to completion). NaN-poison the float outputs so the failure is
            # loud — FLAGS_check_nan_inf and loss monitoring catch it.
            cvals, _ = _functional(cond, ext, ext_vals, *rebuild(out))
            truncated = _as_pred(cvals[0])
            poisoned = tuple(
                jnp.where(truncated, jnp.full_like(v, jnp.nan), v)
                if jnp.issubdtype(v.dtype, jnp.inexact) else v
                for v in out)
            return poisoned

        outs = call_op(run, *ext, *flat, op_name="while")

    outs = outs if isinstance(outs, tuple) else (outs,)
    return jax.tree_util.tree_unflatten(treedef, list(outs))


# ---------------------------------------------------------------------------
# fixed_loop
# ---------------------------------------------------------------------------

def _signature(leaves):
    return [(tuple(jnp.shape(unwrap(l))), jnp.result_type(unwrap(l)))
            for l in leaves]


def fixed_loop(body, loop_vars, trips, rolled=None):
    """`for _ in range(trips): vars = body(*vars)`; returns the list of
    loop vars with every trip's value stacked on a new first axis
    (`out[i][t]` is var i after trip t+1, `out[i][-1]` the final one).

    Reference: while_op (`operators/controlflow/while_op.cc`) under a
    bound known when the program is built. `rolled=None` rolls the loop
    exactly when the loop vars are traced (`to_static`): the body is
    captured once, the tensors it reads from the enclosing scope become
    operands, and `lax.scan(length=trips)` runs it as one tape node, so
    their gradients are summed over the trips by the scan's transpose.
    There is no predicate and no select over the carry. Eagerly (or with
    `rolled=False`) it is the python loop, differentiated by the tape op
    by op. A layer inside the body with `enable_recompute` keeps its
    remat segment in both forms. In a compiled step the region's device
    time goes under the scope `loop`.

    The body returns the loop vars' structure, shapes and dtypes, and
    must not mutate framework state (dropout's generator, BN statistics)
    when rolled."""
    if not isinstance(loop_vars, (list, tuple)) or not loop_vars:
        raise ValueError("loop_vars must be a non-empty list/tuple")
    trips = int(trips)
    if trips < 1:
        raise ValueError(f"trips must be >= 1, got {trips}")
    vars_ = list(loop_vars)

    def flatten(tree):
        return jax.tree_util.tree_flatten(
            tree, is_leaf=lambda x: isinstance(x, Tensor))

    flat, treedef = flatten(vars_)
    if rolled is None:
        rolled = any(_is_traced(unwrap(v)) for v in flat) \
            and not _static_recording()

    def check(out_leaves, out_def):
        if out_def != treedef or _signature(out_leaves) != _signature(flat):
            raise TypeError(
                f"fixed_loop: the body must return the loop vars as it "
                f"got them: {treedef} {_signature(flat)}, got {out_def} "
                f"{_signature(out_leaves)}")

    with scope("loop"):
        if not rolled:
            per_trip = []
            for _ in range(trips):
                leaves, out_def = flatten(_as_var_list(body(*vars_)))
                check(leaves, out_def)
                vars_ = jax.tree_util.tree_unflatten(treedef, leaves)
                per_trip.append(leaves)
            outs = [call_op(lambda *v: jnp.stack(v), *trip, op_name="stack")
                    for trip in zip(*per_trip)]
            return jax.tree_util.tree_unflatten(treedef, outs)

        before = [(t, t._value) for _uid, t in state_mod.snapshot()]
        ext, body_out = _capture(body, *vars_)
        for t, value in before:
            if t._value is not value:
                t._value = value
                raise RuntimeError(
                    f"fixed_loop: the body changed framework state "
                    f"({t.name!r}); a rolled body must be free of side "
                    f"effects (dropout inside it needs rolled=False)")
        check(*flatten(_as_var_list(body_out)))
        n_ext = len(ext)

        def rebuild(carry):
            return jax.tree_util.tree_unflatten(
                treedef, [Tensor(v) for v in carry])

        def run(*vals):
            ext_vals, var_vals = vals[:n_ext], vals[n_ext:]

            def trip(carry, _):
                new, _ = _functional(body, ext, ext_vals, *rebuild(carry),
                                     tape_off=autograd.functional_region)
                return tuple(new), tuple(new)

            _, stacked = lax.scan(trip, tuple(var_vals), None, length=trips)
            return stacked

        from ..jit.to_static import note_structure
        note_structure("rolled_loop_trips", trips)
        outs = call_op(run, *ext, *flat, op_name="fixed_loop")
    outs = outs if isinstance(outs, tuple) else (outs,)
    return jax.tree_util.tree_unflatten(treedef, list(outs))


# ---------------------------------------------------------------------------
# TensorArray (LoDTensorArray) — eager-only list semantics
# ---------------------------------------------------------------------------

def create_array(dtype="float32"):
    """Reference: `fluid/layers/control_flow.py:create_array` (LoDTensorArray).
    Eager list semantics; inside traced control flow use loop_vars with a
    preallocated Tensor + index writes instead (XLA static shapes)."""
    return []


def _check_eager_array(array, opname):
    if not isinstance(array, list):
        raise TypeError(f"{opname} expects a list created by create_array")


def array_write(x, i, array=None):
    if array is None:
        array = create_array()
    _check_eager_array(array, "array_write")
    idx = int(np.asarray(unwrap(i) if isinstance(i, Tensor) else i).reshape(()))
    if idx == len(array):
        array.append(x)
    elif idx < len(array):
        array[idx] = x
    else:
        raise IndexError(
            f"array_write index {idx} beyond array length {len(array)}")
    return array


def array_read(array, i):
    _check_eager_array(array, "array_read")
    idx = int(np.asarray(unwrap(i) if isinstance(i, Tensor) else i).reshape(()))
    return array[idx]


def array_length(array):
    _check_eager_array(array, "array_length")
    return Tensor(jnp.asarray(len(array), dtype=jnp.int64))
