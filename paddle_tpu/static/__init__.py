"""paddle_tpu.static — the static-graph front-end.

The reference's Program/Executor machine (`python/paddle/fluid/framework.py`,
`executor.py`) exists to hand a whole graph to a compiler; on TPU the
whole-graph compiler *is* XLA, so `paddle.static` here is a thin veneer: a
Program records a python callable built from `paddle.static.data`
placeholders, and Executor.run jit-compiles it. The imperative+to_static path
is the blessed one; this module exists for API parity so static-style user
code ports over. (Full ProgramDesc IR with ops-as-protobuf is deliberately
NOT rebuilt — see SURVEY.md §7 design stance.)
"""
from .program import (  # noqa: F401
    Program, program_guard, default_main_program, default_startup_program,
    data, Executor, global_scope, name_scope,
    append_backward, gradients, Block, Operator,
)
from ..jit.to_static import InputSpec  # noqa: F401
from .passes import apply_pass, register_pass, list_passes, prune  # noqa: F401
from .transpiler import (  # noqa: F401
    DistributeTranspiler, DistributeTranspilerConfig, PsServerProgram)
from .. import nn as _nn  # re-export for paddle.static.nn style usage

_STATIC_MODE = [False]


def _enable_static(flag=True):
    _STATIC_MODE[0] = flag


def _static_mode():
    return _STATIC_MODE[0]


def save(program, model_path, protocol=4):
    """Persist a program's trainable state for TRAINING resume (reference:
    `fluid/io.py save:1840` — persistables + optimizer accumulators; the
    serving artifact is save_inference_model). Writes `{path}.pdparams`
    and `{path}.pdopt` (npz with names)."""
    import io as _io
    import numpy as _np

    # keyed by program SLOT: slot order is the program structure, stable
    # across rebuilds (auto-generated tensor names are not)
    params = {str(s): _np.asarray(t._value)
              for s, t in sorted(program.params.items())}
    buf = _io.BytesIO()
    _np.savez(buf, **{f"p{i}": v for i, v in enumerate(params.values())})
    with open(model_path + ".pdparams", "wb") as f:
        f.write(buf.getvalue())
    opt_state = {}
    opt = program._optimizer
    if opt is not None:
        id_to_slot = {id(t): s for s, t in program.params.items()}
        for (acc_name, pid), t in sorted(opt._accumulators.items(),
                                         key=lambda kv: str(kv[0])):
            ps = id_to_slot.get(pid)
            if ps is not None:
                opt_state[f"{ps}.{acc_name}"] = _np.asarray(t._value)
        opt_state["@step"] = _np.asarray(opt._step_count._value)
        opt_state["@lr"] = _np.asarray(opt._lr.value())
        sched = opt._lr.scheduler
        if sched is not None:
            sd = sched.state_dict()
            opt_state["@sched.last_epoch"] = _np.asarray(
                sd.get("last_epoch", -1))
            opt_state["@sched.last_lr"] = _np.asarray(
                sd.get("last_lr", opt.get_lr()))
    buf2 = _io.BytesIO()
    _np.savez(buf2, **{f"o{i}": v for i, v in enumerate(opt_state.values())})
    with open(model_path + ".pdopt", "wb") as f:
        f.write(buf2.getvalue())
    import json as _json
    with open(model_path + ".pdmeta", "w") as f:
        _json.dump({"params": list(params.keys()),
                    "opt": list(opt_state.keys())}, f)


def load(program, model_path, executor=None, var_list=None):
    """Restore state written by static.save (reference: fluid/io.py
    load:1948)."""
    import json as _json
    import numpy as _np

    with open(model_path + ".pdmeta") as f:
        meta = _json.load(f)
    data = _np.load(model_path + ".pdparams")
    for i, slot in enumerate(meta["params"]):
        t = program.params.get(int(slot))
        if t is not None:
            t.set_value(data[f"p{i}"])
    opt = program._optimizer
    if opt is not None and meta["opt"]:
        odata = _np.load(model_path + ".pdopt")
        slot_to_id = {s: id(t) for s, t in program.params.items()}
        acc_by_key = {(acc_name, pid): t
                      for (acc_name, pid), t in opt._accumulators.items()}
        sched_state = {}
        for i, key in enumerate(meta["opt"]):
            v = odata[f"o{i}"]
            if key == "@step":
                opt._step_count.set_value(v)
            elif key == "@lr":
                opt._lr.set(v)
            elif key.startswith("@sched."):
                sched_state[key[len("@sched."):]] = v.item()
            else:
                ps, acc_name = key.split(".", 1)
                pid = slot_to_id.get(int(ps))
                acc = acc_by_key.get((acc_name, pid))
                if acc is not None:
                    acc.set_value(v)
        if sched_state and opt._lr.scheduler is not None:
            # restore AFTER @lr so the scheduler's _push wins consistently
            opt._lr.scheduler.set_state_dict(sched_state)


def create_parameter(shape, dtype="float32", name=None, attr=None,
                     is_bias=False, default_initializer=None):
    """Reference: `paddle.static.create_parameter`
    (`python/paddle/fluid/layers/tensor.py`)."""
    from ..core.tensor import Parameter
    from ..nn import initializer as I
    from ..nn.layer.layers import ParamAttr
    attr = ParamAttr._to_attr(attr)
    init = (attr.initializer or default_initializer
            or (I._default_bias_init() if is_bias
                else I._default_weight_init()))
    value = init(list(shape), dtype)
    p = Parameter(value, name=name or attr.name)
    return p


def save_inference_model(path_prefix, feed_vars, fetch_vars, executor,
                         program=None):
    """Serialize the program pruned to feed→fetch as a StableHLO artifact
    (reference: `fluid/io.py:1246` — prune + ProgramDesc + persistables)."""
    from ..jit.export import save_exported
    from .passes import prune as _prune
    prog = (program or default_main_program()).clone(for_test=True)
    prog = _prune(prog, fetch_vars)  # reference: prune.cc feed/fetch slice
    layer = prog.as_layer(feed_vars, fetch_vars)
    specs = []
    for v in feed_vars:
        name = v.name
        slot_shape_dtype = prog.feed_vars.get(name)
        if slot_shape_dtype is not None:
            _, shape, dtype = slot_shape_dtype
            specs.append(InputSpec([None if s == -1 else s for s in shape],
                                   dtype=dtype, name=name))
        else:
            specs.append(v)
    # the program's persistable slots (parameters/buffers it replays against)
    # are exactly the reference's pruned persistables set
    items = [(t.name, t) for t in prog.params.values()]
    save_exported(path_prefix, layer.forward, items, specs,
                  output_names=[getattr(v, "name", f"output_{i}")
                                for i, v in enumerate(fetch_vars)])


def load_inference_model(path_prefix, executor):
    from ..jit.io import load as _jit_load
    layer = _jit_load(path_prefix)
    feed_names = getattr(layer, "input_names", None)
    fetch_names = getattr(layer, "output_names", None)
    return layer, feed_names, fetch_names


def py_func(func, x, out, backward_func=None, skip_vars_in_backward_input=None):
    """Embed a host-python callback in the computation (reference:
    operators/py_func_op.cc / paddle.static.py_func). `out` declares the
    result shape/dtype (an InputSpec or template Tensor). Eager calls run
    the callback directly on host values with a tape node for
    `backward_func`; under tracing the call lowers to jax.pure_callback."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from ..core import autograd
    from ..core.dispatch import unwrap, wrap
    from ..core.tensor import Tensor

    xs = list(x) if isinstance(x, (list, tuple)) else [x]
    outs = out if isinstance(out, (list, tuple)) else [out]
    shapes = [jax.ShapeDtypeStruct(tuple(o.shape),
                                   np.dtype(getattr(o, "dtype", "float32")
                                            if not isinstance(o, Tensor)
                                            else o.numpy().dtype))
              for o in outs]
    vals = [unwrap(v) for v in xs]
    single = not isinstance(out, (list, tuple))

    def host_fwd(*a):
        res = func(*[np.asarray(v) for v in a])
        res = res if isinstance(res, (list, tuple)) else [res]
        return [np.asarray(r, dtype=s.dtype).reshape(s.shape)
                for r, s in zip(res, shapes)]

    traced = any(isinstance(v, jax.core.Tracer) for v in vals)
    if traced:
        res = jax.pure_callback(
            lambda *a: tuple(host_fwd(*a)), tuple(shapes), *vals)
        res = list(res)
    else:
        res = [jnp.asarray(r) for r in host_fwd(*vals)]

    diff_pos = [i for i, t in enumerate(xs)
                if isinstance(t, Tensor) and not t.stop_gradient]
    diff = [xs[i] for i in diff_pos]
    if backward_func is None or not diff or not autograd.grad_enabled():
        wrapped = [wrap(r) for r in res]
        return wrapped[0] if single else wrapped

    skip = set()
    if skip_vars_in_backward_input is not None:
        sk = (skip_vars_in_backward_input
              if isinstance(skip_vars_in_backward_input, (list, tuple))
              else [skip_vars_in_backward_input])
        skip = {id(t) for t in sk}
    bwd_in = [v for t, v in zip(xs, vals) if id(t) not in skip]
    out_vals = list(res)

    def vjp_fn(cots):
        # reference contract (operators/py_func_op.cc): backward_func
        # receives (non-skipped inputs) + outputs + output-grads and
        # returns one gradient per input of x, in x order
        grads = backward_func(*[np.asarray(v) for v in bwd_in],
                              *[np.asarray(o) for o in out_vals],
                              *[np.asarray(c) for c in cots])
        grads = grads if isinstance(grads, (list, tuple)) else [grads]
        grads = [None if g is None else jnp.asarray(g) for g in grads]
        if len(grads) == len(xs):
            picked = [grads[i] for i in diff_pos]
        elif len(grads) == len(diff_pos):
            picked = grads  # already one per differentiable input
        else:
            raise ValueError(
                f"backward_func returned {len(grads)} grads for "
                f"{len(xs)} inputs ({len(diff_pos)} differentiable)")
        return tuple(jnp.zeros(np.shape(v), np.asarray(v).dtype)
                     if g is None else g
                     for g, v in zip(picked, (vals[i] for i in diff_pos)))

    node = autograd.TapeNode(vjp_fn, diff,
                             [(tuple(r.shape), r.dtype) for r in res],
                             name="py_func")
    wrapped = []
    for i, r in enumerate(res):
        t = Tensor(r, stop_gradient=False)
        t._tape_node = node
        t._tape_index = i
        wrapped.append(t)
    return wrapped[0] if single else wrapped
