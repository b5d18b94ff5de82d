"""@to_static: compile the imperative training step into one XLA computation.

The reference reaches whole-program execution via AST transformation →
ProgramDesc → run_program op (`python/paddle/fluid/dygraph/dygraph_to_static/
program_translator.py:759`, `partial_program.py:111`,
`operators/run_program_op.cc:176`). On TPU we get the same result by *tracing*:
the eager Tensor wraps whatever jax hands it, so running the user's python
step function under `jax.jit` with all framework state (parameters, buffers,
optimizer accumulators, RNG key, lr) threaded through as donated inputs turns
`forward(); loss.backward(); opt.step()` into a single compiled, fused,
buffer-aliased XLA program — the "north star" fast path.

Sharding: state tensors carry an optional PartitionSpec (`Tensor.pspec`);
when a mesh is active (fleet.init / paddle_tpu.distributed.set_mesh) state and
inputs are device_put onto NamedShardings before compilation, and GSPMD
inserts the collectives (the analog of the reference's c_allreduce insertion
by fleet meta-optimizers).
"""
import contextlib
import functools
import gc
import logging
import time
import weakref

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from .. import monitor
from ..core import state as state_mod
from ..core.dispatch import _CAPTURE as _dispatch_capture
from ..core.tensor import Tensor
from ..observability import scopes as _scopes
from ..observability import tracing as _obs
from ..testing import faults as _faults
from . import compile_cache

_is_tracing = False
_log = logging.getLogger("paddle_tpu.jit")

# the host phases of one step call, in order (`_CallPhases`)
CALL_PHASES = ("flatten", "snapshot", "place", "key", "launch", "wrap")
_PHASE_COUNTER = {name: f'to_static_call_ns{{phase="{name}"}}'
                  for name in CALL_PHASES}
# a cached call's worst case beside its mean
_PHASE_MAX = {name: f'to_static_call_max_ns{{phase="{name}"}}'
              for name in ("place", "launch")}
# the phases of a building call, disjoint and in order: a cached call's
# without `launch`, which is here in its four parts (jax's own trace of
# the real program, its lowering, the executable's load or compile, and
# what is left of `launch`: cache key, transfers, dispatch), after the
# analysis trace of `_build`
BUILD_PHASES = ("flatten", "snapshot", "place", "key", "analysis_trace",
                *compile_cache.JAX_STEPS, "launch_rest", "wrap")
_BUILD_COUNTER = {name: f'to_static_build_ns{{phase="{name}"}}'
                  for name in BUILD_PHASES}
_jax = compile_cache.this_thread


class _CallPhases:
    """The phases of one `StaticFunction` call: ``with phases("place"):``
    is a child span of `executor/step` while tracing is on (and so an
    annotation in any profile being captured), and always its
    nanoseconds. `commit()` adds a call that hit the program cache to
    the always-on counters ``to_static_calls`` and
    ``to_static_call_ns{phase=}``, and either to
    ``to_static_calls_recompiled`` and ``to_static_recompiled_launch_ns``
    (jax made a program under it: it re-specialised below the program
    cache) or to the maxima ``to_static_call_max_ns{phase=}``.
    `commit_build()` adds the building call to
    ``to_static_build_ns{phase=}``, once a build."""

    __slots__ = ("ns", "jax_ns", "jax_steps")

    def __init__(self):
        self.ns = {}
        # the thread's count of jax steps only rises, so a call nested in
        # this one takes nothing from what this one sees
        self.jax_steps = _jax.steps

    @contextlib.contextmanager
    def __call__(self, name):
        with _obs.trace_span("executor/step/" + name, cat="executor"):
            t0 = time.perf_counter_ns()
            try:
                yield
            finally:
                self.ns[name] = time.perf_counter_ns() - t0

    def commit(self):
        ns = self.ns
        monitor.stat_add("to_static_calls", 1)
        for name, counter in _PHASE_COUNTER.items():
            monitor.stat_add(counter, ns[name])
        # a call under which jax made a program is no steady call: it is
        # counted and kept out of the worst case
        if _jax.steps != self.jax_steps:
            monitor.stat_add("to_static_calls_recompiled", 1)
            monitor.stat_add("to_static_recompiled_launch_ns", ns["launch"])
        else:
            for name, counter in _PHASE_MAX.items():
                monitor.stat_max(counter, ns[name])
        if _host_pauses["during"] != "steady":  # the first cached call
            _host_pauses["during"] = "steady"
            # 0 is a reading too: listed from here on
            monitor.stat_add("to_static_calls_recompiled", 0)

    def commit_build(self):
        ns = dict(self.ns, **self.jax_ns)
        ns["launch_rest"] = max(
            ns.pop("launch") - sum(self.jax_ns.values()), 0)
        for name, counter in _BUILD_COUNTER.items():
            monitor.stat_add(counter, ns[name])


# -- the collector's pauses -----------------------------------------------
# One `gc.callbacks` entry from the first build on: every collection's
# nanoseconds, by whether the first cached call has committed yet, and
# the longest. A pause of seconds inside a window is otherwise nobody's.
_GC_WARN_NS = 500_000_000
_gc_clock = time.perf_counter_ns
_host_pauses = {"during": "setup", "open": None}
_GC_COUNTERS = {during: tuple(f'host_gc_{kind}{{during="{during}"}}'
                              for kind in ("ns", "collections", "max_ns"))
                for during in ("setup", "steady")}


def _on_gc(phase, info):
    if phase == "start":
        _host_pauses["open"] = (_obs.begin_span("host/gc", cat="executor"),
                                _gc_clock())
        return
    if _host_pauses["open"] is None:  # watched from inside a collection
        return
    span, t0 = _host_pauses["open"]
    ns = _gc_clock() - t0
    span.end()
    _host_pauses["open"] = None
    total, collections, longest = _GC_COUNTERS[_host_pauses["during"]]
    monitor.stat_add(total, ns)
    monitor.stat_add(collections, 1)
    monitor.stat_max(longest, ns)
    if ns > _GC_WARN_NS:
        _log.warning(
            "host paused %.3f s in one garbage collection (generation %d, "
            "%d objects collected, during %s)", ns / 1e9,
            info["generation"], info["collected"], _host_pauses["during"])


def _watch_host_pauses():
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


# step hooks: callables run inside every traced step body, after the
# framework state swaps to tracers and before the user function — the seam
# ZeRO-3 uses for just-in-time parameter materialization (per-bucket
# all_gather from the sharded carry). A hook returns an optional cleanup
# callable invoked when the body ends (success or error). Held weakly so a
# dead owner (a dropped optimizer) stops contributing ops.
_step_hooks = []


def register_step_hook(hook):
    """Register ``hook() -> cleanup|None`` to run at every step-body trace
    entry. Hooks are held WEAKLY (bound methods via WeakMethod) so the
    hook dies with its owner instead of pinning it — which means a bare
    closure/lambda with no other strong reference is collected before it
    ever fires; pass a bound method or a module-level function.
    Re-registering the same callable is a no-op."""
    for ref in _step_hooks:
        if ref() == hook:
            return hook
    _step_hooks.append(weakref.WeakMethod(hook)
                       if hasattr(hook, "__self__") else weakref.ref(hook))
    return hook


def _run_step_hooks(cleanups):
    """Run every live hook, appending each cleanup to ``cleanups`` AS IT
    IS PRODUCED — if a later hook raises, the caller's finally still
    unwinds the earlier hooks' overrides instead of leaking tracers onto
    live tensors."""
    dead = []
    for ref in _step_hooks:
        h = ref()
        if h is None:
            dead.append(ref)
            continue
        c = h()
        if c is not None:
            cleanups.append(c)
    for ref in dead:
        _step_hooks.remove(ref)


def _data_dependent_errors():
    import jax
    errs = []
    for name in ("TracerBoolConversionError", "ConcretizationTypeError",
                 "TracerIntegerConversionError"):
        e = getattr(jax.errors, name, None)
        if e is not None:
            errs.append(e)
    return tuple(errs)


_DATA_DEPENDENT_ERRORS = _data_dependent_errors()


def in_tracing():
    return _is_tracing


_capture_depth = [0]


@contextlib.contextmanager
def capture_pass():
    """A control-flow capture pass (`nn/control_flow.py:_capture`: the
    body run once to find what it reads): its time while a step trace
    runs, the outermost pass alone, in ``jit_capture_pass_ns`` and as a
    ``jit/capture_pass`` span. Every trace of the body pays it."""
    if not _is_tracing or _capture_depth[0]:
        yield
        return
    _capture_depth[0] += 1
    t0 = time.perf_counter_ns()
    try:
        with _obs.trace_span("jit/capture_pass", cat="jit"):
            yield
    finally:
        _capture_depth[0] -= 1
        monitor.stat_add("jit_capture_pass_ns", time.perf_counter_ns() - t0)


# what the newest trace of a step body staged, by kind: rolled regions'
# trip counts, remat segments, activation factors saved for the backward
# (`F.gelu`'s erfc), ZeRO buckets exchanged in their gradients' 16-bit type,
# held-expert layers and their experts, short-convolution, Gated DeltaNet and
# grouped-query attention layers, attention backwards on the fused flash
# kernel. Every trace of a build's boundary step stages the same: each
# restarts `jit_<kind>`.
_STRUCTURE = dict.fromkeys((
    "rolled_loop_trips", "recompute_segments", "saved_activation_factors",
    "zero_exchanged_buckets", "moe_layers", "moe_experts_held",
    "short_conv_layers", "gdn_layers", "gqa_attention_layers",
    "flash_fused_backwards"), 0)


def note_structure(kind, count=1):
    """Called by a construct as it stages itself into a step trace
    (nothing outside one, nothing from a capture pass, whose operations
    are dropped)."""
    if _is_tracing and not _dispatch_capture.stack:
        _STRUCTURE[kind] += count


def _is_dynamic(x):
    return isinstance(x, (Tensor, jax.Array, np.ndarray, np.generic))


class _StateSwap:
    """Swap registered state values (and accumulated grads) with tracers for
    the trace duration. Grads thread through like the reference's persistable
    @GRAD vars: accumulated-but-unconsumed gradients survive the compiled
    call (e.g. a step that only runs backward, stepping eagerly later)."""

    def __init__(self, items, values, grads):
        self.items = items
        self.values = values
        self.grads = grads
        self.saved = None

    def __enter__(self):
        global _is_tracing
        self.saved = [(t._value, t._tape_node, t._grad) for _, t in self.items]
        for (_, t), v, g in zip(self.items, self.values, self.grads):
            t._value = v
            t._tape_node = None
            t._grad = g
        self._was_tracing = _is_tracing
        _is_tracing = True
        return self

    def capture(self):
        return ([t._value for _, t in self.items],
                [t._grad for _, t in self.items])

    def __exit__(self, *exc):
        global _is_tracing
        _is_tracing = self._was_tracing
        for (_, t), (v, node, g) in zip(self.items, self.saved):
            t._value = v
            t._tape_node = node
            t._grad = g
        return False


def _leaf_key(x):
    if _is_dynamic(x):
        return ("dyn", tuple(np.shape(x)), np.dtype(
            x.dtype if hasattr(x, "dtype") else type(x)).str)
    try:
        hash(x)
        return ("static", x)
    except TypeError:
        return ("static", repr(x))


def jnp_issubdtype(dtype):
    """Inexact leaves are pmean-able; ints (indices, counters) must be
    rank-invariant already and pass through untouched."""
    return np.issubdtype(np.dtype(dtype), np.inexact)


def _abstract_arg(v):
    """ShapeDtypeStruct twin of a call argument (sharding kept for jax
    Arrays) — lets the AOT ``lower().compile()`` stats path re-derive the
    exact program without pinning live HBM buffers in the entry."""
    if isinstance(v, jax.Array):
        try:
            multi = len(v.sharding.device_set) > 1
        except Exception:
            multi = False
        if multi:
            # mesh-resident state keeps its layout; single-device args
            # (host-fed batches) stay unconstrained — mixing their
            # default placement with the mesh's would fail AOT lowering
            return jax.ShapeDtypeStruct(v.shape, v.dtype,
                                        sharding=v.sharding)
        return jax.ShapeDtypeStruct(v.shape, v.dtype)
    arr = np.asarray(v)
    return jax.ShapeDtypeStruct(arr.shape, arr.dtype)


def _is_sharded_spec(spec):
    return spec is not None and any(s is not None for s in spec)


def _axis_sizes(mesh):
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _local_shape(shape, spec, mesh):
    """Per-rank block shape of a global array under a PartitionSpec."""
    if spec is None:
        return tuple(shape)
    sizes = _axis_sizes(mesh)
    shape = list(shape)
    for d, s in enumerate(spec):
        if s is None:
            continue
        for name in (s if isinstance(s, tuple) else (s,)):
            f = sizes.get(name, 1)
            if shape[d] % f:
                raise ValueError(
                    f"dim {d} of shape {tuple(shape)} is not divisible by "
                    f"mesh axis {name!r} (size {f})")
            shape[d] //= f
    return tuple(shape)


def _global_shape(shape, spec, mesh):
    """Inverse of _local_shape: scale a per-rank block back up."""
    if spec is None:
        return tuple(shape)
    sizes = _axis_sizes(mesh)
    shape = list(shape)
    for d, s in enumerate(spec):
        if s is None:
            continue
        for name in (s if isinstance(s, tuple) else (s,)):
            shape[d] *= sizes.get(name, 1)
    return tuple(shape)


def _analysis_trace(pure_fn, state_vals, dyn_template, grad_vals, n, info):
    """Abstractly trace ``pure_fn(state, dyn, grads)`` and decide which
    state/grad inputs the program actually reads. Fills ``info`` (via the
    trace itself) and returns ``(closed_jaxpr, val_used, grad_used)``.
    ``dyn_template``/``grad_vals`` entries may be ``jax.ShapeDtypeStruct``
    placeholders — only shape/dtype matter here, nothing executes."""
    a_args = (state_vals, dyn_template, grad_vals)
    a_leaves, a_tdef = jax.tree_util.tree_flatten(a_args)
    closed = jax.make_jaxpr(
        lambda *ls: pure_fn(*jax.tree_util.tree_unflatten(a_tdef, ls))
    )(*a_leaves)
    used_vars = set()
    for eqn in closed.jaxpr.eqns:
        # Literals (hasattr .val) may be unhashable; only Vars matter
        used_vars.update(v for v in eqn.invars if not hasattr(v, "val"))
    # an invar returned verbatim in the *user-visible* outputs (fn
    # returns an unmodified param) must stay a runtime input, not be
    # frozen as a constant. Only the first n_out outvars are the user
    # outputs — an invar in its OWN slot of the new_state/new_grads
    # passthrough tail must NOT mark it used (or nothing would ever be
    # skippable), but landing in a DIFFERENT slot (EMA/target-network
    # sync: a.set_value(b) creates no eqn) is a real use.
    used_vars.update(v for v in closed.jaxpr.outvars[:info["n_out"]]
                     if not hasattr(v, "val"))
    invar_slot = {}
    for i in range(n):
        invar_slot[closed.jaxpr.invars[i]] = ("val", i)
    pos_in = n + len(dyn_template)
    for i, g in enumerate(grad_vals):
        if g is not None:
            invar_slot[closed.jaxpr.invars[pos_in]] = ("grad", i)
            pos_in += 1
    pos_out = info["n_out"]
    for j in range(n):  # new_state tail
        v = closed.jaxpr.outvars[pos_out]
        if (not hasattr(v, "val")
                and invar_slot.get(v, ("val", j)) != ("val", j)):
            used_vars.add(v)
        pos_out += 1
    for j, present in enumerate(info["grad_out_mask"]):  # new_grads tail
        if present:
            v = closed.jaxpr.outvars[pos_out]
            if (not hasattr(v, "val")
                    and invar_slot.get(v, ("grad", j)) != ("grad", j)):
                used_vars.add(v)
            pos_out += 1
    leaf_used = [v in used_vars for v in closed.jaxpr.invars]
    # map flat leaves back to (state, dyn, grad) slots; None grads were
    # dropped by tree_flatten, so enumerate in flatten order
    val_used = leaf_used[:n]
    grad_used = {}
    pos = n + len(dyn_template)
    for i, g in enumerate(grad_vals):
        if g is not None:
            grad_used[i] = leaf_used[pos]
            pos += 1
    return closed, val_used, grad_used


class StaticFunction:
    """Callable wrapper with a compile cache keyed on arg shapes/dtypes and
    the framework-state registry version (reference: StaticFunction
    program_translator.py:232 + its program cache).

    ``scan_steps=k`` selects the scan-compiled step program: ``fn`` is the
    SINGLE-step body, the wrapper consumes ``[k, ...]``-stacked dynamic
    inputs, and the body is traced ONCE and rolled with ``jax.lax.scan``
    carrying the full framework state — trace/compile time is ~independent
    of k (the unrolled program's is linear in k), which is what unlocks
    large dispatch-amortization factors. See ``_build_scan``.
    """

    def __init__(self, fn, input_spec=None, donate_state=True,
                 scan_steps=None, dp_axis=None, accumulate_steps=None,
                 xla_flags=None):
        from . import xla_flags as _xla_flags_mod
        self._fn = fn
        self._cache = {}
        self._donate = donate_state
        self._input_spec = input_spec
        # per-program XLA compiler options (latency-hiding A/B knob):
        # resolved once at wrap time (env overlay included), applied to
        # every compiled entry via _jit(). Scan-stepped programs with no
        # explicit request default to the latency-hiding preset IF the
        # backend registers it — judged lazily at first build (probing
        # at wrap time would force backend init at decoration);
        # xla_flags=False opts out (the A/B control arm spelling)
        self._xla_flags = _xla_flags_mod.resolve(xla_flags)
        self._xla_flags_default_pending = (
            xla_flags is None and scan_steps is not None
            and not self._xla_flags)  # env flags outrank the default too
        self._flagged_jits = []
        if scan_steps is not None and int(scan_steps) < 1:
            raise ValueError(f"scan_steps must be >= 1, got {scan_steps}")
        self._scan_steps = int(scan_steps) if scan_steps is not None else None
        if dp_axis is not None and self._scan_steps is None:
            raise ValueError(
                "dp_axis is an option of the scan step program; pass "
                "scan_steps=k (k=1 compiles a single-step scan)")
        self._dp_axis = dp_axis
        self._accumulate_steps = None
        if accumulate_steps is not None:
            a = int(accumulate_steps)
            if self._scan_steps is None:
                raise ValueError(
                    "accumulate_steps is an option of the scan step "
                    "program; pass scan_steps=k")
            if a < 1:
                raise ValueError(
                    f"accumulate_steps must be >= 1, got {accumulate_steps}")
            if a > 1 and self._scan_steps % a:
                raise ValueError(
                    f"scan_steps={self._scan_steps} must be a multiple of "
                    f"accumulate_steps={a} (whole accumulation windows)")
            self._accumulate_steps = a if a > 1 else None
        self._last_aux = None
        functools.update_wrapper(self, fn)

    def _jit(self, fun, **kwargs):
        """``jax.jit`` for one compiled entry, carrying this program's
        XLA compiler options (``jit.xla_flags``): unknown-flag errors
        degrade to an unflagged recompile with the fallback recorded as
        provenance — see :meth:`xla_flags`."""
        from . import xla_flags as _xla_flags_mod
        # the module's name carries the metadata schema, so that a
        # persistent cache never serves this program an executable from
        # before it had scopes (compile_cache.program_name)
        fun.__name__ = compile_cache.program_name(fun.__name__)
        if self._xla_flags_default_pending:
            self._xla_flags_default_pending = False
            preset = _xla_flags_mod.PRESETS[
                _xla_flags_mod.DEFAULT_SCAN_PRESET]
            if _xla_flags_mod.backend_accepts(preset):
                self._xla_flags = dict(preset)
        flagged = _xla_flags_mod.jit(fun, xla_flags=self._xla_flags,
                                     **kwargs)
        self._flagged_jits.append(flagged)
        return flagged

    def xla_flags(self):
        """Flag provenance of this program: the resolved per-program
        compiler options (env overlay included) and whether the backend
        accepted them — ``applied`` is True once a flagged compile
        succeeded, False after the unknown-flag fallback (with the
        error), None while no compiled entry has been judged yet. The
        value the bench records and runlogs carry next to any A/B
        row."""
        prov = {"flags": dict(self._xla_flags), "applied": None,
                "fallback_error": None}
        if not self._xla_flags:
            prov["applied"] = False  # nothing to apply
            return prov
        for fj in self._flagged_jits:
            if fj.applied is True:
                prov["applied"] = True
            elif fj.applied is False and prov["applied"] is None:
                prov["applied"] = False
            if fj.fallback_error and not prov["fallback_error"]:
                prov["fallback_error"] = fj.fallback_error
        return prov

    # -- sharding helpers -------------------------------------------------
    @staticmethod
    def _mesh():
        from ..distributed import parallel_env
        return parallel_env.current_mesh()

    @staticmethod
    def _place_state(items, mesh):
        """device_put state onto NamedShardings per tensor pspec (committed
        arrays steer GSPMD; donation keeps them in place thereafter). Arrays
        committed to a *different* mesh (stale from an earlier fleet.init)
        are re-placed onto the current one."""
        for _, t in items:
            v = t._value
            spec = t.pspec if t.pspec is not None else PartitionSpec()
            desired = NamedSharding(mesh, spec)

            def _placed(arr):
                if isinstance(arr, jax.Array) and getattr(arr, "committed", False):
                    try:
                        if arr.sharding.is_equivalent_to(desired, arr.ndim):
                            return arr  # already laid out as requested
                    except Exception:
                        pass  # unknown sharding type: re-place
                return jax.device_put(arr, desired)

            t._value = _placed(v)
            if t._grad is not None:  # accumulated grads follow the same layout
                t._grad = _placed(t._grad)

    def __call__(self, *args, **kwargs):
        if _is_tracing:  # nested to_static: inline
            return self._fn(*args, **kwargs)
        if not _obs.enabled("executor"):
            return self._call_impl(args, kwargs)
        # "executor/step": the compiled-program execution span — for the
        # to_static path this wrapper IS the executor of the jitted step
        with _obs.trace_span("executor/step", cat="executor",
                             fn=getattr(self, "__name__", "fn")):
            return self._call_impl(args, kwargs)

    def _call_impl(self, args, kwargs):
        phases = _CallPhases()
        with phases("flatten"):
            leaves, treedef = jax.tree_util.tree_flatten(
                (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor))
            dyn_idx = [i for i, l in enumerate(leaves) if _is_dynamic(l)]
            dyn_vals = [leaves[i]._value if isinstance(leaves[i], Tensor)
                        else leaves[i] for i in dyn_idx]
        with phases("snapshot"):
            state_items = state_mod.snapshot()
            mesh = self._mesh()
        with phases("place"):
            if mesh is not None:
                self._place_state(state_items, mesh)
                dyn_vals = self._place_args(dyn_vals, mesh)
        with phases("key"):
            # registry version determines membership/order, so uids need
            # not be part of the key; grad presence changes program
            # structure
            key = (treedef, tuple(_leaf_key(l) for l in leaves),
                   state_mod.version(),
                   tuple(t._grad is not None for _, t in state_items),
                   mesh is not None)
            entry = self._cache.get(key)
        if entry is not None:
            _obs.count("jit_cache_hit", cat="jit")
            out = self._run(entry, phases, dyn_vals)
            phases.commit()
            return out
        # the building call, counted apart: a program jax makes from
        # here to the end of this call's launch is the step's
        with compile_cache.owned_by("step"):
            entry = self._cache[key] = self._new_entry(
                phases, treedef, leaves, dyn_idx, state_items)
            # the analysis trace is over: jax's own steps, which come in
            # this call's launch, are the build's
            _jax.build = phases.jax_ns = dict.fromkeys(
                compile_cache.JAX_STEPS, 0)
            out = self._run(entry, phases, dyn_vals)
        phases.commit_build()
        return out

    def _new_entry(self, phases, treedef, leaves, dyn_idx, state_items):
        """A program-cache miss: `_build`'s analysis trace of the body
        (jax's own trace, the lowering and the executable come lazily,
        in the first `launch`) and the once-a-build counters."""
        _watch_host_pauses()
        scopes_before = _scopes.entered()
        with _obs.trace_span("jit/compile", cat="jit",
                             fn=getattr(self, "__name__", "fn"),
                             cache_size=len(self._cache)):
            t0 = time.perf_counter_ns()
            try:
                entry = self._build(treedef, leaves, dyn_idx, state_items)
            except _DATA_DEPENDENT_ERRORS as e:
                # data-dependent python control flow: fall back to the AST
                # transformation (reference: program_translator.py always
                # AST-transforms; here the plain trace is the fast path)
                if not self._try_ast_fallback(e):
                    raise
                entry = self._build(treedef, leaves, dyn_idx, state_items)
            phases.ns["analysis_trace"] = time.perf_counter_ns() - t0
        monitor.stat_add("jit_cache_miss", 1)
        monitor.stat_add("jit_build_ns", phases.ns["analysis_trace"])
        for kind, count in _STRUCTURE.items():
            monitor.stat_add("jit_" + kind, count)
        entry[2]["traced_with_scopes"] = _scopes.entered() > scopes_before
        from ..analysis import debug_enabled
        if debug_enabled():
            # analysis debug mode: the fresh build's state partition
            # must be hazard-free before the entry is ever run
            from ..analysis import VerifyError, errors
            bad = errors(self.verify())
            if bad:
                raise VerifyError(
                    bad, context=f"to_static build of "
                    f"{getattr(self, '__name__', 'fn')!r}")
        return entry

    def _run(self, entry, phases, dyn_vals):
        compiled, out_wrap, aux = entry
        self._last_aux = aux
        # chaos seam: an injected RESOURCE_EXHAUSTED here simulates a
        # training-step allocation failure on the exact path a real XLA
        # OOM surfaces (the flight recorder classifies and dumps it)
        _faults.kill_point("jit/step")
        with phases("launch"):
            out_flat = compiled(dyn_vals)
        with phases("wrap"):
            return out_wrap(out_flat)

    def _make_aux(self, get_jitted, **meta):
        """Per-entry introspection handle: captures abstract twins of the
        first call's arguments, from which the optimized (post-SPMD) HLO
        AND the executable's XLA memory analysis can be re-derived on
        demand — the sources of truth for in-trace collective byte
        accounting and per-program HBM attribution. The lazy
        ``lower().compile()`` is a second backend compile (abstract
        args: no HBM buffers pinned), paid once per entry on the first
        stats request and shared by every accessor."""
        aux = dict(meta)

        def capture(args):
            if "example_args" not in aux:
                aux["example_args"] = jax.tree_util.tree_map(
                    _abstract_arg, args)

        def _materialize():
            # ONE lazy AOT compile feeds every introspection artifact
            # (HLO text, memory stats, top buffers); the loaded
            # executable itself is NOT retained — on a real backend its
            # generated code occupies device memory, and pinning a
            # duplicate executable per entry for the lifetime of the
            # StaticFunction would double the footprint this layer
            # exists to account for
            if "hlo" in aux:
                return
            ex = aux.get("example_args")
            if ex is None:
                raise RuntimeError(
                    "program has not executed yet; run the step once "
                    "before asking for its compiled HLO")
            from ..observability import memory
            with compile_cache.owned_by("introspect"):
                compiled = get_jitted().lower(*ex).compile()
            hlo = compiled.as_text()
            try:
                aux["memory"] = memory.program_stats(compiled)
                aux["memory_buffers"] = memory.top_buffers(hlo)
            except memory.MemoryAttributionError as e:
                # a backend without usable memory_analysis() must not
                # break hlo_text(); memory_stats() re-raises
                aux["memory_error"] = e
            # what names each instruction: derived from the same text,
            # and the newest kept in the process-wide program registry
            # so that a reader need not hold the step
            aux["scopes"] = _scopes.scope_table(
                hlo, aux.get("traced_with_scopes"))
            memory.record_program_scopes(
                f"{getattr(self, '__name__', 'fn')}:"
                f"{aux.get('kind', 'unrolled')}", aux["scopes"], hlo)
            aux["hlo"] = hlo

        def scope_table():
            _materialize()
            return aux["scopes"]

        def hlo_text():
            _materialize()
            return aux["hlo"]

        def memory_stats():
            # argument/output/temp/alias/generated-code bytes + the
            # top result buffers (what an OOM dump names); cached per
            # entry like the HLO text
            _materialize()
            if "memory" not in aux:
                raise aux["memory_error"]
            return aux["memory"]

        def traced_stats():
            # jaxpr-level liveness meter (observability.jaxpr_mem): the
            # backend-independent structural view that stays honest about
            # rematerialization where the CPU executable meter cannot
            # (XLA CPU strips optimization barriers and CSEs remat away)
            ex = aux.get("example_args")
            if ex is None:
                raise RuntimeError(
                    "program has not executed yet; run the step once "
                    "before asking for its traced memory stats")
            if "traced" not in aux:
                from ..observability import jaxpr_mem
                # donated state (the default) compiles carried stores to
                # in-place updates; the meter models that same aliasing
                aux["traced"] = jaxpr_mem.traced_peak_stats(
                    get_jitted(), *ex, alias_io=self._donate)
            return aux["traced"]

        def schedulable_stats(mesh=None, **cost_kwargs):
            # jaxpr-level emission-order overlap headroom
            # (observability.overlap.schedulable_stats): like the
            # liveness meter, sourced from the traced program — the
            # compiled text's postorder re-sort erases the pipeline
            # structure this measures
            ex = aux.get("example_args")
            if ex is None:
                raise RuntimeError(
                    "program has not executed yet; run the step once "
                    "before asking for its schedulable-overlap stats")
            key = ("schedulable", tuple(sorted(cost_kwargs.items())))
            if key not in aux:
                from ..observability import overlap
                aux[key] = overlap.schedulable_stats(
                    get_jitted(), ex, mesh=mesh, **cost_kwargs)
            return aux[key]

        def traced_jaxpr():
            # the traced program itself (pre-XLA), for structural
            # analyzers that walk equations rather than prices — the
            # sharding checker (analysis.shardcheck) propagates
            # shard_map pspecs over exactly this view
            ex = aux.get("example_args")
            if ex is None:
                raise RuntimeError(
                    "program has not executed yet; run the step once "
                    "before asking for its traced jaxpr")
            if "jaxpr" not in aux:
                fun = get_jitted()
                inner = getattr(fun, "_fun", fun)
                aux["jaxpr"] = jax.make_jaxpr(inner)(*ex)
            return aux["jaxpr"]

        aux["capture"] = capture
        aux["hlo_text"] = hlo_text
        aux["scope_table"] = scope_table
        aux["memory_stats"] = memory_stats
        aux["traced_stats"] = traced_stats
        aux["schedulable_stats"] = schedulable_stats
        aux["traced_jaxpr"] = traced_jaxpr
        return aux

    def hlo_text(self):
        """Optimized (post-SPMD-partitioning) HLO of the most recent
        entry — the program XLA actually runs, GSPMD/shard_map collectives
        included."""
        if self._last_aux is None:
            raise RuntimeError("no compiled entry yet; call the step once")
        return self._last_aux["hlo_text"]()

    def scope_table(self):
        """``{instruction name: scope path}`` of the most recent entry's
        compiled HLO (``observability.scopes.scope_table``): which
        layer, op, attention path or optimizer part each device
        instruction belongs to, from the ``pt.`` scopes entered while
        the step was traced. ``table["stale"]`` is True when the step
        was traced with scopes and its executable names none (an
        executable kept by a persistent compile cache from before the
        program had scopes). Shares the lazy AOT compile of
        :meth:`hlo_text`; the newest table is also registered in
        ``observability.memory.program_scopes()``."""
        if self._last_aux is None:
            raise RuntimeError("no compiled entry yet; call the step once")
        return self._last_aux["scope_table"]()

    def collective_stats(self, per_execution=False):
        """In-trace collective accounting of the most recent entry: one
        record per (op, axis) with call count and payload bytes, parsed
        from the compiled HLO (closing the 'in-trace collectives are
        invisible to python timers' gap — see observability.hlo_bytes).
        ``per_execution=True`` multiplies ops inside while-loops by their
        known trip counts, so a k-step scan bills its collectives k times
        — the number that shows gradient accumulation cutting collective
        bytes per program execution ~a×."""
        from ..observability import hlo_bytes
        return hlo_bytes.collective_stats(self.hlo_text(),
                                          mesh=self._mesh(),
                                          per_execution=per_execution)

    def export_collective_bytes(self):
        """Export collective_stats() into the shared monitor registry as
        ``collective_bytes{op=...,axis=...}`` / ``collective_count{...}``
        counters; returns the stats."""
        from ..observability import hlo_bytes
        stats = self.collective_stats()
        hlo_bytes.export_collective_bytes(stats)
        return stats

    def overlap_stats(self, **cost_kwargs):
        """Schedule-level latency-hiding analysis of the most recent
        entry (``observability.overlap``): pairs async collective
        ``-start``/``-done`` ops with the compute scheduled between
        them and prices hidden vs exposed collective time with a static
        cost model, reporting ``collective_overlap_efficiency``,
        ``exposed_collective_frac``, per-op splits, and the
        ``backend_sync_schedule`` flag (XLA:CPU emits mostly-sync
        schedules — efficiency 0.0 there is the honest baseline the
        ``xla_flags`` latency-hiding A/B is judged against on real
        hardware). Cost-model rates (``link_gbps``, ``hbm_gbps``,
        ``peak_flops``) and ``per_execution`` pass through.

        The ``schedulable_overlap`` / ``schedulable_ns`` fields are
        spliced in from the TRACED JAXPR (:meth:`schedulable_stats`)
        when the traced program is reachable: the compiled text's
        dependency-postorder re-sort erases the emission-order pipeline
        structure that score measures, so the text-derived value would
        read 0.0 even for a correctly pipelined step. The text-walk
        numbers remain in each ``pairs`` record."""
        from ..observability import overlap
        per_exec = cost_kwargs.pop("per_execution", True)
        rates = dict(cost_kwargs)
        stats = overlap.overlap_stats(self.hlo_text(), mesh=self._mesh(),
                                      per_execution=per_exec, **rates)
        try:
            sched = self.schedulable_stats(**rates)
        except Exception:
            return stats  # no traced program (e.g. restored dump)
        stats["schedulable_overlap"] = sched["schedulable_overlap"]
        stats["schedulable_ns"] = sched["schedulable_ns"]
        stats["schedulable_pairs"] = sched["pairs"]
        for op, slot in sched["per_op"].items():
            tslot = stats["per_op"].setdefault(
                op, {"hidden_ns": 0.0, "exposed_ns": 0.0,
                     "collective_ns": 0.0, "efficiency": 0.0})
            tslot["schedulable_ns"] = slot["schedulable_ns"]
            tslot["schedulable"] = slot["schedulable"]
        stats["assumptions"]["schedulable_source"] = sched["source"]
        return stats

    def schedulable_stats(self, **cost_kwargs):
        """Backend-independent schedulable-overlap score of the most
        recent entry, measured on its traced jaxpr emission order
        (``observability.overlap.schedulable_stats``): how much
        collective time the program structure leaves hideable, before
        any backend scheduler has its say. The serial on-demand ZeRO-3
        step scores 0.0; the double-buffered prefetch pipeline scores
        > 0 — on every backend, including the CPU smoke mesh."""
        if self._last_aux is None:
            raise RuntimeError("no compiled entry yet; call the step once")
        return self._last_aux["schedulable_stats"](mesh=self._mesh(),
                                                   **cost_kwargs)

    def export_overlap_stats(self, **cost_kwargs):
        """Export :meth:`overlap_stats` onto the gauge board
        (``collective_overlap_efficiency`` per program + per op-kind,
        ``exposed_collective_ns_estimate{op=,axis=}``,
        ``collective_async_pairs_total``/``collective_sync_total``) and
        the active run-log; returns the stats."""
        from ..observability import overlap
        stats = self.overlap_stats(**cost_kwargs)
        overlap.export_overlap_stats(
            stats, program=getattr(self, "__name__", "fn"))
        return stats

    def memory_stats(self):
        """Per-program HBM attribution from the compiled executable's
        XLA ``memory_analysis()`` — one record per compiled entry
        (build order), keyed ``<fn>#<i>:<kind>``::

            {"train_step#0:scan": {"argument_bytes": ..,
                                   "output_bytes": .., "temp_bytes": ..,
                                   "alias_bytes": ..,
                                   "generated_code_bytes": ..,
                                   "peak_bytes": ..}}

        Donated state rides the carry as aliased input/output pairs, so
        ``alias_bytes`` ≈ the carried state and ``peak_bytes`` counts it
        once. Only entries that have executed at least once are
        attributable (the abstract arg twins are captured on first
        call); unexecuted entries are skipped."""
        out = {label: aux["memory_stats"]()
               for label, aux in self._memory_entries()}
        if not out:
            raise RuntimeError(
                "no executed compiled entry yet; call the step once "
                "before asking for its memory attribution")
        return out

    def traced_memory_stats(self):
        """Jaxpr-liveness memory attribution per compiled entry
        (``observability.jaxpr_mem``): the sequential high-water bytes
        of the TRACED step program, keyed like :meth:`memory_stats`.
        Backend-independent and remat-aware — an activation-recompute
        policy shrinks this number even on the CPU smoke host, where
        the compiled-executable meter is blind to rematerialization
        (barriers stripped + CSE). The TPU re-pin captures the
        executable view."""
        out = {label: aux["traced_stats"]()
               for label, aux in self._memory_entries()}
        if not out:
            raise RuntimeError(
                "no executed compiled entry yet; call the step once "
                "before asking for its memory attribution")
        return out

    def _memory_entries(self):
        """``(label, aux)`` per attributable compiled entry — the ONE
        place the ``<fn>#<i>:<kind>`` label scheme lives."""
        name = getattr(self, "__name__", "fn")
        out = []
        for i, (_key, entry) in enumerate(self._cache.items()):
            aux = entry[2]
            if aux.get("example_args") is None:
                continue
            out.append((f"{name}#{i}:{aux.get('kind', 'unrolled')}", aux))
        return out

    def export_memory_stats(self):
        """Export :meth:`memory_stats` as
        ``program_hbm_bytes{entry=,kind=}`` gauges and register each
        entry (with its top buffers) in the process-wide program-memory
        registry the flight recorder snapshots at death; returns the
        stats."""
        from ..observability import memory
        # ONE walk builds and registers: a second _memory_entries()
        # pass could see an entry another thread compiled in between
        stats = {}
        for label, aux in self._memory_entries():
            stats[label] = memory.record_program_memory(
                label, aux["memory_stats"](),
                buffers=aux.get("memory_buffers"))
        if not stats:
            raise RuntimeError(
                "no executed compiled entry yet; call the step once "
                "before asking for its memory attribution")
        return stats

    def _place_args(self, dyn_vals, mesh):
        """Respect explicit input shardings; default: leave placement to jax
        (replicated). DataParallel layers set `_arg_pspec` on the wrapper."""
        specs = getattr(self, "_arg_pspecs", None)
        if specs is None:
            return dyn_vals
        out = []
        for v, spec in zip(dyn_vals, specs):
            if spec is None:
                out.append(v)
            else:
                out.append(jax.device_put(v, NamedSharding(mesh, spec)))
        return out

    def _make_pure_fn(self, treedef, template_leaves, dyn_idx, state_items,
                      out_template, info):
        """The functionalized user step: ``(state, dyn, grads) -> (outs,
        new_state, new_grads)``. Fills ``out_template``/``info`` as a side
        effect of tracing (both build modes share it).

        Under ``dp_axis`` the body runs per-rank inside shard_map: the dp
        axis is published (``parallel_env.current_dp_axis``) so the
        optimizer/AMP layers route gradient reduction through explicit
        collectives, and the user outputs — per-rank partial losses over
        the local microbatch — are pmean'd back to the global value the
        replicated program would have returned."""
        fn = self._fn
        dp_axis = self._dp_axis

        def pure_fn(state_vals, dyn_vals, grad_vals):
            from ..distributed import parallel_env
            for kind in _STRUCTURE:
                _STRUCTURE[kind] = 0
            leaves = list(template_leaves)
            for i, v in zip(dyn_idx, dyn_vals):
                leaves[i] = Tensor(v)
            args, kwargs = jax.tree_util.tree_unflatten(treedef, leaves)
            with _StateSwap(state_items, state_vals, grad_vals) as swap, \
                    parallel_env.dp_axis_ctx(dp_axis):
                cleanups = []
                try:
                    _run_step_hooks(cleanups)
                    out = fn(*args, **kwargs)
                    out_leaves, out_treedef = jax.tree_util.tree_flatten(
                        out, is_leaf=lambda x: isinstance(x, Tensor))
                    out_vals = [l._value if isinstance(l, Tensor) else l
                                for l in out_leaves]
                    if dp_axis is not None \
                            and parallel_env.axis_bound(dp_axis):
                        out_vals = [
                            jax.lax.pmean(v, dp_axis)
                            if (hasattr(v, "dtype")
                                and jnp_issubdtype(v.dtype)) else v
                            for v in out_vals]
                    out_template["treedef"] = out_treedef
                    new_state, new_grads = swap.capture()
                finally:
                    for c in cleanups:
                        c()
            info["w_val"] = [nv is not ov
                             for nv, ov in zip(new_state, state_vals)]
            info["w_grad"] = [ng is not og
                              for ng, og in zip(new_grads, grad_vals)]
            info["n_out"] = len(jax.tree_util.tree_flatten(out_vals)[0])
            info["grad_out_mask"] = [ng is not None for ng in new_grads]
            return out_vals, new_state, new_grads

        return pure_fn

    def _build(self, treedef, template_leaves, dyn_idx, state_items):
        compile_cache.ensure_enabled()  # backend is initialized by now
        if self._scan_steps is not None:
            return self._build_scan(treedef, template_leaves, dyn_idx,
                                    state_items)
        return self._build_unrolled(treedef, template_leaves, dyn_idx,
                                    state_items)

    def _build_unrolled(self, treedef, template_leaves, dyn_idx, state_items):
        """Two-phase build.

        Phase A traces the user function once (abstractly) threading *all*
        state, and records which state values / grads the program actually
        writes (object identity of the tracer survives only if untouched)
        and which inputs it reads (jaxpr var usage).

        Phase B compiles the real program threading only what matters:
        written entries are donated inputs + outputs (PJRT aliasing — the
        in-place Variable update of the reference); read-only entries are
        plain inputs (no donation, no passthrough output — XLA would
        otherwise materialize a full copy of every parameter in grad-only
        programs); untouched entries are not passed at all (keeps dispatch
        overhead proportional to the program's real state footprint).
        """
        out_template = {}
        info = {}
        pure_fn = self._make_pure_fn(treedef, template_leaves, dyn_idx,
                                     state_items, out_template, info)
        n = len(state_items)
        state_vals = [t._value for _, t in state_items]
        grad_vals = [t._grad for _, t in state_items]

        # ---- phase A: analysis trace ----
        dyn_template = [l._value if isinstance(l, Tensor) else l
                        for l in (template_leaves[i] for i in dyn_idx)]
        closed, val_used, grad_used = _analysis_trace(
            pure_fn, state_vals, dyn_template, grad_vals, n, info)

        w_val, w_grad = info["w_val"], info["w_grad"]
        don_val_idx = [i for i in range(n) if w_val[i]]
        ro_val_idx = [i for i in range(n)
                      if not w_val[i] and val_used[i]]
        # only *written* grads are donated (their buffers are replaced from
        # the outputs); grads the program merely reads must stay un-donated
        # or XLA may alias them to a same-shaped output and delete the
        # buffer out from under the live Tensor._grad
        don_grad_idx = [i for i in range(n)
                        if grad_vals[i] is not None and w_grad[i]]
        ro_grad_idx = [i for i in range(n)
                       if grad_vals[i] is not None and not w_grad[i]
                       and grad_used.get(i, False)]
        out_grad_idx = [i for i in range(n) if w_grad[i]]
        # skipped entries are only materialized at (re)trace time, read from
        # the live tensors — capturing concrete arrays here would pin stale
        # HBM buffers in the compile cache for the life of the entry
        skip_val_idx = [i for i in range(n)
                        if not w_val[i] and not val_used[i]]
        skip_grad_idx = [i for i in range(n)
                         if i not in don_grad_idx and i not in ro_grad_idx]

        # ---- phase B: the real program ----
        def pure_fn2(don_vals, don_grads, dyn_vals, ro_vals, ro_grads):
            sv = [None] * n
            gv = [None] * n
            for i, v in zip(don_val_idx, don_vals):
                sv[i] = v
            for i, v in zip(ro_val_idx, ro_vals):
                sv[i] = v
            for i in skip_val_idx:  # trace-time read of the live value
                sv[i] = state_items[i][1]._value
            for i, g in zip(don_grad_idx, don_grads):
                gv[i] = g
            for i, g in zip(ro_grad_idx, ro_grads):
                gv[i] = g
            for i in skip_grad_idx:
                gv[i] = state_items[i][1]._grad
            out_vals, new_state, new_grads = pure_fn(sv, dyn_vals, gv)
            return (out_vals,
                    [new_state[i] for i in don_val_idx],
                    [new_grads[i] for i in out_grad_idx])

        donate = (0, 1) if self._donate else ()
        jitted = self._jit(pure_fn2, donate_argnums=donate)

        # introspection (tests / debugging): which state uids ended up where
        uids = [uid for uid, _ in state_items]
        self._last_partition = {
            "donated": [uids[i] for i in don_val_idx],
            "readonly": [uids[i] for i in ro_val_idx],
            "skipped": [uids[i] for i in skip_val_idx],
            "donated_grads": [uids[i] for i in don_grad_idx],
            "readonly_grads": [uids[i] for i in ro_grad_idx],
            "sharded": [uids[i] for i in range(n)
                        if _is_sharded_spec(state_items[i][1].pspec)],
            "carry_optional": [uids[i] for i in range(n)
                               if getattr(state_items[i][1],
                                          "_carry_optional", False)],
            "dp_axis": None,
            "donate": bool(self._donate),
            "state_meta": {uids[i]: {
                "name": getattr(state_items[i][1], "name", None),
                "category": getattr(state_items[i][1],
                                    "_ledger_category", None),
                "pspec": state_items[i][1].pspec,
            } for i in range(n)},
        }

        # direct Tensor references per partition: the per-call hot path
        # touches only the state the program actually uses
        don_ts = [state_items[i][1] for i in don_val_idx]
        ro_ts = [state_items[i][1] for i in ro_val_idx]
        dong_ts = [state_items[i][1] for i in don_grad_idx]
        rog_ts = [state_items[i][1] for i in ro_grad_idx]
        outg_ts = [state_items[i][1] for i in out_grad_idx]

        aux = self._make_aux(lambda: jitted, kind="unrolled")

        def compiled(dyn_vals):
            args = ([t._value for t in don_ts],
                    [t._grad for t in dong_ts],
                    dyn_vals,
                    [t._value for t in ro_ts],
                    [t._grad for t in rog_ts])
            aux["capture"](args)
            out_flat, new_w, new_g = jitted(*args)
            for t, v in zip(don_ts, new_w):
                t._value = v
            for t, g in zip(outg_ts, new_g):
                t._grad = g
            return out_flat

        def out_wrap(out_flat):
            wrapped = [Tensor(v) if isinstance(v, jax.Array) else v
                       for v in out_flat]
            return jax.tree_util.tree_unflatten(out_template["treedef"], wrapped)

        return compiled, out_wrap, aux

    def _build_scan(self, treedef, template_leaves, dyn_idx, state_items):
        """Scan-compiled step program: trace the single-step body once and
        roll it k times with ``jax.lax.scan``.

        The full framework state rides the scan carry — written state
        values (params, optimizer accumulators + fp32 masters, the RNG
        key, a scheduled lr) and written/accumulated grads — so the
        reference's persistable-@GRAD survival semantics hold through the
        carry: a grad accumulated in inner step i is the grad input of
        inner step i+1, and one that survives the last step is written
        back to ``Tensor._grad``. Read-only state enters as plain
        (broadcast) inputs, untouched state is skipped exactly like the
        unrolled build. The stacked ``[k, ...]`` dynamic args are the scan
        ``xs``, so each inner step consumes a fresh microbatch; per-step
        user outputs come back ``[k, ...]``-stacked.

        Grad carry structure must be iteration-invariant, which python
        ``None`` grads are not, so presence is solved to a fixpoint: a
        grad the body CREATES (None at entry, live at exit) joins the
        carry initialized to zeros (additive accumulation makes zeros ≡
        "no grad yet"), and a grad the body CLEARS (opt.clear_grad) flows
        to the next step as zeros and is written back as ``None`` after
        the scan, matching the unrolled program observably.

        ``dp_axis``: the whole scan runs inside ``shard_map`` with that
        mesh axis manual — the body sees per-rank microbatch shards and
        per-rank shards of any PartitionSpec-sharded carry state (the
        ZeRO optimizer stores), gradient reduction happens through the
        explicit collectives the optimizer issues (per-param psum for the
        replicated control, bucketed psum_scatter, or all_to_all of 16-bit
        gradients, + all_gather under ZeRO); the fixpoint runs over LOCAL
        shapes so the analysis trace matches the shard_map body exactly.
        """
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec

        k = self._scan_steps
        dp_axis = self._dp_axis
        mesh = self._mesh()
        if dp_axis is not None:
            if mesh is None:
                raise RuntimeError(
                    f"dp_axis={dp_axis!r} needs an active device mesh "
                    "(fleet.init or parallel_env.set_mesh)")
            sizes = _axis_sizes(mesh)
            if dp_axis not in sizes:
                raise ValueError(
                    f"mesh axes {list(sizes)} have no {dp_axis!r}")
            for name, size in sizes.items():
                if name != dp_axis and size != 1:
                    raise NotImplementedError(
                        f"the dp-sharded scan step binds every mesh axis "
                        f"manually; axis {name!r} has size {size} — build "
                        "the step mesh with only the dp axis > 1")
            dp = sizes[dp_axis]
        out_template = {}
        info = {}
        pure_fn = self._make_pure_fn(treedef, template_leaves, dyn_idx,
                                     state_items, out_template, info)
        n = len(state_items)
        state_vals = [t._value for _, t in state_items]
        state_specs = [t.pspec for _, t in state_items]

        # single-step abstract templates from the [k, ...]-stacked args
        dyn_stacked = [template_leaves[i]._value
                       if isinstance(template_leaves[i], Tensor)
                       else template_leaves[i] for i in dyn_idx]
        xs_specs = None
        if dp_axis is not None:
            user_specs = getattr(self, "_arg_pspecs", None)
            # default microbatch sharding is only safe when EVERY stacked
            # arg agrees on the dim-1 size (features + labels of one
            # batch); a lone divisible aux input must not get silently
            # split 1/dp — that computes on a fraction of its values
            dim1 = {tuple(np.shape(v))[1] for v in dyn_stacked
                    if len(np.shape(v)) >= 2}
            auto_ok = len(dim1) == 1 and next(iter(dim1)) % dp == 0
            if not auto_ok and user_specs is None and dp > 1:
                import warnings
                warnings.warn(
                    f"dp_axis={dp_axis!r}: stacked inputs disagree on a "
                    f"microbatch dim (dim-1 sizes {sorted(dim1)}); all "
                    "inputs stay REPLICATED per rank — set "
                    "`sfn._arg_pspecs` to shard the batch explicitly")
            xs_specs = []
            for j, v in enumerate(dyn_stacked):
                shape = tuple(np.shape(v))
                if user_specs is not None and j < len(user_specs) \
                        and user_specs[j] is not None:
                    spec = user_specs[j]
                elif auto_ok and len(shape) >= 2:
                    # microbatch dim of the [k, batch, ...] stack
                    spec = PartitionSpec(None, dp_axis)
                else:
                    spec = PartitionSpec()
                if len(spec) > 0 and spec[0] is not None:
                    raise ValueError(
                        f"xs arg {j}: the leading [k] scan dim cannot be "
                        f"sharded (spec {spec})")
                xs_specs.append(spec)
        step_tmpl = []
        for j, v in enumerate(dyn_stacked):
            shape = tuple(np.shape(v))
            if not shape or shape[0] != k:
                raise ValueError(
                    f"scan_steps={k}: every dynamic input must be stacked "
                    f"[k, ...]; got shape {shape}")
            if dp_axis is not None:
                shape = _local_shape(shape, xs_specs[j], mesh)
            step_tmpl.append(jax.ShapeDtypeStruct(shape[1:],
                                                  np.dtype(v.dtype)))

        # analysis templates: sharded state enters the shard_map body as
        # its per-rank block, so the fixpoint must trace local shapes
        if dp_axis is not None:
            a_state = [jax.ShapeDtypeStruct(
                           _local_shape(np.shape(v), spec, mesh),
                           np.dtype(v.dtype))
                       if _is_sharded_spec(spec) else v
                       for v, spec in zip(state_vals, state_specs)]
        else:
            a_state = state_vals

        # accumulation windows trace the SAME body in two phases: "accum"
        # (updates defer, grads survive clear_grad) for the first a-1
        # steps of each window and "fire" (one update over the 1/a-scaled
        # accumulated grads) for the window boundary. The phase is
        # published through parallel_env.accum_ctx, which the
        # optimizer/GradScaler consult.
        a = self._accumulate_steps

        def _phase_fn(phase):
            if a is None:
                return pure_fn

            def wrapped(sv, dv, gv):
                from ..distributed import parallel_env
                with parallel_env.accum_ctx(phase, a):
                    return pure_fn(sv, dv, gv)
            return wrapped

        fire_fn = _phase_fn("fire")
        accum_fn = _phase_fn("accum") if a is not None else None

        # grad-presence fixpoint (presence only grows, so it terminates);
        # grads follow their tensor's layout (localize like the values).
        # With accumulation BOTH body flavors contribute: the carry must
        # cover the union of their written state and surviving grads.
        grad_tmpl = [t._grad for _, t in state_items]
        if dp_axis is not None:
            grad_tmpl = [jax.ShapeDtypeStruct(
                             _local_shape(np.shape(g), spec, mesh),
                             np.dtype(g.dtype))
                         if g is not None and _is_sharded_spec(spec) else g
                         for g, spec in zip(grad_tmpl, state_specs)]
        # the boundary body is traced last: the structure counters are its
        modes = [("accum", accum_fn)] if accum_fn is not None else []
        modes.append(("fire", fire_fn))
        mode_res = {}
        for _ in range(2 * (n + 1)):
            grew = False
            for mname, mfn in modes:
                closed, m_used, mg_used = _analysis_trace(
                    mfn, a_state, step_tmpl, grad_tmpl, n, info)
                mode_res[mname] = (dict(info), m_used, mg_used)
                out_avals = list(closed.out_avals)
                pos = info["n_out"] + n
                for i, present in enumerate(info["grad_out_mask"]):
                    if present:
                        if grad_tmpl[i] is None:
                            grad_tmpl[i] = jax.ShapeDtypeStruct(
                                out_avals[pos].shape, out_avals[pos].dtype)
                            grew = True
                        pos += 1
            if not grew:
                break

        fire_info, val_used, grad_used = mode_res["fire"]
        w_val = list(fire_info["w_val"])
        w_grad = list(fire_info["w_grad"])
        val_used = list(val_used)
        grad_used = dict(grad_used)
        if "accum" in mode_res:
            ainfo, a_used, ag_used = mode_res["accum"]
            w_val = [x or y for x, y in zip(w_val, ainfo["w_val"])]
            w_grad = [x or y for x, y in zip(w_grad, ainfo["w_grad"])]
            val_used = [x or y for x, y in zip(val_used, a_used)]
            for i, u in ag_used.items():
                grad_used[i] = grad_used.get(i, False) or u
        # grads written back after the call follow the BOUNDARY body's
        # exit state (the last inner step of the last window fires)
        steady_mask = list(fire_info["grad_out_mask"])
        info.update(fire_info)
        carry_val_idx = [i for i in range(n) if w_val[i]]
        ro_val_idx = [i for i in range(n) if not w_val[i] and val_used[i]]
        skip_val_idx = [i for i in range(n)
                        if not w_val[i] and not val_used[i]]
        carry_grad_idx = [i for i in range(n)
                          if grad_tmpl[i] is not None and w_grad[i]]
        ro_grad_idx = [i for i in range(n)
                       if grad_tmpl[i] is not None and not w_grad[i]
                       and grad_used.get(i, False)]
        skip_grad_idx = [i for i in range(n)
                         if i not in carry_grad_idx and i not in ro_grad_idx]
        # zeros template per carried grad: the scan-carry aval (used both
        # for the initial carry when the live grad is None and for the
        # cleared-inside-the-step substitution). Under dp_axis the body
        # shape is the per-rank block; the init zeros built OUTSIDE the
        # shard_map need the global shape.
        carry_g_sds = {i: (tuple(grad_tmpl[i].shape),
                           np.dtype(grad_tmpl[i].dtype))
                       for i in carry_grad_idx}
        carry_g_init = {
            i: ((_global_shape(shape, state_specs[i], mesh)
                 if dp_axis is not None else shape), dt)
            for i, (shape, dt) in carry_g_sds.items()}

        def pure_fn2(carry_vals, carry_grads, xs_stacked, ro_vals, ro_grads):
            def _mk_body(step_fn):
                def body(carry, xs):
                    c_vals, c_grads = carry
                    sv = [None] * n
                    gv = [None] * n
                    for i, v in zip(carry_val_idx, c_vals):
                        sv[i] = v
                    for i, v in zip(ro_val_idx, ro_vals):
                        sv[i] = v
                    for i in skip_val_idx:  # trace-time read, live value
                        sv[i] = state_items[i][1]._value
                    for i, g in zip(carry_grad_idx, c_grads):
                        gv[i] = g
                    for i, g in zip(ro_grad_idx, ro_grads):
                        gv[i] = g
                    for i in skip_grad_idx:
                        gv[i] = state_items[i][1]._grad
                    out_vals, new_state, new_grads = step_fn(sv, list(xs),
                                                             gv)
                    next_grads = []
                    for i in carry_grad_idx:
                        g = new_grads[i]
                        if g is None:  # cleared: zeros ≡ cleared for i+1
                            shape, dt = carry_g_sds[i]
                            g = jnp.zeros(shape, dt)
                        next_grads.append(g)
                    return ([new_state[i] for i in carry_val_idx],
                            next_grads), tuple(out_vals)
                return body

            init = (list(carry_vals), list(carry_grads))
            if a is None:
                (f_vals, f_grads), ys = jax.lax.scan(
                    _mk_body(fire_fn), init, tuple(xs_stacked), length=k)
                return list(ys), f_vals, f_grads

            # accumulation windows: outer scan over k/a windows, each an
            # inner scan of a-1 deferred micro steps plus the boundary
            # step that fires the update — the per-window collectives
            # appear once in this body instead of once per inner step
            w = k // a
            tmap = jax.tree_util.tree_map
            xs_win = tmap(lambda x: x.reshape((w, a) + x.shape[1:]),
                          tuple(xs_stacked))
            accum_body = _mk_body(accum_fn)
            fire_body = _mk_body(fire_fn)

            def window(carry, xs_w):
                carry, ys_head = jax.lax.scan(
                    accum_body, carry, tmap(lambda x: x[:a - 1], xs_w),
                    length=a - 1)
                carry, ys_last = fire_body(carry,
                                           tmap(lambda x: x[a - 1], xs_w))
                ys_w = tmap(lambda h, l: jnp.concatenate([h, l[None]], 0),
                            ys_head, ys_last)
                return carry, ys_w

            (f_vals, f_grads), ys = jax.lax.scan(window, init, xs_win,
                                                 length=w)
            ys = tmap(lambda y: y.reshape((k,) + y.shape[2:]), ys)
            return list(ys), f_vals, f_grads

        donate = (0, 1) if self._donate else ()
        if dp_axis is not None:
            def _spec(i):
                return (state_specs[i] if state_specs[i] is not None
                        else PartitionSpec())
            cv_specs = [_spec(i) for i in carry_val_idx]
            cg_specs = [_spec(i) for i in carry_grad_idx]
            ro_specs = [_spec(i) for i in ro_val_idx]
            rog_specs = [_spec(i) for i in ro_grad_idx]
            # ys are pmean'd replicated in the body; final carry values
            # reassemble per their PartitionSpec
            smapped = jax.shard_map(
                pure_fn2, mesh=mesh,
                in_specs=(cv_specs, cg_specs, list(xs_specs), ro_specs,
                          rog_specs),
                out_specs=(PartitionSpec(), cv_specs, cg_specs),
                check_vma=False)
            jitted = self._jit(smapped, donate_argnums=donate)
        else:
            jitted = self._jit(pure_fn2, donate_argnums=donate)

        uids = [uid for uid, _ in state_items]
        self._last_partition = {
            "donated": [uids[i] for i in carry_val_idx],
            "readonly": [uids[i] for i in ro_val_idx],
            "skipped": [uids[i] for i in skip_val_idx],
            "donated_grads": [uids[i] for i in carry_grad_idx],
            "readonly_grads": [uids[i] for i in ro_grad_idx],
            "sharded": [uids[i] for i in range(n)
                        if _is_sharded_spec(state_specs[i])],
            "carry_optional": [uids[i] for i in range(n)
                               if getattr(state_items[i][1],
                                          "_carry_optional", False)],
            "dp_axis": dp_axis, "scan_steps": k, "accumulate_steps": a,
            # of the boundary step's analysis trace, the last one above
            "zero_exchanged_buckets": _STRUCTURE["zero_exchanged_buckets"],
            "donate": bool(self._donate),
            "state_meta": {uids[i]: {
                "name": getattr(state_items[i][1], "name", None),
                "category": getattr(state_items[i][1],
                                    "_ledger_category", None),
                "pspec": state_specs[i],
            } for i in range(n)},
        }

        carry_ts = [state_items[i][1] for i in carry_val_idx]
        ro_ts = [state_items[i][1] for i in ro_val_idx]
        cg_ts = [state_items[i][1] for i in carry_grad_idx]
        rog_ts = [state_items[i][1] for i in ro_grad_idx]

        aux = self._make_aux(lambda: jitted, kind="scan", scan_steps=k,
                             dp_axis=dp_axis, accumulate_steps=a)

        def compiled(dyn_vals):
            init_grads = []
            for i, t in zip(carry_grad_idx, cg_ts):
                g = t._grad
                if g is None:
                    shape, dt = carry_g_init[i]
                    g = jnp.zeros(shape, dt)
                init_grads.append(g)
            args = ([t._value for t in carry_ts], init_grads, dyn_vals,
                    [t._value for t in ro_ts], [t._grad for t in rog_ts])
            aux["capture"](args)
            ys, f_vals, f_grads = jitted(*args)
            for t, v in zip(carry_ts, f_vals):
                t._value = v
            for i, t, g in zip(carry_grad_idx, cg_ts, f_grads):
                t._grad = g if steady_mask[i] else None
            return ys

        def out_wrap(out_flat):
            wrapped = [Tensor(v) if isinstance(v, jax.Array) else v
                       for v in out_flat]
            return jax.tree_util.tree_unflatten(out_template["treedef"],
                                                wrapped)

        return compiled, out_wrap, aux

    def _try_ast_fallback(self, cause):
        """Swap self._fn for its dy2static-transformed version once."""
        import types as _types

        if getattr(self._fn, "_jst_transformed", False):
            return False
        from .dy2static import convert_to_static
        try:
            fn = self._fn
            if isinstance(fn, _types.MethodType):
                conv = convert_to_static(fn.__func__)
                self._fn = _types.MethodType(conv, fn.__self__)
            else:
                self._fn = convert_to_static(fn)
        except (OSError, TypeError, SyntaxError) as e:
            raise RuntimeError(
                "tracing hit data-dependent python control flow "
                f"({cause!s:.200}) and the AST fallback could not transform "
                f"{self._fn!r} ({e}). Rewrite the condition with "
                "paddle_tpu.nn.control_flow (cond/while_loop), or decorate "
                "a plain `def` (lambdas cannot be AST-transformed).")
        return True

    def verify(self):
        """Static-analysis check of the compiled step's state partition
        (paddle_tpu.analysis.check_static_function): donated /
        read-only / skipped state classes must be disjoint. Returns the
        findings; exported as analysis counters."""
        from ..analysis import _export, check_static_function
        findings = check_static_function(self)
        _export(findings)
        return findings

    # paddle API compat
    @property
    def code(self):
        import inspect
        return inspect.getsource(self._fn)

    def concrete_program(self):
        return None


def to_static(function=None, input_spec=None, build_strategy=None,
              scan_steps=None, dp_axis=None, accumulate_steps=None,
              xla_flags=None, **kwargs):
    """Decorator / wrapper, usable as @to_static or to_static(fn).

    ``scan_steps=k`` compiles ``function`` (the single-step body) as a
    ``jax.lax.scan`` over k inner steps: dynamic args must arrive
    ``[k, ...]``-stacked (one microbatch per inner step) and per-step
    outputs return ``[k, ...]``-stacked. Compile time is ~independent of
    k, vs linear in k for a python-unrolled loop over the body.

    ``dp_axis='dp'`` runs the scan inside ``shard_map`` with that mesh
    axis manual: the microbatch is split 1/dp per rank, gradient
    reduction goes through the explicit collectives the optimizer
    issues — per-param psum for a replicated optimizer, bucketed
    ``psum_scatter`` (16-bit gradients: ``all_to_all``) + ``all_gather`` after
    ``optimizer._zero_enable()`` (ZeRO; stage 3 adds per-bucket param
    ``all_gather`` before the forward instead, with params riding the
    carry as 1/dp shards) — and PartitionSpec-sharded optimizer state
    rides the donated carry as per-rank shards. User outputs
    (losses/metrics) are pmean'd over the axis.

    ``accumulate_steps=a`` groups the k inner steps into k/a gradient
    accumulation windows: the first a-1 steps of each window run with
    optimizer/scaler updates deferred (gradients accumulate through the
    scan carry — per-param for replicated/ZeRO-1 state, reduced into the
    sharded per-bucket accumulator for ZeRO-2/3) and the window's last
    step fires one update over the 1/a-scaled accumulated gradients, so
    the reduce/update(/all_gather) collectives bill once per window
    instead of once per step.

    ``xla_flags`` passes per-program XLA compiler options (a
    ``jit.xla_flags`` preset name like ``"latency-hiding"``, a
    ``"flag=value ..."`` string, or a dict; the
    ``PADDLE_TPU_XLA_FLAGS`` env var overlays and wins). Flags a
    backend doesn't register fall back to an unflagged compile with
    provenance recorded — see ``StaticFunction.xla_flags()`` and
    ``overlap_stats()`` for the A/B this knob exists for. Scan-stepped
    programs (``scan_steps=k``) with no explicit value DEFAULT to the
    ``"latency-hiding"`` preset when the backend registers it (judged
    once per process — ``jit.xla_flags.backend_accepts``); pass
    ``xla_flags=False`` to opt a program out (the A/B control arm)."""
    if function is None:
        return lambda fn: to_static(fn, input_spec=input_spec,
                                    scan_steps=scan_steps, dp_axis=dp_axis,
                                    accumulate_steps=accumulate_steps,
                                    xla_flags=xla_flags, **kwargs)
    if isinstance(function, StaticFunction):
        return function
    # Layers: wrap forward, keep the layer object semantics
    from ..nn.layer.layers import Layer
    if isinstance(function, Layer):
        layer = function
        static_forward = StaticFunction(layer.forward, input_spec,
                                        scan_steps=scan_steps,
                                        dp_axis=dp_axis,
                                        accumulate_steps=accumulate_steps,
                                        xla_flags=xla_flags, **kwargs)
        layer.forward = static_forward
        return layer
    return StaticFunction(function, input_spec, scan_steps=scan_steps,
                          dp_axis=dp_axis,
                          accumulate_steps=accumulate_steps,
                          xla_flags=xla_flags, **kwargs)


class InputSpec:
    """Shape/dtype declaration (reference: paddle.static.InputSpec)."""

    def __init__(self, shape, dtype="float32", name=None):
        self.shape = tuple(shape)
        self.dtype = dtype
        self.name = name

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype})"


def not_to_static(fn):
    fn._not_to_static = True
    return fn
