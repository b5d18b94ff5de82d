"""Activation recompute + host offload: the jax.checkpoint policy surface.

ZeRO-3 (ISSUE 5) cut model-state residency to O(params/dp); what binds
batch size and scan depth now is the ACTIVATIONS the backward pass keeps
alive between forward and backward. The reference ships exactly this
lever as ``fleet/utils/recompute.py`` (RecomputeFunction: drop the
segment's intermediate activations, replay the forward in backward with
the RNG state restored) plus the sharding optimizer's
``offload_helper.py`` (park state in host memory). On TPU the same trade
is one primitive — ``jax.checkpoint`` — but models must not call it
directly: which values are worth saving (and WHERE they are parked) is a
backend decision, so it routes through this policy surface
(``analysis/lint.py`` enforces that with the ``raw-remat-outside-policy``
rule).

Policies::

    none       pass-through (the A/B control; no remat region)
    full       recompute everything inside the segment per backward
               (jax default: nothing_saveable) — minimum residency,
               maximum recompute FLOPs
    selective  save matmul/dot outputs, recompute the cheap elementwise
               chain (``jax.checkpoint_policies.checkpoint_dots`` class
               of policy) — the usual sweet spot: dots are the expensive
               ops AND the big activations are mostly elementwise chains
    kernels    save what the Pallas kernels wrote (the flash kernel's
               output and log-sum-exp: 1/h of a layer's activations),
               recompute everything else — ``full`` without replaying
               the one operation XLA cannot fuse into its neighbours
    offload    save dot outputs but park them in PINNED HOST memory
               (``offload_dot_with_no_batch_dims('device',
               'pinned_host')``): device residency of ``full`` with the
               recompute FLOPs of ``selective``, paid in PCIe/ICI
               traffic. Backends without a ``pinned_host`` memory space
               (CPU jaxlib today) FALL BACK LOUDLY to ``selective`` —
               a silent no-op here would fake the memory claim.

Usage (``paddle.recompute`` is this MODULE; the function lives in it)::

    from paddle_tpu.recompute import recompute
    out = recompute(layer_fn, x, policy="selective")   # immediate
    fn  = recompute(layer_fn, policy="full")           # wrapper
    layer.enable_recompute("offload")                  # Layer seam

How it composes with the stack: the segment function is functionalized
with the same ``OpCapture`` + ``bind_values`` seam control-flow lowering
uses — one capture pass discovers the external tensors the segment reads
(parameters, buffers) and the framework state it MUTATES (the RNG key a
dropout advances, BN running stats), then the segment re-runs inside
``jax.checkpoint`` as a pure function whose inputs/outputs thread all of
it explicitly. The whole region dispatches through ``call_op`` as ONE
tape node, so:

- eagerly, the tape holds only the checkpoint's vjp residuals (policy-
  saved values), not the per-op activation chain — real memory savings
  before any jit;
- under ``@to_static(..., scan_steps=k)`` the region stages into the
  step jaxpr as a remat sub-jaxpr: XLA rematerializes in the compiled
  backward, the @GRAD-presence fixpoint sees one op, and the donated
  carry / ZeRO-1/2/3 / accumulation-window machinery is untouched;
- dropout replays BITWISE: the key mathematics (split of the generator
  state) happens INSIDE the remat region on the threaded-in key value,
  so the rematerialized backward re-derives the same keys — the
  reference RecomputeFunction's RNG-state-replay contract, for free.

Cost model: the capture pass runs the segment once per call to discover
its externals/mutations (re-discovered every call on purpose — the
external set can depend on python control flow inside ``fn``, so a
structural cache would silently bind stale parameters). Under
``to_static`` that is trace-time only (the capture ops are dead code
XLA drops). In EAGER training it is a real extra forward per segment
per step — eager recompute trades that and the backward replay for the
dropped residuals; the compiled scan step is the performance path.
"""
import functools
import threading
import warnings

import jax
import numpy as np

from .core import autograd, dispatch
from .core import random as core_random
from .core import state as state_mod
from .core.dispatch import bind_values, call_op
from .core.tensor import Tensor

__all__ = ["recompute", "resolve_policy", "host_offload_available",
           "remat_replay", "is_remat_replay", "POLICIES"]

POLICIES = ("none", "full", "selective", "kernels", "offload")

# host memory kind used by the offload policy (pjit memory kinds)
OFFLOAD_MEMORY_KIND = "pinned_host"


# -- policy resolution ------------------------------------------------------

_offload_probe = [None]  # cached: None = not probed yet
_probe_lock = threading.Lock()


def host_offload_available():
    """True when the default backend exposes a ``pinned_host`` memory
    space (the pjit host-memory-kind the offload policy parks residuals
    in). Probed once per process; CPU jaxlib today has only
    ``unpinned_host`` and returns False."""
    with _probe_lock:
        if _offload_probe[0] is None:
            try:
                jax.local_devices()[0].memory(OFFLOAD_MEMORY_KIND)
                _offload_probe[0] = True
            except Exception:
                _offload_probe[0] = False
        return _offload_probe[0]


def _reset_offload_probe():
    """Test seam: forget the cached backend probe."""
    with _probe_lock:
        _offload_probe[0] = None


def _kernel_outputs_saveable(prim, *_avals, **_params):
    return prim.name == "pallas_call"


def resolve_policy(policy, strict=False):
    """``(jax_policy_or_None, effective_name)`` for a policy name (or a
    raw ``jax.checkpoint_policies`` callable, passed through for power
    users — prefer the names so backends stay swappable).

    ``offload`` degrades to ``selective`` WITH A WARNING when the
    backend has no ``pinned_host`` memory space; ``strict=True`` raises
    instead (for callers that must not fake the residency claim, e.g. a
    test explicitly pinning offload behavior)."""
    if callable(policy):
        return policy, getattr(policy, "__name__", "custom")
    name = str(policy)
    if name not in POLICIES:
        raise ValueError(
            f"unknown recompute policy {policy!r}; pick one of {POLICIES} "
            "(or pass a jax.checkpoint_policies callable)")
    cp = jax.checkpoint_policies
    if name == "none":
        return None, "none"
    if name == "full":
        # jax.checkpoint's default: save nothing, recompute everything
        return cp.nothing_saveable, "full"
    if name == "selective":
        # save dot/matmul outputs without a batch dim (weight-stationary
        # products); recompute the elementwise chains — the
        # checkpoint_dots analog that does not hoard the big batched
        # activations
        return cp.dots_with_no_batch_dims_saveable, "selective"
    if name == "kernels":
        return _kernel_outputs_saveable, "kernels"
    # offload
    if host_offload_available():
        return (cp.offload_dot_with_no_batch_dims(
            "device", OFFLOAD_MEMORY_KIND), "offload")
    msg = (f"recompute policy 'offload' needs a {OFFLOAD_MEMORY_KIND!r} "
           f"memory space on the backend "
           f"({jax.default_backend()!r} has none)")
    if strict:
        raise RuntimeError(msg)
    warnings.warn(msg + "; falling back to 'selective' (dot outputs stay "
                  "in device memory)", stacklevel=3)
    return cp.dots_with_no_batch_dims_saveable, "selective"


# -- remat replay marker (static-graph remat structure) ---------------------

def remat_replay(fn):
    """Stamp ``fn`` as a REMAT REPLAY op: a static-graph recompute
    rewrite re-records a segment's forward ops in the backward region,
    writing the SAME slots the originals produced (the reference
    recompute_optimizer's backward-block replay). The graph verifier
    accepts such a re-write as rematerialization instead of flagging
    ``duplicate-slot-write`` — see ``analysis.verifier.check_graph``."""
    fn._remat_replay = True
    return fn


def is_remat_replay(fn):
    return bool(getattr(fn, "_remat_replay", False))


# -- the functionalized checkpoint segment ----------------------------------

class _suspend_static_hook:
    """Run capture/replay passes outside static-program recording so
    probe ops don't leak into a Program (only the fused recompute op is
    recorded) — the same discipline as control-flow lowering."""

    def __enter__(self):
        self._saved = dispatch._STATIC_HOOK[0]
        dispatch._STATIC_HOOK[0] = None
        return self

    def __exit__(self, *exc):
        dispatch._STATIC_HOOK[0] = self._saved
        return False


def _is_tensor(x):
    return isinstance(x, Tensor)


def _flatten_call(args, kwargs):
    leaves, treedef = jax.tree_util.tree_flatten(
        (args, kwargs), is_leaf=_is_tensor)
    t_idx = [i for i, l in enumerate(leaves) if _is_tensor(l)]
    return leaves, treedef, t_idx


_seg_counter = [0]


def _segment_call(fn, args, kwargs, policy):
    """Run ``fn(*args, **kwargs)`` as ONE rematerializable tape op."""
    jpolicy, effective = resolve_policy(policy)

    leaves, treedef, t_idx = _flatten_call(args, kwargs)
    arg_ts = [leaves[i] for i in t_idx]

    # the default generator is created lazily on first dropout; force it
    # to exist NOW so its registration doesn't read as "the segment
    # created new framework state"
    core_random._default()

    # ---- capture pass: discover reads, writes, and output structure ----
    items = state_mod.snapshot()
    version0 = state_mod.version()
    pre_vals = [t._value for _, t in items]
    pre_grads = [t._grad for _, t in items]
    scope_counters = [s.i for s in core_random._scoped_stack]

    cap = dispatch.OpCapture()
    cap.mark_created(arg_ts)
    created = {id(t) for t in arg_ts}
    with dispatch.capture_ops(cap), _suspend_static_hook():
        out = fn(*args, **kwargs)
    out_leaves, out_tdef = jax.tree_util.tree_flatten(out, is_leaf=_is_tensor)
    # a segment may return an external tensor directly (no op reads it);
    # it must become an operand or its capture-time value bakes in
    cap.note_inputs([t for t in out_leaves
                     if _is_tensor(t) and id(t) not in created])

    if state_mod.version() != version0:
        raise RuntimeError(
            "the recompute segment registered NEW framework state "
            "(lazily-built parameters/buffers or a fresh generator): the "
            "replay would register tracer-valued duplicates. Run the "
            "segment once outside recompute() to build its state first.")
    mut_idx = [i for i, (_uid, t) in enumerate(items)
               if t._value is not pre_vals[i]]
    for i, (_uid, t) in enumerate(items):
        if t._grad is not pre_grads[i]:
            raise RuntimeError(
                f"recompute segments must be forward-only, but "
                f"{t.name!r} got a gradient inside the segment — move "
                "backward()/opt.step() outside the recompute region.")
    mut_ts = [items[i][1] for i in mut_idx]
    mut_pre = [pre_vals[i] for i in mut_idx]
    mut_ids = {id(t) for t in mut_ts}

    # roll the capture run back: mutated state returns to its pre-segment
    # value and scoped-key counters rewind, so the ONE functional run
    # below advances state exactly as a plain (non-recompute) call would
    # — this is what makes dropout masks match the control bitwise
    for t, v in zip(mut_ts, mut_pre):
        t._value = v
    for s, i0 in zip(core_random._scoped_stack, scope_counters):
        s.i = i0

    # externals the segment reads that are NOT also mutated state (those
    # thread through the mut lane so each value has ONE binding)
    ext = [t for t in cap.external
           if id(t) not in mut_ids and id(t) not in created]

    n_args, n_ext, n_mut = len(arg_ts), len(ext), len(mut_ts)
    out_slots = {}  # filled by the traced run below

    # ---- the pure segment: (arg, ext, mut_in) -> (outs..., mut_out) ----
    def run(*vals):
        a_vals = vals[:n_args]
        e_vals = vals[n_args:n_args + n_ext]
        m_vals = vals[n_args + n_ext:]

        def seg(a_vals, e_vals, m_vals):
            lv = list(leaves)
            for i, v in zip(t_idx, a_vals):
                lv[i] = Tensor(v)
            a2, k2 = jax.tree_util.tree_unflatten(treedef, lv)
            with bind_values(list(ext) + list(mut_ts),
                             list(e_vals) + list(m_vals)), \
                    autograd.no_grad(), _suspend_static_hook():
                for s, i0 in zip(core_random._scoped_stack, scope_counters):
                    s.i = i0  # replay scoped keys from the same origin
                o = fn(*a2, **k2)
                o_leaves, o_tdef = jax.tree_util.tree_flatten(
                    o, is_leaf=_is_tensor)
                o_vals = [l._value if _is_tensor(l) else l for l in o_leaves]
                new_mut = [t._value for t in mut_ts]
            out_slots["treedef"] = o_tdef
            out_slots["n"] = len(o_vals)
            return tuple(o_vals) + tuple(new_mut)

        if jpolicy is None and effective == "none":
            return seg(a_vals, e_vals, m_vals)
        return jax.checkpoint(seg, policy=jpolicy)(a_vals, e_vals, m_vals)

    run.__name__ = "recompute"
    run._remat_policy = effective
    if effective != "none":
        from .jit.to_static import note_structure
        note_structure("recompute_segments")
    _seg_counter[0] += 1
    run._remat_segment = _seg_counter[0]

    out_all = call_op(run, *arg_ts, *ext, *mut_ts, op_name="recompute")
    out_all = out_all if isinstance(out_all, tuple) else (out_all,)

    n_out = out_slots.get("n", len(out_all) - n_mut)
    # write mutated state back: values advance exactly one run's worth;
    # side-state (RNG counters, BN stats) carries no gradient, matching
    # the reference recompute contract
    for t, new in zip(mut_ts, out_all[n_out:]):
        t._value = new._value if _is_tensor(new) else new
    wrapped = list(out_all[:n_out])
    return jax.tree_util.tree_unflatten(out_slots.get("treedef", out_tdef),
                                        wrapped)


def recompute(function, *args, policy="full", **kwargs):
    """Run (or wrap) ``function`` as an activation-recompute segment.

    With call arguments, runs immediately (the
    ``paddle.distributed.fleet.utils.recompute`` call shape)::

        y = recompute(block, x, policy="selective")

    Without them, returns a wrapped callable (decorator shape)::

        block = recompute(block.forward, policy="offload")
        y = block(x)

    ``policy`` is one of :data:`POLICIES` (or a raw
    ``jax.checkpoint_policies`` callable). ``policy="none"`` is the
    pass-through control — same dispatch structure, no remat region.
    Segments must be forward-only (no ``backward()``/optimizer inside)
    and must not build new parameters on first call. See the module
    docstring for the composition rules (bitwise dropout replay,
    to_static/ZeRO/accumulation compatibility).
    """
    if not callable(function):
        raise TypeError(f"recompute expects a callable, got {function!r}")
    if not args and not kwargs:
        @functools.wraps(function)
        def wrapped(*a, **k):
            return _segment_call(function, a, k, policy)
        wrapped._recompute_policy = policy
        return wrapped
    return _segment_call(function, args, kwargs, policy)
