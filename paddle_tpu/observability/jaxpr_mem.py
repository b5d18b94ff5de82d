"""Jaxpr-level liveness meter: backend-independent activation accounting.

PR 10's per-program attribution reads the compiled executable's XLA
``memory_analysis()`` — the right meter on TPU, where the compiler
honors ``optimization_barrier`` and rematerialization survives into
buffer assignment. The CPU backend, however, STRIPS optimization
barriers and lets CSE/scheduling undo rematerialization entirely (the
compiled CPU program of a remat'd and a plain step are byte-identical),
so XLA byte accounting on the smoke host cannot show what activation
recompute saves.

This module meters the STRUCTURE instead: a sequential liveness walk
over the traced (pre-XLA) jaxpr of the step program. Every value born
at an equation stays live until its last consumer; the high-water mark
of live bytes is the peak a scheduler that honors program order (the
TPU compile pipeline) has to provision. Rematerialization is visible
here by construction — a remat segment's internal activations die at
the segment boundary and the backward's ``remat2`` equation recomputes
them inside its own (recursively metered) working set, so the
forward→backward residual edges shrink exactly as the policy promises.

Deterministic (pure structure, no wall clock, no backend), so the
traced peak reads the same on every run and every backend, the same way
the PR-10 byte counts do. The XLA ``memory_analysis`` numbers ride along
as metadata, and the TPU re-pin (ROADMAP) re-captures the executable
view where it is meaningful.
"""
import numpy as np

from .jaxpr_walk import jaxpr_vars as _vars
from .jaxpr_walk import last_use_map as _last_use_map
from .jaxpr_walk import sub_jaxprs as _sub_jaxprs

__all__ = ["aval_bytes", "jaxpr_peak_bytes", "jaxpr_peak_stats",
           "traced_peak_stats"]


def aval_bytes(aval):
    """Bytes of one abstract value (0 for non-array avals: tokens,
    opaque effects)."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        try:
            n *= int(d)
        except TypeError:
            return 0  # polymorphic dim: not meterable
    try:
        return n * np.dtype(dtype).itemsize
    except TypeError:
        return 0  # extended dtypes (PRNG keys): key_data views meter them


def _size(var):
    return aval_bytes(var.aval)


def jaxpr_peak_bytes(jaxpr, alias_io=False):
    """Sequential-liveness high-water bytes of one jaxpr: inputs are
    resident throughout their live range, each equation adds its outputs
    plus its internal (recursive) working set, and a value frees after
    its last consumer. Program order is the jaxpr's — the order the
    trace executed and the order a barrier-honoring scheduler keeps.

    ``alias_io=True`` models input→output buffer donation: a jaxpr
    output born at an equation where a same-shaped, same-dtyped input
    has already had its last use is written into that input's buffer
    (XLA's ``donate_argnums`` aliasing at the jit boundary, and the
    in-place carry of a compiled while loop). Without it a donated
    carry — every ZeRO flat store threaded through the scan — is
    double-counted at the boundary equation (the dying input and the
    output physically share one buffer). Off by default so handmade
    jaxprs meter under the plain convention; the program knows whether
    it donates (``StaticFunction`` passes its own donation flag), and
    the model propagates into scan/while bodies where carry aliasing
    is unconditional in XLA."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)  # accept ClosedJaxpr

    last_use = _last_use_map(jaxpr)  # outputs live to the end

    inputs = _vars(list(jaxpr.invars) + list(jaxpr.constvars))

    # Buffer handoff for donation: pair each produced boundary output
    # (in birth order) with a same-shape/dtype input whose last use
    # precedes its birth; the donor then frees just BEFORE the birth
    # equation (its buffer becomes the output's), never double-counted.
    handoff = {}  # birth eqn index -> [donor vars released there]
    handed_off = set()
    if alias_io:
        input_ids = {id(v) for v in inputs}
        birth = {}
        for i, eqn in enumerate(jaxpr.eqns):
            for v in _vars(eqn.outvars):
                birth.setdefault(id(v), i)
        pool = {}
        for v in inputs:
            aval = v.aval
            key = (getattr(aval, "shape", None), str(getattr(aval, "dtype", "")))
            pool.setdefault(key, []).append(v)
        for v in _vars(jaxpr.outvars):
            if id(v) in input_ids or id(v) not in birth:
                continue  # pass-through outputs already share the buffer
            b = birth[id(v)]
            aval = v.aval
            key = (getattr(aval, "shape", None), str(getattr(aval, "dtype", "")))
            donor = next((d for d in pool.get(key, [])
                          if last_use.get(d, -1) <= b), None)
            if donor is not None:
                pool[key].remove(donor)  # one donor funds one output
                handoff.setdefault(b, []).append(donor)
                handed_off.add(id(donor))  # released via handoff, not the walk

    live = sum(_size(v) for v in inputs)
    peak = live

    for i, eqn in enumerate(jaxpr.eqns):
        for donor in handoff.get(i, ()):
            live -= _size(donor)
        inner = 0
        for sub in _sub_jaxprs(eqn):
            # the sub-jaxpr's boundary values ARE the equation's operands
            # — already counted in the outer live set; only the working
            # set it allocates BEYOND its inputs is additional footprint
            sub_j = getattr(sub, "jaxpr", sub)
            base = sum(_size(v) for v in _vars(list(sub_j.invars)
                                               + list(sub_j.constvars)))
            inner = max(inner, max(0, jaxpr_peak_bytes(sub_j, alias_io=alias_io)
                                   - base))
        born = sum(_size(v) for v in _vars(eqn.outvars))
        peak = max(peak, live + born + inner)
        live += born
        for v in _vars(list(eqn.invars) + list(eqn.outvars)):
            if id(v) not in handed_off and last_use.get(v, -1) <= i:
                live -= _size(v)
    return peak


def jaxpr_peak_stats(closed_jaxpr, alias_io=False):
    """``{"peak_bytes", "argument_bytes", "output_bytes", "eqns"}`` for a
    traced program: the liveness high-water plus the boundary sizes that
    contextualize it. ``alias_io`` records whether donation aliasing was
    modeled (see :func:`jaxpr_peak_bytes`)."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    return {
        "peak_bytes": jaxpr_peak_bytes(jaxpr, alias_io=alias_io),
        "argument_bytes": sum(_size(v) for v in jaxpr.invars),
        "output_bytes": sum(_size(v) for v in jaxpr.outvars),
        "eqns": len(jaxpr.eqns),
        "alias_io": bool(alias_io),
    }


def traced_peak_stats(fn, *abstract_args, alias_io=False):
    """Trace ``fn`` on ShapeDtypeStruct twins and meter the jaxpr —
    the entry point ``StaticFunction.traced_memory_stats()`` uses with
    each compiled entry's captured example args. The caller passes
    ``alias_io=True`` when the program donates its state (to_static's
    default), so carried stores meter as the in-place updates XLA
    actually compiles them to."""
    import jax
    closed = jax.make_jaxpr(fn)(*abstract_args)
    return jaxpr_peak_stats(closed, alias_io=alias_io)
