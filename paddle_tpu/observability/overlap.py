"""Collective overlap analysis over post-scheduling compiled HLO.

``hlo_bytes`` answers *how many bytes* the compiled step moves over each
mesh axis; this module answers the latency-hiding question those bytes
raise: *is any of that traffic hidden behind compute?* The compiled
executable's HLO text is scheduled (``is_scheduled=true`` in the module
header): instruction order within each computation IS the execution
order the scheduler chose, and an async collective appears as an
``<op>-start`` / ``<op>-done`` pair with the overlappable compute
scheduled between them. The analyzer

1. pairs every ``-start`` with its ``-done`` (the done's first operand
   names the start op) per computation, and collects the compute
   instructions scheduled between them;
2. prices both sides with a static cost model — collective time from
   the pair's payload bytes (``hlo_bytes`` convention: the full-tensor
   side) times a ring factor over a configurable link bandwidth;
   compute time as ``max(bytes moved / HBM bandwidth, FLOPs / peak)``
   per instruction, recursing into while bodies / fusions / calls with
   the same ``known_trip_count`` multipliers ``hlo_bytes`` uses — and
   scores ``hidden = min(collective_ns, between_compute_ns)`` per pair;
3. aggregates to ``collective_overlap_efficiency`` (hidden/total, per
   program and per op-kind), ``exposed_collective_ns_estimate{op=,axis=}``,
   and the schedule-shape gauges ``collective_async_pairs_total`` vs
   ``collective_sync_total``.

A synchronous collective (no ``-start`` suffix) is fully exposed by
construction. XLA:CPU emits mostly-synchronous schedules, so on the CPU
smoke mesh the honest report is ``async_pairs_total == 0`` with
efficiency 0.0 and ``backend_sync_schedule=True`` — that finding is the
baseline the latency-hiding flag A/B (``jit/xla_flags``) is measured
against on real hardware. The pairing/interleaving math itself is
backend-independent and pinned by seeded async-HLO fixtures in
tests/test_overlap.py.

Because measured efficiency is 0.0 on every sync-schedule backend, the
analyzer also reports a backend-independent **schedulable-overlap
score**: for every collective (sync ops included), walk FORWARD in
emission order to its first real consumer — taint-following through
zero-cost aliases (``get-tuple-element``/``tuple``/``bitcast``/the
``-done`` half) and through cheap data-movement ops
(slice/pad/concatenate/reshape/convert...), which forward the taint
without crediting compute — and sum the independent compute emitted in
between. ``schedulable_hidden = min(collective_ns, available)`` prices
how much of the collective a latency-hiding scheduler COULD bury given
this emission order, which is what the ZeRO-3 double-buffered prefetch
restructure changes: the serial on-demand step scores 0.0 (every
collective is consumer-adjacent), the pipelined step scores > 0 even
where XLA:CPU executes synchronously.

The authoritative source for that emission order is the TRACED JAXPR
(:func:`schedulable_stats`), not the compiled text: XLA's
StableHLO→HLO conversion re-sorts instructions into dependency
postorder and the CPU scheduler re-serializes them consumer-adjacent,
so the compiled dump destroys exactly the evidence the score measures.
The jaxpr is the program the framework wrote — the same structural
source the jaxpr-liveness memory meter trusts — and
``StaticFunction.overlap_stats()`` splices the jaxpr-derived score
into its report when the traced program is available. The text-order
walk remains as the fallback for standalone HLO dumps (honest there
too: it reports what the final schedule left hideable). That makes the
restructure checkable on the CPU mesh (``tests/test_zero_prefetch.py``).

Cost-model assumptions (all overridable per call, recorded in the
result's ``assumptions``): the schedule is the only evidence — no
measured wall-times (pass a profiler trace to ``tools/overlap_view.py``
to correlate); compute between two collectives hides traffic perfectly
(no contention model); collectives never hide each other (a second
collective between a pair contributes zero hiding); unknown trip counts
fall back to 1, like ``hlo_bytes``.
"""
import math
import re

from .hlo_bytes import (COLLECTIVE_HLO_OPS, _axis_name, _comp_multipliers,
                        _group_size, _shape_bytes)
from .jaxpr_walk import sub_jaxprs as _sub_jaxprs
from .step import PEAK_BF16_FLOPS

__all__ = ["overlap_stats", "schedulable_stats", "export_overlap_stats",
           "attribute_program",
           "DEFAULT_LINK_GBPS", "DEFAULT_HBM_GBPS", "DEFAULT_PEAK_FLOPS",
           "RING_FACTORS"]

# Defaults are v5e-shaped provenance: step.py's published bf16 peak,
# ~819 GB/s HBM, ~100 GB/s usable per-direction ICI. Absolute
# nanoseconds are only as good as these rates; the efficiency RATIO is
# what the gauges report, and it is much less sensitive to them.
DEFAULT_LINK_GBPS = 100.0
DEFAULT_HBM_GBPS = 819.0
DEFAULT_PEAK_FLOPS = PEAK_BF16_FLOPS["TPU v5 lite"]

# wire-bytes factor per payload byte for a ring implementation on a
# group of n: all-reduce moves ~2(n-1)/n, gather/scatter ~(n-1)/n,
# a permute moves the payload once
RING_FACTORS = {
    "all-reduce": lambda n: 2.0 * (n - 1) / n,
    "reduce-scatter": lambda n: (n - 1) / n,
    "all-gather": lambda n: (n - 1) / n,
    "all-to-all": lambda n: (n - 1) / n,
    "collective-permute": lambda n: 1.0,
}

# `%name = <result shapes> opcode(rest-of-line`; the lazy result group
# plus the `opcode(`-adjacency anchor tolerates tuple result types
# (no bare `word(` occurs inside `(f32[1]{0}, f32[8]{0})`)
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*?)\s*([\w\-]+)\((.*)$")
_COMP_HEADER_RE = re.compile(r"^(ENTRY\s+)?%([\w.\-]+)\s*\(.*\{\s*$")
_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")
_TRIP_RE = re.compile(r'"known_trip_count"\s*:\s*\{\s*"n"\s*:\s*"(\d+)"')
_BODY_RE = re.compile(r"\bbody=%([\w.\-]+)")
_COND_RE = re.compile(r"\bcondition=%([\w.\-]+)")
_CALLS_RE = re.compile(r"\b(?:calls|to_apply)=%([\w.\-]+)")
_BRANCHES_RE = re.compile(r"\bbranch_computations=\{([^}]*)\}")

_COLLECTIVE_SET = set(COLLECTIVE_HLO_OPS)

# metadata-only / aliasing ops: no bytes move, no flops
_ZERO_COST_OPS = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "partition-id", "replica-id", "domain", "opt-barrier",
    "optimization-barrier",
}

# cheap data-movement/layout ops (HLO spelling): in the schedulable
# walk these neither end a hiding window (a tainted slice just unpacks
# the collective's result — it forwards the taint) nor count as hiding
# material when independent (crediting a pad/concatenate as "compute"
# would let the serial step's grad-flattening prep masquerade as
# overlap headroom)
_MOVEMENT_OPS = {
    "slice", "dynamic-slice", "dynamic-update-slice", "pad",
    "concatenate", "reshape", "broadcast", "convert", "transpose",
    "copy", "reverse", "reduce-precision",
}

# the same class in jaxpr-primitive spelling, for schedulable_stats
_MOVEMENT_PRIMS = {
    "slice", "dynamic_slice", "dynamic_update_slice", "pad",
    "concatenate", "reshape", "broadcast_in_dim", "squeeze",
    "expand_dims", "convert_element_type", "transpose", "copy", "rev",
    "bitcast_convert_type", "split", "device_put", "sharding_constraint",
    "stop_gradient", "reduce_precision",
}

# jaxpr collective primitive -> the HLO op name the cost model prices
_COLLECTIVE_PRIMS = {
    "all_gather": "all-gather",
    "psum_scatter": "reduce-scatter",
    "reduce_scatter": "reduce-scatter",
    "psum": "all-reduce",
    "all_to_all": "all-to-all",
    "ppermute": "collective-permute",
}


def _parse_computations(hlo_text):
    """``(comps, entry)``: computation name -> scheduled instruction
    list (dicts with name/opcode/result_text/rest/line), plus the ENTRY
    computation's name. Instruction order is schedule order when the
    module prints ``is_scheduled=true``."""
    comps = {}
    entry = None
    current = None
    for line in hlo_text.splitlines():
        h = _COMP_HEADER_RE.match(line)
        if h is not None:
            current = []
            comps[h.group(2)] = current
            if h.group(1):
                entry = h.group(2)
            continue
        if current is None:
            continue
        m = _INSTR_RE.match(line)
        if m is None:
            continue
        current.append({"name": m.group(1), "result_text": m.group(2),
                        "opcode": m.group(3), "rest": m.group(4),
                        "line": line})
    return comps, entry


def _collective_kind(opcode):
    """``(base_op, phase)`` for collective opcodes — phase is "start",
    "done", or "sync" — else ``(None, None)``."""
    for suffix, phase in (("-start", "start"), ("-done", "done"),
                          ("", "sync")):
        if opcode.endswith(suffix):
            base = opcode[:len(opcode) - len(suffix)] if suffix else opcode
            if base in _COLLECTIVE_SET:
                return base, phase
    return None, None


def _elements(shape_text):
    """Element count of the largest array shape in `shape_text`."""
    best = 0
    for dims in re.findall(r"\[([0-9,]*)\]", shape_text):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        best = max(best, n)
    return best


def _instr_flops(instr):
    """Static FLOP estimate for one instruction. Post-optimization HLO
    hides contraction dims inside fusion bodies and dot configs; rather
    than re-deriving each dnums, ``dot``/``convolution`` use the
    geometric-mean heuristic ``2*sqrt(|A|*|B|*|OUT|)`` (exact for square
    matmul, within the right order of magnitude for the shapes that
    matter), and everything else is one FLOP per output element."""
    if instr["opcode"] in ("dot", "convolution"):
        operands = [_elements(s) for s in
                    re.findall(r"\b(?:[a-z]+[0-9]+|pred)\[[0-9,]*\]",
                               instr["rest"])]
        a = operands[0] if operands else 1
        b = operands[1] if len(operands) > 1 else a
        out = _elements(instr["result_text"]) or 1
        return 2.0 * math.sqrt(max(a, 1) * max(b, 1) * max(out, 1))
    return float(_elements(instr["result_text"]))


class _CostModel:
    """Memoized static ns-cost of instructions and whole computations."""

    def __init__(self, comps, link_gbps, hbm_gbps, peak_flops):
        self.comps = comps
        self.link_gbps = float(link_gbps)
        self.hbm_gbps = float(hbm_gbps)
        self.peak_flops = float(peak_flops)
        self._comp_cost = {}

    def collective_ns(self, op, nbytes, group_size):
        n = group_size if group_size and group_size > 1 else 2
        factor = RING_FACTORS.get(op, lambda _: 1.0)(n)
        # GB/s == bytes/ns, so wire bytes / link_gbps is already ns
        return nbytes * factor / self.link_gbps

    def compute_ns(self, instr):
        """Roofline-ish cost of one COMPUTE instruction (collective ops
        score 0 here — they are traffic, not hiding material)."""
        opcode = instr["opcode"]
        if opcode in _ZERO_COST_OPS:
            return 0.0
        base, _phase = _collective_kind(opcode)
        if base is not None:
            return 0.0
        if opcode == "while":
            body = _BODY_RE.search(instr["rest"])
            cond = _COND_RE.search(instr["rest"])
            trip = _TRIP_RE.search(instr["line"])
            n = int(trip.group(1)) if trip else 1
            inner = sum(self.comp_ns(m.group(1))
                        for m in (body, cond) if m is not None)
            return n * inner
        branches = _BRANCHES_RE.search(instr["rest"])
        if branches is not None:
            names = [x.strip().lstrip("%")
                     for x in branches.group(1).split(",")]
            return max((self.comp_ns(n) for n in names if n), default=0.0)
        callee = _CALLS_RE.search(instr["rest"])
        if callee is not None and callee.group(1) in self.comps:
            return self.comp_ns(callee.group(1))
        nbytes = (_shape_bytes(instr["result_text"])
                  + _shape_bytes(instr["rest"]))
        flops = _instr_flops(instr)
        return max(nbytes / self.hbm_gbps,
                   flops / (self.peak_flops / 1e9))

    def comp_ns(self, name):
        """Total compute ns of one execution of computation `name`."""
        if name in self._comp_cost:
            return self._comp_cost[name]
        self._comp_cost[name] = 0.0  # cycle guard (degenerate HLO)
        total = sum(self.compute_ns(i) for i in self.comps.get(name, ()))
        self._comp_cost[name] = total
        return total


def _schedulable_available(model, instrs, operand_sets, idx, done_idx=None):
    """Between-compute AVAILABLE to hide the collective at ``idx``:
    walk forward in schedule order until its first real consumer,
    summing ``compute_ns`` of independent instructions. The collective's
    result names are a taint set; zero-cost and data-movement ops (and
    the async ``-done`` half) consuming a tainted name forward the
    taint instead of ending the window; independent movement ops earn
    no credit; other collectives contribute zero hiding (the cost
    model's standing assumption). No consumer in this computation (the
    result leaves via the root) extends the window to the end."""
    taint = {instrs[idx]["name"]}
    if done_idx is not None:
        taint.add(instrs[done_idx]["name"])
    avail = 0.0
    for j in range(idx + 1, len(instrs)):
        ins = instrs[j]
        base, phase = _collective_kind(ins["opcode"])
        movement = (ins["opcode"] in _ZERO_COST_OPS
                    or ins["opcode"] in _MOVEMENT_OPS
                    or (base is not None and phase == "done"))
        if operand_sets[j] & taint:
            if not movement:
                break  # first real consumer: the hiding window ends
            taint.add(ins["name"])
            continue
        if base is not None or movement:
            continue  # no hiding credit from collectives or data moves
        avail += model.compute_ns(ins)
    return avail


def _pair_bytes(start, done):
    """Payload bytes of an async pair, billed once: the largest single
    shape on either line (the -start result tuple repeats the operand
    buffer — hlo_bytes' `largest` convention)."""
    candidates = [start["result_text"], start["rest"]]
    if done is not None:
        candidates += [done["result_text"], done["rest"]]
    return max(_shape_bytes(t, largest=True) for t in candidates)


def overlap_stats(hlo_text, mesh=None, link_gbps=DEFAULT_LINK_GBPS,
                  hbm_gbps=DEFAULT_HBM_GBPS,
                  peak_flops=DEFAULT_PEAK_FLOPS, per_execution=True):
    """Analyze a compiled module's schedule into hidden/exposed
    collective time. Returns::

        {"collective_overlap_efficiency": hidden/total (0.0 when no
                                          collective time),
         "exposed_collective_frac": exposed/total (1.0 when sync-only),
         "hidden_ns": ..., "exposed_ns": ..., "collective_ns": ...,
         "schedulable_overlap": schedulable_hidden/total — the
                                backend-independent score: how much
                                collective time the EMISSION ORDER
                                leaves hideable (0.0 for the serial
                                consumer-adjacent ZeRO step, > 0 for
                                the prefetch-pipelined one, even on a
                                sync-schedule backend),
         "schedulable_ns": trip-weighted schedulable hidden time,
         "async_pairs_total": N, "sync_total": M,
         "backend_sync_schedule": True when collectives exist but the
                                  scheduler emitted zero async pairs
                                  (the XLA:CPU finding),
         "per_op": {op: {"hidden_ns", "exposed_ns", "collective_ns",
                         "efficiency", "schedulable_ns",
                         "schedulable"}},
         "pairs": [per-collective records: op/axis/phase/name/
                   computation/count/collective_ns/overlap_ns/
                   hidden_ns/exposed_ns/schedulable_available_ns/
                   schedulable_hidden_ns],
         "assumptions": {...}}

    ``per_execution=True`` (the default — exposure is a per-step cost)
    weights every collective and its hiding compute by its enclosing
    computation's ``known_trip_count`` multiplier, so a k-step scan's
    in-body collectives bill k times."""
    comps, _entry = _parse_computations(hlo_text)
    mults = _comp_multipliers(hlo_text) if per_execution else {}
    model = _CostModel(comps, link_gbps, hbm_gbps, peak_flops)

    pairs = []
    for comp_name, instrs in comps.items():
        weight = mults.get(comp_name, 1) if per_execution else 1
        if weight == 0:
            continue
        done_by_start = {}
        for idx, instr in enumerate(instrs):
            base, phase = _collective_kind(instr["opcode"])
            if base is None or phase != "done":
                continue
            m = _OPERAND_NAME_RE.search(instr["rest"])
            if m is not None:
                done_by_start.setdefault(m.group(1), idx)
        operand_sets = [frozenset(_OPERAND_NAME_RE.findall(i["rest"]))
                        for i in instrs]
        for idx, instr in enumerate(instrs):
            base, phase = _collective_kind(instr["opcode"])
            if base is None or phase == "done":
                continue
            group = _group_size(instr["line"])
            axis = _axis_name(group, mesh)
            rec = {"op": base, "axis": axis, "name": instr["name"],
                   "computation": comp_name, "count": weight,
                   "index": idx}
            done_idx = None
            if phase == "start" and instr["name"] in done_by_start:
                done_idx = done_by_start[instr["name"]]
                done = instrs[done_idx]
                nbytes = _pair_bytes(instr, done)
                coll_ns = model.collective_ns(base, nbytes, group)
                between = sum(model.compute_ns(instrs[j])
                              for j in range(idx + 1, done_idx))
                hidden = min(coll_ns, between)
                rec.update(phase="async", bytes=nbytes,
                           collective_ns=coll_ns, overlap_ns=between,
                           hidden_ns=hidden,
                           exposed_ns=coll_ns - hidden)
            else:
                # sync — or a -start whose -done the parser cannot
                # find, which blocks like a sync op
                nbytes = _pair_bytes(instr, None)
                coll_ns = model.collective_ns(base, nbytes, group)
                rec.update(phase="sync", bytes=nbytes,
                           collective_ns=coll_ns, overlap_ns=0.0,
                           hidden_ns=0.0, exposed_ns=coll_ns)
            avail = _schedulable_available(model, instrs, operand_sets,
                                           idx, done_idx)
            rec["schedulable_available_ns"] = avail
            rec["schedulable_hidden_ns"] = min(rec["collective_ns"],
                                               avail)
            pairs.append(rec)

    hidden = sum(p["hidden_ns"] * p["count"] for p in pairs)
    exposed = sum(p["exposed_ns"] * p["count"] for p in pairs)
    total = hidden + exposed
    schedulable = sum(p["schedulable_hidden_ns"] * p["count"]
                      for p in pairs)
    n_async = sum(p["count"] for p in pairs if p["phase"] == "async")
    n_sync = sum(p["count"] for p in pairs if p["phase"] == "sync")
    per_op = {}
    for p in pairs:
        slot = per_op.setdefault(p["op"], {"hidden_ns": 0.0,
                                           "exposed_ns": 0.0,
                                           "collective_ns": 0.0,
                                           "schedulable_ns": 0.0})
        slot["hidden_ns"] += p["hidden_ns"] * p["count"]
        slot["exposed_ns"] += p["exposed_ns"] * p["count"]
        slot["collective_ns"] += p["collective_ns"] * p["count"]
        slot["schedulable_ns"] += p["schedulable_hidden_ns"] * p["count"]
    for slot in per_op.values():
        slot["efficiency"] = (slot["hidden_ns"] / slot["collective_ns"]
                              if slot["collective_ns"] else 0.0)
        slot["schedulable"] = (slot["schedulable_ns"]
                               / slot["collective_ns"]
                               if slot["collective_ns"] else 0.0)
    return {
        "collective_overlap_efficiency": hidden / total if total else 0.0,
        "exposed_collective_frac": exposed / total if total else 1.0,
        "hidden_ns": hidden,
        "exposed_ns": exposed,
        "collective_ns": total,
        "schedulable_overlap": schedulable / total if total else 0.0,
        "schedulable_ns": schedulable,
        "async_pairs_total": n_async,
        "sync_total": n_sync,
        "backend_sync_schedule": bool(pairs) and n_async == 0,
        "per_op": per_op,
        "pairs": sorted(pairs, key=lambda p: -p["collective_ns"]),
        "assumptions": {"link_gbps": link_gbps, "hbm_gbps": hbm_gbps,
                        "peak_flops": peak_flops,
                        "per_execution": per_execution,
                        "cost_model": "static schedule estimate; no "
                                      "measured wall-times; collectives "
                                      "do not hide each other"},
    }


def _aval_bytes(v):
    """Array bytes of one jaxpr atom's aval (0 for abstract tokens)."""
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * dtype.itemsize


def _eqn_compute_ns(eqn, hbm_gbps, peak_flops):
    """Roofline-ish cost of one jaxpr equation, mirroring the HLO cost
    model: dot/conv by the geometric-mean FLOP heuristic, everything
    else one FLOP per output element; collectives and data-movement ops
    score 0; call-like equations recurse."""
    from jax.extend.core import Var
    prim = eqn.primitive.name
    if prim in _COLLECTIVE_PRIMS or prim in _MOVEMENT_PRIMS:
        return 0.0
    subs = list(_sub_jaxprs(eqn))
    if subs:
        return sum(_eqn_compute_ns(e, hbm_gbps, peak_flops)
                   for s in subs for e in s.eqns)
    out_bytes = sum(_aval_bytes(v) for v in eqn.outvars)
    out_elems = sum(
        int(math.prod(getattr(v.aval, "shape", ()) or (1,)))
        for v in eqn.outvars if hasattr(v, "aval"))
    if prim in ("dot_general", "conv_general_dilated"):
        a = math.prod(eqn.invars[0].aval.shape or (1,)) \
            if eqn.invars else 1
        b = math.prod(eqn.invars[1].aval.shape or (1,)) \
            if len(eqn.invars) > 1 else a
        flops = 2.0 * math.sqrt(max(a, 1) * max(b, 1)
                                * max(out_elems, 1))
    else:
        flops = float(out_elems)
    nbytes = out_bytes + sum(
        _aval_bytes(v) for v in eqn.invars
        if isinstance(v, Var))
    return max(nbytes / hbm_gbps, flops / (peak_flops / 1e9))


def _prim_group_size(eqn, mesh):
    """Participant count of one collective equation from its axis-name
    params and the mesh shape (falls back to 2, like the text model)."""
    names = eqn.params.get("axis_name", eqn.params.get("axes", ()))
    if not isinstance(names, (tuple, list)):
        names = (names,)
    size = 1
    shape = dict(getattr(mesh, "shape", {}) or {}) if mesh is not None \
        else {}
    for n in names:
        size *= int(shape.get(n, 0)) or 0
    return size if size > 1 else 2


def schedulable_stats(fun, example_args, mesh=None,
                      link_gbps=DEFAULT_LINK_GBPS,
                      hbm_gbps=DEFAULT_HBM_GBPS,
                      peak_flops=DEFAULT_PEAK_FLOPS):
    """Backend-independent schedulable-overlap score of a traceable
    step function, measured on its JAXPR — the emission order the
    framework wrote, before XLA's StableHLO→HLO conversion re-sorts
    instructions into dependency postorder and the backend scheduler
    re-serializes them (both of which erase exactly the structure this
    score measures; see the module docstring).

    Per collective equation: walk forward in emission order to its
    first real consumer — data-movement ops forward the taint with no
    compute credit, other collectives contribute nothing — and sum the
    independent compute in between. ``hidden = min(collective_ns,
    available)``; scan-body equations weigh by the scan length. Returns
    ``{"schedulable_overlap", "schedulable_ns", "collective_ns",
    "pairs": [...], "per_op": {...}, "source": "traced-jaxpr",
    "assumptions": {...}}``.

    ``fun`` may be a plain callable, a ``jax.jit`` wrapper, or the
    ``xla_flags.FlaggedJit`` wrapper ``to_static`` builds (unwrapped
    via its ``_fun``); ``example_args`` are the abstract or concrete
    arguments of one call."""
    import jax
    from jax.extend.core import Var
    inner = getattr(fun, "_fun", fun)
    jaxpr = jax.make_jaxpr(inner)(*example_args)

    pairs = []

    def walk(jx, weight):
        eqns = jx.eqns
        for idx, eqn in enumerate(eqns):
            prim = eqn.primitive.name
            w = weight * (int(eqn.params.get("length", 1))
                          if prim == "scan" else 1)
            for sub in _sub_jaxprs(eqn):
                walk(sub, w)
            base = _COLLECTIVE_PRIMS.get(prim)
            if base is None:
                continue
            nbytes = max(
                [_aval_bytes(v) for v in list(eqn.outvars) + [
                    i for i in eqn.invars if isinstance(i, Var)
                ]] or [0])
            group = _prim_group_size(eqn, mesh)
            factor = RING_FACTORS.get(base, lambda _: 1.0)(group)
            coll_ns = nbytes * factor / link_gbps
            taint = {v for v in eqn.outvars
                     if isinstance(v, Var)}
            avail = 0.0
            for j in range(idx + 1, len(eqns)):
                nxt = eqns[j]
                p2 = nxt.primitive.name
                tainted = any(iv in taint for iv in nxt.invars
                              if isinstance(iv, Var))
                if p2 in _MOVEMENT_PRIMS:
                    if tainted:
                        taint.update(v for v in nxt.outvars
                                     if isinstance(v, Var))
                    continue
                if tainted:
                    break  # first real consumer ends the window
                if p2 in _COLLECTIVE_PRIMS:
                    continue  # collectives do not hide each other
                avail += _eqn_compute_ns(nxt, hbm_gbps, peak_flops)
            axis_names = eqn.params.get("axis_name",
                                        eqn.params.get("axes", ()))
            if not isinstance(axis_names, (tuple, list)):
                axis_names = (axis_names,)
            pairs.append({
                "op": base,
                "axis": ",".join(str(a) for a in axis_names) or None,
                "bytes": nbytes, "count": weight,
                "collective_ns": coll_ns,
                "available_ns": avail,
                "hidden_ns": min(coll_ns, avail),
            })

    walk(jaxpr.jaxpr, 1)
    total = sum(p["collective_ns"] * p["count"] for p in pairs)
    hidden = sum(p["hidden_ns"] * p["count"] for p in pairs)
    per_op = {}
    for p in pairs:
        slot = per_op.setdefault(p["op"], {"collective_ns": 0.0,
                                           "schedulable_ns": 0.0})
        slot["collective_ns"] += p["collective_ns"] * p["count"]
        slot["schedulable_ns"] += p["hidden_ns"] * p["count"]
    for slot in per_op.values():
        slot["schedulable"] = (slot["schedulable_ns"]
                               / slot["collective_ns"]
                               if slot["collective_ns"] else 0.0)
    return {
        "schedulable_overlap": hidden / total if total else 0.0,
        "schedulable_ns": hidden,
        "collective_ns": total,
        "pairs": sorted(pairs, key=lambda p: -p["collective_ns"]),
        "per_op": per_op,
        "source": "traced-jaxpr",
        "assumptions": {"link_gbps": link_gbps, "hbm_gbps": hbm_gbps,
                        "peak_flops": peak_flops,
                        "cost_model": "static jaxpr emission-order "
                                      "estimate; data-movement ops "
                                      "forward taint with no credit; "
                                      "collectives do not hide each "
                                      "other"},
    }


def export_overlap_stats(stats, program=None):
    """Publish one program's :func:`overlap_stats` onto the gauge board
    (``collective_overlap_efficiency`` per program and per op-kind,
    ``exposed_collective_ns_estimate{op=,axis=}``, and the
    ``collective_async_pairs_total`` / ``collective_sync_total``
    schedule-shape gauges) and mirror the aggregate into the active
    run-log as one ``collective_overlap`` event. Gauges are last-value:
    export once per compiled program."""
    from . import runlog
    from .export import format_labels, set_gauge
    prog_labels = (format_labels("collective_overlap_efficiency",
                                 program=program) if program else "")
    set_gauge("collective_overlap_efficiency" + prog_labels,
              stats["collective_overlap_efficiency"])
    set_gauge("collective_schedulable_overlap" + prog_labels,
              stats["schedulable_overlap"])
    set_gauge("collective_async_pairs_total" + prog_labels,
              stats["async_pairs_total"])
    set_gauge("collective_sync_total" + prog_labels,
              stats["sync_total"])
    for op, slot in stats["per_op"].items():
        labels = dict(op=op)
        if program:
            labels["program"] = program
        set_gauge("collective_overlap_efficiency"
                  + format_labels("collective_overlap_efficiency",
                                  **labels),
                  slot["efficiency"])
    exposed = {}
    for p in stats["pairs"]:
        key = (p["op"], p["axis"])
        exposed[key] = exposed.get(key, 0.0) \
            + p["exposed_ns"] * p["count"]
    for (op, axis), ns in exposed.items():
        labels = dict(op=op, axis=axis)
        if program:
            labels["program"] = program
        set_gauge("exposed_collective_ns_estimate"
                  + format_labels("exposed_collective_ns_estimate",
                                  **labels),
                  ns)
    if runlog.active() is not None:
        runlog.event(
            "collective_overlap", program=program,
            efficiency=stats["collective_overlap_efficiency"],
            schedulable=stats["schedulable_overlap"],
            exposed_frac=stats["exposed_collective_frac"],
            hidden_ns=stats["hidden_ns"], exposed_ns=stats["exposed_ns"],
            async_pairs=stats["async_pairs_total"],
            sync=stats["sync_total"],
            backend_sync_schedule=stats["backend_sync_schedule"])
    return stats


def attribute_program(prog, targets, mesh=None, **cost_kwargs):
    """Overlap attribution of a recorded ``static.Program`` twin:
    AOT-compile the program's pure function on abstract feeds (the
    ``observability.memory`` attribution path) and run
    :func:`overlap_stats` over the executable's scheduled HLO. Raises
    ``MemoryAttributionError`` when the twin fails to compile — ladder
    verification surfaces that as an error finding, the same contract
    as memory attribution."""
    from .memory import compile_program_twin
    compiled = compile_program_twin(prog, targets)
    return overlap_stats(compiled.as_text(), mesh=mesh, **cost_kwargs)
