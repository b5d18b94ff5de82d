"""Shardcheck: whole-program sharding & collective-budget analysis.

The ZeRO/prefetch line (optimizer sharding stages 1-3, gradient
accumulation, the double-buffered bucket prefetch) rests on invariants
the repo used to spot-check with hand-written HLO regexes inside
individual tests: optimizer state resident 1/dp, exactly one
all-gather + reduce-scatter pair per bucket per window, no gathered
full parameter outliving its micro step, donated carries billed once.
This module makes those invariants a checked contract — one verifier
every ladder twin and every ``to_static`` step must survive — with
three cooperating passes over the two program views the stack runs:

**Jaxpr sharding propagation** (:func:`analyze_jaxpr`, entry
:func:`check_jaxpr_sharding`). Find the step's ``shard_map`` regions,
seed per-value sharding from their ``in_specs`` pspecs, and propagate
taint through the equation graph (scan/pjit/cond bodies included,
positional carry mapping — the traversal is
``observability.jaxpr_walk``, shared with the liveness memory meter and
the schedulable-overlap scorer). Rules:

- ``replication-blowup`` (WARNING): a region input above
  ``REPLICATION_THRESHOLD_BYTES`` enters replicated (empty pspec) while
  the region also threads values sharded over a checked mesh axis — the
  full-parameter residency regression ZeRO-3 exists to remove.
- ``materialization-window`` (ERROR): more than
  ``MATERIALIZATION_BUDGET`` all-gathered full values escape a region
  boundary (scan carry / step output). A gathered value consumed inside
  its region dies at its last consumer by construction; escaping the
  carry is the one way its live range widens across steps, and the
  ZeRO-3 prefetch slot is the single sanctioned escape — one bucket is
  the budget. Alias-forwarding through data-movement ops (reshape/
  slice/convert...) keeps a repacked gather in its group.

**Donation accounting** (:func:`check_donation_leak`).
``donation-leak``: the step carries state across the jit/scan boundary
but was built with ``donate_state=False``, so every carried store is
double-billed (live input + fresh output) per step — ERROR when
sharded (ZeRO) stores ride that carry, WARNING otherwise.

**Collective budget** (:func:`predict_collective_budget`,
:func:`check_collective_budget`). From the layout alone —
(zero stage, scan steps k, accumulate_steps a, bucket count nb,
prefetch) — predict the per-execution collective multiset on the zero
axis, with ``windows = k // a``:

=========  =====================  ==========================
stage      reduce-scatter         all-gather
=========  =====================  ==========================
1          ``nb * windows``       ``nb * windows``
2          ``nb * k``             ``nb * windows``
3          ``nb * k``             ``nb * k``, minus
                                  ``k - windows`` when the
                                  prefetch slot is on (the
                                  warm bucket-0 slot elides
                                  the re-gather on intra-
                                  window micro steps)
=========  =====================  ==========================

A bucket whose gradients are 16-bit floats is not reduce-scattered: the
ZeRO step exchanges it in that type (one ``all-to-all`` in the
reduce-scatter's place, summed in float32 where it lands), so of the
``nb`` buckets the ``exchanged_buckets`` the build's trace counted
(``jit_zero_exchanged_buckets``, kept in the partition) are budgeted as
``all-to-all`` at the reduce-scatter's cadence and only the rest as
``reduce-scatter``.

Diff it against the trip-weighted compiled multiset from
``StaticFunction.collective_stats(per_execution=True)``
(``observability.hlo_bytes``), emitting ``collective-budget-mismatch``
(ERROR) findings that name the op, axis, and count delta. All-reduce is
deliberately unconstrained: the per-step loss pmean, global-norm
clipping, and loss-scaler found-inf checks all legitimately add
all-reduces that are not part of the ZeRO schedule. The layout is
inferred from the compiled step's state partition
(:func:`infer_zero_layout` reads the ``zero_<slot>_b<bucket>`` store
names and ledger categories ``to_static`` records) or passed explicitly
(``Optimizer.zero_layout()``). The predictor takes a ``mesh_axes``
tuple so a future tp/hybrid axis lands as data, not new code.

**Record-level twins** (:func:`check_program_sharding`,
:func:`program_shard_stats`). Ladder miniatures stamp identity stand-in
collectives (``fn._collective_axis``); the record-level pass budgets
those the same way — an axis whose gradients are reduce-scattered but
whose params are never re-gathered is a ``collective-budget-mismatch``
— and summarizes the stamped multiset for ``lint_program --ladder``'s
``shard=`` column.

Findings route through the shared ``analysis_findings{rule=,severity=}``
counter export and the ``# lint: <rule>`` structured-suppression syntax
like every other checker; ``check_static_function`` runs shardcheck by
default.
"""
import re

from ..observability.jaxpr_mem import aval_bytes
from ..observability.jaxpr_walk import jaxpr_vars, last_use_map, sub_jaxprs
from ..observability.overlap import _MOVEMENT_PRIMS
from .findings import ERROR, WARNING, Finding

__all__ = [
    "REPLICATION_THRESHOLD_BYTES", "MATERIALIZATION_BUDGET",
    "predict_collective_budget", "infer_zero_layout",
    "check_collective_budget", "analyze_jaxpr", "check_jaxpr_sharding",
    "check_donation_leak", "check_sharding", "check_program_sharding",
    "program_shard_stats", "format_shard_stats", "check_zero_residency",
]

# a replicated region input at least this large warns when the region
# also threads sharded values — below it, replication is the cheap and
# correct layout (biases, norm scales, LR/step scalars)
REPLICATION_THRESHOLD_BYTES = 1 << 20

# gathered full values allowed to escape one region boundary: the ZeRO-3
# prefetch slot (one bucket warm across steps) and nothing else
MATERIALIZATION_BUDGET = 1

_ZERO_STORE_RE = re.compile(r"^zero_([A-Za-z0-9]+)_b(\d+)$")

# shard-producing jaxpr primitives: the output is a 1/axis shard
_SHARD_PRODUCING_PRIMS = ("psum_scatter", "reduce_scatter", "all_to_all")

# record-level stamped op name -> collective kind (the ladder twins'
# identity stand-ins; distributed.collective stamps the real lowerings
# the same way)
_RECORD_OPS = {
    "c_allreduce": "all-reduce",
    "c_reducescatter": "reduce-scatter",
    "c_allgather": "all-gather",
    "c_broadcast": "broadcast",
    "c_alltoall": "all-to-all",
}

_OP_ABBREV = {"all-gather": "ag", "reduce-scatter": "rs",
              "all-reduce": "ar", "broadcast": "bc", "all-to-all": "a2a"}


# ---------------------------------------------------------------------------
# collective budget (HLO side)
# ---------------------------------------------------------------------------

def predict_collective_budget(stage, scan_steps=1, accumulate_steps=None,
                              n_buckets=1, prefetch=False, axis="dp",
                              mesh_axes=("dp",), exchanged_buckets=0):
    """The per-execution collective multiset a ZeRO layout budgets:
    ``{(op, axis): count}`` for the gather/scatter schedule (all-reduce
    is unconstrained — see the module docstring's table and the
    intra-window elision the prefetch slot buys under stage 3 with
    accumulation). ``mesh_axes`` names the axes the checker constrains;
    an ``axis`` outside it returns an empty budget (a tp axis becomes
    checkable by widening the tuple, not by new code).
    ``exchanged_buckets`` of the ``n_buckets`` move their 16-bit
    gradients by ``all-to-all`` where the others reduce-scatter; the
    ``all-to-all`` key appears only when there are any."""
    if axis not in tuple(mesh_axes or ()):
        return {}
    stage = int(stage)
    if stage <= 0:
        return {}
    k = max(1, int(scan_steps or 1))
    a = max(1, int(accumulate_steps or 1))
    windows = max(1, k // a)
    nb = max(1, int(n_buckets or 1))
    if stage == 1:
        rs = ag = nb * windows
    elif stage == 2:
        # grads reduce-scatter into the sharded accumulator every micro
        # step; refreshed params re-gather once per update window
        rs = nb * k
        ag = nb * windows
    else:
        rs = nb * k
        ag = nb * k - ((k - windows) if prefetch else 0)
    budget = {("all-gather", axis): ag, ("reduce-scatter", axis): rs}
    nx = min(max(0, int(exchanged_buckets or 0)), nb)
    if nx:
        per_bucket = rs // nb
        budget[("reduce-scatter", axis)] = per_bucket * (nb - nx)
        budget[("all-to-all", axis)] = per_bucket * nx
    return budget


def infer_zero_layout(sfn):
    """Recover the ZeRO layout of a compiled step from its state
    partition — the ``zero_<slot>_b<bucket>`` store names and ledger
    categories ``to_static`` records in ``_last_partition["state_meta"]``
    — or ``None`` when no sharded store rides the carry. Stage is read
    from the threaded store classes (``zero_param`` ⇒ 3, a donated
    ``gacc`` accumulator ⇒ 2, else 1; a non-accumulating stage-2 step
    skips its gacc store and infers as stage 1, whose budget is
    identical). Prefer ``Optimizer.zero_layout()`` when the optimizer is
    at hand — this inference exists so the checker needs only the
    ``StaticFunction``."""
    part = getattr(sfn, "_last_partition", None)
    if not isinstance(part, dict):
        return None
    meta = part.get("state_meta") or {}
    donated = set(part.get("donated", ()))
    slots, buckets = set(), set()
    prefetch = False
    for uid, m in meta.items():
        if uid not in donated:
            continue  # only state this build actually threads
        name = str((m or {}).get("name") or "")
        cat = (m or {}).get("category")
        mt = _ZERO_STORE_RE.match(name)
        if mt:
            slots.add(mt.group(1))
            buckets.add(int(mt.group(2)))
        elif cat == "zero_prefetch" or name == "zero3_prefetch_slot":
            prefetch = True
    if not buckets:
        return None
    if "param" in slots:
        stage = 3
    elif "gacc" in slots:
        stage = 2
    else:
        stage = 1
    return {
        "stage": stage,
        "axis": part.get("dp_axis") or "dp",
        "n_buckets": max(buckets) + 1,
        "prefetch": prefetch,
        "scan_steps": part.get("scan_steps") or 1,
        "accumulate_steps": part.get("accumulate_steps") or 1,
        "exchanged_buckets": part.get("zero_exchanged_buckets") or 0,
        "source": "partition",
    }


def check_collective_budget(sfn, layout=None, mesh_axes=None):
    """Diff the compiled step's trip-weighted collective multiset
    (``collective_stats(per_execution=True)``) against the layout's
    predicted budget; every count delta on a checked axis is one
    ``collective-budget-mismatch`` ERROR naming op/axis/delta. Returns
    ``[]`` when no ZeRO layout is active (nothing to budget).

    One count is a range: stage 3 re-gathers every bucket on every micro
    step, but inside an accumulation window the shards do not change,
    and a compiler that proves it may publish them once per window (XLA
    inlines the trip-1 inner scan at accumulate=2 and merges the
    identical gathers). Anything from one gather per bucket per window
    up to the emitted count is the same data; below or above is not."""
    if layout is None:
        layout = infer_zero_layout(sfn)
    if not layout or int(layout.get("stage", 0)) <= 0:
        return []
    axis = layout.get("axis")
    if mesh_axes is None:
        mesh_axes = (axis,) if axis else ()
    k = int(layout.get("scan_steps") or 1)
    a = int(layout.get("accumulate_steps") or 1)
    budget = predict_collective_budget(
        layout["stage"], scan_steps=k, accumulate_steps=a,
        n_buckets=layout.get("n_buckets", 1),
        prefetch=layout.get("prefetch", False),
        axis=axis, mesh_axes=mesh_axes,
        exchanged_buckets=layout.get("exchanged_buckets", 0))
    if not budget:
        return []
    floor = dict(budget)
    if int(layout["stage"]) == 3 and a > 1:
        floor[("all-gather", axis)] = (
            int(layout.get("n_buckets", 1)) * max(1, k // a))
    actual = {}
    for s in sfn.collective_stats(per_execution=True):
        key = (s["op"], s["axis"])
        actual[key] = actual.get(key, 0) + s["count"]
    findings = []
    for (op, ax), expected in sorted(budget.items()):
        got = int(actual.get((op, ax), 0))
        if floor[(op, ax)] <= got <= expected:
            continue
        findings.append(Finding(
            "collective-budget-mismatch", ERROR,
            f"{op} on axis {ax!r}: compiled step executes {got} per "
            f"program execution, ZeRO-{layout['stage']} layout "
            f"(buckets={layout.get('n_buckets')}, k={k}, accumulate={a}, "
            f"prefetch={bool(layout.get('prefetch'))}) budgets "
            f"{expected} ({got - expected:+d}) — a surplus means a "
            "bucket re-materializes or re-reduces outside its window, a "
            "deficit that a shard is never published/reduced",
            op_name=op, slot=ax))
    return findings


# ---------------------------------------------------------------------------
# jaxpr sharding propagation
# ---------------------------------------------------------------------------

def _eqn_axes(eqn):
    """The mesh axis names a collective equation runs over."""
    names = eqn.params.get("axis_name", eqn.params.get("axes", ()))
    if not isinstance(names, (tuple, list)):
        names = (names,)
    return tuple(str(n) for n in names)


def _spec_sharded(spec, mesh_axes):
    """True when one shard_map in_specs/out_specs entry (a
    PartitionSpec: per dim None, an axis name or a tuple of them) pins
    a dim to a checked mesh axis."""
    for axes in spec:
        if axes is None:
            continue
        if not isinstance(axes, (tuple, list)):
            axes = (axes,)
        if any(str(a) in mesh_axes for a in axes):
            return True
    return False


def _is_var(a):
    return hasattr(a, "aval") and not hasattr(a, "val")


def _walk_region(jx, in_flags, st, region):
    """Propagate sharding taint through one (open) jaxpr region and
    audit its all-gathered values' live ranges. ``in_flags`` marks which
    invars are sharded over a checked axis; returns the outvars' flags.
    Gathered-value alias groups (movement ops forward membership) are
    finalized at the region boundary: overlap depth feeds the
    ``max_live_gathered`` stat, escapes beyond the budget are
    ``materialization-window`` errors."""
    jx = getattr(jx, "jaxpr", jx)
    sharded = {id(v) for v, f in zip(jx.invars, in_flags)
               if f and _is_var(v)}
    last = {id(v): i for v, i in last_use_map(jx).items()}
    outvar_ids = {id(v) for v in jaxpr_vars(jx.outvars)}
    groups = []   # {"birth", "bytes", "axes", "vars": {ids}}
    by_var = {}   # id(var) -> its gather group
    n_eqns = len(jx.eqns)

    for idx, eqn in enumerate(jx.eqns):
        prim = eqn.primitive.name
        in_vars = jaxpr_vars(eqn.invars)
        tainted = any(id(v) in sharded for v in in_vars)

        if prim == "shard_map":
            out_flags = _check_shard_map(eqn, st)
            for v, f in zip(eqn.outvars, out_flags):
                if f and _is_var(v):
                    sharded.add(id(v))
            continue

        if prim == "all_gather":
            axes = _eqn_axes(eqn)
            if any(a in st["mesh_axes"] for a in axes):
                g = {"birth": idx, "axes": axes, "vars": set(),
                     "bytes": max((aval_bytes(v.aval) for v in eqn.outvars
                                   if hasattr(v, "aval")), default=0)}
                for v in jaxpr_vars(eqn.outvars):
                    g["vars"].add(id(v))
                    by_var[id(v)] = g
                groups.append(g)
                st["n_gathered"] += 1
            continue  # the gathered output is FULL, not sharded

        if prim in _SHARD_PRODUCING_PRIMS:
            if any(a in st["mesh_axes"] for a in _eqn_axes(eqn)):
                for v in jaxpr_vars(eqn.outvars):
                    sharded.add(id(v))
            continue

        if prim == "psum":
            continue  # a psum'd partial is replicated, not sharded

        subs = sub_jaxprs(eqn)
        if subs:
            eqn_flags = [_is_var(v) and id(v) in sharded
                         for v in eqn.invars]
            out_any = [False] * len(eqn.outvars)
            for sub in subs:
                body = getattr(sub, "jaxpr", sub)
                d = len(eqn.invars) - len(body.invars)
                if d >= 0:   # cond's leading predicate and kin
                    flags = eqn_flags[d:]
                else:
                    flags = [False] * (-d) + eqn_flags
                sub_out = _walk_region(body, flags, st, region)
                for i in range(min(len(sub_out), len(out_any))):
                    out_any[i] = out_any[i] or sub_out[i]
            for v, f in zip(eqn.outvars, out_any):
                if f and _is_var(v):
                    sharded.add(id(v))
            continue

        # movement ops forward gather-group membership: a reshaped /
        # sliced / converted gather is still the same full allocation
        src = next((by_var[id(v)] for v in in_vars if id(v) in by_var),
                   None)
        if src is not None and prim in _MOVEMENT_PRIMS:
            for v in jaxpr_vars(eqn.outvars):
                src["vars"].add(id(v))
                by_var[id(v)] = src
        if tainted:
            for v in jaxpr_vars(eqn.outvars):
                sharded.add(id(v))

    # region boundary: finalize the gather groups
    escaped = []
    intervals = []
    for g in groups:
        esc = any(vid in outvar_ids for vid in g["vars"])
        end = n_eqns if esc else max(
            (last.get(vid, g["birth"]) for vid in g["vars"]),
            default=g["birth"])
        intervals.append((g["birth"], end))
        if esc:
            escaped.append(g)
    for birth, _end in intervals:
        depth = sum(1 for b2, e2 in intervals if b2 <= birth <= e2)
        st["max_live_gathered"] = max(st["max_live_gathered"], depth)
    st["escaped_gathered"] += len(escaped)
    if st["budget"] is not None and len(escaped) > st["budget"]:
        axes = sorted({a for g in escaped for a in g["axes"]})
        nbytes = sum(g["bytes"] for g in escaped)
        st["findings"].append(Finding(
            "materialization-window", ERROR,
            f"{len(escaped)} all-gathered full values (axes {axes}, "
            f"{nbytes} bytes) escape a {region} boundary and stay "
            "materialized across steps — the prefetch budget is "
            f"{st['budget']} bucket; a gathered param must die at its "
            "last consumer inside the step", slot=",".join(axes)))
    return [_is_var(v) and id(v) in sharded for v in jx.outvars]


def _check_shard_map(eqn, st):
    """One shard_map region: seed sharding from in_specs, flag oversized
    replicated inputs, recurse into the body, and report the outvars'
    sharding per out_specs."""
    st["shard_map_regions"] += 1
    body = eqn.params.get("jaxpr")
    body = getattr(body, "jaxpr", body)
    in_specs = tuple(eqn.params["in_specs"])
    out_specs = tuple(eqn.params["out_specs"])
    flags = [_spec_sharded(d, st["mesh_axes"]) for d in in_specs]
    if body is None or not hasattr(body, "eqns"):
        return [_spec_sharded(d, st["mesh_axes"]) for d in out_specs]
    if len(flags) < len(body.invars):
        flags += [False] * (len(body.invars) - len(flags))
    if any(flags):
        # a sharded producer/consumer chain exists: every oversized
        # replicated input is a residency regression candidate
        for v, f in zip(body.invars, flags):
            if f or not _is_var(v):
                continue
            nbytes = aval_bytes(v.aval)
            if nbytes >= st["replication_threshold"]:
                shape = tuple(getattr(v.aval, "shape", ()))
                st["findings"].append(Finding(
                    "replication-blowup", WARNING,
                    f"shard_map input {shape} "
                    f"({getattr(v.aval, 'dtype', '?')}, {nbytes} bytes) "
                    "enters replicated while the region threads "
                    f"state sharded over {sorted(st['mesh_axes'])} — "
                    "every rank pays the full tensor; shard it or raise "
                    "REPLICATION_THRESHOLD_BYTES if replication is "
                    "intended", slot=str(shape)))
    _walk_region(body, flags, st, "shard_map")
    return [_spec_sharded(d, st["mesh_axes"]) for d in out_specs]


def analyze_jaxpr(closed_jaxpr, mesh_axes=("dp",),
                  replication_threshold=REPLICATION_THRESHOLD_BYTES,
                  budget=MATERIALIZATION_BUDGET):
    """Sharding-propagation analysis of one traced program: returns
    ``(findings, stats)`` where stats reports ``shard_map_regions``,
    ``n_gathered`` (all-gather equations over checked axes),
    ``max_live_gathered`` (peak simultaneously-live gathered values in
    any region — serial ZeRO-3 holds ~one per bucket through the
    fwd+bwd reuse, the double-buffered prefetch adds one), and
    ``escaped_gathered`` (gathered values crossing a region boundary —
    the prefetch slot's sanctioned count is 1)."""
    st = {
        "mesh_axes": tuple(str(a) for a in mesh_axes),
        "replication_threshold": int(replication_threshold),
        "budget": int(budget) if budget is not None else None,
        "findings": [],
        "shard_map_regions": 0,
        "n_gathered": 0,
        "max_live_gathered": 0,
        "escaped_gathered": 0,
    }
    jx = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    _walk_region(jx, [False] * len(jx.invars), st, "program")
    stats = {k: st[k] for k in ("shard_map_regions", "n_gathered",
                                "max_live_gathered", "escaped_gathered")}
    return st["findings"], stats


def check_jaxpr_sharding(sfn, mesh_axes=None,
                         replication_threshold=REPLICATION_THRESHOLD_BYTES,
                         budget="auto"):
    """Jaxpr-side shardcheck of a compiled ``StaticFunction``: runs
    :func:`analyze_jaxpr` over the step's traced program (the
    ``traced_jaxpr`` aux accessor — same source as the liveness meter).
    A step with no dp axis has no shard_map region and returns ``[]``.

    ``budget="auto"`` enforces the materialization window only under an
    inferred ZeRO-3 layout: below stage 3 the updated full params are
    re-gathered INTO the replicated carry by design, so gathered values
    escaping the region are the contract, not a leak. Under stage 3 the
    params are sharded residents and the only sanctioned escapee is the
    prefetch slot (``MATERIALIZATION_BUDGET`` = 1 bucket). Pass an int
    to pin the budget, or ``None`` to disable the escape rule."""
    part = getattr(sfn, "_last_partition", None)
    aux = getattr(sfn, "_last_aux", None)
    if not isinstance(part, dict) or aux is None:
        return []
    axis = part.get("dp_axis")
    if axis is None:
        return []
    if mesh_axes is None:
        mesh_axes = (axis,)
    if budget == "auto":
        layout = infer_zero_layout(sfn)
        budget = (MATERIALIZATION_BUDGET
                  if layout is not None and layout.get("stage") == 3
                  else None)
    maker = aux.get("traced_jaxpr") if hasattr(aux, "get") else None
    if maker is None:
        return []
    try:
        closed = maker()
    except RuntimeError:
        return []  # never executed: nothing traced to check
    findings, _stats = analyze_jaxpr(
        closed, mesh_axes=mesh_axes,
        replication_threshold=replication_threshold, budget=budget)
    return findings


# ---------------------------------------------------------------------------
# donation accounting
# ---------------------------------------------------------------------------

def check_donation_leak(sfn):
    """``donation-leak``: the compiled step threads a carry but was
    built with ``donate_state=False``, so XLA cannot alias the carried
    buffers and every store is billed twice (live input + fresh output)
    per step. ERROR when sharded (ZeRO) stores ride the un-donated
    carry — the 1/dp residency claim is silently doubled — WARNING for
    a replicated carry (legitimate while debugging aliasing)."""
    part = getattr(sfn, "_last_partition", None)
    if not isinstance(part, dict) or part.get("donate", True):
        return []
    carried = list(part.get("donated", ())) \
        + list(part.get("donated_grads", ()))
    if not carried:
        return []
    sharded = sorted(set(part.get("sharded", ()))
                     & set(part.get("donated", ())))
    sev = ERROR if sharded else WARNING
    what = (f"{len(sharded)} sharded store(s) among them"
            if sharded else "all replicated")
    return [Finding(
        "donation-leak", sev,
        f"step carries {len(carried)} state buffer(s) across the "
        f"jit/scan boundary ({what}) but donate_state=False: the carry "
        "is re-billed every step instead of aliased in place — donate "
        "the carry, or drop the state from the step")]


# ---------------------------------------------------------------------------
# the StaticFunction entry point
# ---------------------------------------------------------------------------

def check_sharding(sfn, hlo=True, mesh_axes=None):
    """Full shardcheck of a compiled ``StaticFunction``: donation
    accounting, jaxpr sharding propagation, and (``hlo=True``, only
    when a ZeRO layout is active — the one case with a budget to hold)
    the compiled collective-budget diff, which pays the entry's one
    lazy AOT compile if nothing else has. ``check_static_function``
    calls this by default; it is separately callable for explicit
    layouts via :func:`check_collective_budget`."""
    findings = list(check_donation_leak(sfn))
    part = getattr(sfn, "_last_partition", None)
    if not isinstance(part, dict) or part.get("dp_axis") is None:
        return findings
    findings += check_jaxpr_sharding(sfn, mesh_axes=mesh_axes)
    if hlo:
        layout = infer_zero_layout(sfn)
        if layout is not None:
            try:
                findings += check_collective_budget(
                    sfn, layout=layout, mesh_axes=mesh_axes)
            except RuntimeError:
                pass  # not executed yet: no compiled program to diff
    return findings


# ---------------------------------------------------------------------------
# record-level twins (ladder programs)
# ---------------------------------------------------------------------------

def program_shard_stats(prog, mesh_axes=None):
    """Stamped-collective summary of a recorded ``static.Program``:
    ``{"axes": {axis: {op kind: count}}, "collectives": total}``.
    Counts come from the ``fn._collective_axis`` stamps the ladder
    twins (and ``distributed.collective``'s real lowerings) carry;
    ``mesh_axes`` filters to the checked axes when given."""
    from .collectives import collective_sequence
    axes = {}
    total = 0
    for _i, name, axis, _nbytes, _every in collective_sequence(prog):
        kind = _RECORD_OPS.get(name, name)
        if axis is None:
            continue  # unstamped: the order checker owns that finding
        if mesh_axes is not None and axis not in mesh_axes:
            continue
        slot = axes.setdefault(axis, {})
        slot[kind] = slot.get(kind, 0) + 1
        total += 1
    return {"axes": axes, "collectives": total}


def format_shard_stats(stats):
    """One-cell rendering for the lint CLI's ``shard=`` column:
    ``dp:ag1+rs2`` per stamped axis, ``-`` for a program with no
    stamped collectives."""
    if not stats["axes"]:
        return "-"
    cells = []
    for axis, ops in sorted(stats["axes"].items()):
        part = "+".join(f"{_OP_ABBREV.get(k, k)}{n}"
                        for k, n in sorted(ops.items()))
        cells.append(f"{axis}:{part}")
    return ",".join(cells)


def check_program_sharding(prog, mesh_axes=("dp",)):
    """Record-level collective budget of a program twin: on every
    checked axis, gradient shards that are reduce-scattered — or, in
    the twin of a step with 16-bit gradients, exchanged by all-to-all
    and summed on arrival — must be matched by at least one all-gather
    republishing the updated params (the ZeRO contract the stamped
    schedules encode) — a scatter-only axis is a
    ``collective-budget-mismatch`` ERROR. Rank-order and cadence
    divergence stay with ``check_collective_order``."""
    stats = program_shard_stats(prog, mesh_axes=mesh_axes)
    findings = []
    for axis, ops in sorted(stats["axes"].items()):
        rs = ops.get("reduce-scatter", 0)
        a2a = ops.get("all-to-all", 0)
        ag = ops.get("all-gather", 0)
        if (rs or a2a) and not ag:
            findings.append(Finding(
                "collective-budget-mismatch", ERROR,
                f"axis {axis!r}: {rs} reduce-scatter(s) and {a2a} "
                "all-to-all(s) but no all-gather — gradient shards are "
                "reduced but the updated params are never republished "
                "(expected >= 1 all-gather per update window, got 0)",
                slot=axis))
    return findings


# ---------------------------------------------------------------------------
# runtime residency
# ---------------------------------------------------------------------------

def check_zero_residency(opt):
    """1/degree residency audit of a live optimizer's ZeRO stores: every
    flat store's addressable shard must hold ``full_rows / degree`` and
    ``_zero_state_bytes`` must equal the full state divided by the
    degree — the claim the zero-sharding tests used to assert with
    hand-rolled shape math. Returns ``zero-residency`` ERROR findings;
    ``[]`` when ZeRO is off or when single-device placement leaves
    nothing sharded to audit."""
    import numpy as np
    cfg = getattr(opt, "_zero", None)
    if not cfg:
        return []
    findings = []
    degree = int(cfg["degree"])
    total_full = 0
    for zb, sdict in zip(cfg["buckets"], cfg["stores"]):
        for _slot, sd in sdict.items():
            val = sd.tensor._value
            full = tuple(int(d) for d in val.shape)
            nbytes = int(np.prod(full or (1,))) * val.dtype.itemsize
            total_full += nbytes
            try:
                shard = tuple(int(d) for d in
                              val.addressable_shards[0].data.shape)
            except (AttributeError, IndexError):
                continue
            if not full or shard[0] * degree != full[0]:
                findings.append(Finding(
                    "zero-residency", ERROR,
                    f"store {sd.tensor.name!r}: full rows {full} but "
                    f"per-rank shard {shard} — expected 1/{degree} "
                    f"residency over axis {cfg['axis']!r}",
                    slot=sd.tensor.name))
    billed = opt._zero_state_bytes() * degree
    if total_full and billed != total_full:
        findings.append(Finding(
            "zero-residency", ERROR,
            f"_zero_state_bytes bills {billed // degree} per rank "
            f"(x{degree} = {billed}) but the stores hold {total_full} "
            "bytes of full state — the per-rank accounting and the "
            "actual layout disagree"))
    return findings
