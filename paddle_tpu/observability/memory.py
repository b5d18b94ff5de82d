"""HBM memory accounting: per-program XLA attribution + state ledger.

Two complementary views answer "where does HBM go?":

- **Per-program attribution** — the compiled executable's XLA
  ``memory_analysis()`` (``CompiledMemoryStats``) splits a program's
  footprint into argument / output / temp / alias / generated-code
  bytes. ``StaticFunction.memory_stats()`` reaches it through the same
  lazy AOT aux entries ``collective_stats()`` uses, and the serving
  engine reports one record per bucket executable. Donated carries show
  up as ``alias_bytes`` (the input and output buffer are the same HBM),
  which is why ``peak_bytes`` subtracts them — a donated scan step must
  not bill its state twice.
- **Framework-state residency ledger** — a walk of the registered
  state (``core.state``) classifying every live stateful tensor by
  structural category (params, optimizer moments, fp32 masters, ZeRO
  flat stores per bucket, gradient-accumulation stores, RNG/lr,
  hbm_cache tables) and summing both the *global* logical bytes and the
  *per-rank resident* bytes (one device's shards of a sharded store —
  the number that proves ZeRO-3's model state really lives 1/dp per
  chip, numerically, not by HLO pattern-matching).

Byte accounting is backend-deterministic (unlike wall time): the same
program reports the same bytes on the CPU host as on the chip's
compiler, so tests pin these numbers exactly.

The flight recorder embeds :func:`flight_section` in every crash dump;
combined with :func:`is_oom_error` classification a
``RESOURCE_EXHAUSTED`` death names the top program buffers and state
categories at the moment of death.
"""
import re
import threading

import numpy as np

from .. import monitor

__all__ = ["program_stats", "peak_bytes", "top_buffers",
           "state_ledger", "export_state_ledger", "classify_tensor",
           "record_program_memory", "program_memory",
           "record_program_scopes", "program_scopes",
           "export_program_memory", "snapshot", "runlog_snapshot",
           "flight_section", "is_oom_error", "attribute_program",
           "compile_program_twin",
           "MemoryAttributionError", "MEMORY_KINDS", "STATE_CATEGORIES"]

# the CompiledMemoryStats fields exported as program_hbm_bytes{kind=}
MEMORY_KINDS = ("argument", "output", "temp", "alias", "generated_code")

# host-memory CompiledMemoryStats fields (jaxlib exposes host_* twins on
# backends with host memory spaces): summed into ONE "host_offload" kind
# — the bytes the offload recompute policy parked OFF the device. Absent
# fields read as 0 (older jaxlib / backends without host spaces).
HOST_MEMORY_KINDS = ("host_argument", "host_output", "host_temp",
                     "host_alias", "host_generated_code")

STATE_CATEGORIES = ("param", "buffer", "opt_moment", "master",
                    "zero_param", "zero_moment", "zero_master", "gacc",
                    "rng", "lr", "hbm_cache", "grad", "host_offload",
                    "other")


class MemoryAttributionError(RuntimeError):
    """XLA memory analysis failed for a program (backend without
    ``memory_analysis`` support, or a program that does not compile
    abstractly). Ladder verification treats this like a verify error."""


# -- per-program attribution ----------------------------------------------

def program_stats(compiled):
    """Normalize a compiled executable's ``memory_analysis()`` into a
    plain dict: ``{argument,output,temp,alias,generated_code}_bytes``
    plus the derived ``peak_bytes``. Raises
    :class:`MemoryAttributionError` when the backend exposes no usable
    analysis — callers gate on attribution, so silence would hide a
    coverage hole."""
    try:
        ma = compiled.memory_analysis()
    except Exception as e:
        raise MemoryAttributionError(
            f"memory_analysis() failed: {e}") from e
    if ma is None:
        raise MemoryAttributionError(
            "backend returned no memory analysis for this executable")
    out = {}
    for kind in MEMORY_KINDS:
        val = getattr(ma, f"{kind}_size_in_bytes", None)
        if val is None:
            raise MemoryAttributionError(
                f"memory analysis lacks {kind}_size_in_bytes "
                f"(got {type(ma).__name__})")
        out[f"{kind}_bytes"] = int(val)
    # residuals the offload recompute policy parked in host memory: they
    # are NOT device HBM (peak_bytes excludes them by construction — the
    # host_* fields are separate) but the ledger must show where the
    # bytes went, so they surface as one aggregated kind
    host = 0
    for kind in HOST_MEMORY_KINDS:
        host += int(getattr(ma, f"{kind}_size_in_bytes", 0) or 0)
    out["host_offload_bytes"] = host
    out["peak_bytes"] = peak_bytes(out)
    return out


def peak_bytes(stats):
    """Program-attributable HBM high-water estimate: arguments +
    outputs + temps + generated code, minus aliased bytes (a donated
    input/output pair is ONE buffer — counting both sides would bill
    the carried training state twice)."""
    return (stats["argument_bytes"] + stats["output_bytes"]
            + stats["temp_bytes"] + stats["generated_code_bytes"]
            - stats["alias_bytes"])


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}
# `%name = dtype[dims]{layout} op(...)` — the result buffer of one HLO
# instruction (tuple-typed results match their first element; good
# enough for a largest-buffers ranking)
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\(?\s*"
    r"([a-z]+[0-9]+|pred)\[([0-9,]*)\]")


def top_buffers(hlo_text, n=10):
    """The ``n`` largest instruction result buffers of a compiled HLO
    program: ``[{"name", "bytes", "shape"}]`` sorted descending. An
    approximation of the buffer-assignment view (XLA reuses buffers),
    but it names the tensors that dominate an OOM — which is what a
    crash dump needs."""
    out = []
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if m is None:
            continue
        name, dtype, dims = m.groups()
        size = _DTYPE_BYTES.get(dtype)
        if size is None:
            continue
        count = 1
        if dims:
            for d in dims.split(","):
                count *= int(d)
        out.append({"name": name, "bytes": count * size,
                    "shape": f"{dtype}[{dims}]"})
    out.sort(key=lambda b: -b["bytes"])
    return out[:n]


# registry of the most recent per-program attribution (entry -> record);
# the flight recorder and runlog snapshots read it at death/boundary time
_programs = {}
_programs_lock = threading.Lock()


def record_program_memory(entry, stats, buffers=None):
    """Register one program's attribution under ``entry`` (the newest
    record per entry wins) and export it as
    ``program_hbm_bytes{entry=,kind=}`` gauges. Returns ``stats``."""
    rec = dict(stats)
    if buffers:
        rec["top_buffers"] = list(buffers)
    with _programs_lock:
        _programs[str(entry)] = rec
    export_program_memory(entry, stats)
    return stats


def program_memory():
    """``{entry: record}`` view of every program attribution recorded
    this process (records carry the byte kinds + optional
    ``top_buffers``)."""
    with _programs_lock:
        return {k: dict(v) for k, v in _programs.items()}


def clear_program_memory():
    with _programs_lock:
        _programs.clear()
        _program_scopes.clear()


# the newest compiled step's scope table with the HLO text it came from
# (one record: the text is megabytes, and a flight dump has no use for it)
_program_scopes = {}


def record_program_scopes(entry, table, hlo_text):
    """Register a compiled program's ``observability.scopes`` table and
    its HLO text; the newest replaces the one before."""
    with _programs_lock:
        _program_scopes.clear()
        _program_scopes.update(entry=str(entry), table=table,
                               hlo=hlo_text)


def program_scopes():
    """``{"entry", "table", "hlo"}`` of the newest compiled program whose
    introspection aux was materialised (``StaticFunction.hlo_text()`` /
    ``scope_table()`` / ``memory_stats()``), or None — for a reader in
    the same process that does not hold the step."""
    with _programs_lock:
        return dict(_program_scopes) or None


def export_program_memory(entry, stats):
    """Export one program's byte kinds as
    ``program_hbm_bytes{entry=,kind=}`` gauges (peak and — when the
    record carries it — the host_offload aggregate included)."""
    from . import export
    for kind in MEMORY_KINDS + ("peak", "host_offload"):
        val = stats.get(f"{kind}_bytes")
        if val is None:
            continue  # records from older captures lack host_offload
        export.set_gauge(
            "program_hbm_bytes" + export.format_labels(
                "program_hbm_bytes", entry=entry, kind=kind),
            val)


# -- framework-state residency ledger -------------------------------------

_NAME_CATEGORIES = (
    # structural-name fallbacks for tensors created before (or outside)
    # the tagged constructors — the ZeRO store names are part of the
    # checkpoint contract, so they are stable
    (re.compile(r"^zero_param_b\d+$"), "zero_param"),
    (re.compile(r"^zero_master_b\d+$"), "zero_master"),
    (re.compile(r"^zero_gacc_b\d+$"), "gacc"),
    (re.compile(r"^zero_\w+_b\d+$"), "zero_moment"),
    (re.compile(r"^hbm_cache_table_"), "hbm_cache"),
)


def is_host_parked(arr):
    """True when a jax.Array lives in a HOST memory space of a device
    whose default memory is elsewhere (the pjit ``pinned_host`` memory
    kind the offload recompute policy uses). On CPU the default memory
    IS a host space, so nothing classifies as parked — the category
    only lights up where offload actually moved bytes off the device."""
    import jax
    if not isinstance(arr, jax.Array):
        return False
    try:
        mk = arr.sharding.memory_kind
        if mk is None or "host" not in str(mk):
            return False
        dev = next(iter(arr.sharding.device_set))
        return str(mk) != str(dev.default_memory().kind)
    except Exception:
        return False


def classify_tensor(t):
    """Ledger category of a registered stateful tensor: host-parked
    values (offload policy) classify ``host_offload`` first — residency
    proof must show where the bytes went — then an explicit
    ``_ledger_category`` tag (set by the optimizer / RNG / lr / cache
    constructors), then the structural-name patterns, then the
    Parameter/buffer fallback."""
    if is_host_parked(getattr(t, "_value", None)):
        return "host_offload"
    cat = getattr(t, "_ledger_category", None)
    if cat is not None:
        return cat
    name = getattr(t, "name", "") or ""
    for pat, cat in _NAME_CATEGORIES:
        if pat.match(name):
            return cat
    from ..core.tensor import Parameter
    if isinstance(t, Parameter):
        return "param"
    if getattr(t, "persistable", False):
        return "buffer"
    return "other"


def value_bytes(arr):
    """``(global_bytes, per_rank_bytes)`` of one array. For a sharded
    jax.Array the per-rank number is what ONE device holds (its shards
    deduped by device; replicated arrays hold the full buffer per
    rank); metadata-only — nothing is transferred or materialized."""
    import jax
    shape = tuple(np.shape(arr))
    itemsize = np.dtype(getattr(arr, "dtype", np.float32)).itemsize
    count = 1
    for d in shape:
        count *= int(d)
    global_bytes = count * itemsize
    if isinstance(arr, jax.Array):
        try:
            if len(arr.sharding.device_set) > 1:
                per_dev = {}
                for s in arr.addressable_shards:
                    n = 1
                    for d in s.data.shape:
                        n *= int(d)
                    key = getattr(s.device, "id", s.device)
                    per_dev[key] = per_dev.get(key, 0) + n * itemsize
                if per_dev:
                    return global_bytes, max(per_dev.values())
        except Exception:
            pass  # non-addressable / exotic sharding: fall through
    return global_bytes, global_bytes


def state_ledger():
    """Walk the registered framework state into a residency ledger::

        {"categories": {cat: {"bytes": per-rank, "global_bytes",
                              "count"}},
         "entries": [{"name", "category", "shape", "dtype", "bytes",
                      "global_bytes"}],
         "total_bytes": per-rank total, "total_global_bytes": ...}

    ``bytes`` is always the PER-RANK resident number (one device's
    shards); surviving gradients (accumulation windows) are counted as
    their own ``grad`` category — they are real HBM between steps."""
    from ..core import state as state_mod
    cats = {}
    entries = []
    total = total_global = 0

    def _add(name, cat, arr):
        nonlocal total, total_global
        g, r = value_bytes(arr)
        slot = cats.setdefault(cat, {"bytes": 0, "global_bytes": 0,
                                     "count": 0})
        slot["bytes"] += r
        slot["global_bytes"] += g
        slot["count"] += 1
        total += r
        total_global += g
        entries.append({
            "name": name, "category": cat,
            "shape": list(np.shape(arr)),
            "dtype": str(np.dtype(getattr(arr, "dtype", np.float32))),
            "bytes": r, "global_bytes": g})

    for _uid, t in state_mod.snapshot():
        _add(t.name, classify_tensor(t), t._value)
        g = getattr(t, "_grad", None)
        if g is not None and not hasattr(g, "rows"):  # dense grads only
            _add(t.name + "@GRAD", "grad", g)
    entries.sort(key=lambda e: -e["bytes"])
    return {"categories": cats, "entries": entries,
            "total_bytes": total, "total_global_bytes": total_global}


def export_state_ledger(ledger=None, rank=None):
    """Export the ledger as ``state_resident_bytes{category=}`` gauges
    plus ``state_resident_bytes_total``; returns the ledger.

    ``rank`` adds a ``rank`` label to every gauge — the multi-host
    story: each pod process exports its OWN residency, a scrape across
    ranks (or ``tools/trace_view.py --stats`` over the merged run-logs)
    sums them. Defaults to ``PADDLE_TRAINER_ID`` when that is set (a
    launched rank), else unlabeled (single-process, the PR-10
    behavior)."""
    import os as _os

    from . import export
    ledger = ledger if ledger is not None else state_ledger()
    if rank is None:
        rank = _os.environ.get("PADDLE_TRAINER_ID")
    labels = {} if rank is None else {"rank": str(rank)}
    for cat, slot in ledger["categories"].items():
        export.set_gauge(
            "state_resident_bytes" + export.format_labels(
                "state_resident_bytes", category=cat, **labels),
            slot["bytes"])
    if labels:
        export.set_gauge(
            "state_resident_bytes_total" + export.format_labels(
                "state_resident_bytes_total", **labels),
            ledger["total_bytes"])
    else:
        export.set_gauge("state_resident_bytes_total",
                         ledger["total_bytes"])
    return ledger


# -- snapshots (runlog / flight) ------------------------------------------

def snapshot(top_n=8):
    """JSON-ready memory snapshot: per-category state bytes, the top-N
    resident state entries, and every recorded program attribution —
    the record a run-log ``memory_snapshot`` event and a flight dump's
    ``memory`` section carry."""
    ledger = state_ledger()
    return {
        "state": {
            "categories": {c: dict(v)
                           for c, v in ledger["categories"].items()},
            "total_bytes": ledger["total_bytes"],
            "total_global_bytes": ledger["total_global_bytes"],
            "top_entries": ledger["entries"][:top_n],
        },
        "programs": program_memory(),
    }


def runlog_snapshot(rank=None, export=False):
    """Emit a ``memory_snapshot`` event into the active run-log (no-op
    when none is active); returns the snapshot or None. The event is
    rank-tagged when a rank is known (explicit ``rank`` or
    ``PADDLE_TRAINER_ID``) so ``tools/trace_view.py --stats`` can sum
    per-rank residency across a pod's merged logs; ``export=True`` also
    publishes the ``state_resident_bytes`` gauges
    (:func:`export_state_ledger`) — rank-labeled only when a rank is
    known, so single-process callers keep the PR-10 unlabeled series."""
    import os as _os

    from . import runlog
    if runlog.active() is None:
        return None
    if rank is None:
        rank = _os.environ.get("PADDLE_TRAINER_ID")
    snap = snapshot()
    if rank is None:
        runlog.event("memory_snapshot", **snap)
    else:
        runlog.event("memory_snapshot", rank=str(rank), **snap)
    if export:
        export_state_ledger(rank=rank)
    return snap


def flight_section():
    """The crash dump's memory section. Never raises, and walks
    metadata only — it runs inside excepthooks, possibly during the
    OOM it is describing."""
    try:
        return snapshot()
    except Exception as e:
        return {"error": str(e)[:300]}


# -- OOM classification ---------------------------------------------------

_OOM_RE = re.compile(
    r"RESOURCE[ _]EXHAUSTED|out of memory|\bOOM\b"
    r"|allocation (failure|failed)|failed to allocate"
    r"|exceeds the memory capacity", re.IGNORECASE)


def is_oom_error(exc):
    """True when an exception is an allocation failure: python
    ``MemoryError``, or any exception (XlaRuntimeError surfaces as
    different concrete types across jaxlib versions) whose message
    matches the XLA allocation-failure vocabulary
    (``RESOURCE_EXHAUSTED``, "out of memory", "failed to allocate",
    ...)."""
    if exc is None:
        return False
    if isinstance(exc, MemoryError):
        return True
    try:
        return bool(_OOM_RE.search(str(exc)))
    except Exception:
        return False


# -- static-Program attribution (ladder / mem_view) ------------------------

def compile_program_twin(prog, targets, bump=0):
    """AOT-compile a recorded ``static.Program``'s pure function on
    abstract (ShapeDtypeStruct) feeds/params — no real buffers — and
    return the compiled executable. The shared front half of every
    attribution pass over program twins (memory here,
    ``observability.overlap`` for schedule analysis). Raises
    :class:`MemoryAttributionError` when the program fails to
    compile."""
    import jax

    from ..core.dtype import convert_dtype
    from ..core.tensor import Tensor

    feed_names = list(prog.feed_vars.keys())
    feed_slots = [prog.feed_vars[n][0] for n in feed_names]
    fetch_slots = [prog._slot_of(t, create=False) for t in targets]
    if any(s is None for s in fetch_slots):
        raise MemoryAttributionError(
            "a fetch target was never recorded in the program")
    param_slots = sorted(prog.params.keys())
    run = prog._pure(feed_slots, fetch_slots, param_slots)

    def _sds(shape, dtype):
        shape = tuple(1 + bump if (d is None or d == -1) else int(d)
                      for d in shape)
        return jax.ShapeDtypeStruct(shape, np.dtype(dtype))

    feeds = [_sds(prog.feed_vars[n][1], convert_dtype(prog.feed_vars[n][2]))
             for n in feed_names]
    params = []
    for s in param_slots:
        t = prog.params[s]
        v = t._value if isinstance(t, Tensor) else t
        params.append(jax.ShapeDtypeStruct(tuple(np.shape(v)),
                                           np.dtype(v.dtype)))
    try:
        return jax.jit(run).lower(feeds, params).compile()
    except MemoryAttributionError:
        raise
    except Exception as e:
        raise MemoryAttributionError(
            f"program failed to AOT-compile for attribution: "
            f"{str(e)[:300]}") from e


def attribute_program(prog, targets, bump=0):
    """Memory attribution of a recorded ``static.Program``: compile the
    program's pure function on abstract feeds via
    :func:`compile_program_twin` and return :func:`program_stats` of
    the executable. Raises :class:`MemoryAttributionError` when the
    program fails to compile or the backend yields no analysis; ladder
    verification surfaces that as an error finding, refusing the
    ladder the same way a verify failure does."""
    return program_stats(compile_program_twin(prog, targets, bump=bump))


_MB = 1024 * 1024


def mb(nbytes):
    """Bytes -> MB (binary), rounded to 3 decimals — the unit the bench
    rows and mem_view tables report."""
    return round(nbytes / _MB, 3)
