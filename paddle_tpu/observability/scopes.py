"""Device time under the program's names.

Host spans (tracing.py) say what the host did; this module names what the
DEVICE does. While a ``to_static`` trace is running, the seams that stage
work — ``nn.Layer.__call__``, ``core.dispatch.call_op``, the attention
functional, the optimizer step, the model heads — enter :func:`scope`,
a thin wrapper of ``jax.named_scope`` with the one prefix ``pt.``. XLA
keeps the name stack as each instruction's ``metadata={op_name="…"}``, so
the compiled step's HLO text maps every instruction to a scope path:

    jit(step)/while/body/pt.gpt/pt.blocks.3/pt.qkv/pt.linear/dot_general
        -> path "gpt/blocks.3/qkv/linear"

``scope_table(hlo_text)`` derives that map (``StaticFunction.
scope_table()`` serves it from the lazy AOT aux and registers the newest
in ``observability.memory``'s program registry); ``device_time_by_scope
(trace_dir, table)`` joins it with a profiler trace into seconds per
scope path and per kind (``tools/trace_view.py --scopes``).

An eager call pays one flag read: every seam tests :func:`tracing`
(``jit.to_static._is_tracing``) before it builds anything.

The backward: the tape runs a node's ``vjp_fn`` inside
``loss.backward()``, far from the layer that recorded it, and jax keeps
only the part of the name stack that lay INSIDE the ``jax.vjp`` call
(``transpose(jvp())/mul``). ``TapeNode`` therefore remembers
:func:`current_path` and ``autograd.backward`` re-enters it
(:func:`reenter`): a backward instruction reads
``…/pt.qkv/pt.linear/transpose(jvp())/dot_general`` — the forward's
path, with ``transpose(`` as the backward flag.
"""
import glob
import importlib
import json
import os
import re

import jax

__all__ = ["PREFIX", "scope", "tracing", "current_path", "reenter",
           "layer_scope", "entered", "scope_table", "time_by_scope",
           "device_time_by_scope", "save_table", "load_table",
           "format_by_scope"]

PREFIX = "pt."
TABLE_FILE = "scope_table.json"

_clean_re = re.compile(r"[^A-Za-z0-9_.]")

# the scope names entered on this trace, outermost first (the python
# twin of jax's name stack: what a TapeNode remembers)
_stack = []
# layer calls in flight: (names of the root's sublayers by id, full name
# of the layer being called)
_layer_stack = []
_entered = [0]
_ts = None
_named_scope = jax.named_scope  # the one thing a scope does to the trace


def tracing():
    """Is a ``to_static`` trace running? (`jit.to_static._is_tracing`,
    bound on first use: `core` imports this module's callers.)"""
    global _ts
    if _ts is None:
        # the module: `jit.to_static` the attribute is the decorator
        _ts = importlib.import_module("paddle_tpu.jit.to_static")
    return _ts._is_tracing


def entered():
    """Scopes entered so far in this process — `StaticFunction` samples
    it around a build to know the step was traced with scopes."""
    return _entered[0]


def _clean(name):
    return _clean_re.sub("_", str(name)).strip(".") or "_"


class _NullScope:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SCOPE = _NullScope()


class _Scope:
    """`names` pushed on the python stack and entered as ONE
    ``jax.named_scope("pt.a/pt.b")`` (a re-entered path costs one
    context, not one per component)."""

    __slots__ = ("names", "_cm")

    def __init__(self, names):
        self.names = names
        self._cm = None

    def __enter__(self):
        _entered[0] += 1
        _stack.extend(self.names)
        self._cm = _named_scope(
            "/".join(PREFIX + n for n in self.names))
        self._cm.__enter__()
        return self

    def __exit__(self, *exc):
        self._cm.__exit__(*exc)
        del _stack[len(_stack) - len(self.names):]
        return False


def scope(name):
    """``with scope("optimizer"): ...`` — a ``jax.named_scope("pt.
    optimizer")`` while a `to_static` trace is running, nothing
    otherwise. Characters outside ``[A-Za-z0-9_.]`` become ``_``."""
    if not tracing():
        return NULL_SCOPE
    return _Scope((_clean(name),))


def current_path():
    """The scope names entered right now (a tuple, outermost first), or
    None outside a trace — what ``TapeNode`` remembers."""
    if not _stack or not tracing():
        return None
    return tuple(_stack)


def reenter(path):
    """Re-enter a remembered :func:`current_path` (the backward of a
    node, run far from the layer that recorded it)."""
    if not path or not tracing():
        return NULL_SCOPE
    return _Scope(tuple(path))


class _LayerScope:
    """The scope of one ``Layer.__call__``: the name the parent
    registered the sublayer under (``blocks.3``, ``qkv``), relative to
    the nearest layer call around it; the class name for a root."""

    __slots__ = ("layer", "_scope")

    def __init__(self, layer):
        self.layer = layer
        self._scope = None

    def __enter__(self):
        layer = self.layer
        names, parent, full = None, "", None
        if _layer_stack:
            names, parent = _layer_stack[-1]
            full = names.get(id(layer))
        if full is None:  # a root: nobody in flight registered it
            names = {id(sub): n for n, sub in layer.named_sublayers()}
            full, rel = "", type(layer).__name__
        elif parent and full.startswith(parent + "."):
            rel = full[len(parent) + 1:]
        else:
            rel = full
        _layer_stack.append((names, full))
        self._scope = _Scope((_clean(rel),))
        self._scope.__enter__()
        return self

    def __exit__(self, *exc):
        self._scope.__exit__(*exc)
        _layer_stack.pop()
        return False


def layer_scope(layer):
    return _LayerScope(layer) if tracing() else NULL_SCOPE


# ---------------------------------------------------------------- the table

# one instruction line of compiled HLO text:  [ROOT] %name = shape opcode(...
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE_RE = re.compile(r"(?<![\w.\-])([a-z][a-z0-9\-]*)\(")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_OPERAND_RE = re.compile(r"\(\s*%([\w.\-]+)")
_COMPUTATION_RE = re.compile(
    r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_COMPONENT_RE = re.compile(
    r"(?:^|[/(])" + re.escape(PREFIX) + r"([A-Za-z0-9_.]+)")
_INDEX_RE = re.compile(r"\.\d+$")

# the operations whose device time matters: one without a scope is
# listed in the table's `unscoped`
NONTRIVIAL = ("fusion", "dot", "convolution", "custom-call", "reduce",
              "reduce-window", "all-gather", "all-reduce",
              "reduce-scatter", "collective-permute", "all-to-all")
# operations that only contain other operations, and the launching half
# of an async pair: neither counts as device time (the rule of the
# benchmark's trace reduction)
CONTAINERS = ("while", "conditional", "call")


def path_of(op_name):
    """'jit(f)/while/body/pt.gpt/pt.qkv/transpose(jvp(pt.act))/mul' ->
    ('gpt/qkv/act', True): the ``pt.`` components in order, the
    ``jvp(…)``/``transpose(…)`` wrappers stripped and kept as the
    backward flag. A backward may bring part of its forward's stack
    along inside the wrapper, behind the one the tape re-entered (a
    custom VJP the whole of it: ``pt.a/pt.b/transpose(pt.a)/pt.b/
    jvp()/mul``): what the two share counts once."""
    # (the compiler joins the names of instructions it merged with ';')
    head, backward, tail = op_name.split(";")[0].partition("transpose(")
    outer = _COMPONENT_RE.findall(head)
    inner = _COMPONENT_RE.findall(tail)
    shared = next(k for k in range(min(len(outer), len(inner)), -1, -1)
                  if outer[len(outer) - k:] == inner[:k])
    return "/".join(outer + inner[shared:]), bool(backward)


def _computations(hlo_text):
    """``{computation: [(instruction, rest of its line, is root)]}`` in
    the text's order."""
    comps, current = {}, None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            comp = _COMPUTATION_RE.match(line)
            current = comps.setdefault(comp.group(1), []) if comp else None
            continue
        m = _INSTR_RE.match(line) if current is not None else None
        if m:
            current.append((m.group(1), m.group(2),
                            line.lstrip().startswith("ROOT")))
    return comps


def _op_path(rest):
    """(path, backward, named): `named` is False where the instruction
    carries no op_name at all — the compiler made it, not the program."""
    op_name = _OP_NAME_RE.search(rest)
    return path_of(op_name.group(1)) + (True,) if op_name else (
        "", False, False)


def _fusion_path(rest, comps):
    """A fusion takes its own metadata (its root's, as XLA writes it);
    where the compiler left that without a scope (XLA:CPU's fusions
    carry none), its root's, then its members' most common one."""
    own = _op_path(rest)
    called = _CALLS_RE.search(rest)
    members = comps.get(called.group(1), ()) if called else ()
    if own[0] or not members:
        return own
    seen, named = {}, own[2]
    for _name, member_rest, is_root in members:
        found = _op_path(member_rest)
        named = named or found[2]
        if found[0]:
            if is_root:
                return found
            seen[found] = seen.get(found, 0) + 1
    return max(seen, key=seen.get) if seen else ("", False, named)


def scope_table(hlo_text, traced_with_scopes=None):
    """``{instruction name: scope path}`` of compiled HLO text.

    Returns ``{"instructions": {name: {"path", "backward", "opcode"}},
    "unscoped": [NONTRIVIAL instructions the program staged outside
    every scope: an op_name and no ``pt.`` in it], "unnamed": [NONTRIVIAL
    instructions with no op_name at all and none to inherit],
    "nontrivial": their count in all, "stale": bool}``.
    Instructions inside fused computations are left out: the fusion is
    what a device trace shows. A NONTRIVIAL instruction without any
    op_name is one the compiler rewrote and dropped the metadata of
    (XLA:TPU turns a reduce-scatter into an all-reduce-and-slice fusion
    that way): it takes the scope of the nearest operand that has one
    and is marked ``"inherited": True``. The compiler's own copies and
    slices are not NONTRIVIAL and stay without a scope. ``stale`` is
    True when the step was traced with scopes (``traced_with_scopes``)
    and the executable names none — an executable that a persistent
    compile cache kept from before the program had scopes (jax's cache
    key leaves this metadata out)."""
    comps = _computations(hlo_text)
    fused = set()
    for members in comps.values():
        for _name, rest, _root in members:
            if " fusion(" in rest or rest.startswith("fusion("):
                called = _CALLS_RE.search(rest)
                if called:
                    fused.add(called.group(1))
    instructions, operand, rewritten = {}, {}, []
    unscoped, unnamed, nontrivial = [], [], 0
    for comp, members in comps.items():
        if comp in fused:
            continue
        for name, rest, _root in members:
            opcode = _OPCODE_RE.search(rest)
            first = _OPERAND_RE.match(rest, opcode.end() - 1) if opcode \
                else None
            opcode = opcode.group(1) if opcode else ""
            path, backward, named = (
                _fusion_path(rest, comps) if opcode == "fusion"
                else _op_path(rest))
            instructions[name] = {"path": path, "backward": backward,
                                  "opcode": opcode}
            if first:
                operand[name] = first.group(1)
            base = opcode
            for half in ("-start", "-done"):
                if base.endswith(half):
                    base = base[:-len(half)]
            if base in NONTRIVIAL:
                nontrivial += 1
                if not path:
                    (unscoped if named else rewritten).append(name)
    for name in rewritten:
        source = operand.get(name)
        for _hop in range(4):  # through the compiler's own copies
            if source is None or instructions.get(source, {}).get("path"):
                break
            source = operand.get(source)
        found = instructions.get(source)
        if found and found["path"]:
            instructions[name].update(path=found["path"],
                                      backward=found["backward"],
                                      inherited=True)
        else:
            unnamed.append(name)
    scoped_any = any(rec["path"] for rec in instructions.values())
    return {"instructions": instructions, "unscoped": unscoped,
            "unnamed": unnamed, "nontrivial": nontrivial,
            "stale": bool(traced_with_scopes) and not scoped_any}


def _event_name(name):
    """'%fusion.12 = bf16[..] fusion(...)' -> 'fusion.12'."""
    return name.split(" = ")[0].strip().lstrip("%")


def _counts(name):
    base = re.sub(r"[.\d]+$", "", name)
    return base not in CONTAINERS and not base.endswith("-start")


def kind_of(component):
    """'blocks.3' -> 'blocks': a component without its index."""
    return _INDEX_RE.sub("", component)


def time_by_scope(devices, table):
    """Reduce ``{device: [[instruction, start_ns, duration_ns], ...]}``
    with a :func:`scope_table` into seconds (the mean over devices):
    ``paths`` per whole scope path, ``kinds`` per component with its
    index stripped (an instruction counts once under every component of
    its path, so ``kinds["optimizer"]`` and ``kinds["cast"]`` both hold
    a cast inside the optimizer), ``unscoped`` per instruction base name
    for what carries no scope, ``backward_s``, ``unscoped_s`` and
    ``total_s``. Containers (`while`, `call`) and ``-start`` halves are
    left out, as in the benchmark's busy time."""
    instructions = table["instructions"]
    paths, kinds, unscoped = {}, {}, {}
    total = backward = 0.0
    for events in devices.values():
        for name, _start, dur in events:
            name = _event_name(name)
            if not _counts(name):
                continue
            sec = dur / 1e9
            total += sec
            rec = instructions.get(name)
            path = rec["path"] if rec else ""
            if not path:
                base = re.sub(r"[.\d]+$", "", name)
                unscoped[base] = unscoped.get(base, 0.0) + sec
                continue
            if rec["backward"]:
                backward += sec
            paths[path] = paths.get(path, 0.0) + sec
            for kind in {kind_of(c) for c in path.split("/")}:
                kinds[kind] = kinds.get(kind, 0.0) + sec
    n = max(1, len(devices))

    def mean(d):
        return {k: v / n for k, v in sorted(d.items(),
                                            key=lambda kv: -kv[1])}

    return {"paths": mean(paths), "kinds": mean(kinds),
            "unscoped": mean(unscoped),
            "unscoped_s": sum(unscoped.values()) / n,
            "backward_s": backward / n, "total_s": total / n}


def load_device_events(trace_dir):
    """``{plane name: [[instruction, start_ns, duration_ns], ...]}`` of
    the newest ``*.xplane.pb`` under `trace_dir`: each device plane's
    "XLA Ops" line."""
    from jax.profiler import ProfileData

    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise RuntimeError(f"no *.xplane.pb under {trace_dir}")
    devices = {}
    for plane in ProfileData.from_file(found[-1]).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                devices[plane.name] = [
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events]
    if not devices:
        raise RuntimeError(
            f"no device plane with an 'XLA Ops' line in {found[-1]}")
    return devices


def device_time_by_scope(trace_dir, table=None):
    """Seconds of device time per scope path and per kind, from a
    profiler trace directory (``jax.profiler.start_trace``) and the
    step's :func:`scope_table`; with no table given, the one
    :func:`save_table` left in the directory. A stale table raises: it
    would read as "everything unscoped"."""
    if table is None:
        table = load_table(trace_dir)
    if table.get("stale"):
        raise RuntimeError(
            "the scope table is stale: the step was traced with scopes "
            "and its executable names none (loaded from a compile cache "
            "that predates them?)")
    return time_by_scope(load_device_events(trace_dir), table)


def save_table(trace_dir, table):
    """Keep a table beside a trace, for ``trace_view --scopes``."""
    path = os.path.join(trace_dir, TABLE_FILE)
    with open(path, "w") as f:
        json.dump(table, f)
    return path


def load_table(path):
    if os.path.isdir(path):
        path = os.path.join(path, TABLE_FILE)
    with open(path) as f:
        return json.load(f)


def format_by_scope(by_scope, limit=25):
    """The reduction as the text ``trace_view --scopes`` prints."""
    total = by_scope["total_s"] or 1.0
    lines = [f"device time {by_scope['total_s']:.6f} s a device; "
             f"backward {100 * by_scope['backward_s'] / total:.1f} %, "
             f"unscoped {100 * by_scope['unscoped_s'] / total:.1f} %"]
    for title, key in (("by kind (a path counts under each of its "
                        "components)", "kinds"),
                       ("by scope path", "paths"),
                       ("unscoped, by instruction", "unscoped")):
        rows = list(by_scope[key].items())
        lines.append(f"{title}:")
        for name, sec in rows[:limit]:
            lines.append(f"  {sec:12.6f} s {100 * sec / total:6.2f} %  "
                         f"{name}")
        if len(rows) > limit:
            rest = sum(sec for _n, sec in rows[limit:])
            lines.append(f"  {rest:12.6f} s {100 * rest / total:6.2f} %  "
                         f"({len(rows) - limit} more)")
    return "\n".join(lines)
