"""device_trace: the share of busy time spent under the program's scopes.

The program names its device work (`paddle_tpu.observability.scopes`:
`pt.<name>` components in each instruction's `op_name` metadata) and
keeps the newest compiled step's HLO text in its registry
(`observability.memory.program_scopes()`, filled when `run.py` asks for
`step.hlo_text()`). This reader parses that text with its own regex,
joins it with the traced slice's events by instruction name, and returns
100 x the time of the instructions whose scope path has a component in
`args["scopes"]` over the slice's busy time (`trace.busy`, the
denominator of `attention.step_share`); with `args["unscoped"]`, of the
instructions that carry no scope at all. An operation the compiler
rewrote without keeping its op_name (a fusion, a collective) counts
under its operand's scope. Containers and `-start` halves
are left out by the rule of `trace.work_intervals`, and a kind's time is
the union of its intervals, so no share passes 100.

None where the program keeps no such registry (a commit before it had
scopes), where nothing was registered, or where the table is flagged
`stale` (an executable from a compile cache that predates the scopes
names nothing: that is not "everything unscoped")."""
import re

from chipbench import trace as trace_mod

LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$", re.M)
OP_NAME = re.compile(r'\bop_name="([^";]*)')  # of ';'-joined names, the first
OPCODE = re.compile(r"(?<![\w.\-])([a-z][a-z0-9\-]*)\(\s*(?:%([\w.\-]+))?")
COMPONENT = re.compile(r"(?:^|[/(])pt\.([A-Za-z0-9_.]+)")
# operations the compiler does not make up: one without any op_name is a
# rewrite that dropped it (XLA:TPU's reduce-scatter fusion)
REWRITTEN = ("fusion", "dot", "convolution", "custom-call", "reduce",
             "all-gather", "all-reduce", "all-to-all", "collective-permute")


def scope_paths(hlo_text):
    """{instruction name: [scope components]} for every instruction of
    the text that carries an op_name (an empty list: no scope in it),
    and for a REWRITTEN one that carries none, the components of the
    nearest operand that has one."""
    paths, operand, rewritten = {}, {}, []
    for name, rest in LINE.findall(hlo_text):
        op_name = OP_NAME.search(rest)
        if op_name:
            paths[name] = COMPONENT.findall(op_name.group(1))
            continue
        opcode = OPCODE.search(rest)
        if opcode and opcode.group(2):
            operand[name] = opcode.group(2)
            if opcode.group(1).startswith(REWRITTEN):
                rewritten.append(name)
    for name in rewritten:
        source = operand[name]
        for _hop in range(4):  # through the compiler's own copies
            if source in paths or source not in operand:
                break
            source = operand[source]
        if paths.get(source):
            paths[name] = paths[source]
    return paths


def registered():
    """The program's newest {"table", "hlo"} record, or None."""
    try:
        from paddle_tpu.observability import memory
    except ImportError:
        return None
    getter = getattr(memory, "program_scopes", None)
    return getter() if getter is not None else None


def share(trace, busy_s, paths, scopes=(), unscoped=False):
    wanted = set(scopes)

    def keep(name):
        components = paths.get(name, ())
        if unscoped:
            return not components
        return not wanted.isdisjoint(components)

    per_device = [
        trace_mod.covered(trace_mod.union(
            trace_mod.work_intervals(events, keep))) / 1e9
        for events in trace["devices"].values()]
    return 100.0 * sum(per_device) / len(per_device) / busy_s


def read(run, args):
    traced = run.get("traced")
    if not traced or not traced.get("busy"):
        return None
    record = registered()
    if not record or record["table"].get("stale"):
        return None
    paths = scope_paths(record["hlo"])
    if not any(paths.values()):
        return None
    return share(traced["trace"], traced["busy"]["busy_s"], paths,
                 scopes=args.get("scopes", ()),
                 unscoped=bool(args.get("unscoped")))
