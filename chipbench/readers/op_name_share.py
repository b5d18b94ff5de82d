"""device_trace: the share of busy time spent in instructions whose
`op_name` carries a mark.

The scope reader's join (`scope_share`: the program's registered HLO
text by instruction name, the traced slice's events, the union of the
kept intervals over `trace.busy`'s busy time), keyed on the whole
`op_name` and not on its `pt.` components: jax writes
`rematted_computation` into the name of every operation it replays in a
backward pass under `jax.checkpoint`, which is how
`recompute.replay_share` finds the recomputed forward.

None where the program keeps no registry or a stale table, as
`scope_share`, and where no instruction carries any of `args["marks"]`
(a program without the construct: nothing to read, not a share of 0)."""
from chipbench import trace as trace_mod
from chipbench.readers import scope_share


def marked(hlo_text, marks):
    """The names of the instructions whose op_name has one of `marks`."""
    names = set()
    for name, rest in scope_share.LINE.findall(hlo_text):
        op_name = scope_share.OP_NAME.search(rest)
        if op_name and any(m in op_name.group(1) for m in marks):
            names.add(name)
    return names


def read(run, args):
    traced = run.get("traced")
    if not traced or not traced.get("busy"):
        return None
    record = scope_share.registered()
    if not record or record["table"].get("stale"):
        return None
    names = marked(record["hlo"], args["marks"])
    if not names:
        return None
    per_device = [
        trace_mod.covered(trace_mod.union(
            trace_mod.work_intervals(events, names.__contains__))) / 1e9
        for events in traced["trace"]["devices"].values()]
    return (100.0 * sum(per_device) / len(per_device)
            / traced["busy"]["busy_s"])
