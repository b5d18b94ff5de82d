"""device_trace: a scope's share of its roofline.

100 x the least time the chip could take for the work the family counts
under a scope (`chipbench/models/<family>.kernel_work(cfg, cell,
pairs_per_step)[scope]`: operations and bytes from shapes and from the
counted token-expert pairs a step alone, the same whatever implements
it), over the device time of every instruction under that scope in the
traced slice — the scope reader's join (`scope_share`: the program's
registered HLO text by instruction name, the union of the kept
intervals). The least time is the larger of operations over the peak
rate and bytes over the peak bandwidth (peaks.json). Replayed and
relayout instructions under the scope are time and not work, so the
share cannot pass 100; nothing is clipped.

`args["scopes"]` names one scope. `args["instructions"]` lists prefixes
of instruction names that belong to the scope though the compiler named
them itself: XLA:TPU rewrites a `ragged_dot` into custom calls called
`ragged-dot-none.N` whose `op_name` is that name again, the program's
scope gone (my chip-less compile for a described v5e, PR 33), and
`scope_share` counts an instruction that has an `op_name` without a
`pt.` component as unscoped. Such an instruction counts here only while
it carries no scope of its own: a roofline whose time left those calls
out would read work over less than the time it took.

None where the program keeps no registry or a stale table (as
`scope_share`), where the family counts no work for the scope, or where
nothing ran under it."""
import importlib

from chipbench import manifest
from chipbench.readers import moe_stat, scope_share


def family_work(config_name):
    """(the `kernel_work` of the configuration's family or None, the
    configuration)."""
    try:
        cfg = manifest.Manifest().config(config_name)
    except KeyError:
        return None, None
    module = importlib.import_module(f"chipbench.models.{cfg['family']}")
    return getattr(module, "kernel_work", None), cfg


def least_seconds(work, peaks):
    return max(work["flops"] / peaks["bf16_flops_per_s"],
               work["bytes"] / peaks["hbm_bytes_per_s"])


def scope_seconds(trace, paths, name, instructions=()):
    """Device seconds (the mean over devices) of the union of the
    instructions under scope `name`, and of those the compiler named
    with one of the prefixes `instructions` and left unscoped."""
    named = dict(paths)
    prefixes = tuple(instructions)
    if prefixes:
        for events in trace["devices"].values():
            for event, _start, _duration in events:
                if event.startswith(prefixes) and not named.get(event):
                    named[event] = [name]
    return scope_share.share(trace, 1.0, named, scopes=[name]) / 100.0


def read(run, args):
    traced = run.get("traced")
    if not traced or not traced.get("busy"):
        return None
    record = scope_share.registered()
    if not record or record["table"].get("stale"):
        return None
    paths = scope_share.scope_paths(record["hlo"])
    if not any(paths.values()):
        return None
    (name,) = args["scopes"]
    seconds = scope_seconds(traced["trace"], paths, name,
                            args.get("instructions", ()))
    if not seconds:
        return None
    kernel_work, cfg = family_work(run["cell"]["config"])
    if kernel_work is None:
        return None
    pairs = moe_stat.read(run, moe_stat.PAIRS_PER_STEP) or 0.0
    work = kernel_work(cfg, run["cell"], pairs).get(name)
    if not work or not work["flops"]:
        return None
    return (100.0 * least_seconds(work, run["peaks"])
            * traced["device_steps"] / seconds)
