"""program_counter: what the program's routed-expert layers counted on
the device.

The layers keep their counts in device buffers inside the compiled step
and the program offers one call, `paddle_tpu.incubate.moe.
routing_stats()`, that fetches them (one `device_get`, after the window
and the traced slice have closed) and writes the totals to
`paddle_tpu.monitor`. This reader makes that call in the process that
ran the cell and then reads as `program_stat` does: `args["counter"]`
over `args["per"]`, times `args["scale"]`, and times the counter
`args["times"]` where given (a mean over layer applications times
`jit_moe_layers`, the layers the step's trace staged: a step's total).

None where the program has no such call (a commit before it had the
layer) or no live layer counted anything."""
from chipbench.readers import program_stat

# the counted token-expert pairs of one step (`moe.pairs_per_step`, and
# the work `scope_roofline` counts for the experts)
PAIRS_PER_STEP = {"counter": "moe_routed_pairs", "per": "moe_steps",
                  "times": "jit_moe_layers"}


def routing_stats():
    """The program's totals, or None where it keeps none."""
    try:
        from paddle_tpu.incubate import moe
    except ImportError:
        return None
    fetch = getattr(moe, "routing_stats", None)
    return fetch() if fetch is not None else None


def read(run, args):
    if not routing_stats():
        return None
    value = program_stat.read(run, args)
    if value is None or "times" not in args:
        return value
    from paddle_tpu import monitor

    return value * monitor.stat_get(args["times"]) or None
