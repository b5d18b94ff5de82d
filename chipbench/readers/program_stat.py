"""program_counter: the program's own always-on counters
(`paddle_tpu.monitor`), read in the process that ran the cell.

`args["counter"]` is read as it stands, divided by the counter
`args["per"]` where given (nanoseconds of a phase per step call), times
`args["scale"]`. None where the counter, or the one it is divided by,
reads 0: a commit that does not keep it."""


def read(run, args):
    from paddle_tpu import monitor

    value = float(monitor.stat_get(args["counter"]))
    if not value:
        return None
    if args.get("per"):
        per = monitor.stat_get(args["per"])
        if not per:
            return None
        value /= per
    return value * args.get("scale", 1.0)
