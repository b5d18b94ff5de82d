"""program_counter: a counter of the program's that may honestly read 0
(cached calls under which jax made a program), read in the process that
ran the cell. The readers run when a traced run ends, after the profile
has been parsed in this process: a counter that goes on counting after
the window (the collector's `host_gc_*{during="steady"}`) is not the
window's here, and no metric file gives one.

As `program_stat` reads `args["counter"]` times `args["scale"]`. But 0
is a reading here and not an absence, so a second counter,
`args["witness"]`, says whether the program keeps the first at all: one
that the same code writes and that cannot read 0 once the cell has run.
0.0 where the counter reads 0 and the witness does not; None where both
read 0 (a commit without the counters)."""


def read(run, args):
    from paddle_tpu import monitor

    value = float(monitor.stat_get(args["counter"]))
    if not value and not monitor.stat_get(args["witness"]):
        return None
    return value * args.get("scale", 1.0)
