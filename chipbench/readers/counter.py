"""program_counter: counts, read as they are.

`window_compiles`: backend-compile events (jax.monitoring; a load from
the persistent cache fires it too) between the window's first dispatch
and its last completion — 0 is the only healthy value.
`collective_mb`: payload of the compiled step's collectives a step,
from the program's own `collective_stats(per_execution=True)`."""


def read(run, args):
    counter = args["counter"]
    if counter == "window_compiles":
        return float(run["window_compiles"])
    if counter == "collective_mb":
        stats = run.get("collective_stats")
        if not stats:
            return None
        total = sum(rec.get("bytes", 0) for rec in stats)
        return total / run["k"] / 1e6 if total else None
    raise ValueError(f"counter reader knows no counter {counter!r}")
