"""program_span: the benchmark's own host spans around the calls into
the program (`window.drive`), and the first call's wall time."""
import statistics


def read(run, args):
    if args["span"] == "first_call":
        return run["first_call_s"]
    samples = run["window"]["spans"].get(args["span"])
    if not samples:
        return None
    return statistics.median(samples) * args.get("scale", 1.0)
