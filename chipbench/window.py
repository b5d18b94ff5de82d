"""The measured window: what a training loop that logs its loss does.

One call is kept in flight: the loop makes the next batch, dispatches
call i+1, then waits for call i's losses. A step counts when its loss
has been read on the host and is finite. Host spans (batch, dispatch,
read) are taken with the host clock and also written into the
profiler's trace as `TraceAnnotation`s, so idle gaps on the device can
be attributed to what the host was doing."""
import math
import time

import numpy as np

SPANS = ("batch", "dispatch", "read")


def drive(step, feed, first_call, seconds=None, calls=None):
    """Drive `step(*feed(i))` from call `first_call` on until `seconds`
    have passed at a completion (or `calls` calls completed). Returns
    {"t_start", "t_end", "done": [completion times], "losses": [[k
    floats] a call], "spans": {name: [seconds a call]}, "next_call"}.
    The call still in flight at the end is waited for and not counted.
    """
    import jax

    spans = {name: [] for name in SPANS}

    def dispatch(i):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench/batch"):
            args = feed(i)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench/dispatch"):
            out = step(*args)
        t2 = time.perf_counter()
        spans["batch"].append(t1 - t0)
        spans["dispatch"].append(t2 - t1)
        return out

    done, losses = [], []
    i = first_call
    t_start = time.perf_counter()
    pending = dispatch(i)
    while True:
        i += 1
        following = dispatch(i)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench/read"):
            values = np.asarray(pending.numpy(), np.float64).ravel()
        now = time.perf_counter()
        spans["read"].append(now - t0)
        done.append(now)
        losses.append(values.tolist())
        pending = following
        if seconds is not None and now - t_start >= seconds:
            break
        if calls is not None and len(done) >= calls:
            break
    jax.block_until_ready(pending._value)
    return {"t_start": t_start, "t_end": done[-1], "done": done,
            "losses": losses, "spans": spans, "next_call": i + 1}


def summarize(record, k, tokens_per_step, chips):
    """Counts and whole-window rates of one `drive` record."""
    flat = [x for call in record["losses"] for x in call]
    failed = sum(not math.isfinite(x) for x in flat)
    elapsed = record["t_end"] - record["t_start"]
    ends = [record["t_start"]] + record["done"]
    # the first interval includes the pipeline's fill (no call was in
    # flight before it), so the tail is over the intervals after it
    gaps = [(b - a) / k for a, b in zip(ends[1:], ends[2:])]
    return {"attempted": len(flat), "failed": failed,
            "elapsed_s": elapsed,
            "tokens_per_s_chip": (len(flat) - failed) * tokens_per_step
            / elapsed / chips,
            "step_s": gaps}
