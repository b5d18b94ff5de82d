"""host_clock: the end-to-end readings the harness takes itself, over
all the work and all the time of the window."""
import numpy as np

MIN_SPAN_S = 0.25


def read(run, args):
    quantity = args["quantity"]
    if quantity == "setup_s":
        return run["setup_s"]
    window = run["window"]
    if quantity == "tokens_per_s_chip":
        return window["tokens_per_s_chip"]
    if quantity == "step_ms_p90":
        # every between-completions interval of the window, over k. The
        # host's clock is off by some half a millisecond, so a reading
        # spans MIN_SPAN_S or more: where a call is shorter, as many
        # consecutive calls as reach it (every such run of calls, sliding)
        steps = np.asarray(window["step_s"]) * run["k"]
        if len(steps) < 10:
            return None
        n = max(1, int(np.ceil(MIN_SPAN_S / np.median(steps))))
        spans = np.convolve(steps, np.ones(n), mode="valid") / (n * run["k"])
        return float(np.percentile(spans, 90)) * 1e3
    raise ValueError(f"window reader knows no quantity {quantity!r}")
