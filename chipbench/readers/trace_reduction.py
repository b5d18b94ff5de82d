"""device_trace: reductions of the traced slice's profile (trace.py).

Work counts are functions of shapes alone (`work.py`, the family's
`flops_per_token`), the peaks come from peaks.json; nothing is clipped:
a share above 100 % means the work is counted too high or the time
leaves part of it out, and has to show."""
from chipbench import trace as trace_mod
from chipbench import work


def read(run, args):
    traced = run.get("traced")
    if not traced:
        return None
    trace = traced["trace"]
    quantity = args["quantity"]
    busy = traced["busy"]
    if quantity == "idle_share":
        return None if busy is None else 100.0 * busy["idle_share"]
    if quantity == "step_mfu":
        # the slice's steps over the device's own window (first
        # operation's start to the last one's end), not the host's clock
        if busy is None:
            return None
        cell = run["cell"]
        tokens = traced["device_steps"] * cell["batch"] * cell["seq"]
        rate = tokens / cell["chips"] / busy["window_s"]
        return 100.0 * rate * run["flops_per_token"] / run["peaks"][
            "bf16_flops_per_s"]
    if quantity in ("kernel_roofline", "kernel_step_share"):
        calls = run.get("attention_calls")
        names = run.get("kernel_names")
        if not calls or not names:
            return None
        seconds = trace_mod.seconds_of(trace, names)
        if seconds is None:
            return None
        if quantity == "kernel_step_share":
            return 100.0 * seconds / busy["busy_s"]
        least = work.attention_least_seconds(calls, run["peaks"])
        return 100.0 * least["seconds"] * calls["calls_per_step"] \
            * traced["device_steps"] / seconds
    if quantity == "collective_exposed_ms":
        exposed = trace_mod.collective_exposed_s(trace)
        if exposed is None:
            return None
        return exposed * 1e3 / traced["device_steps"]
    raise ValueError(f"trace reader knows no quantity {quantity!r}")
