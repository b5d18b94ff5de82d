"""From a profiler trace (xplane) to numbers, with jax alone.

`load()` reads the newest `*.xplane.pb` under a directory through
`jax.profiler.ProfileData` into a plain structure:

    {"devices": {plane name: [[op name, start_ns, duration_ns], ...]},
     "host": [[annotation name, start_ns, duration_ns], ...]}

`devices` holds each TPU plane's "XLA Ops" line; `host` the
`chipbench/*` annotations of the host threads. Every reduction below
works on that structure, so the tests can run them on a small recorded
trace kept as JSON (testdata/) and on hand-made ones.
"""
import glob
import os
import re

OPS_LINE = "XLA Ops"
HOST_PREFIX = "chipbench/"
# ops that only contain other ops: counting them would make the device
# busy for as long as its loop runs
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")


def op_name(event_name):
    """'%fusion.12 = bf16[..] fusion(...)' -> 'fusion.12'."""
    return event_name.split(" = ")[0].strip().lstrip("%")


def base_name(name):
    """'fusion.12' -> 'fusion'."""
    return re.sub(r"[.\d]+$", "", name)


def load(trace_dir):
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise RuntimeError(f"no *.xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in data.planes:
        is_device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if is_device and line.name == OPS_LINE:
                devices[plane.name] = [
                    [op_name(e.name), float(e.start_ns),
                     float(e.duration_ns)] for e in line.events]
            elif not is_device:
                host.extend([e.name, float(e.start_ns),
                             float(e.duration_ns)] for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    if not devices:
        raise RuntimeError(
            f"no TPU plane with an {OPS_LINE!r} line in {paths[-1]}: "
            f"planes {[p.name for p in data.planes]}")
    return {"devices": devices, "host": host}


def is_container(name):
    return base_name(name) in CONTAINERS


def is_collective(name):
    return base_name(name).startswith(COLLECTIVES)


def is_async_start(name):
    return base_name(name).endswith("-start")


def union(intervals):
    """Sorted, merged [start, end] intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def covered(merged):
    return sum(end - start for start, end in merged)


def work_intervals(events, keep=lambda name: True):
    return [(s, s + d) for name, s, d in events
            if not is_container(name) and not is_async_start(name)
            and keep(name)]


def busy(trace):
    """{"busy_s", "window_s", "idle_share"} of the traced window. A
    device's window runs from its first operation's start to its last
    one's end; busy is the union of its operations' intervals. busy_s
    and window_s are means over the devices, the idle share is the
    worst device's."""
    busy_s, window_s, idle = [], [], []
    for events in trace["devices"].values():
        merged = union(work_intervals(events))
        if not merged:
            continue
        span = merged[-1][1] - merged[0][0]
        busy_s.append(covered(merged) / 1e9)
        window_s.append(span / 1e9)
        idle.append(1.0 - covered(merged) / span)
    if not busy_s:
        return None
    n = len(busy_s)
    return {"busy_s": sum(busy_s) / n, "window_s": sum(window_s) / n,
            "idle_share": max(idle)}


def seconds_of(trace, names):
    """Summed duration of the named operations, the mean over devices;
    None where none of them ran."""
    names = set(names)
    per_device = [sum(d for name, _s, d in events if name in names) / 1e9
                  for events in trace["devices"].values()]
    total = sum(per_device)
    return total / len(per_device) if total > 0 else None


def collective_exposed_s(trace):
    """Per device, the time inside collective operations (a `-start`
    only launches; its `-done` waits) during which no other operation
    runs there; the worst device's. None where no collective ran."""
    worst = None
    for events in trace["devices"].values():
        coll = union(work_intervals(events, is_collective))
        if not coll:
            continue
        compute = union(work_intervals(
            events, lambda name: not is_collective(name)))
        hidden, j = 0.0, 0
        for start, end in coll:
            while j < len(compute) and compute[j][1] <= start:
                j += 1
            i = j
            while i < len(compute) and compute[i][0] < end:
                hidden += min(end, compute[i][1]) - max(start, compute[i][0])
                i += 1
        exposed = (covered(coll) - hidden) / 1e9
        worst = exposed if worst is None else max(worst, exposed)
    return worst


def top_ops(trace, limit=10):
    """[[base name, seconds]], the device operations that took most
    time (mean over devices)."""
    totals = {}
    for events in trace["devices"].values():
        for name, _s, d in events:
            if not is_container(name) and not is_async_start(name):
                key = base_name(name)
                totals[key] = totals.get(key, 0.0) + d / 1e9
    n = max(1, len(trace["devices"]))
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:limit]
    return [[name, seconds / n] for name, seconds in ranked]


def idle_gaps(trace, limit=10):
    """[[what the host was doing, seconds]]: the first device's longest
    idle gaps, each named after the host annotation that covers most of
    it (`chipbench/batch`, `/dispatch`, `/read`), or `host:other`."""
    events = next(iter(trace["devices"].values()))
    merged = union(work_intervals(events))
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(merged, merged[1:])), reverse=True)
    out = []
    for length, start, end in gaps[:limit]:
        best, most = "host:other", 0.0
        for name, s, d in trace["host"]:
            overlap = min(end, s + d) - max(start, s)
            if overlap > most:
                best, most = name, overlap
        out.append([best, length / 1e9])
    return out


def custom_call_names(hlo_text, target="tpu_custom_call"):
    """The names of the compiled program's instructions that call a
    Mosaic kernel."""
    pattern = re.compile(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*custom-call\(.*"
        r'custom_call_target="' + re.escape(target) + '"', re.M)
    return sorted(set(pattern.findall(hlo_text)))
