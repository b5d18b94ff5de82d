"""Merge multi-rank/multi-process run-logs into one chrome-trace.

Every process in a run (trainer ranks, the PS server, a serving
replica) writes its own JSONL run-log (``observability/runlog.py``).
This tool merges any number of them into a single ``chrome://tracing``
/ Perfetto JSON file:

- each (file, process-tag) pair becomes a chrome *process* track,
  labeled from its manifest (``run_id`` / ``rank`` / ``pid``);
- clocks are aligned via each manifest's (wall, monotonic) anchor pair,
  so logs from processes — or hosts — with different monotonic bases
  land on one wall-clock timeline;
- spans keep their (trace, span, parent) ids in ``args``; span *links*
  (a serving batch serving N request traces) become chrome flow events
  (``ph: s/f``), so clicking a request's arrow lands on the batch and
  device step that served it;
- discrete events (checkpoint publishes, PS retries, fault injections,
  step stats) render as instant events on their process track.

Usage:
    python tools/trace_view.py RUNLOG.jsonl [...] -o trace.json
    python tools/trace_view.py logs/*.jsonl --trace <16-hex-trace-id>
    python tools/trace_view.py logs/*.jsonl --stats
    python tools/trace_view.py --scopes TRACE_DIR [--table TABLE.json|HLO.txt]

``--trace`` restricts the output to one trace id plus everything
reachable from it through parent edges and links — the "show me this
p99 request" view. ``--stats`` prints a per-trace/per-process summary
instead of writing a file. ``--scopes`` reads a device profile
(``jax.profiler.start_trace``) instead of run-logs and prints its device
time per scope path and per kind (``observability.scopes``).

The module doubles as a library: ``load_events``, ``build_chrome_trace``
and ``connected_spans`` are importable (the test suite reconstructs
cross-process traces through them).
"""
import argparse
import collections
import json
import os
import re
import sys

# rotated run-log parts (<base>.partN.jsonl, observability/runlog.py
# max_bytes rolling) merge back onto their base file's process track
_PART_RE = re.compile(r"\.part\d+(\.jsonl)?$")


def _base_file(path):
    if path.endswith(".jsonl"):
        return _PART_RE.sub(r"\1", path)
    return _PART_RE.sub("", path)


def load_events(paths):
    """Read run-log files into a flat event list; each event is tagged
    ``_file`` (source path, with rotation parts folded onto their base
    file so a rolled log stays ONE process track) and ``_offset_ns``
    (monotonic->wall clock offset from its file's manifest, 0 when
    absent). Unparseable lines (the torn last line of a crashed writer)
    are skipped, counted in the returned ``(events, n_bad)``."""
    events, n_bad = [], 0
    for path in paths:
        offset = 0
        tag = _base_file(path)
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    n_bad += 1
                    continue
                if rec.get("kind") == "manifest":
                    try:
                        offset = int(rec["time"] * 1e9) - int(rec["mono_ns"])
                    except (KeyError, TypeError):
                        offset = 0
                rec["_file"] = tag
                rec["_offset_ns"] = offset
                events.append(rec)
    return events, n_bad


def _span_key(rec):
    return (rec.get("trace"), rec.get("span"))


def spans_by_id(events):
    """{(trace, span): span-record} over all loaded span events."""
    return {_span_key(r): r for r in events if r.get("kind") == "span"}


def _links_of(rec):
    """Linked (trace, span) keys of a span record (from the ``links``
    attr: a list of "trace:span" hex strings)."""
    out = []
    for ln in (rec.get("attrs") or {}).get("links", []) or []:
        parts = str(ln).split(":")
        if len(parts) == 2:
            out.append((parts[0], parts[1]))
    return out


def connected_spans(events, trace_id):
    """Every span reachable from ``trace_id``: same-trace spans, plus
    spans connected through links (in either direction), transitively —
    the full cross-process story of one request/push/save. Returns span
    records sorted by start time."""
    spans = [r for r in events if r.get("kind") == "span"]
    by_trace = collections.defaultdict(list)
    link_edges = collections.defaultdict(set)  # trace -> linked traces
    for r in spans:
        by_trace[r["trace"]].append(r)
        for (lt, _ls) in _links_of(r):
            link_edges[r["trace"]].add(lt)
            link_edges[lt].add(r["trace"])
    seen, frontier = set(), [str(trace_id)]
    while frontier:
        t = frontier.pop()
        if t in seen:
            continue
        seen.add(t)
        frontier.extend(link_edges.get(t, ()))
    out = [r for t in seen for r in by_trace.get(t, [])]
    return sorted(out, key=lambda r: r.get("t0", 0))


def _proc_label(manifest):
    if manifest is None:
        return "unknown"
    bits = [str(manifest.get("run_id") or "run"),
            f"rank{manifest.get('rank', '?')}",
            f"pid{manifest.get('pid', '?')}"]
    if manifest.get("process") and manifest["process"] != "main":
        bits.append(manifest["process"])
    return "/".join(bits)


def build_chrome_trace(events, trace_filter=None):
    """Chrome-trace dict (``{"traceEvents": [...]}``) from loaded
    run-log events. ``trace_filter`` keeps only spans connected to that
    trace id (events/instants always pass)."""
    keep = None
    if trace_filter is not None:
        keep = {_span_key(r) for r in connected_spans(events, trace_filter)}

    # one chrome pid per (file, process tag); manifests name them
    pids = {}
    manifests = {}
    out = []

    def _pid(rec):
        key = (rec["_file"], rec.get("process") or "main")
        if key not in pids:
            pids[key] = len(pids) + 1
            m = manifests.get(rec["_file"])
            label = _proc_label(m)
            if rec.get("process") and rec["process"] != "main":
                label += f"/{rec['process']}"
            out.append({"name": "process_name", "ph": "M", "pid": pids[key],
                        "args": {"name": label}})
        return pids[key]

    for rec in events:
        kind = rec.get("kind")
        if kind == "manifest":
            manifests[rec["_file"]] = rec
            continue
    flow_id = [0]

    span_index = spans_by_id(events)
    for rec in events:
        kind = rec.get("kind")
        if kind == "span":
            if keep is not None and _span_key(rec) not in keep:
                continue
            pid = _pid(rec)
            ts_us = (rec["t0"] + rec["_offset_ns"]) / 1e3
            args = {"trace": rec.get("trace"), "span": rec.get("span")}
            if rec.get("parent"):
                args["parent"] = rec["parent"]
            args.update(rec.get("attrs") or {})
            ev = {"name": rec["name"], "cat": rec.get("cat", "user"),
                  "ph": "X", "ts": ts_us, "dur": max(rec["dur"], 1) / 1e3,
                  "pid": pid, "tid": rec.get("tid", 0), "args": args}
            out.append(ev)
            # links -> chrome flow arrows (start at this span, finish at
            # the linked span), connecting traces across processes
            for lk in _links_of(rec):
                target = span_index.get(lk)
                if target is None or (keep is not None
                                      and lk not in keep):
                    continue
                flow_id[0] += 1
                fid = flow_id[0]
                out.append({"name": "link", "cat": "link", "ph": "s",
                            "id": fid, "pid": pid,
                            "tid": rec.get("tid", 0), "ts": ts_us})
                out.append({"name": "link", "cat": "link", "ph": "f",
                            "bp": "e", "id": fid, "pid": _pid(target),
                            "tid": target.get("tid", 0),
                            "ts": (target["t0"] + target["_offset_ns"])
                            / 1e3})
        elif kind == "event":
            pid = _pid(rec)
            out.append({"name": rec.get("event", "event"), "cat": "event",
                        "ph": "i", "s": "p", "pid": pid, "tid": 0,
                        "ts": (rec.get("t", 0) + rec["_offset_ns"]) / 1e3,
                        "args": {k: v for k, v in rec.items()
                                 if not k.startswith("_")
                                 and k not in ("kind", "t")}})
    return {"traceEvents": out}


def state_residency(events):
    """Pod-wide state residency from the LAST ``memory_snapshot`` event
    of each process log: ``(per_category_bytes, n_ranks)``. Each rank's
    ledger (``memory.runlog_snapshot``, rank-tagged) counts what THAT
    process holds; summing the latest snapshot per rank is the
    multi-host total a single-process scrape can't see."""
    last = {}
    for r in events:
        if r.get("kind") == "event" and r.get("event") == "memory_snapshot":
            key = (r["_file"], r.get("rank", r.get("process", "0")))
            if key not in last or r.get("t", 0) >= last[key].get("t", 0):
                last[key] = r
    cats = collections.Counter()
    for r in last.values():
        for cat, slot in ((r.get("state") or {}).get("categories")
                          or {}).items():
            cats[cat] += int(slot.get("bytes", 0))
    return dict(cats), len(last)


def reform_timeline(events):
    """The pod's generation history from ``pod_reform`` run-log events:
    one entry per generation (every rank logs the same transition —
    grouped by gen, earliest wall time wins), ordered by generation.
    Each entry: ``{"gen", "direction", "old_world", "new_world", "t"
    (wall seconds), "took_s" (slowest rank's reform wall time)}`` — the
    kill→shrink→heal→grow lifecycle as a timeline."""
    by_gen = {}
    for r in events:
        if r.get("kind") != "event" or r.get("event") != "pod_reform":
            continue
        gen = r.get("gen")
        wall = (r.get("t", 0) + r["_offset_ns"]) / 1e9
        cur = by_gen.setdefault(gen, {
            "gen": gen, "direction": r.get("direction"),
            "old_world": r.get("old_world"), "new_world": r.get("new_world",
                                                               r.get("world")),
            "t": wall, "took_s": r.get("took_s", 0) or 0})
        cur["t"] = min(cur["t"], wall)
        cur["took_s"] = max(cur["took_s"], r.get("took_s", 0) or 0)
        if cur.get("direction") is None:
            cur["direction"] = r.get("direction")
    return [by_gen[g] for g in sorted(by_gen, key=lambda g: (g is None, g))]


def print_stats(events, n_bad, file=None):
    file = file if file is not None else sys.stdout
    spans = [r for r in events if r.get("kind") == "span"]
    evs = [r for r in events if r.get("kind") == "event"]
    manifests = [r for r in events if r.get("kind") == "manifest"]
    traces = collections.Counter(r["trace"] for r in spans)
    print(f"{len(manifests)} process log(s), {len(spans)} spans, "
          f"{len(evs)} events, {len(traces)} traces"
          + (f", {n_bad} unparseable line(s)" if n_bad else ""),
          file=file)
    for m in manifests:
        print(f"  {_proc_label(m)}  <- {os.path.basename(m['_file'])}",
              file=file)
    by_event = collections.Counter(r.get("event") for r in evs)
    if by_event:
        print("  events: " + ", ".join(f"{k}={v}" for k, v in
                                       sorted(by_event.items())),
              file=file)
    cats, n_ranks = state_residency(events)
    if cats:
        total = sum(cats.values())
        print(f"  state residency (last snapshot per rank, summed over "
              f"{n_ranks} rank(s), {total / 1e6:.3f} MB): "
              + ", ".join(f"{c}={b / 1e6:.3f}MB"
                          for c, b in sorted(cats.items(),
                                             key=lambda kv: -kv[1])),
              file=file)
    timeline = reform_timeline(events)
    if timeline:
        t0 = min(e["t"] for e in timeline)
        print("  reform timeline:", file=file)
        for e in timeline:
            worlds = (f"world {e['old_world']}->{e['new_world']}"
                      if e.get("old_world") is not None
                      else f"world {e['new_world']}")
            print(f"    gen {e['gen']}: {e.get('direction') or '?':<6} "
                  f"{worlds} at +{e['t'] - t0:.3f}s "
                  f"(reform {e['took_s']:.3f}s)", file=file)
    top = traces.most_common(5)
    if top:
        print("  largest traces: " + ", ".join(
            f"{t[:8]}…×{n}" for t, n in top), file=file)


def print_scopes(trace_dir, table_path=None, file=sys.stdout):
    """Device time of a profiler trace by the program's scopes."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from paddle_tpu.observability import scopes
    table = None
    if table_path and not table_path.endswith(".json"):
        with open(table_path) as f:  # a compiled step's HLO text
            table = scopes.scope_table(f.read())
    elif table_path:
        table = scopes.load_table(table_path)
    print(scopes.format_by_scope(
        scopes.device_time_by_scope(trace_dir, table)), file=file)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="merge run-log JSONL files into one chrome-trace")
    ap.add_argument("logs", nargs="*", help="run-log .jsonl files")
    ap.add_argument("--scopes", metavar="TRACE_DIR",
                    help="print a profiler trace directory's device time "
                    "per scope path and per kind, by the scope table "
                    "saved beside it (observability.scopes.save_table) "
                    "or given with --table")
    ap.add_argument("--table", help="with --scopes: a scope table's JSON "
                    "file, or a compiled step's HLO text to derive it from")
    ap.add_argument("-o", "--out", default="trace.json",
                    help="chrome-trace output path (default trace.json)")
    ap.add_argument("--trace", help="restrict to one trace id (16-hex) "
                    "plus everything linked to it")
    ap.add_argument("--stats", action="store_true",
                    help="print a summary instead of writing the trace")
    args = ap.parse_args(argv)

    if args.scopes:
        print_scopes(args.scopes, args.table)
        return 0
    if not args.logs:
        ap.error("give run-log files, or --scopes TRACE_DIR")
    events, n_bad = load_events(args.logs)
    if args.stats:
        print_stats(events, n_bad)
        return 0
    trace = build_chrome_trace(events, trace_filter=args.trace)
    with open(args.out, "w") as f:
        json.dump(trace, f)
    n_spans = sum(1 for e in trace["traceEvents"] if e.get("ph") == "X")
    print(f"wrote {args.out}: {n_spans} spans from {len(args.logs)} "
          f"log(s)" + (f" ({n_bad} unparseable line(s) skipped)"
                       if n_bad else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())
