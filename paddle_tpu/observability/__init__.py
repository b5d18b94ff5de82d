"""Unified observability: span tracing, counters, step telemetry and
metric exporters.

Built on the two primitives the reference stack ships (profiler.py
``RecordEvent``/chrome-trace export ≈ `platform/profiler.cc`; monitor.py
counter registry ≈ `platform/monitor.cc` StatRegistry) and wired into
every hot path: the static Executor and the to_static compile cache,
op dispatch (sampled), collectives, the DataLoader, and the PS runtime.

Quick start::

    import paddle_tpu.observability as obs

    obs.enable()                       # spans + counters on
    ... train ...
    obs.export_chrome_trace("/tmp/trace.json")   # chrome://tracing
    print(obs.export.prometheus_text())          # scrape text
    obs.disable()

Device time gets the program's names through ``obs.scope(name)``
(``scopes.py``): the layer, op, attention and optimizer seams enter it
while a ``to_static`` step is traced, ``StaticFunction.scope_table()``
maps the compiled step's instructions to scope paths, and
``obs.device_time_by_scope(trace_dir, table)`` reduces a device profile
to seconds per scope path and per kind.

Scraping a live job: ``obs.export.start_http_server(9100)`` serves
``/metrics``; ``hapi.callbacks.TelemetryCallback`` publishes per-step
tokens/s / MFU / data-wait gauges into it.
"""
from .. import profiler as _profiler
from . import export, flight, hlo_bytes, runlog, step  # noqa: F401
from . import memory, overlap, scopes, tracing  # noqa: F401
from .hlo_bytes import collective_stats, export_collective_bytes  # noqa: F401
from .memory import state_ledger  # noqa: F401
from .overlap import export_overlap_stats, overlap_stats  # noqa: F401
from .runlog import start_run, stop_run  # noqa: F401
from .scopes import device_time_by_scope, scope, scope_table  # noqa: F401
from .step import StepTimer  # noqa: F401
from .tracing import (CATEGORIES, attach_context, count,  # noqa: F401
                      current_span, disable, enable, enabled,
                      mint_context, record_span, trace_context, trace_span)

__all__ = [
    "enable", "disable", "enabled", "trace_span", "current_span", "count",
    "CATEGORIES", "StepTimer", "export_chrome_trace",
    "collective_stats", "export_collective_bytes", "state_ledger",
    "overlap_stats", "export_overlap_stats",
    "trace_context", "attach_context", "mint_context", "record_span",
    "start_run", "stop_run",
    "scope", "scope_table", "device_time_by_scope",
    "tracing", "export", "hlo_bytes", "step", "runlog", "flight",
    "memory", "overlap", "scopes",
]


def export_chrome_trace(path):
    """Export every recorded span/event as chrome://tracing JSON (the
    profiler's exporter — spans and profiler events share one buffer)."""
    return _profiler.export_chrome_tracing(path)


def reset():
    """Clear recorded events, counters-board gauges, summary windows,
    and the program-memory attribution registry (monitor counters are
    shared state and are left alone; reset them individually)."""
    _profiler.reset()
    export.clear_gauges()
    export.clear_summaries()
    memory.clear_program_memory()
