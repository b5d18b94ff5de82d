"""Span tracing over the profiler/monitor primitives.

The profiler (profiler.py) gives RAII host events + a chrome-trace
exporter; the monitor (monitor.py) gives the shared counter registry.
This module is the unified emission API the runtime instruments against:

- ``trace_span(name, cat, **attrs)`` — lightweight context-managed span
  with a thread-local span stack. When tracing is disabled (the default)
  it returns a shared no-op span: the hot-path cost is one list read and
  one set lookup, no allocation (the reference's analog is the
  ``RecordEvent`` guard on ``FLAGS_enable_host_event_recorder_hook``).
- **trace context** (Dapper-style): every recorded span carries a
  ``(trace_id, span_id, parent_id)`` triple. Nested spans inherit the
  trace and parent from the thread-local stack; a root span mints a new
  trace id. ``trace_context()`` reads the current (trace, span) pair for
  wire propagation; ``attach_context(trace, parent)`` adopts a remote
  parent on this thread (a batcher worker serving a request, a server
  handling an RPC); ``mint_context()`` reserves ids for a span that will
  be recorded retrospectively via ``record_span(..., span_id=...)``.
- ``count(name, value)`` — guarded counter into the monitor registry.
- per-category toggles: every instrumented subsystem emits under one of
  ``CATEGORIES``; ``enable(categories=[...])`` turns on a subset.
  ``dispatch`` (per-op spans through the core.dispatch observer seam) is
  OFF by default even under ``enable()`` — it is sampled, and still the
  only category with per-op cost.
- jax's own steps of making a program (trace, lowering, backend compile
  or cache load) are ``jax/<leaf>`` spans of category ``jit`` — the
  compile-cache visibility the CUPTI timeline gave the reference's
  device side. The program's one ``jax.monitoring`` mirror opens them
  (``jit/compile_cache.py``), beside the always-on counters of the same
  events; this module listens to nothing.
- every ``Span`` is also a ``jax.profiler.TraceAnnotation("pt/<name>")``
  for its lifetime: whenever anyone captures a device trace, the
  program's spans are in the same xplane on the same clock
  (:func:`epoch_offset_ns` maps a span's ``t0`` onto it). Device-side
  names are ``observability.scopes``' part.

Completed spans fan out to three sinks: the profiler event buffer (the
chrome-trace exporter), the flight-recorder ring (``flight.py`` — crash
evidence), and, when a run-log is active, the per-run JSONL stream
(``runlog.py`` — the multi-process merge source for
``tools/trace_view.py``).
"""
import random
import threading
import time

from jax.profiler import TraceAnnotation

from .. import monitor, profiler
from . import flight, runlog

__all__ = ["enable", "disable", "enabled", "trace_span", "begin_span",
           "current_span",
           "count", "now_ns", "epoch_offset_ns", "CATEGORIES",
           "DEFAULT_CATEGORIES",
           "trace_context", "attach_context", "mint_context",
           "record_span"]

# every instrumented subsystem; "dispatch" is opt-in (sampled per-op spans)
CATEGORIES = ("executor", "jit", "dataloader", "collective", "ps",
              "dispatch", "step", "serving", "checkpoint", "user")
DEFAULT_CATEGORIES = frozenset(c for c in CATEGORIES if c != "dispatch")

_enabled_cats = [None]  # None = disabled; frozenset of categories otherwise


class _SpanStack(threading.local):
    def __init__(self):
        self.stack = []
        self.remote = None  # (trace_id, parent_span_id) adopted via
        # attach_context — the cross-process/thread parent for root spans
        # opened on this thread
        self.rng = None


_tls = _SpanStack()


def _new_id():
    """64-bit span/trace id. Per-thread RNG (random.Random instances are
    not thread-safe) seeded from SystemRandom so concurrent processes
    and restarts never collide."""
    rng = _tls.rng
    if rng is None:
        rng = _tls.rng = random.Random(
            random.SystemRandom().getrandbits(64))
    return rng.getrandbits(64) or 1  # 0 is the "no id" sentinel


def now_ns():
    return profiler._now_ns()


ANNOTATION_PREFIX = "pt/"


def epoch_offset_ns():
    """What to add to a span-clock time (:func:`now_ns`, monotonic) to
    get nanoseconds since the epoch — the clock of a profiler trace,
    whose event times count from its ``profile_start_time``. Read it
    near the spans it is for: the two clocks drift apart by NTP's
    slew."""
    best = None
    for _ in range(3):
        before = profiler._now_ns()
        wall = time.time_ns()
        after = profiler._now_ns()
        if best is None or after - before < best[0]:
            best = (after - before, wall - (before + after) // 2)
    return best[1]


def trace_context():
    """The current (trace_id, span_id) pair on this thread — what a
    client piggybacks on an outgoing RPC — or None outside any span
    (an adopted remote context counts: it returns (trace, parent))."""
    stack = _tls.stack
    if stack:
        s = stack[-1]
        return (s.trace_id, s.span_id)
    return _tls.remote


def mint_context():
    """Reserve ids for a span recorded retrospectively (a serving
    request whose duration is only known at resolve time). Returns
    ``(trace_id, span_id, parent_id)``: a child of the current span
    when one is active, else a new root trace."""
    ctx = trace_context()
    if ctx is not None:
        return (ctx[0], _new_id(), ctx[1])
    return (_new_id(), _new_id(), 0)


class attach_context:
    """Adopt a remote parent on this thread: spans opened inside become
    children of ``(trace_id, parent_id)`` instead of starting new
    traces — the receive side of wire propagation.

    >>> with tracing.attach_context(*request_ctx[:2]):
    ...     with trace_span("serve", cat="serving"): ...
    """

    def __init__(self, trace_id, parent_id):
        self._ctx = (int(trace_id), int(parent_id))
        self._saved = None

    def __enter__(self):
        self._saved = _tls.remote
        _tls.remote = self._ctx
        return self

    def __exit__(self, *exc):
        _tls.remote = self._saved
        return False


def enabled(cat=None):
    """Fast guard: is tracing on (for `cat`)? Instrumented paths call this
    before doing any measurement work."""
    cats = _enabled_cats[0]
    if cats is None:
        return False
    return True if cat is None else cat in cats


def _emit(name, cat, t0, t1, trace_id, span_id, parent_id, attrs):
    """One completed span to every sink: profiler buffer (chrome-trace
    export), flight-recorder ring (crash evidence), active run-log
    (multi-process merge source)."""
    ids = {"trace_id": f"{trace_id:016x}", "span_id": f"{span_id:016x}"}
    if parent_id:
        ids["parent_id"] = f"{parent_id:016x}"
    if attrs:
        ids.update(attrs)
    profiler.record_span(name, cat, t0, t1, ids)
    flight.record(name, cat, t0, t1, trace_id, span_id, parent_id, attrs)
    if runlog.active() is not None:
        runlog.span(name, cat, t0, t1, trace_id, span_id, parent_id,
                    attrs)


def record_span(name, cat, t0_ns, t1_ns, trace_id=None, span_id=None,
                parent_id=None, **attrs):
    """Record a completed span retrospectively (queue-wait measured
    after the fact, a request span closed at resolve time). Missing ids
    are minted from the current thread context; pass explicit ids (from
    :func:`mint_context`) to place the span in a remote trace. Returns
    ``(trace_id, span_id)`` — no-op (returns None) when tracing or the
    category is off."""
    cats = _enabled_cats[0]
    if cats is None or cat not in cats:
        return None
    if trace_id is None:
        trace_id, span_id, parent_id = mint_context()
    elif span_id is None:
        span_id = _new_id()
    _emit(name, cat, int(t0_ns), int(t1_ns), int(trace_id), int(span_id),
          int(parent_id or 0), attrs or None)
    return (trace_id, span_id)


class Span:
    """Active span; records into the profiler event buffer (and the
    flight ring + run-log) on exit. Nesting is tracked on a thread-local
    stack (``current_span()``); the trace context (trace_id, span_id,
    parent_id) is inherited from the enclosing span, an attached remote
    context, or minted fresh for a root span."""

    __slots__ = ("name", "cat", "attrs", "_t0", "_annotation",
                 "trace_id", "span_id", "parent_id")

    def __init__(self, name, cat, attrs):
        self.name = name
        self.cat = cat
        self.attrs = attrs
        self._t0 = None
        self._annotation = None
        self.trace_id = 0
        self.span_id = 0
        self.parent_id = 0

    def set_attr(self, **kwargs):
        self.attrs.update(kwargs)
        return self

    @property
    def context(self):
        """(trace_id, span_id) — piggyback this on outgoing work."""
        return (self.trace_id, self.span_id)

    def __enter__(self):
        stack = _tls.stack
        if stack:
            top = stack[-1]
            self.trace_id, self.parent_id = top.trace_id, top.span_id
        elif _tls.remote is not None:
            self.trace_id, self.parent_id = _tls.remote
        else:
            self.trace_id, self.parent_id = _new_id(), 0
        self.span_id = _new_id()
        stack.append(self)
        # outside a profiler session a TraceMe is a flag test
        self._annotation = TraceAnnotation(ANNOTATION_PREFIX + self.name)
        self._t0 = profiler._now_ns()
        self._annotation.__enter__()
        return self

    @property
    def t0(self):
        """Start of the span on the span clock (:func:`now_ns`)."""
        return self._t0

    def __exit__(self, *exc):
        self._annotation.__exit__(None, None, None)
        end = profiler._now_ns()
        stack = _tls.stack
        if stack and stack[-1] is self:
            stack.pop()
        _emit(self.name, self.cat, self._t0, end, self.trace_id,
              self.span_id, self.parent_id, self.attrs or None)
        return False

    def end(self):
        """Close a span opened by :func:`begin_span`."""
        self.__exit__(None, None, None)


class _NullSpan:
    """Shared disabled span — no state, no allocation per use."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_attr(self, **kwargs):
        return self

    def end(self):
        pass


NULL_SPAN = _NullSpan()


def trace_span(name, cat="user", **attrs):
    """Open a span: ``with trace_span("executor/run", cat="executor"): ...``.
    Returns the shared no-op span when tracing (or `cat`) is disabled."""
    cats = _enabled_cats[0]
    if cats is None or cat not in cats:
        return NULL_SPAN
    return Span(name, cat, attrs)


def begin_span(name, cat="user", **attrs):
    """Open a span whose end comes in another call: a pair of callbacks
    (jax's start mark and duration event, the collector's start and
    stop). The caller keeps the span and ends it with ``span.end()``,
    on the same thread and in stack order. Wherever one block holds
    both ends, ``with trace_span(...)`` is the form."""
    span = trace_span(name, cat, **attrs)
    span.__enter__()
    return span


def current_span():
    """Innermost active span on this thread, or None."""
    stack = _tls.stack
    return stack[-1] if stack else None


def count(name, value=1, cat=None):
    """Guarded counter add into the shared monitor registry."""
    cats = _enabled_cats[0]
    if cats is None or (cat is not None and cat not in cats):
        return
    monitor.stat_add(name, value)


# -- sampled op-dispatch observer -----------------------------------------

_op_label_re = None


def _op_label(name):
    """Sanitize an op name into a Prometheus label value. Op names come
    from ``dispatch.op_display_name`` — the same string the analyzer's
    program lint and a chrome-trace profile show — so the per-op series
    and static findings join on the label."""
    global _op_label_re
    if _op_label_re is None:
        import re
        _op_label_re = re.compile(r'[^0-9A-Za-z_./:-]')
    return _op_label_re.sub("_", name)


class _SampledOpObserver:
    """Per-op spans through the core.dispatch observer seam, sampled by
    period so the op hot path stays cheap (one counter increment per op,
    one span per `period` ops)."""

    def __init__(self, sample_rate=0.01):
        self.period = max(1, int(round(1.0 / max(sample_rate, 1e-9))))
        self._n = 0

    def begin(self, name):
        self._n += 1
        if self._n % self.period:
            return None
        return profiler._now_ns()

    def end(self, token, name, outputs):
        if token is None:
            return
        end_ns = profiler._now_ns()
        profiler.record_span(f"op/{name}", "dispatch", token, end_ns)
        monitor.stat_add("dispatch_sampled_ops", 1)
        # per-op export (label-suffixed counters ride both exporters'
        # label-aware name path): sampled call count + sampled wall ns,
        # keyed by the canonical dispatch op name, label-escaped per the
        # exposition format
        from .export import format_labels
        key = format_labels("dispatch_op", op=_op_label(name))
        monitor.stat_add("dispatch_op_sampled" + key, 1)
        monitor.stat_add("dispatch_op_ns" + key, end_ns - token)


def enable(categories=None, dispatch_sample_rate=0.01):
    """Turn on tracing for `categories` (default: everything except the
    sampled per-op ``dispatch`` category). Also enables profiler event
    collection so spans reach the chrome-trace exporter."""
    cats = (frozenset(categories) if categories is not None
            else DEFAULT_CATEGORIES)
    unknown = cats - frozenset(CATEGORIES)
    if unknown:
        raise ValueError(
            f"unknown trace categories {sorted(unknown)}; "
            f"valid: {list(CATEGORIES)}")
    _enabled_cats[0] = cats
    profiler.enable_collection()
    runlog.maybe_start_from_env()   # PADDLE_TPU_RUNLOG_DIR
    flight.maybe_install_from_env()  # PADDLE_TPU_FLIGHT_DIR
    from ..core import dispatch
    if "dispatch" in cats:
        dispatch.add_observer("observability",
                              _SampledOpObserver(dispatch_sample_rate))
    else:
        # re-enable without "dispatch" must tear the sampler down, or a
        # previous enable(categories=["dispatch"]) keeps recording ops
        dispatch.remove_observer("observability")


def disable():
    """Turn tracing off and stop profiler event collection. Recorded
    events stay exportable until ``profiler.reset()``."""
    _enabled_cats[0] = None
    from ..core import dispatch
    dispatch.remove_observer("observability")
    profiler.disable_collection()
