"""Step telemetry: windowed training/serving rates from wall clock +
runtime counters.

A StepTimer marks step boundaries; over a sliding window it derives
- tokens/s and examples/s (caller supplies per-step token/example counts),
- an MFU estimate (``flops_per_step / step_time / peak_flops`` — the
  standard 6*N*T dense-transformer estimate when the caller passes
  ``flops_per_step=6 * n_params * tokens_per_step``; pass the per-model
  ``flops_per_token`` override — e.g. ``model.flops_per_token(seq)`` —
  for exact attention-aware accounting),
- compile-stall fraction: host time the window spent making programs —
  a ``to_static`` building call's analysis trace, jax's own trace of
  the body, the lowering and the executable's load or compile
  (``to_static_build_ns{phase=}``), the lowering and executable of
  every program that is not a step's (``jit_program_ns{program=}``),
  and ``executor_compile_ns``; all always on,
- data-wait fraction: time the window spent blocked on input
  (``dataloader_wait_ns``).

Each ``step()`` publishes the current window to the export gauge board
(``export.publish``) so a Prometheus scrape always sees fresh step
telemetry without the trainer doing anything else.
"""
import collections
import time

from .. import monitor
from . import export as export_mod

__all__ = ["StepTimer", "PEAK_BF16_FLOPS", "peak_bf16_flops"]

# Published per-chip bf16 peak FLOP/s, keyed by jax's ``device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16).
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def peak_bf16_flops(device_kind=None):
    """The published bf16 peak of ``device_kind`` (default: the first
    attached device). A device that is not in the table is an error,
    never a default: a utilization against a guessed peak is no
    measurement."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    if device_kind not in PEAK_BF16_FLOPS:
        raise ValueError(
            f"no published bf16 peak for device kind {device_kind!r} "
            f"(known: {sorted(PEAK_BF16_FLOPS)}); pass peak_flops= to "
            "state one")
    return PEAK_BF16_FLOPS[device_kind]

_COMPILE_COUNTERS = (
    *(f'to_static_build_ns{{phase="{phase}"}}' for phase in
      ("analysis_trace", "jit_trace", "lower", "executable")),
    'jit_program_ns{program="eager"}', 'jit_program_ns{program="introspect"}',
    "executor_compile_ns")
_WAIT_COUNTER = "dataloader_wait_ns"


def _compile_ns():
    return sum(monitor.stat_get(c) for c in _COMPILE_COUNTERS)


class StepTimer:
    """Windowed step telemetry aggregator.

    Call ``step(tokens=..., examples=...)`` once per training/serving
    step; the first call only anchors the window start. ``telemetry()``
    returns the current window aggregate (also returned by each
    subsequent ``step()`` call).
    """

    def __init__(self, window=20, tokens_per_step=None,
                 examples_per_step=None, flops_per_step=None,
                 flops_per_token=None, peak_flops=None, publish_as="step"):
        self.window = int(window)
        self.tokens_per_step = tokens_per_step
        self.examples_per_step = examples_per_step
        self.flops_per_step = flops_per_step
        # per-model FLOP count (e.g. model.flops_per_token(seq)): exact
        # attention accounting instead of the 6*N*T dense estimate; when
        # set it takes precedence and MFU follows the window's actual
        # token count, so variable-size batches stay correct
        self.flops_per_token = flops_per_token
        # None = look the attached device up at the first MFU reading
        self.peak_flops = peak_flops
        self.publish_as = publish_as
        # (dt_s, tokens, examples, wait_ns, compile_ns) per completed step
        self._window = collections.deque(maxlen=self.window)
        self.total_steps = 0
        self._t_last = None
        self._wait_last = 0
        self._compile_last = 0

    def start(self):
        """Anchor the window start (optional — the first step() call
        anchors implicitly and reports from the second on)."""
        self._t_last = time.perf_counter()
        self._wait_last = monitor.stat_get(_WAIT_COUNTER)
        self._compile_last = _compile_ns()
        return self

    def step(self, tokens=None, examples=None):
        """Mark a step boundary; returns the window telemetry dict (None
        until one full step has elapsed)."""
        now = time.perf_counter()
        if self._t_last is None:
            self.start()
            return None
        dt = now - self._t_last
        self._t_last = now
        wait = monitor.stat_get(_WAIT_COUNTER)
        comp = _compile_ns()
        d_wait, self._wait_last = wait - self._wait_last, wait
        d_comp, self._compile_last = comp - self._compile_last, comp
        self._window.append((
            dt,
            tokens if tokens is not None else self.tokens_per_step,
            examples if examples is not None else self.examples_per_step,
            max(d_wait, 0), max(d_comp, 0)))
        self.total_steps += 1
        t = self.telemetry()
        if self.publish_as:
            export_mod.publish(self.publish_as, t)
            from . import runlog
            if runlog.active() is not None:
                # the per-step record in the run-log stream: trace_view
                # renders these as instants on the publishing rank's track
                runlog.event("step", name=self.publish_as,
                             **{k: round(v, 6) if isinstance(v, float)
                                else v for k, v in t.items()})
                if self.total_steps % self.window == 0:
                    # window boundary: a memory_snapshot event (state
                    # residency by category + recorded program
                    # attributions) lands next to the step stream —
                    # a metadata-only walk, paid once per window
                    from . import memory
                    try:
                        memory.runlog_snapshot()
                    except Exception:
                        pass  # telemetry must never fail the step
        return t

    def telemetry(self):
        """Aggregate over the current window."""
        w = list(self._window)
        if not w:
            return {"steps_total": self.total_steps, "window_steps": 0}
        wall = sum(dt for dt, *_ in w)
        tokens = sum(tk for _, tk, _e, _w, _c in w if tk is not None)
        examples = sum(ex for _, _t, ex, _w, _c in w if ex is not None)
        wait_ns = sum(wn for *_x, wn, _c in w)
        comp_ns = sum(cn for *_x, cn in w)
        out = {
            "steps_total": self.total_steps,
            "window_steps": len(w),
            "step_time_ms": wall / len(w) * 1e3,
            "data_wait_frac": min(wait_ns / 1e9 / wall, 1.0) if wall else 0.0,
            "compile_stall_frac": (min(comp_ns / 1e9 / wall, 1.0)
                                   if wall else 0.0),
        }
        if tokens:
            out["tokens_per_s"] = tokens / wall
        if examples:
            out["examples_per_s"] = examples / wall
        achieved = None
        if self.flops_per_token is not None and tokens and wall:
            achieved = self.flops_per_token * tokens / wall
        elif self.flops_per_step is not None and wall:
            achieved = self.flops_per_step * len(w) / wall
        if achieved is not None:
            if self.peak_flops is None:
                self.peak_flops = peak_bf16_flops()
            out["mfu"] = achieved / self.peak_flops
        return out
