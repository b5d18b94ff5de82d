"""Per-program XLA compiler flags — the latency-hiding A/B knob.

``observability.overlap`` measures whether a compiled step hides its
collective traffic behind compute; this module is the knob that
measurement exists to evaluate: pass XLA's latency-hiding-scheduler /
async-collective flags to ONE program without touching the rest of the
process (the global ``XLA_FLAGS`` env var is process-wide and frozen at
backend init — useless for an in-process A/B).

    step = paddle.jit.to_static(train_step, scan_steps=8, dp_axis="dp",
                                xla_flags="latency-hiding")
    ...
    step.xla_flags()        # {"flags": {...}, "applied": True/False, ...}
    step.overlap_stats()    # did the schedule actually change?

``xla_flags`` accepts a preset name (:data:`PRESETS`), a
``"flag=value flag2=value2"`` string, a dict, or ``False`` (hard off —
no flags, no env overlay, no default; the A/B control spelling). The
``PADDLE_TPU_XLA_FLAGS`` env var overlays (and wins over) the per-call
value, so a runner can A/B a training script without editing it.
Scan-stepped programs that pass nothing default to
:data:`DEFAULT_SCAN_PRESET` when :func:`backend_accepts` says the
backend registers it — the double-buffered ZeRO pipeline is built for
that scheduler, and the smoke CPU (which rejects ``xla_tpu_*``
options) probes once and stays unflagged.

Flags ride ``jax.jit(..., compiler_options=...)``. XLA validates them at
the FIRST CALL (or AOT compile), not at ``jit()`` time, and rejects
options the backend doesn't register — ``xla_tpu_*`` flags on the CPU
smoke mesh raise ``INVALID_ARGUMENT: No such compile option``. That is
expected on the A/B's CPU control host, so there :class:`FlaggedJit`
degrades: the unknown-flag error triggers ONE recompile without the
options, and the fallback is recorded as provenance (``applied=False``
+ the error) in :meth:`FlaggedJit.provenance`, bench-record metadata,
and a ``xla_flags_fallback`` run-log event — the A/B row then says
honestly that the treatment never applied, instead of comparing two
identical programs. On a TPU backend the options are the treatment: one
the compiler rejects is an error, never a second compile. Any other
compile error propagates.
"""
import os

__all__ = ["PRESETS", "ENV_VAR", "DEFAULT_SCAN_PRESET", "parse_flags",
           "env_flags", "merge", "resolve", "backend_accepts", "jit",
           "FlaggedJit"]

ENV_VAR = "PADDLE_TPU_XLA_FLAGS"

# Preset a scan-compiled step program gets BY DEFAULT when the caller
# passed no xla_flags and the backend registers the options (see
# backend_accepts): the double-buffered ZeRO pipeline emits its
# collectives early precisely so the latency-hiding scheduler can sink
# them under compute — on backends with the scheduler, shipping the
# pipeline without the flags would measure the serial schedule. Opt out
# per program with ``xla_flags=False`` (the A/B control spelling).
DEFAULT_SCAN_PRESET = "latency-hiding"

# Named flag bundles for the standard A/Bs. The tpu-prefixed options
# only exist on TPU backends (falling back on CPU is the designed
# control behavior); both arms are spelled out so a --diff has two real
# configurations to compare.
PRESETS = {
    "latency-hiding": {
        "xla_tpu_enable_latency_hiding_scheduler": True,
        "xla_tpu_enable_async_collective_fusion": True,
        "xla_tpu_enable_async_collective_fusion_fuse_all_gather": True,
    },
    "no-latency-hiding": {
        "xla_tpu_enable_latency_hiding_scheduler": False,
        "xla_tpu_enable_async_collective_fusion": False,
    },
}


def _coerce(value):
    """XLA's compile-option parser rejects string-typed bools ("'false'
    is not a valid bool value"): coerce the textual forms to the python
    types the option registry expects."""
    low = value.lower()
    if low in ("true", "1"):
        return True if low == "true" else 1
    if low in ("false", "0"):
        return False if low == "false" else 0
    try:
        return int(value)
    except ValueError:
        pass
    try:
        return float(value)
    except ValueError:
        return value


def parse_flags(text):
    """``"a=true b=3"`` (space/comma separated; a leading ``--`` per
    token and bare ``flag`` meaning ``flag=true`` both accepted — the
    ``XLA_FLAGS`` spelling pastes in) -> options dict."""
    flags = {}
    for token in text.replace(",", " ").split():
        token = token.lstrip("-")
        if not token:
            continue
        if "=" in token:
            key, value = token.split("=", 1)
            flags[key] = _coerce(value)
        else:
            flags[token] = True
    return flags


def env_flags():
    """Options from ``PADDLE_TPU_XLA_FLAGS`` (preset name or flag
    string; empty dict when unset)."""
    text = os.environ.get(ENV_VAR, "").strip()
    if not text:
        return {}
    if text in PRESETS:
        return dict(PRESETS[text])
    return parse_flags(text)


def merge(*flag_dicts):
    """Left-to-right overlay; later dicts win per key."""
    out = {}
    for d in flag_dicts:
        if d:
            out.update(d)
    return out


def resolve(xla_flags):
    """Normalize a ``to_static(xla_flags=...)`` value — ``None``, a
    preset name, a flag string, or a dict — and overlay the env var
    (env wins: the runner doing the A/B outranks the script).

    ``False`` (or the strings ``"none"``/``"off"``) is the hard off
    switch: no flags, no env overlay, and no scan-body default — the
    spelling an A/B driver uses for its control arm, where "the runner
    outranks the script" must not re-arm the treatment."""
    if xla_flags is False or (isinstance(xla_flags, str)
                              and xla_flags.lower() in ("none", "off")):
        return {}
    if xla_flags is None:
        base = {}
    elif isinstance(xla_flags, dict):
        base = dict(xla_flags)
    elif isinstance(xla_flags, str):
        base = dict(PRESETS[xla_flags]) if xla_flags in PRESETS \
            else parse_flags(xla_flags)
    else:
        raise TypeError(
            f"xla_flags must be None, a preset name, a flag string, or "
            f"a dict; got {type(xla_flags).__name__}")
    return merge(base, env_flags())


def _fallback_allowed(exc):
    """An option the backend does not register — and a backend where
    running without it is the designed control (not a TPU)."""
    import jax
    msg = str(exc)
    return (("No such compile option" in msg or "Unknown flag" in msg)
            and jax.default_backend() != "tpu")


_BACKEND_ACCEPTS = {}  # flag-set key -> bool, cached per process


def backend_accepts(flags):
    """Whether the current backend registers these compile options,
    judged ONCE per process per flag set by compiling a trivial flagged
    program. The scan-body default preset consults this before
    attaching itself: an explicit ``xla_flags=`` request never probes
    (FlaggedJit's per-program fallback records honest provenance
    instead), but a DEFAULT that the backend is known to reject would
    only buy every program a doomed first compile."""
    if not flags:
        return True
    key = tuple(sorted((k, str(v)) for k, v in flags.items()))
    if key not in _BACKEND_ACCEPTS:
        import jax
        import jax.numpy as jnp
        try:
            jax.jit(lambda x: x + 1,
                    compiler_options=dict(flags))(jnp.float32(0))
            _BACKEND_ACCEPTS[key] = True
        except Exception as e:
            if not _fallback_allowed(e):
                raise
            _BACKEND_ACCEPTS[key] = False
    return _BACKEND_ACCEPTS[key]


def _log_fallback(flags, exc):
    from ..observability import runlog
    if runlog.active() is not None:
        runlog.event("xla_flags_fallback", flags=dict(flags),
                     error=str(exc)[:300])


class _FlaggedLowered:
    """AOT half of the fallback contract: ``lower().compile()`` applies
    the same options the call path uses, with the same unknown-flag
    degradation, so introspection (`hlo_text`, `overlap_stats`) sees
    the schedule the flags produced."""

    def __init__(self, lowered, owner):
        self._lowered = lowered
        self._owner = owner

    def compile(self):
        owner = self._owner
        if owner.flags and owner.applied is not False:
            try:
                compiled = self._lowered.compile(
                    compiler_options=dict(owner.flags))
                owner.applied = True
                return compiled
            except Exception as e:
                if not _fallback_allowed(e):
                    raise
                owner._note_fallback(e)
        return self._lowered.compile()

    def __getattr__(self, name):
        return getattr(self._lowered, name)


class FlaggedJit:
    """``jax.jit`` wrapper carrying per-program compiler options with
    unknown-flag fallback and provenance. With empty ``flags`` it is a
    transparent pass-through (provenance still answers)."""

    def __init__(self, fun, flags=None, **jit_kwargs):
        import jax
        self._fun = fun
        self._jit_kwargs = jit_kwargs
        self.flags = dict(flags or {})
        #: True once a flagged compile succeeded, False after the
        #: unknown-flag fallback, None before the backend has judged
        self.applied = None if self.flags else False
        self.fallback_error = None
        if self.flags:
            self._jitted = jax.jit(fun, compiler_options=dict(self.flags),
                                   **jit_kwargs)
        else:
            self._jitted = jax.jit(fun, **jit_kwargs)

    def _note_fallback(self, exc):
        import jax
        self.applied = False
        self.fallback_error = str(exc)[:300]
        _log_fallback(self.flags, exc)
        self._jitted = jax.jit(self._fun, **self._jit_kwargs)

    def __call__(self, *args, **kwargs):
        if self.flags and self.applied is None:
            try:
                out = self._jitted(*args, **kwargs)
                self.applied = True
                return out
            except Exception as e:
                if not _fallback_allowed(e):
                    raise
                self._note_fallback(e)
        return self._jitted(*args, **kwargs)

    def lower(self, *args, **kwargs):
        import jax
        if not self.flags:
            return self._jitted.lower(*args, **kwargs)
        # lower WITHOUT options (lowering is flag-independent), apply
        # them at compile() where the registry validates
        lowered = jax.jit(self._fun,
                          **self._jit_kwargs).lower(*args, **kwargs)
        return _FlaggedLowered(lowered, self)

    def provenance(self):
        """Flag provenance for bench records / runlogs: the resolved
        options, whether the backend accepted them (None = not judged
        yet), and the fallback error when it refused."""
        return {"flags": dict(self.flags), "applied": self.applied,
                "fallback_error": self.fallback_error}


def jit(fun, xla_flags=None, **jit_kwargs):
    """``jax.jit`` with a resolved per-program flag set (see
    :func:`resolve`) — the constructor ``to_static`` routes every
    program build through."""
    return FlaggedJit(fun, flags=xla_flags, **jit_kwargs)
