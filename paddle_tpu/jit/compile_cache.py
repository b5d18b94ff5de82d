"""Persistent XLA compilation cache wiring.

The flagship step program costs tens of seconds (TPU) to minutes (large k)
of backend compile on a cold process; jax's persistent compilation cache
(`jax_compilation_cache_dir`) keys the serialized executable on the HLO +
compile options + backend version, so a restart replays the compile from
disk. This module owns the policy:

- ``configure_from_env()`` runs at ``paddle_tpu`` import and only RECORDS
  the on/off switch — it must not touch the backend, because
  ``import paddle_tpu`` stays backend-clean for multi-process init.
- ``ensure_enabled()`` runs at first ``to_static`` build, when the backend
  is initialized anyway: default ON for accelerators, OFF for CPU smoke
  (cache writes would churn on every tiny test program) unless
  ``JAX_COMPILATION_CACHE_DIR`` asks for one. The switch overrides the
  default in either direction.
- placement: where ``JAX_COMPILATION_CACHE_DIR`` is set, jax's own
  setting places the cache and this module sets no directory in code;
  where it is not, the cache lives at :data:`DEFAULT_CACHE_DIR`, a fixed
  path inside the checkout: a directory that moves (home, temp name,
  pid) never hits, and a machine that keeps only the checkout keeps
  only this.
- cache effectiveness is observable: jax's compile events are mirrored
  into the shared monitor registry from import on, with no switch
  (``jit_backend_compile_ns`` / ``jit_backend_compiles`` per backend
  compile or cache load, ``jit_persistent_cache_load_ns``, and while
  the cache is on ``jit_persistent_cache_hits`` / ``_misses`` /
  ``_saved_ns``), so the cold/warm compile delta shows up in any
  metrics scrape.
- the same mirror (:func:`_install_event_mirror`, the program's one
  ``jax.monitoring`` listener set) says whose program jax is making:
  ``jit_programs{program=}`` and ``jit_program_ns{program=}`` book
  every lowering and its executable to ``step`` (a ``to_static``
  building call is on this thread's stack), ``introspect`` (the second,
  ahead-of-time compile behind ``hlo_text()``) or ``eager`` (everything
  else), by :func:`owned_by`; it hands a building call jax's own trace,
  lowering and executable seconds (``to_static_build_ns{phase=}``),
  counts the steps jax starts on a thread (``this_thread.steps``) for
  the call that wants to know whether jax re-specialised under it, and,
  with tracing on, opens a ``jax/<leaf>`` span for the duration of each.
- scoped and unscoped executables are kept apart: jax's cache key
  leaves an instruction's ``op_name`` metadata out, so an executable
  cached before the program entered ``observability.scopes`` would be a
  hit for the scoped program and name nothing. The key does hold the
  module's name, so every step program ``to_static`` compiles is named
  by :func:`program_name` with :data:`METADATA_SCHEMA` in it: the step
  programs (and only they) miss the older entries once, wherever the
  placement rule put the cache. Bump the schema when what the program
  writes into that metadata changes (file names and line numbers are
  deliberately not in the key: every edit would cost every user a cold
  compile).

Env:
    PADDLE_TPU_COMPILE_CACHE       "1"/"on" force-enable (any backend),
                                   "0"/"off" disable.
    JAX_COMPILATION_CACHE_DIR      jax's own placement; wins when set.
"""
import contextlib
import os
import threading

from .. import monitor
from ..observability import tracing as _obs

__all__ = ["configure_from_env", "ensure_enabled", "enable", "disable",
           "is_enabled", "cache_dir", "DEFAULT_CACHE_DIR", "program_name",
           "METADATA_SCHEMA", "owned_by", "this_thread", "JAX_STEPS"]

# what the program writes into instruction metadata: s2 = the `pt.`
# scopes of observability.scopes (s1, unnamed: nothing)
METADATA_SCHEMA = "s2"


def program_name(name):
    """The name a compiled step program goes by (its module's name,
    which jax's persistent-cache key holds)."""
    return f"{name}_{METADATA_SCHEMA}"

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_ENV_SWITCH = "PADDLE_TPU_COMPILE_CACHE"
_ENV_JAX_DIR = "JAX_COMPILATION_CACHE_DIR"

# policy: None = decide from backend at first compile; True/False = forced
_state = {"policy": None, "enabled": False, "resolved": False}

# jax's three steps of making a program (each a `log_elapsed_time` in
# jax: a scalar of its name when it starts, its duration when it ends):
# the phase of a building call that each is, and its span's name
_JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration":
        ("jit_trace", "jax/jaxpr_trace"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("lower", "jax/jaxpr_to_mlir_module"),
    "/jax/core/compile/backend_compile_duration":
        ("executable", "jax/backend_compile"),
}
JAX_STEPS = tuple(phase for phase, _span in _JAX_EVENTS.values())
_PROGRAMS = {owner: f'jit_programs{{program="{owner}"}}'
             for owner in ("eager", "step", "introspect")}
_PROGRAM_NS = {owner: f'jit_program_ns{{program="{owner}"}}'
               for owner in _PROGRAMS}


class _ThreadState(threading.local):
    """What this thread's program is doing when a jax event fires."""

    def __init__(self):
        self.owner = "eager"  # whose programs jax is making (owned_by)
        self.build = None     # a building call's {JAX_STEPS: ns}
        self.open = []        # spans of the jax steps under way
        self.steps = 0        # jax steps started so far; never lowered


this_thread = _ThreadState()


@contextlib.contextmanager
def owned_by(owner):
    """Book the programs jax makes on this thread inside the block to
    ``owner`` (``step`` or ``introspect``; outside any block they are
    ``eager``). A building call sets ``this_thread.build`` (a dict over
    :data:`JAX_STEPS`) inside its block, from where its analysis trace
    is over, to be given the outermost steps' nanoseconds; the block's
    end takes it back."""
    saved = this_thread.owner, this_thread.build
    this_thread.owner, this_thread.build = owner, None
    try:
        yield
    finally:
        this_thread.owner, this_thread.build = saved


def configure_from_env():
    """Record the env switch (import-time safe: no jax backend access)."""
    switch = os.environ.get(_ENV_SWITCH, "").strip().lower()
    if switch in ("1", "on", "true", "yes"):
        _state["policy"] = True
    elif switch in ("0", "off", "false", "no"):
        _state["policy"] = False
    return _state["policy"]


def _install_event_mirror():
    """The program's one set of ``jax.monitoring`` listeners, installed
    when this module is imported (once a process: nothing takes them
    off, and registering touches no backend). Always on, since a program
    is made once and a guard saves nothing: a backend compile (a load from
    the persistent cache fires it too) and the cache's retrieval time;
    per owner, the programs lowered and their lowering + executable
    time; a building call's phases; the persistent cache's hits, misses
    and saved time while this module has it enabled.

    jax's steps nest (the trace of a step program holds the traces of
    the ``jit``s inside it, and each fires the same event), so the start
    marks keep a stack and a step counts for a phase or an owner only
    when nothing is left under way as it ends: the outermost. The
    retrieval lies inside the backend-compile event, so it is not added
    to ``jit_program_ns`` a second time."""
    from jax import monitoring as _jm

    def _on_event(event, **kwargs):
        if not _state["enabled"]:
            return
        if event == "/jax/compilation_cache/cache_hits":
            monitor.stat_add("jit_persistent_cache_hits", 1)
        elif event == "/jax/compilation_cache/cache_misses":
            monitor.stat_add("jit_persistent_cache_misses", 1)

    def _on_start(event, _value, **kwargs):
        if event not in _JAX_EVENTS:
            return
        this_thread.steps += 1
        # a span (and a `pt/` annotation) while tracing is on, else the
        # shared no-op
        this_thread.open.append(_obs.begin_span(
            _JAX_EVENTS[event][1], cat="jit", fn=kwargs.get("fun_name")))

    def _on_duration(event, duration, **kwargs):
        if event in _JAX_EVENTS:
            phase = _JAX_EVENTS[event][0]
            ns = int(duration * 1e9)
            under_way = this_thread.open
            if under_way:
                under_way.pop().end()
            if phase == "executable":
                monitor.stat_add("jit_backend_compile_ns", ns)
                monitor.stat_add("jit_backend_compiles", 1)
            if under_way:  # inside another step, which counts it
                return
            owner = this_thread.owner
            if phase == "lower":
                monitor.stat_add(_PROGRAMS[owner], 1)
            if phase != "jit_trace":  # traces of eager ops: not a program's
                monitor.stat_add(_PROGRAM_NS[owner], ns)
            if this_thread.build is not None:
                this_thread.build[phase] += ns
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            monitor.stat_add("jit_persistent_cache_load_ns",
                             int(duration * 1e9))
        elif (event == "/jax/compilation_cache/compile_time_saved_sec"
              and _state["enabled"]):
            monitor.stat_add("jit_persistent_cache_saved_ns",
                             int(duration * 1e9))

    _jm.register_event_listener(_on_event)
    _jm.register_scalar_listener(_on_start)
    _jm.register_event_duration_secs_listener(_on_duration)


_install_event_mirror()


def enable(directory=None, min_compile_time_secs=None):
    """Turn the persistent cache on (explicit API; also used by
    ``ensure_enabled``). ``directory`` places it for this process (tests,
    a trainer that owns its cache); with none given the placement rule of
    the module docstring applies. ``min_compile_time_secs=0`` caches every
    program — the right setting for tests; the jax default (1s) skips
    trivial programs in production."""
    import jax

    if os.environ.get(_ENV_JAX_DIR):
        if directory is not None:
            raise ValueError(
                f"{_ENV_JAX_DIR} is set and places the compile cache; "
                f"refusing to move it to {directory!r} in code")
    else:
        directory = directory or DEFAULT_CACHE_DIR
        os.makedirs(directory, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_enable_compilation_cache", True)
    if min_compile_time_secs is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(min_compile_time_secs))
        # min_entry_size -1 disables the size floor so tiny smoke programs
        # round-trip too (only consulted when the time floor passes)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _reset_jax_cache()
    _state["enabled"] = True
    _state["resolved"] = True
    return cache_dir()


def _reset_jax_cache():
    """jax initializes its cache object ONCE per process and never
    re-reads the config after that, so flipping it mid-process (a
    long-lived trainer enabling the cache after warmup compiles, or the
    tests) needs an explicit re-init."""
    from jax.experimental.compilation_cache import compilation_cache as _cc
    _cc.reset_cache()


def disable():
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    _reset_jax_cache()
    _state["enabled"] = False
    _state["resolved"] = True


def ensure_enabled():
    """Resolve the policy once, at first compile (backend already up):
    accelerators default on, CPU defaults off unless jax's own env
    setting places a cache, the switch overrides both."""
    if _state["resolved"]:
        return _state["enabled"]
    policy = _state["policy"]
    if policy is None:
        import jax
        policy = (bool(os.environ.get(_ENV_JAX_DIR))
                  or jax.default_backend() != "cpu")
    if policy:
        enable()
    elif _state["policy"] is False:
        disable()  # jax's env setting would otherwise cache on its own
    else:
        _state["resolved"] = True
    return _state["enabled"]


def is_enabled():
    return _state["enabled"]


def cache_dir():
    """Where the cache lives while enabled (jax's own setting)."""
    if not _state["enabled"]:
        return None
    import jax
    return jax.config.jax_compilation_cache_dir
