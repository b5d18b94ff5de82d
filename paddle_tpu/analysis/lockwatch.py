"""Runtime lock-order watchdog — the dynamic half of the concurrency
analyzer (the static half is :mod:`paddle_tpu.analysis.concurrency`).

Drop-in instrumented ``Lock``/``RLock``/``Condition`` factories that
record a process-wide held-set and lock-acquisition-order edge graph,
detect order cycles ONLINE (a potential deadlock is reported even when
the process never interleaved fatally), export
``lockwatch_contention_ns{lock=...}`` / ``lockwatch_order_violations_total``
through the metrics board, and ride every flight-recorder dump (crash,
kill-point, ``reason="pod_failure"``) with the edge graph + holder
stacks while armed.

Opt-in via ``PADDLE_TPU_LOCKWATCH=1`` (set before the process imports
paddle_tpu to cover module-level locks; the virtual-pod chaos tier arms
its child ranks this way) or :func:`enable` before constructing a
subsystem. Disarmed, the factories return the raw ``threading``
primitives (``tests/test_analysis.py`` checks that they are).

Recipe::

    from paddle_tpu.analysis import lockwatch

    lockwatch.enable()                 # or: PADDLE_TPU_LOCKWATCH=1
    mu = lockwatch.Lock("mystage.mu")  # instead of threading.Lock()
    cv = lockwatch.Condition(mu, name="mystage.cv")
    ...
    lockwatch.held_names()             # this thread's held locks
    lockwatch.violations()             # detected order cycles
    lockwatch.snapshot()               # edge graph + held sets (the
                                       # flight dump's lockwatch section)

The implementation lives in the dependency-free
:mod:`paddle_tpu._lockwatch` so the earliest importers (``pod.py`` is
pulled in during package init) can construct watched locks without
importing the analysis package.
"""
from .._lockwatch import (ENV_VAR, Condition, Lock, RLock,  # noqa: F401
                          disable, enable, enabled, held_names, reset,
                          snapshot, violations)

__all__ = ["Lock", "RLock", "Condition", "enabled", "enable", "disable",
           "snapshot", "held_names", "violations", "reset", "ENV_VAR"]
