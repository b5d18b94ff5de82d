"""Program- and repo-level lint.

Two surfaces:

- ``lint_program(prog)``: advisory checks over a recorded Program that are
  legal but hurt on TPU — host callbacks embedded in the compiled stream
  (``py_func`` lowers to ``jax.pure_callback``: a device->host->device
  round-trip per step), eager collectives that recorded as identities, etc.
  Op naming matches the runtime's sampled dispatch telemetry
  (``dispatch.op_display_name``) so a hot op flagged here is the same
  string a profile shows.

- ``lint_source(paths)``: AST lint over repo python — the rule families
  the CI gate runs on every PR:
  * ``nondeterminism-in-traced``: wall-clock / RNG host calls inside a
    ``@to_static``-decorated function. The trace bakes the value at compile
    time (a ``Date``-like constant frozen into the program), so the
    compiled step silently disagrees with the eager one.
  * ``eager-jnp-in-hot-path``: device-touching ``jnp.*`` calls in the
    dispatch/observability hot paths outside an ``enabled()``-style guard —
    one stray ``jnp.zeros`` in ``call_op`` is a device allocation per op
    dispatch.
  * ``retry-without-backoff``: a retry loop (``while True`` — error — or a
    bounded ``for`` — warning) wrapping an RPC/socket call in try/except
    with no backoff sleep and no deadline check. Tight retry loops turn a
    restarting server into a thundering-herd DoS and hide outages from
    latency metrics; route retries through
    ``distributed.ps.retry.RetryPolicy`` instead. Scanned by default over
    the RPC client paths (``RPC_PATHS``).
  * ``span-without-context-manager``: a ``trace_span(...)`` call whose
    result never enters a ``with`` — the span is pushed on the
    thread-local stack only by ``__enter__``, so a span that is created
    and dropped (or assigned and never entered) silently leaks: it never
    records, and any context the caller expected to propagate is absent.
    Scanned by default over the instrumented modules (``SPAN_PATHS``).
  * ``barrier-without-timeout``: a bare ``barrier(...)`` call in a
    multi-process path with no deadline evidence (no ``timeout=``-style
    kwarg, no timeout/deadline-named argument). A collective barrier
    with no deadline turns ONE hung or dead rank into a whole-pod
    deadlock that no metric ever surfaces — every barrier in a
    multi-process path must fail loudly instead
    (``distributed.pod.PodRuntime.barrier`` raises
    ``BarrierTimeoutError`` naming the absent ranks). Scanned by
    default over ``distributed/``, ``serving/`` and
    ``checkpoint/multihost.py`` (``BARRIER_PATHS``).
  * ``raw-remat-outside-policy``: a direct ``jax.remat`` /
    ``jax.checkpoint`` call in model/layer code. Which activations are
    worth saving — and whether saved residuals park in device or pinned
    host memory — is a BACKEND decision; a model that hardcodes a jax
    policy can't be re-tuned per backend. Route segments through
    ``paddle_tpu.recompute`` (``recompute(fn, policy=...)`` /
    ``Layer.enable_recompute``) so policies stay swappable. Scanned by
    default over the model/layer sources (``REMAT_PATHS``);
    ``paddle_tpu/recompute.py`` itself is the one legitimate caller.
  * ``respawn-without-backoff``: a retry-shaped loop (``while`` or
    ``for range(...)``) that spawns/relaunches a PROCESS with no
    backoff/budget evidence — an ERROR. An unpaced respawn loop turns a
    crash-looping rank into a machine-burning fork bomb (and a fleet of
    supervisors restarting after a shared-cause outage into a
    thundering herd); route every relaunch through
    ``distributed.restart.RestartPolicy`` (bounded budget + exponential
    backoff + seedable jitter — the pod supervisor and
    ``fleet/elastic.py``'s relaunch path share it). Per-item fan-outs
    (one spawn per trainer in a ``for t in trainers`` loop) are not
    retry loops and are exempt. Scanned by default over
    ``distributed/`` + ``fleet/elastic.py`` + ``serving/``
    (``RESPAWN_PATHS``).

Deliberate violations carry the structured suppression comment the
concurrency pass introduced (``# lint: <rule-or-prefix> <reason>`` on
the flagged line or the line above): the finding demotes to INFO with
the reason attached — auditable in every sweep, never silently dropped.
The concurrency rule family (lock-order cycles, blocking calls under a
lock, Condition.wait discipline, notify-without-lock) lives in
``analysis/concurrency.py``; its runtime complement is
``analysis/lockwatch.py``.
"""
import ast
import os

from .concurrency import apply_suppressions, parse_suppressions
from .findings import ERROR, WARNING, Finding

__all__ = ["lint_program", "lint_source", "HOT_PATHS", "RPC_PATHS",
           "SPAN_PATHS", "BARRIER_PATHS", "RESPAWN_PATHS", "REMAT_PATHS"]

# host-callback op names: each is a device->host round-trip inside the
# compiled program (stalls the TPU pipeline every step)
_HOST_CALLBACK_OPS = frozenset({"py_func", "pure_callback", "host_callback"})

# hot-path functions (relpath -> function names) where an unguarded
# device-touching jnp call is a per-op-dispatch cost
HOT_PATHS = {
    os.path.join("paddle_tpu", "core", "dispatch.py"): {
        "call_op", "call_op_nograd", "_dispatch", "_call_op_impl",
        "_call_op_nograd_impl", "_observed", "unwrap", "wrap",
    },
    os.path.join("paddle_tpu", "observability", "tracing.py"): {
        "trace_span", "count", "enabled", "now_ns",
    },
}

# jnp attributes that are metadata-only (no device work) and allowed in
# hot paths
_JNP_META_OK = frozenset({"shape", "ndim", "dtype", "result_type", "size"})

# files holding RPC client code: scanned by default for the
# retry-without-backoff rule (add new RPC surfaces here)
RPC_PATHS = (
    os.path.join("paddle_tpu", "distributed", "ps", "client.py"),
    os.path.join("paddle_tpu", "distributed", "ps", "retry.py"),
    os.path.join("paddle_tpu", "distributed", "ps", "communicator.py"),
    os.path.join("paddle_tpu", "distributed", "ps", "graph.py"),
    os.path.join("paddle_tpu", "distributed", "ps", "async_cache.py"),
    os.path.join("paddle_tpu", "distributed", "fleet", "elastic.py"),
    os.path.join("paddle_tpu", "distributed", "pod.py"),
)

# files holding span-instrumented runtime code: scanned by default for
# the span-without-context-manager rule (observability/tracing.py itself
# is exempt — it DEFINES the factory and the re-exports)
SPAN_PATHS = (
    os.path.join("paddle_tpu", "serving", "engine.py"),
    os.path.join("paddle_tpu", "serving", "batching.py"),
    os.path.join("paddle_tpu", "checkpoint", "core.py"),
    os.path.join("paddle_tpu", "distributed", "ps", "client.py"),
    os.path.join("paddle_tpu", "distributed", "ps", "server.py"),
    os.path.join("paddle_tpu", "distributed", "collective.py"),
    os.path.join("paddle_tpu", "jit", "to_static.py"),
    os.path.join("paddle_tpu", "static", "program.py"),
    os.path.join("paddle_tpu", "io", "dataloader.py"),
    os.path.join("paddle_tpu", "hapi", "model.py"),
)

# multi-process paths scanned by default for barrier-without-timeout:
# directories expand recursively to every .py file at scan time
BARRIER_PATHS = (
    os.path.join("paddle_tpu", "distributed"),
    os.path.join("paddle_tpu", "serving"),
    os.path.join("paddle_tpu", "checkpoint", "multihost.py"),
    os.path.join("paddle_tpu", "testing", "virtual_pod.py"),
)

# kwarg names / identifier fragments accepted as deadline evidence on a
# barrier call
_BARRIER_TIMEOUT_KWARGS = frozenset({"timeout", "deadline", "timeout_s",
                                     "io_timeout", "deadline_s"})
_BARRIER_TIMEOUT_HINTS = ("timeout", "deadline")

# multi-process paths scanned by default for respawn-without-backoff
# (fleet/elastic.py lives under distributed/, named for emphasis: its
# relaunch path is the reference's restart loop)
RESPAWN_PATHS = (
    os.path.join("paddle_tpu", "distributed"),
    os.path.join("paddle_tpu", "distributed", "fleet", "elastic.py"),
    os.path.join("paddle_tpu", "serving"),
    os.path.join("paddle_tpu", "testing", "virtual_pod.py"),
)

# model/layer sources scanned by default for raw-remat-outside-policy:
# directories expand recursively; paddle_tpu/recompute.py is the policy
# surface itself and is exempt
REMAT_PATHS = (
    os.path.join("paddle_tpu", "models"),
    os.path.join("paddle_tpu", "nn"),
    os.path.join("paddle_tpu", "vision"),
    os.path.join("paddle_tpu", "text"),
    os.path.join("paddle_tpu", "parallel"),
)

# call-chain leaves that mark a direct jax remat/checkpoint invocation
_RAW_REMAT_CHAINS = frozenset({
    "jax.remat", "jax.checkpoint", "jax.ad_checkpoint.checkpoint",
    "jax.ad_checkpoint.remat", "ad_checkpoint.checkpoint",
})

# call names that mark a statement as spawning/relaunching a process
_SPAWN_CALL_HINTS = frozenset({
    "Popen", "spawn", "spawn_fn", "spawn_trainer", "start_local_trainers",
    "relaunch", "respawn", "start_process", "_spawn_rank", "Process",
})

# evidence that a respawn loop paces itself / bounds its budget
# (NOT "wait": proc.wait() is child-reaping, the signature move of the
# very keep-alive loop this rule exists to flag)
_RESPAWN_EVIDENCE_CALLS = frozenset({"sleep", "schedule",
                                     "next_delay", "allow"})
_RESPAWN_EVIDENCE_NAMES = ("backoff", "budget", "policy", "restart",
                           "delay", "not_before", "deadline")

# call names that mark a statement as an RPC/socket round-trip
_RPC_CALL_HINTS = frozenset({
    "sendall", "send", "recv", "connect", "create_connection",
    "_call", "_call_impl", "urlopen", "request", "getresponse",
})

# evidence that a retry loop paces itself / bounds its total latency
_BACKOFF_CALL_HINTS = frozenset({"sleep", "wait", "backoff_s", "run"})
_BACKOFF_NAME_HINTS = ("backoff", "deadline", "retry_policy", "delay")

# nondeterministic host calls that a trace would freeze into the program
_NONDET_CALLS = {
    ("time", "time"), ("time", "time_ns"), ("time", "perf_counter"),
    ("time", "monotonic"), ("datetime", "now"), ("datetime", "utcnow"),
    ("datetime", "today"), ("date", "today"), ("os", "urandom"),
    ("uuid", "uuid1"), ("uuid", "uuid4"),
}
_NONDET_NP_RANDOM = frozenset({
    "rand", "randn", "randint", "random", "normal", "uniform", "choice",
    "permutation", "shuffle", "random_sample", "standard_normal",
})


def lint_program(prog):
    findings = []
    for i, op in enumerate(prog.ops):
        if op.name in _HOST_CALLBACK_OPS:
            findings.append(Finding(
                "host-callback-in-program", WARNING,
                f"{op.name} embeds a host python callback in the compiled "
                "stream — a device->host->device round-trip per run "
                "(unsupported on backends without host send/recv)",
                op_index=i, op_name=op.name))
    if prog.ops and prog.random_seed is None and any(
            op.name in ("dropout", "gaussian_random", "uniform_random")
            for op in prog.ops):
        findings.append(Finding(
            "unseeded-random-op", WARNING,
            "program records RNG ops but Program.random_seed is unset; "
            "replays are not reproducible across processes"))
    return findings


# -- source lint ----------------------------------------------------------

def _attr_chain(node):
    """'a.b.c' for an Attribute/Name chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_to_static_decorated(fn_node):
    for dec in fn_node.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        chain = _attr_chain(target) or ""
        if chain.split(".")[-1] == "to_static":
            return True
    return False


def _nondet_reason(chain):
    if chain is None:
        return None
    parts = chain.split(".")
    if len(parts) >= 2 and (parts[-2], parts[-1]) in _NONDET_CALLS:
        return f"{parts[-2]}.{parts[-1]}()"
    if parts[0] == "random" and len(parts) == 2:
        return f"random.{parts[1]}()"
    if len(parts) >= 3 and parts[-2] == "random" and \
            parts[0] in ("np", "numpy") and parts[-1] in _NONDET_NP_RANDOM:
        return f"{chain}() (module-level numpy RNG; use a seeded "\
               "RandomState/Generator outside the traced fn)"
    return None


class _TracedFnChecker(ast.NodeVisitor):
    """Flags nondeterministic host calls inside to_static-decorated fns."""

    def __init__(self, path, findings):
        self.path = path
        self.findings = findings
        self._in_traced = 0

    def _visit_fn(self, node):
        traced = _is_to_static_decorated(node)
        self._in_traced += traced
        self.generic_visit(node)
        self._in_traced -= traced

    visit_FunctionDef = visit_AsyncFunctionDef = _visit_fn

    def visit_Call(self, node):
        if self._in_traced:
            reason = _nondet_reason(_attr_chain(node.func))
            if reason:
                self.findings.append(Finding(
                    "nondeterminism-in-traced", ERROR,
                    f"{reason} inside a @to_static function: the trace "
                    "bakes the value at compile time, so the compiled "
                    "step replays a frozen constant",
                    loc=f"{self.path}:{node.lineno}"))
        self.generic_visit(node)


class _HotPathChecker(ast.NodeVisitor):
    """Flags device-touching jnp calls in hot-path fns outside an
    enabled()-style guard."""

    def __init__(self, path, hot_fns, findings):
        self.path = path
        self.hot_fns = hot_fns
        self.findings = findings
        self._hot = 0
        self._guarded = 0

    def _visit_fn(self, node):
        hot = node.name in self.hot_fns
        self._hot += hot
        self.generic_visit(node)
        self._hot -= hot

    visit_FunctionDef = visit_AsyncFunctionDef = _visit_fn

    def visit_If(self, node):
        guard = "enabled(" in ast.unparse(node.test) or \
            "_OBSERVER_LIST" in ast.unparse(node.test)
        self._guarded += guard
        self.generic_visit(node)
        self._guarded -= guard

    def visit_Call(self, node):
        if self._hot and not self._guarded:
            chain = _attr_chain(node.func) or ""
            parts = chain.split(".")
            if len(parts) >= 2 and parts[0] in ("jnp", "jax") and \
                    parts[-1] not in _JNP_META_OK and \
                    (parts[0] == "jnp" or
                     (len(parts) >= 3 and parts[1] == "numpy")):
                self.findings.append(Finding(
                    "eager-jnp-in-hot-path", ERROR,
                    f"unguarded {chain}() in hot-path function — a "
                    "device op per dispatch; gate it behind the "
                    "observability enabled() guard or hoist it",
                    loc=f"{self.path}:{node.lineno}"))
        self.generic_visit(node)


class _RetryLoopChecker(ast.NodeVisitor):
    """Flags retry loops around RPC calls that neither back off nor
    check a deadline (the PS client's original sin: `for _ in
    range(attempts)` re-sending as fast as the kernel fails it)."""

    def __init__(self, path, findings):
        self.path = path
        self.findings = findings

    @staticmethod
    def _loop_facts(body_nodes, loop_vars):
        """(has_retried_rpc, has_try, has_backoff). An RPC call that
        consumes the loop variable is a per-target FAN-OUT (one call per
        server), not a retry of the same request — those don't count."""
        has_rpc = has_try = has_backoff = False
        for node in body_nodes:
            for sub in ast.walk(node):
                if isinstance(sub, ast.Try):
                    has_try = True
                elif isinstance(sub, ast.Call):
                    chain = _attr_chain(sub.func) or ""
                    leaf = chain.split(".")[-1]
                    if leaf in _RPC_CALL_HINTS:
                        arg_names = {
                            n.id for a in list(sub.args)
                            + [kw.value for kw in sub.keywords]
                            for n in ast.walk(a)
                            if isinstance(n, ast.Name)}
                        if not (loop_vars & arg_names):
                            has_rpc = True
                    if leaf in _BACKOFF_CALL_HINTS:
                        has_backoff = True
                elif isinstance(sub, (ast.Name, ast.Attribute)):
                    ident = (sub.id if isinstance(sub, ast.Name)
                             else sub.attr).lower()
                    if any(h in ident for h in _BACKOFF_NAME_HINTS):
                        has_backoff = True
        return has_rpc, has_try, has_backoff

    def _check(self, node, unbounded):
        loop_vars = set()
        target = getattr(node, "target", None)
        if target is not None:
            loop_vars = {n.id for n in ast.walk(target)
                         if isinstance(n, ast.Name)}
        has_rpc, has_try, has_backoff = self._loop_facts(node.body,
                                                         loop_vars)
        if has_rpc and has_try and not has_backoff:
            kind = "while True" if unbounded else "bounded for"
            self.findings.append(Finding(
                "retry-without-backoff", ERROR if unbounded else WARNING,
                f"{kind} retry loop around an RPC call with no backoff "
                "sleep or deadline check — a restarting server gets "
                "hammered as fast as the kernel can fail the socket; "
                "route it through distributed.ps.retry.RetryPolicy",
                loc=f"{self.path}:{node.lineno}"))

    def visit_While(self, node):
        test = node.test
        unbounded = (isinstance(test, ast.Constant) and bool(test.value))
        if unbounded:
            self._check(node, unbounded=True)
        self.generic_visit(node)

    def visit_For(self, node):
        chain = _attr_chain(node.iter.func) if isinstance(node.iter,
                                                         ast.Call) else None
        if chain and chain.split(".")[-1] == "range":
            self._check(node, unbounded=False)
        self.generic_visit(node)


class _RespawnChecker(ast.NodeVisitor):
    """Flags retry-shaped loops that spawn/relaunch a process with no
    backoff/budget evidence (see module docstring). The loop-variable
    heuristic from the retry rule exempts fan-outs: a spawn call whose
    arguments consume the loop variable launches one process per item
    (``for t in trainers: spawn_trainer(..., t, ...)``), it does not
    RE-launch the same one."""

    def __init__(self, path, findings):
        self.path = path
        self.findings = findings

    @staticmethod
    def _loop_facts(body_nodes, loop_vars):
        has_spawn = has_evidence = False
        for node in body_nodes:
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call):
                    chain = _attr_chain(sub.func) or ""
                    leaf = chain.split(".")[-1]
                    if leaf in _SPAWN_CALL_HINTS:
                        arg_names = {
                            n.id for a in list(sub.args)
                            + [kw.value for kw in sub.keywords]
                            for n in ast.walk(a)
                            if isinstance(n, ast.Name)}
                        if not (loop_vars & arg_names):
                            has_spawn = True
                    if leaf in _RESPAWN_EVIDENCE_CALLS:
                        has_evidence = True
                elif isinstance(sub, (ast.Name, ast.Attribute)):
                    ident = (sub.id if isinstance(sub, ast.Name)
                             else sub.attr).lower()
                    if any(h in ident for h in _RESPAWN_EVIDENCE_NAMES):
                        has_evidence = True
        return has_spawn, has_evidence

    def _check(self, node):
        loop_vars = set()
        target = getattr(node, "target", None)
        if target is not None:
            loop_vars = {n.id for n in ast.walk(target)
                         if isinstance(n, ast.Name)}
        has_spawn, has_evidence = self._loop_facts(node.body, loop_vars)
        if has_spawn and not has_evidence:
            self.findings.append(Finding(
                "respawn-without-backoff", ERROR,
                "loop spawns/relaunches a process with no backoff or "
                "budget evidence — a crash-looping child gets relaunched "
                "as fast as fork can fail; route the respawn through "
                "distributed.restart.RestartPolicy (bounded budget + "
                "exponential backoff with jitter)",
                loc=f"{self.path}:{node.lineno}"))

    def visit_While(self, node):
        self._check(node)
        self.generic_visit(node)

    def visit_For(self, node):
        chain = _attr_chain(node.iter.func) if isinstance(node.iter,
                                                         ast.Call) else None
        if chain and chain.split(".")[-1] == "range":
            self._check(node)
        self.generic_visit(node)


class _BarrierChecker(ast.NodeVisitor):
    """Flags ``barrier(...)`` calls with no deadline evidence.

    Evidence: a timeout/deadline-named keyword, or any argument whose
    identifier chain mentions timeout/deadline (a variable carrying the
    deadline counts — the rule checks that SOME bound exists, not its
    value). Definitions are not calls; non-barrier ops that merely
    mention the word are untouched."""

    def __init__(self, path, findings):
        self.path = path
        self.findings = findings

    @staticmethod
    def _has_deadline_evidence(node):
        for kw in node.keywords:
            if kw.arg and kw.arg.lower() in _BARRIER_TIMEOUT_KWARGS:
                return True
        for a in list(node.args) + [kw.value for kw in node.keywords]:
            for sub in ast.walk(a):
                if isinstance(sub, (ast.Name, ast.Attribute)):
                    ident = (sub.id if isinstance(sub, ast.Name)
                             else sub.attr).lower()
                    if any(h in ident for h in _BARRIER_TIMEOUT_HINTS):
                        return True
        return False

    def visit_Call(self, node):
        chain = _attr_chain(node.func) or ""
        if chain.split(".")[-1] == "barrier" \
                and not self._has_deadline_evidence(node):
            self.findings.append(Finding(
                "barrier-without-timeout", WARNING,
                f"bare {chain}(...) with no deadline evidence — one hung "
                "or dead rank deadlocks every participant forever; pass "
                "timeout= (PodRuntime.barrier raises naming the absent "
                "ranks) or route a deadline variable through the call",
                loc=f"{self.path}:{node.lineno}"))
        self.generic_visit(node)


class _RawRematChecker(ast.NodeVisitor):
    """Flags direct ``jax.remat`` / ``jax.checkpoint`` calls in model
    and layer code — the policy surface (``paddle_tpu.recompute``) is
    where backend-specific save/offload decisions live, and a model
    that hardcodes one pins every backend to it. Both call styles are
    caught: dotted chains (``jax.checkpoint(...)``) and bare names
    bound by ``from jax[.ad_checkpoint] import remat/checkpoint
    [as alias]``."""

    def __init__(self, path, findings):
        self.path = path
        self.findings = findings
        self._bare = {}  # local alias -> canonical dotted chain

    def visit_ImportFrom(self, node):
        if node.module in ("jax", "jax.ad_checkpoint"):
            for alias in node.names:
                if alias.name in ("remat", "checkpoint"):
                    self._bare[alias.asname or alias.name] = \
                        f"{node.module}.{alias.name}"
        self.generic_visit(node)

    def _flag(self, chain, lineno, how):
        self.findings.append(Finding(
            "raw-remat-outside-policy", WARNING,
            f"direct {chain} {how} in model/layer code — the "
            "save/offload policy is a backend decision; route the "
            "segment through paddle_tpu.recompute "
            "(recompute(fn, policy=...) or "
            "Layer.enable_recompute(policy)) so policies stay "
            "swappable", loc=f"{self.path}:{lineno}"))

    def _canonical(self, node):
        chain = _attr_chain(node) or ""
        chain = self._bare.get(chain, chain)
        return chain if chain in _RAW_REMAT_CHAINS else None

    def visit_Call(self, node):
        chain = self._canonical(node.func)
        if chain:
            self._flag(chain, node.lineno, "call")
        self.generic_visit(node)

    def _visit_fn(self, node):
        # the idiomatic bare-decorator form (@jax.checkpoint with no
        # parens) is an Attribute in decorator_list, never a Call
        for dec in node.decorator_list:
            if isinstance(dec, (ast.Attribute, ast.Name)):
                chain = self._canonical(dec)
                if chain:
                    self._flag(chain, dec.lineno, "decorator")
        self.generic_visit(node)

    visit_FunctionDef = visit_AsyncFunctionDef = _visit_fn


class _SpanLeakChecker(ast.NodeVisitor):
    """Flags ``trace_span(...)`` results that never enter a ``with``.

    Accepted shapes: a with-item context expression (directly or via a
    chained ``.set_attr(...)``), an assignment to a name later used as a
    with-item in the same function, or a ``return`` (a factory handing
    the span to its caller). A bare expression statement is an ERROR
    (the span is constructed and immediately dropped); an assignment
    never entered is a WARNING (it may escape through attributes — but
    that pattern defeats the stack discipline and deserves a look).
    """

    def __init__(self, path, findings):
        self.path = path
        self.findings = findings

    @staticmethod
    def _is_span_call(node):
        if not isinstance(node, ast.Call):
            return False
        chain = _attr_chain(node.func) or ""
        return chain.split(".")[-1] == "trace_span"

    def _span_calls_in(self, node):
        return [n for n in ast.walk(node) if self._is_span_call(n)]

    def _visit_fn(self, node):
        ok_calls = set()      # trace_span Call nodes that enter a with
        with_names = set()    # names used as with-item context exprs
        assigned = {}         # name -> (call node, lineno)
        returned = set()
        for sub in ast.walk(node):
            if isinstance(sub, (ast.With, ast.AsyncWith)):
                for item in sub.items:
                    for c in self._span_calls_in(item.context_expr):
                        ok_calls.add(id(c))
                    for nm in ast.walk(item.context_expr):
                        if isinstance(nm, ast.Name):
                            with_names.add(nm.id)
            elif isinstance(sub, ast.Return) and sub.value is not None:
                for c in self._span_calls_in(sub.value):
                    returned.add(id(c))
            elif isinstance(sub, ast.Assign) and \
                    self._is_span_call(sub.value):
                for tgt in sub.targets:
                    if isinstance(tgt, ast.Name):
                        assigned[tgt.id] = (sub.value, sub.lineno)
        for sub in ast.walk(node):
            if not self._is_span_call(sub) or id(sub) in ok_calls \
                    or id(sub) in returned:
                continue
            # chained trace_span(...).set_attr(...) inside a with-item is
            # already collected by _span_calls_in walking the whole expr
            parentless = True
            for name, (call, lineno) in assigned.items():
                if call is sub:
                    parentless = False
                    if name not in with_names:
                        self.findings.append(Finding(
                            "span-without-context-manager", WARNING,
                            f"span assigned to {name!r} is never entered "
                            "with a `with` in this function — it records "
                            "nothing and leaks the trace context it was "
                            "meant to carry",
                            loc=f"{self.path}:{lineno}"))
                    break
            if parentless:
                self.findings.append(Finding(
                    "span-without-context-manager", ERROR,
                    "trace_span(...) result discarded without entering a "
                    "`with` — the span never records and is a pure leak; "
                    "write `with trace_span(...):` (or bind it to a "
                    "with-item)",
                    loc=f"{self.path}:{sub.lineno}"))

    visit_FunctionDef = visit_AsyncFunctionDef = _visit_fn


def _expand_py(entries, repo_root):
    """Expand path entries (files or directories, repo-relative or
    absolute) to .py files; directories recurse."""
    out = []
    for p in entries:
        full = p if os.path.isabs(p) else os.path.join(repo_root, p)
        if os.path.isdir(full):
            for dirpath, _dirs, files in os.walk(full):
                out.extend(os.path.join(dirpath, f)
                           for f in sorted(files) if f.endswith(".py"))
        else:
            out.append(full)
    return out


def lint_source(paths=None, repo_root=None):
    """AST-lint python sources. Default: the registered hot-path files,
    the RPC client paths, the span-instrumented modules, and — for the
    barrier + respawn rules only — every file under ``BARRIER_PATHS`` /
    ``RESPAWN_PATHS``; or every file in ``paths`` (all rules). Returns
    findings; files that fail to parse are reported, not raised."""
    if repo_root is None:
        repo_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    findings = []
    targets = []
    barrier_only = set()
    remat_only = set()
    if paths:
        targets.extend(paths)
    else:
        targets.extend(os.path.join(repo_root, p) for p in HOT_PATHS)
        targets.extend(os.path.join(repo_root, p) for p in RPC_PATHS)
        targets.extend(os.path.join(repo_root, p) for p in SPAN_PATHS)
        full_rule_files = {os.path.abspath(p) for p in targets}
        barrier_files = _expand_py(BARRIER_PATHS + RESPAWN_PATHS,
                                   repo_root)
        # files reached ONLY through BARRIER_PATHS/RESPAWN_PATHS get
        # just the multi-process rules — widening the default sweep to a
        # whole package must not retroactively subject every file in it
        # to every rule
        barrier_only = {os.path.abspath(p) for p in barrier_files
                        if os.path.abspath(p) not in full_rule_files}
        targets.extend(barrier_files)
        # likewise for the model/layer sources: the default sweep runs
        # ONLY raw-remat-outside-policy on files reached via REMAT_PATHS
        remat_files = _expand_py(REMAT_PATHS, repo_root)
        remat_only = {os.path.abspath(p) for p in remat_files
                      if os.path.abspath(p) not in full_rule_files}
        targets.extend(remat_files)
    seen = set()
    for path in targets:
        path = os.path.abspath(path)
        if path in seen or not os.path.isfile(path):
            continue
        seen.add(path)
        rel = os.path.relpath(path, repo_root)
        try:
            with open(path) as f:
                src = f.read()
            tree = ast.parse(src, filename=path)
        except SyntaxError as e:
            findings.append(Finding(
                "syntax-error", ERROR, str(e), loc=f"{rel}:{e.lineno}"))
            continue
        # per-file findings so the structured suppression comments
        # (# lint: <rule-or-prefix> <reason> — shared with the
        # concurrency pass) demote deliberate cases to auditable INFO
        fs = []
        is_policy_surface = rel == os.path.join("paddle_tpu",
                                                "recompute.py")
        if path in remat_only:
            if not is_policy_surface:
                _RawRematChecker(rel, fs).visit(tree)
            findings.extend(apply_suppressions(fs,
                                               parse_suppressions(src)))
            continue
        _BarrierChecker(rel, fs).visit(tree)
        _RespawnChecker(rel, fs).visit(tree)
        if path not in barrier_only:
            if not is_policy_surface:  # the one legitimate
                _RawRematChecker(rel, fs).visit(tree)  # jax.checkpoint caller
            _TracedFnChecker(rel, fs).visit(tree)
            _RetryLoopChecker(rel, fs).visit(tree)
            if os.path.basename(rel) != "tracing.py":  # the factory itself
                _SpanLeakChecker(rel, fs).visit(tree)
            hot_fns = HOT_PATHS.get(rel)
            if hot_fns:
                _HotPathChecker(rel, hot_fns, fs).visit(tree)
        findings.extend(apply_suppressions(fs, parse_suppressions(src)))
    return findings
