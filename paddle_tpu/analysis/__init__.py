"""paddle_tpu.analysis — static analysis over the recorded IR.

The reference keeps its ~80 IR passes and `framework/prune.cc` honest with
C++-side graph checks; the collapsed trace->XLA pipeline gets the same
protection here, BEFORE compile, over the two program representations the
stack actually runs:

- the static ``Program`` op-list (``paddle_tpu.static``) — graph verifier
  (`verifier.check_graph`), dtype/shape consistency via abstract
  ``jax.eval_shape`` replay (`dtype_check.check_dtypes`),
  donation/aliasing hazards (`donation.check_donation`), collective-order
  checks for per-rank programs (`collectives.check_collective_order`), and
  TPU-specific program lint (`lint.lint_program`);
- ``to_static`` traced functions — state-partition consistency of the
  compiled step (`donation.check_static_function`).

Entry points::

    import paddle_tpu.analysis as analysis

    analysis.verify(prog)                  # graph+donation+collectives
    analysis.verify(prog, dtypes=True)     # + abstract dtype/shape replay
    analysis.lint(prog)                    # TPU program lint
    analysis.set_debug(True)               # auto-verify after passes/prune

With debug mode on (or ``PADDLE_TPU_VERIFY=1``), every
``static.apply_pass``/``static.prune`` output is verified automatically
and error findings raise ``VerifyError`` — the fluid-era "Pass validates
the graph before execution" contract. Findings always export as
observability counters (``analysis_findings{rule=...,severity=...}``).
The repo-level front-end is ``tools/lint_program.py`` (CI gate: source
lint + the verified tiny programs in `ladder`).
"""
import os

from .. import monitor as _monitor
from . import concurrency as _concurrency
from . import lockwatch  # noqa: F401  (the runtime watchdog facade)
from .collectives import (check_collective_order,  # noqa: F401
                          check_collectives, collective_sequence)
from .donation import check_donation, check_static_function  # noqa: F401
from .dtype_check import check_dtypes  # noqa: F401
from .findings import (ERROR, INFO, WARNING, Finding,  # noqa: F401
                       VerifyError, errors, format_findings)
from .lint import lint_program, lint_source  # noqa: F401
from .shardcheck import (check_collective_budget,  # noqa: F401
                         check_program_sharding, check_sharding,
                         check_zero_residency, infer_zero_layout,
                         predict_collective_budget, program_shard_stats)
from .verifier import check_graph  # noqa: F401

__all__ = [
    "verify", "lint", "Finding", "VerifyError", "errors",
    "format_findings", "check_graph", "check_dtypes", "check_donation",
    "check_static_function", "check_collectives", "check_collective_order",
    "collective_sequence", "lint_program", "lint_source",
    "check_concurrency", "lockwatch",
    "check_sharding", "check_collective_budget", "check_program_sharding",
    "check_zero_residency", "infer_zero_layout",
    "predict_collective_budget", "program_shard_stats",
    "set_debug", "debug_enabled",
]

# debug mode: auto-verify after every apply_pass/prune (env or set_debug)
_DEBUG = [os.environ.get("PADDLE_TPU_VERIFY", "").lower()
          in ("1", "true", "on")]


def set_debug(flag=True):
    """Toggle debug mode: static.apply_pass / static.prune verify their
    outputs and raise VerifyError on error findings; to_static verifies
    the state partition after every fresh build. Returns the prior
    value."""
    prev = _DEBUG[0]
    _DEBUG[0] = bool(flag)
    return prev


def debug_enabled():
    return _DEBUG[0]


def _export(findings):
    """Findings ride the shared counter registry (always on — verification
    is never a hot path) so scrapes see rule-level totals next to the
    runtime profile. Labels render through ``format_labels`` so the
    per-metric cardinality guard caps a runaway rule/severity blowup the
    same way it caps every other labeled series."""
    from ..observability.export import format_labels
    _monitor.stat_add("analysis_runs", 1)
    for f in findings:
        _monitor.stat_add(
            "analysis_findings" + format_labels(
                "analysis_findings", rule=f.rule, severity=f.severity), 1)


def verify(program, targets=None, donated=None, mesh_axes=None,
           dtypes=False, raise_on_error=False, context=None):
    """Verify a recorded Program: graph structure, donation/aliasing,
    collective sanity, and (``dtypes=True``) the abstract dtype/shape
    replay. Returns the findings; ``raise_on_error=True`` raises
    ``VerifyError`` when any error-severity finding is present."""
    findings = list(check_graph(program, targets=targets))
    findings += check_donation(program, donated=donated)
    findings += check_collectives(program, mesh_axes=mesh_axes)
    if dtypes:
        findings += check_dtypes(program)
    _export(findings)
    if raise_on_error and errors(findings):
        raise VerifyError(findings, context=context)
    return findings


def lint(program):
    """TPU program lint (host callbacks in the compiled stream, unseeded
    RNG ops, ...). Advisory: findings are warnings, never raised."""
    findings = lint_program(program)
    _export(findings)
    return findings


def check_concurrency(paths=None, repo_root=None):
    """Static concurrency rules (lock-order cycles, blocking calls under
    a lock, Condition.wait discipline, notify-without-lock) over the
    thread-heavy runtime modules — see
    :mod:`paddle_tpu.analysis.concurrency`. Findings export as counters
    like every other checker; the runtime complement is
    :mod:`paddle_tpu.analysis.lockwatch`."""
    findings = _concurrency.check_concurrency(paths=paths,
                                              repo_root=repo_root)
    _export(findings)
    return findings


from . import ladder  # noqa: E402,F401  (no cycle: lazy builder imports)
