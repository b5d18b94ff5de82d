"""Shardcheck: whole-program sharding & collective-budget analysis.

Two proof obligations, mirrored from the analyzer's contract:

1. CLEAN — the default build grid (zero{0,1,3} x scan k x accumulation
   x prefetch on/off) passes the full verifier with zero shardcheck
   findings: the budget predictor's table matches what XLA actually
   compiled, layout inference recovers (stage, buckets, prefetch) from
   the partition alone, and the ZeRO stores measure 1/dp resident.
2. SEEDED — each rule demonstrably fires on a program carrying exactly
   its defect: a >=1MB replicated shard_map input (replication-blowup),
   two gathered values escaping the region (materialization-window), an
   un-donated sharded carry (donation-leak), a bucket-count lie against
   the compiled schedule (collective-budget-mismatch), and a
   record-level twin that reduce-scatters but never re-gathers.

The export/suppression seams (analysis_findings label-cardinality
guard, `# lint:` suppression round-trip) and the default lint sweep
are covered here too — shardcheck routes through the same
finding plumbing as every other checker.
"""
import importlib.util
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor, nn
from paddle_tpu.analysis import shardcheck
from paddle_tpu.analysis.findings import ERROR, INFO, WARNING, errors
from paddle_tpu.distributed import parallel_env

DP = 8
COMM_MB = 0.003  # layer-aligned 2 buckets on the 16->32->8 MLP
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# every rule shardcheck owns — the clean grid must emit NONE of these
# (other tests' leaked optimizers may legitimately produce unrelated
# sharded-state-skipped warnings in a shared pytest process)
SHARD_RULES = frozenset({
    "replication-blowup", "materialization-window", "donation-leak",
    "collective-budget-mismatch", "zero-residency",
})


@pytest.fixture(autouse=True)
def _mesh():
    mesh = parallel_env.make_mesh({"dp": DP})
    parallel_env.set_mesh(mesh)
    yield mesh
    parallel_env.set_mesh(None)
    from paddle_tpu.distributed.fleet.base import topology
    topology.set_hybrid_communicate_group(None)


rng = np.random.RandomState(55)


def _mlp():
    return nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 8))


def _build(stage, k, acc=None, prefetch=None, donate=True, seed=11,
           bf16=False):
    paddle.seed(seed)
    m = _mlp()
    if bf16:
        m.to("bfloat16")
    opt = paddle.optimizer.AdamW(parameters=m.parameters(),
                                 learning_rate=0.05, multi_precision=bf16)
    if stage:
        opt._zero_enable(axis="dp", stage=stage, comm_buffer_mb=COMM_MB,
                         prefetch=prefetch)

    def one(xb, yb):
        loss = nn.functional.cross_entropy(m(xb), yb)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(one, scan_steps=k, dp_axis="dp",
                                accumulate_steps=acc, donate_state=donate)
    return step, m, opt


def _batches(k, batch=16):
    x = rng.rand(k, batch, 16).astype("float32")
    y = rng.randint(0, 8, (k, batch)).astype("int64")
    return paddle.to_tensor(x), paddle.to_tensor(y)


# -- the budget predictor table ---------------------------------------------

def test_predict_budget_table():
    """The (stage, k, a, nb, prefetch) -> multiset table, pinned
    value-by-value (these are the counts the compiled-schedule diffs in
    the clean grid below hold the real programs to)."""
    P = shardcheck.predict_collective_budget
    ag, rs = ("all-gather", "dp"), ("reduce-scatter", "dp")
    # stage 0: nothing to budget
    assert P(0, scan_steps=4, n_buckets=2) == {}
    # stage 1: one rs+ag pair per bucket per update window
    assert P(1, scan_steps=4, n_buckets=2) == {ag: 8, rs: 8}
    assert P(1, scan_steps=4, accumulate_steps=2, n_buckets=2) == \
        {ag: 4, rs: 4}
    # stage 2: rs every micro step into the sharded accumulator, ag per
    # window; without accumulation it collapses to the stage-1 schedule
    assert P(2, scan_steps=4, accumulate_steps=2, n_buckets=2) == \
        {ag: 4, rs: 8}
    assert P(2, scan_steps=4, n_buckets=2) == P(1, scan_steps=4,
                                                n_buckets=2)
    # stage 3: rs and ag per micro step; the warm prefetch slot elides
    # the bucket-0 re-gather on each intra-window micro step
    assert P(3, scan_steps=4, n_buckets=2) == {ag: 8, rs: 8}
    assert P(3, scan_steps=4, accumulate_steps=2, n_buckets=2,
             prefetch=False) == {ag: 8, rs: 8}
    assert P(3, scan_steps=4, accumulate_steps=2, n_buckets=2,
             prefetch=True) == {ag: 6, rs: 8}
    # prefetch without accumulation elides nothing (every step is a
    # window boundary)
    assert P(3, scan_steps=4, n_buckets=2, prefetch=True) == \
        {ag: 8, rs: 8}
    # buckets whose 16-bit gradients are exchanged narrow: an all-to-all
    # at the reduce-scatter's cadence in its place, bucket by bucket
    a2a = ("all-to-all", "dp")
    assert P(3, scan_steps=4, n_buckets=2, exchanged_buckets=2) == \
        {ag: 8, rs: 0, a2a: 8}
    assert P(1, scan_steps=4, accumulate_steps=2, n_buckets=2,
             exchanged_buckets=2) == {ag: 4, rs: 0, a2a: 4}
    assert P(2, scan_steps=4, accumulate_steps=2, n_buckets=3,
             exchanged_buckets=1) == {ag: 6, rs: 8, a2a: 4}
    assert P(3, scan_steps=4, n_buckets=2, exchanged_buckets=0) == \
        {ag: 8, rs: 8}


def test_predict_budget_mesh_axes_gating():
    """The mesh-axes tuple is the extension seam: an axis outside it is
    unbudgeted (returns {}), widening the tuple makes it land as data —
    the hybrid-mesh tp axis needs no new code here."""
    P = shardcheck.predict_collective_budget
    assert P(1, scan_steps=2, n_buckets=1, axis="tp") == {}
    got = P(1, scan_steps=2, n_buckets=1, axis="tp",
            mesh_axes=("dp", "tp"))
    assert got == {("all-gather", "tp"): 2, ("reduce-scatter", "tp"): 2}


# -- the clean grid ---------------------------------------------------------

GRID = [
    (0, 1, None, None), (0, 4, None, None), (0, 4, 2, None),
    (1, 1, None, None), (1, 4, None, None), (1, 4, 2, None),
    (3, 1, None, False), (3, 4, None, False), (3, 4, 2, False),
    (3, 1, None, True), (3, 4, None, True), (3, 4, 2, True),
]


@pytest.mark.parametrize("stage,k,acc,pf", GRID,
                         ids=[f"z{s}_k{k}_a{a or 1}_pf{int(bool(p))}"
                              for s, k, a, p in GRID])
def test_clean_grid_no_shardcheck_findings(stage, k, acc, pf):
    """Acceptance bar: the default build grid verifies clean — layout
    inference agrees with the optimizer's own zero_layout(), the
    compiled collective multiset sits exactly on the predicted budget,
    the stores are 1/dp resident, and the jaxpr pass flags nothing."""
    s, _m, opt = _build(stage, k, acc=acc, prefetch=pf)
    x, y = _batches(k)
    s(x, y)
    findings = s.verify()
    assert errors(findings) == []
    assert [f for f in findings if f.rule in SHARD_RULES] == []
    layout = shardcheck.infer_zero_layout(s)
    if stage == 0:
        assert layout is None
    else:
        assert layout["stage"] == stage
        assert layout["n_buckets"] == 2
        assert layout["scan_steps"] == k
        assert layout["accumulate_steps"] == (acc or 1)
        if stage == 3:
            assert layout["prefetch"] == bool(pf)
        zl = opt.zero_layout()
        assert zl["stage"] == stage
        assert zl["n_buckets"] == layout["n_buckets"]
        assert shardcheck.check_collective_budget(s) == []
        assert shardcheck.check_zero_residency(opt) == []


BF16_GRID = [(1, 4, None, None), (2, 4, 2, None), (3, 4, None, False),
             (3, 4, 2, True)]


@pytest.mark.parametrize("stage,k,acc,pf", BF16_GRID,
                         ids=[f"z{s}_k{k}_a{a or 1}_pf{int(bool(p))}"
                              for s, k, a, p in BF16_GRID])
def test_clean_grid_bf16_expects_the_all_to_all(stage, k, acc, pf):
    """bf16 parameters: every bucket's gradients are exchanged narrow,
    the partition says so, the budget holds the compiled step to an
    all-to-all where the float32 step reduce-scatters — and a layout
    that claims the float32 schedule is short of its reduce-scatters."""
    s, _m, opt = _build(stage, k, acc=acc, prefetch=pf, bf16=True)
    x, y = _batches(k)
    s(x, y)
    findings = s.verify()
    assert errors(findings) == []
    assert [f for f in findings if f.rule in SHARD_RULES] == []
    layout = shardcheck.infer_zero_layout(s)
    assert layout["stage"] == stage
    assert layout["exchanged_buckets"] == layout["n_buckets"] == 2
    assert shardcheck.check_collective_budget(s) == []
    assert shardcheck.check_zero_residency(opt) == []
    lie = dict(layout, exchanged_buckets=0)
    fs = shardcheck.check_collective_budget(s, layout=lie)
    assert {f.op_name for f in fs} == {"reduce-scatter"}
    assert all(f.rule == "collective-budget-mismatch" for f in fs)


# -- seeded defects: one per rule -------------------------------------------

def test_seeded_replication_blowup(_mesh):
    """A >=1MB input entering a shard_map region replicated while the
    region threads dp-sharded values is the full-parameter residency
    regression — WARNING naming the shape and byte size."""
    import jax
    from jax.sharding import PartitionSpec as P
    big = np.ones((512, 1024), np.float32)  # 2 MiB, replicated
    xs = np.ones((DP, 4), np.float32)

    def f(b, x):
        return (x * b[0, 0]).sum(axis=1)

    fn = jax.shard_map(f, mesh=_mesh, in_specs=(P(), P("dp")),
                       out_specs=P("dp"))
    jx = jax.make_jaxpr(fn)(big, xs)
    fs, stats = shardcheck.analyze_jaxpr(jx)
    hits = [f for f in fs if f.rule == "replication-blowup"]
    assert hits and hits[0].severity == WARNING
    assert "2097152 bytes" in hits[0].message
    assert stats["shard_map_regions"] == 1
    # the same program below the threshold is clean
    fs2, _ = shardcheck.analyze_jaxpr(
        jx, replication_threshold=4 << 20)
    assert [f for f in fs2 if f.rule == "replication-blowup"] == []


def test_seeded_materialization_window(_mesh):
    """Two all-gathered full values escaping the region boundary (a
    widened prefetch-slot live range: the gathered params ride out of
    the step instead of dying at their last consumer) blow the one-
    bucket budget — ERROR; a budget of 2 or None tolerates."""
    import jax
    from jax.sharding import PartitionSpec as P
    a = np.ones((DP, 4), np.float32)
    b = np.ones((DP, 4), np.float32)

    def f(u, v):
        return (jax.lax.all_gather(u, "dp", tiled=True),
                jax.lax.all_gather(v, "dp", tiled=True))

    fn = jax.shard_map(f, mesh=_mesh, in_specs=(P("dp"), P("dp")),
                       out_specs=(P(), P()), check_vma=False)
    jx = jax.make_jaxpr(fn)(a, b)
    fs, stats = shardcheck.analyze_jaxpr(jx, budget=1)
    hits = [f for f in fs if f.rule == "materialization-window"]
    assert hits and hits[0].severity == ERROR
    assert "2 all-gathered" in hits[0].message
    assert stats["n_gathered"] == 2
    assert stats["escaped_gathered"] == 2
    # widening the budget (the stage-1/2 replicated-param contract) or
    # disabling the rule tolerates the same escapes
    assert shardcheck.analyze_jaxpr(jx, budget=2)[0] == []
    assert shardcheck.analyze_jaxpr(jx, budget=None)[0] == []


def test_seeded_donation_leak():
    """donate_state=False with ZeRO stores riding the carry silently
    doubles the 1/dp residency claim — ERROR from the default verify
    entry point; a replicated (non-ZeRO) un-donated carry is the
    legitimate-while-debugging WARNING."""
    k = 2
    x, y = _batches(k)
    s, _m, _opt = _build(1, k, donate=False)
    s(x, y)
    findings = s.verify()
    hits = [f for f in findings if f.rule == "donation-leak"]
    assert hits and hits[0].severity == ERROR
    assert "donate_state=False" in hits[0].message
    # replicated carry: warning, and verify() still has no errors
    s0, _m0, _o0 = _build(0, k, donate=False)
    s0(x, y)
    f0 = s0.verify()
    hits0 = [f for f in f0 if f.rule == "donation-leak"]
    assert hits0 and hits0[0].severity == WARNING
    assert errors(f0) == []


def test_seeded_collective_budget_mismatch():
    """Lying about the bucket count makes the compiled schedule carry
    surplus collectives vs the budget — one ERROR per op naming the
    count delta (the 'extra all-gather' acceptance defect: got > the
    single-bucket budget)."""
    k = 2
    s, _m, _opt = _build(1, k)
    x, y = _batches(k)
    s(x, y)
    layout = dict(shardcheck.infer_zero_layout(s))
    assert layout["n_buckets"] == 2  # the truth...
    layout["n_buckets"] = 1          # ...and the lie
    fs = shardcheck.check_collective_budget(s, layout=layout)
    assert fs and all(f.rule == "collective-budget-mismatch"
                      and f.severity == ERROR for f in fs)
    by_op = {f.op_name: f for f in fs}
    assert set(by_op) == {"all-gather", "reduce-scatter"}
    ag = by_op["all-gather"]
    assert ag.slot == "dp"
    assert f"budgets {k}" in ag.message      # nb=1 -> k expected
    assert f"(+{k})" in ag.message           # 2*k compiled -> +k extra
    # the honest layout diffs clean
    assert shardcheck.check_collective_budget(s) == []


def test_record_level_rs_without_ag():
    """Record-level twins: an axis whose gradients reduce-scatter but
    whose params are never re-gathered starves every rank's replicas —
    ERROR; adding the gather back clears it; the stamped multiset
    summarizes for the ladder's shard= column."""
    from paddle_tpu import static
    from paddle_tpu.core.dispatch import call_op

    def prog_with(ops):
        prog = static.Program()
        with static.program_guard(prog):
            g = static.data("g", [4], "float32")
            out = g
            for op_name in ops:
                def _c(v):
                    return v
                _c._collective_axis = "dp"
                _c._collective_nbytes = 16
                out = call_op(_c, out, op_name=op_name)
            paddle.sum(out)
        return prog

    bad = prog_with(["c_reducescatter"])
    fs = shardcheck.check_program_sharding(bad)
    assert fs and fs[0].rule == "collective-budget-mismatch"
    assert fs[0].severity == ERROR
    good = prog_with(["c_reducescatter", "c_allgather"])
    assert shardcheck.check_program_sharding(good) == []
    stats = shardcheck.program_shard_stats(good)
    assert stats["collectives"] == 2
    assert stats["axes"]["dp"] == {"reduce-scatter": 1, "all-gather": 1}
    assert shardcheck.format_shard_stats(stats) == "dp:ag1+rs1"
    # the twin of a bf16 step: the all-to-all stands for the scatter
    bad16 = prog_with(["c_alltoall"])
    fs = shardcheck.check_program_sharding(bad16)
    assert fs and fs[0].rule == "collective-budget-mismatch"
    assert "1 all-to-all" in fs[0].message
    good16 = prog_with(["c_alltoall", "c_allgather"])
    assert shardcheck.check_program_sharding(good16) == []
    assert shardcheck.format_shard_stats(
        shardcheck.program_shard_stats(good16)) == "dp:ag1+a2a1"
    assert shardcheck.format_shard_stats(
        shardcheck.program_shard_stats(prog_with([]))) == "-"


# -- the lint sweep ---------------------------------------------------------

def _load_script(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_lint_program_default_sweep_clean(capsys):
    """The full default lint_program sweep (ladder + source +
    concurrency, shardcheck riding verify_ladder and the shard= column
    in the ladder rows) reports zero ERROR findings on the repo as it
    ships."""
    lp = _load_script("lint_program_under_test",
                      os.path.join(REPO, "tools", "lint_program.py"))
    rc = lp.main([])
    outp = capsys.readouterr().out
    assert rc == 0, outp
    assert "0 error(s)" in outp
    # the shard= column renders the stamped multiset per zero twin
    assert "shard=" in outp
    assert "dp:ag" in outp


# -- export & suppression seams ---------------------------------------------

def test_analysis_findings_label_cardinality_guard(monkeypatch):
    """analysis_findings rides format_labels' per-metric cardinality
    guard: past the cap, new rule/severity combinations collapse to the
    __overflow__ series and bump metrics_label_overflow_total instead
    of growing the registry without bound."""
    from paddle_tpu import analysis
    from paddle_tpu.analysis.findings import Finding
    from paddle_tpu.observability import export
    monkeypatch.setenv("PADDLE_TPU_MAX_LABEL_SETS", "2")
    export.clear_label_sets()
    try:
        for key in (
                'analysis_findings{rule="shardtest-a",severity="warning"}',
                'analysis_findings{rule="shardtest-b",severity="warning"}',
                'analysis_findings{rule="__overflow__",'
                'severity="__overflow__"}',
                "metrics_label_overflow_total"):
            monitor.stat_reset(key)
        analysis._export([
            Finding("shardtest-a", WARNING, "m"),
            Finding("shardtest-b", WARNING, "m"),
            Finding("shardtest-c", WARNING, "m"),
        ])
        assert monitor.stat_get(
            'analysis_findings{rule="shardtest-a",severity="warning"}') == 1
        assert monitor.stat_get(
            'analysis_findings{rule="shardtest-b",severity="warning"}') == 1
        # the third distinct combination overflowed
        assert monitor.stat_get(
            'analysis_findings{rule="__overflow__",'
            'severity="__overflow__"}') == 1
        assert monitor.stat_get("metrics_label_overflow_total") >= 1
    finally:
        export.clear_label_sets()  # don't cap later tests' label sets


def test_suppression_roundtrip_shardcheck_rule(tmp_path):
    """A shardcheck finding carrying a loc demotes through the PR-15
    structured-suppression syntax like any other rule: `# lint:
    collective-budget-mismatch <reason>` on the flagged line turns the
    ERROR into an auditable INFO with the reason attached; other rules
    on the same line stay loud."""
    from paddle_tpu.analysis.concurrency import (apply_suppressions,
                                                 parse_suppressions)
    from paddle_tpu.analysis.findings import Finding
    src = ("def step():\n"
           "    gather()  # lint: collective-budget-mismatch"
           " tp axis lands with the hybrid mesh\n")
    p = tmp_path / "mod.py"
    p.write_text(src)
    sup = parse_suppressions(src)
    assert sup[2][0] == "collective-budget-mismatch"
    f = Finding("collective-budget-mismatch", ERROR,
                "all-gather on axis 'tp': 2 executed, layout budgets 0",
                loc=f"{p}:2")
    out = apply_suppressions([f], sup)
    assert out[0].severity == INFO
    assert out[0].message.startswith(
        "suppressed (tp axis lands with the hybrid mesh): ")
    # an unmatched rule on the same line is untouched
    g = Finding("materialization-window", ERROR, "x", loc=f"{p}:2")
    assert apply_suppressions([g], sup)[0].severity == ERROR
