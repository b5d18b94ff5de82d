"""Activation recompute + host offload (ISSUE 13).

The policy surface (``paddle_tpu.recompute``) must trade memory for
recompute WITHOUT changing the math: remat'd training is bitwise-equal
(fp32) / tolerance-equal (bf16+master) to its non-remat control across
the sharding matrix zero{0,1,3} x k{1,4} x accumulate_steps{1,2},
including dropout models (the RecomputeFunction RNG-replay contract —
masks replay bitwise because the key mathematics threads through the
remat region). Plus: the policy resolution rules (offload falls back
LOUDLY without a pinned_host memory space), segment constraints,
mutated-state threading (BN running stats, scoped keys), the
jaxpr-liveness meter that carries the bench claim, and the analysis
integrations (remat ladder twin, remat-replay-aware verifier, the
raw-remat-outside-policy lint rule, mem_view --diff).
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import recompute as rc
from paddle_tpu.core import random as core_random
from paddle_tpu.distributed import parallel_env

DP = 8


@pytest.fixture(autouse=True)
def _mesh():
    mesh = parallel_env.make_mesh({"dp": DP})
    parallel_env.set_mesh(mesh)
    yield mesh
    parallel_env.set_mesh(None)


rng = np.random.RandomState(7)


def _drop_mlp(bf16=False):
    m = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Dropout(0.25),
                      nn.Linear(32, 8))
    if bf16:
        m.to("bfloat16")
    m.train()
    return m


def _build(remat, zero, k, acc, bf16=False, policy="full", seed=11):
    paddle.seed(seed)
    m = _drop_mlp(bf16)
    opt = paddle.optimizer.AdamW(parameters=m.parameters(),
                                 learning_rate=0.05,
                                 multi_precision=bf16)
    if zero:
        opt._zero_enable(axis="dp", stage=zero)
    if remat:
        m.enable_recompute(policy)

    def one(xb, yb):
        loss = nn.functional.cross_entropy(m(xb), yb)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(one, scan_steps=k, dp_axis="dp",
                                accumulate_steps=acc if acc > 1 else None)
    return step, m


def _batches(k, batch=16):
    # deterministic per shape: the control and its remat twin must see
    # the SAME data (a shared module RNG would hand them different draws)
    r = np.random.RandomState(1000 + k)
    x = r.rand(k, batch, 16).astype("float32")
    y = r.randint(0, 8, (k, batch)).astype("int64")
    return paddle.to_tensor(x), paddle.to_tensor(y)


def _run(remat, zero, k, acc, bf16=False, policy="full"):
    step, m = _build(remat, zero, k, acc, bf16=bf16, policy=policy)
    x, y = _batches(k)
    l1 = np.asarray(step(x, y).numpy())
    l2 = np.asarray(step(x, y).numpy())
    params = [np.asarray(p.numpy()) for p in m.parameters()]
    key = np.asarray(paddle.get_rng_state().numpy())
    return l1, l2, params, key


# every (k, acc) shape: k=1 admits only whole-window acc=1
_MATRIX = [(z, k, a) for z in (0, 1, 3) for (k, a) in ((1, 1), (4, 1),
                                                       (4, 2))]
# tier-1 keeps a cheap zero0 k1 case, the windowed zero3 corner, and
# the zero3 acc1 corner (zero{0,3} x acc{1,2} dropout coverage at
# minimum compile cost); zero1 and the remaining product ride the slow
# tier (zero1's machinery is zero_sharding's well-covered middle
# child) — the tier-1 wall-clock budget is tight
_TIER1 = [(0, 1, 1), (3, 4, 2), (3, 4, 1)]
_SLOW = [c for c in _MATRIX if c not in _TIER1]


def _assert_remat_matches(zero, k, acc, bf16=False):
    ref = _run(False, zero, k, acc, bf16=bf16)
    got = _run(True, zero, k, acc, bf16=bf16)
    for a, b, what in [(ref[0], got[0], "losses#1"),
                       (ref[1], got[1], "losses#2")]:
        if bf16:
            np.testing.assert_allclose(
                a.astype(np.float32), b.astype(np.float32), rtol=2e-2,
                atol=2e-2, err_msg=what)
        else:
            assert a.tobytes() == b.tobytes(), \
                f"{what} diverged at zero{zero} k{k} acc{acc}"
    for pa, pb in zip(ref[2], got[2]):
        if bf16:
            np.testing.assert_allclose(pa.astype(np.float32),
                                       pb.astype(np.float32),
                                       rtol=2e-2, atol=2e-2)
        else:
            assert pa.tobytes() == pb.tobytes()
    # the generator advanced identically: remat consumed the RNG stream
    # exactly once per dropout, not once per replay
    assert ref[3].tobytes() == got[3].tobytes()


@pytest.mark.parametrize("zero,k,acc", _TIER1)
def test_remat_bitwise_matches_control_fp32(zero, k, acc):
    """Dropout model under remat == non-remat control, bitwise, through
    the zero/scan/accumulation machinery (RNG replay contract)."""
    _assert_remat_matches(zero, k, acc)


@pytest.mark.slow
@pytest.mark.parametrize("zero,k,acc", _SLOW)
def test_remat_bitwise_matches_control_fp32_full_matrix(zero, k, acc):
    _assert_remat_matches(zero, k, acc)


def test_remat_bf16_master_tolerance():
    _assert_remat_matches(3, 4, 2, bf16=True)


@pytest.mark.slow
def test_remat_bf16_master_tolerance_zero0():
    _assert_remat_matches(0, 4, 2, bf16=True)


@pytest.mark.slow
def test_remat_selective_policy_bitwise():
    _assert_remat_matches_policy("selective")


def _assert_remat_matches_policy(policy):
    ref = _run(False, 3, 4, 2)
    got = _run(True, 3, 4, 2, policy=policy)
    assert ref[0].tobytes() == got[0].tobytes()
    for pa, pb in zip(ref[2], got[2]):
        assert pa.tobytes() == pb.tobytes()


def test_remat_eager_bitwise_with_dropout():
    """Eager remat: ONE tape node for the segment, grads + RNG advance
    bitwise-equal to the plain tape."""
    def run(remat):
        paddle.seed(5)
        m = _drop_mlp()
        if remat:
            m.enable_recompute("full")
        x = paddle.to_tensor(np.random.RandomState(21)
                             .rand(4, 16).astype("float32"))
        x.stop_gradient = False
        loss = m(x).sum()
        loss.backward()
        return (np.asarray(loss.numpy()),
                [np.asarray(p._grad) for p in m.parameters()],
                np.asarray(x._grad),
                np.asarray(paddle.get_rng_state().numpy()))

    ref, got = run(False), run(True)
    assert ref[0].tobytes() == got[0].tobytes()
    for a, b in zip(ref[1], got[1]):
        assert a.tobytes() == b.tobytes()
    assert ref[2].tobytes() == got[2].tobytes()
    assert ref[3].tobytes() == got[3].tobytes()


def test_recompute_wrapper_form_and_fleet_api():
    paddle.seed(3)
    blk = nn.Sequential(nn.Linear(8, 8), nn.ReLU())
    x = paddle.to_tensor(rng.rand(2, 8).astype("float32"))
    wrapped = rc.recompute(blk.forward, policy="selective")
    np.testing.assert_array_equal(np.asarray(wrapped(x).numpy()),
                                  np.asarray(blk(x).numpy()))
    from paddle_tpu.distributed.fleet.utils import recompute as fleet_rc
    np.testing.assert_array_equal(np.asarray(fleet_rc(blk, x).numpy()),
                                  np.asarray(blk(x).numpy()))


# -- policy resolution ------------------------------------------------------

def test_policy_names_and_errors():
    import jax
    fn, name = rc.resolve_policy("full")
    assert name == "full" and fn is jax.checkpoint_policies.nothing_saveable
    fn, name = rc.resolve_policy("selective")
    assert name == "selective"
    assert rc.resolve_policy("none") == (None, "none")
    with pytest.raises(ValueError, match="unknown recompute policy"):
        rc.resolve_policy("bogus")
    with pytest.raises(ValueError):
        nn.Linear(2, 2).enable_recompute("bogus")
    # raw jax policies pass through (the power-user escape hatch)
    fn, name = rc.resolve_policy(jax.checkpoint_policies.dots_saveable)
    assert fn is jax.checkpoint_policies.dots_saveable


def test_offload_falls_back_loudly_without_pinned_host(monkeypatch):
    # the installed CPU backend has a pinned_host space: offload is real
    assert rc.host_offload_available() is True
    assert rc.resolve_policy("offload", strict=True)[1] == "offload"
    # a backend without one: loud fallback, not a silent no-op
    monkeypatch.setattr(rc, "host_offload_available", lambda: False)
    with pytest.warns(UserWarning, match="pinned_host"):
        fn, name = rc.resolve_policy("offload")
    assert name == "selective"
    with pytest.raises(RuntimeError, match="pinned_host"):
        rc.resolve_policy("offload", strict=True)


@pytest.mark.parametrize("pinned_host", [True, False])
def test_offload_policy_trains(pinned_host, monkeypatch):
    """Offloaded residuals (or, without a pinned_host space, the loud
    selective fallback) change where values live, never the loss."""
    ref = _run(False, 0, 1, 1)
    if pinned_host:
        got = _run(True, 0, 1, 1, policy="offload")
    else:
        monkeypatch.setattr(rc, "host_offload_available", lambda: False)
        with pytest.warns(UserWarning, match="pinned_host"):
            got = _run(True, 0, 1, 1, policy="offload")
    assert ref[0].tobytes() == got[0].tobytes()


# -- segment constraints + state threading ----------------------------------

def test_backward_inside_segment_rejected():
    m = nn.Linear(4, 4)

    def seg(x):
        loss = m(x).sum()
        loss.backward()
        return loss

    x = paddle.to_tensor(rng.rand(2, 4).astype("float32"))
    with pytest.raises(RuntimeError, match="forward-only"):
        rc.recompute(seg, x)


def test_new_state_inside_segment_rejected():
    def seg(x):
        p = paddle.Parameter(np.ones((2, 2), np.float32))
        return x @ p

    x = paddle.to_tensor(rng.rand(2, 2).astype("float32"))
    x.stop_gradient = False
    with pytest.raises(RuntimeError, match="NEW framework state"):
        rc.recompute(seg, x)


def test_batchnorm_buffers_advance_exactly_once():
    """Mutated buffers thread through the remat segment: running stats
    advance one run's worth and match the non-remat control."""
    def run(remat):
        paddle.seed(9)
        m = nn.Sequential(nn.Linear(8, 8), nn.BatchNorm1D(8), nn.ReLU())
        m.train()
        if remat:
            m.enable_recompute("full")
        x = paddle.to_tensor(np.random.RandomState(22)
                             .rand(4, 8).astype("float32"))
        x.stop_gradient = False
        loss = m(x).sum()
        loss.backward()
        bn = m[1]
        return (np.asarray(loss.numpy()),
                np.asarray(bn._mean.numpy()),
                np.asarray(bn._variance.numpy()),
                [np.asarray(p._grad) for p in m.parameters()])

    ref, got = run(False), run(True)
    assert ref[0].tobytes() == got[0].tobytes()
    assert ref[1].tobytes() == got[1].tobytes()
    assert ref[2].tobytes() == got[2].tobytes()
    for a, b in zip(ref[3], got[3]):
        assert a.tobytes() == b.tobytes()


def test_scoped_key_replays_from_same_origin():
    """recompute inside a scoped_key block draws the same deterministic
    keys as the plain run AND leaves the counter where the plain run
    would."""
    import jax

    def seg(x):
        h = nn.functional.dropout(x, p=0.5, training=True)
        return nn.functional.dropout(h, p=0.5, training=True)

    x = paddle.to_tensor(np.ones((64,), np.float32))
    x.stop_gradient = False
    base = jax.random.PRNGKey(42)
    with core_random.scoped_key(base):
        ref = np.asarray(seg(x).numpy())
        i_ref = core_random._scoped_stack[-1].i
    with core_random.scoped_key(base):
        got = np.asarray(rc.recompute(seg, x).numpy())
        i_got = core_random._scoped_stack[-1].i
    assert ref.tobytes() == got.tobytes()
    assert i_ref == i_got == 2


def test_zero_arg_forward_layer_recompute_runs_immediately():
    """A recompute-enabled Layer whose forward takes no inputs must
    still RUN (the public recompute()'s no-arg shape returns a wrapper;
    the Layer seam routes around it)."""
    class Gen(nn.Layer):
        def __init__(self):
            super().__init__()
            self.w = paddle.Parameter(np.ones((3, 3), np.float32))

        def forward(self):
            return (self.w * 2.0).sum()

    g = Gen()
    g.enable_recompute("full")
    out = g()
    assert float(np.asarray(out.numpy())) == 18.0
    out.backward()
    assert g.w._grad is not None


def test_eval_mode_skips_the_remat_region():
    m = _drop_mlp()
    m.enable_recompute("full")
    x = paddle.to_tensor(rng.rand(2, 16).astype("float32"))
    before = rc._seg_counter[0]
    m.eval()
    m(x)
    assert rc._seg_counter[0] == before  # no segment dispatched
    m.train()
    x2 = paddle.to_tensor(rng.rand(2, 16).astype("float32"))
    x2.stop_gradient = False
    m(x2)
    assert rc._seg_counter[0] > before
    m.disable_recompute()
    before = rc._seg_counter[0]
    m(x2)
    assert rc._seg_counter[0] == before


# -- the jaxpr-liveness meter (the bench claim's meter) ---------------------

def test_jaxpr_meter_shows_remat_savings():
    """Per-block full remat lowers the traced liveness peak of the
    compiled step — the deterministic CPU-side evidence the
    mlp_zero3_remat_jaxpr_peak_mb row gates (XLA CPU executables are
    remat-blind: barriers stripped + CSE)."""
    def build(remat):
        paddle.seed(0)
        blks = [nn.Sequential(nn.Linear(32, 256), nn.ReLU(),
                              nn.Linear(256, 32)) for _ in range(3)]
        m = nn.Sequential(*(blks + [nn.Linear(32, 8)]))
        opt = paddle.optimizer.AdamW(parameters=m.parameters(),
                                     learning_rate=0.01)
        if remat:
            for blk in blks:
                blk.enable_recompute("full")

        def one(x, y):
            loss = nn.functional.cross_entropy(m(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        step = paddle.jit.to_static(one, scan_steps=2)
        x = paddle.to_tensor(rng.rand(2, 512, 32).astype("float32"))
        y = paddle.to_tensor(rng.randint(0, 8, (2, 512)).astype("int64"))
        step(x, y)
        return next(iter(step.traced_memory_stats().values()))

    ctl = build(False)
    rem = build(True)
    assert rem["peak_bytes"] < ctl["peak_bytes"], (ctl, rem)
    assert ctl["argument_bytes"] == rem["argument_bytes"]


def test_jaxpr_meter_basics():
    import jax
    from paddle_tpu.observability import jaxpr_mem
    assert jaxpr_mem.aval_bytes(
        jax.ShapeDtypeStruct((4, 8), "float32")) == 128

    def f(a, b):
        c = a @ b       # born 128B
        d = c + 1.0     # c frees after this
        return d.sum()

    closed = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((4, 8), "float32"),
                               jax.ShapeDtypeStruct((8, 4), "float32"))
    stats = jaxpr_mem.jaxpr_peak_stats(closed)
    assert stats["argument_bytes"] == 128 + 128
    assert stats["output_bytes"] == 4
    # high water at the matmul: both args live + c born (a/b free after
    # it, so d never coexists with them)
    assert stats["peak_bytes"] == 256 + 64


# -- XLA attribution: the host_offload kind ---------------------------------

def test_program_stats_carries_host_offload_kind():
    import jax
    from paddle_tpu.observability import memory
    compiled = jax.jit(lambda v: v * 2).lower(
        jax.ShapeDtypeStruct((8,), "float32")).compile()
    stats = memory.program_stats(compiled)
    assert stats["host_offload_bytes"] == 0  # CPU: nothing parked
    # records from pre-host_offload captures still export cleanly
    legacy = {f"{k}_bytes": 1 for k in memory.MEMORY_KINDS}
    legacy["peak_bytes"] = 1
    memory.export_program_memory("legacy_entry", legacy)


def test_state_ledger_has_host_offload_category():
    from paddle_tpu.observability import memory
    assert "host_offload" in memory.STATE_CATEGORIES
    # CPU arrays live in the device's DEFAULT host space: NOT parked
    t = paddle.to_tensor(np.ones((4,), np.float32))
    assert memory.is_host_parked(t._value) is False


# -- analysis integrations --------------------------------------------------

def test_remat_ladder_twin_verifies_clean():
    from paddle_tpu.analysis import errors, ladder
    findings, summary = ladder.verify_ladder(configs=["remat"])
    assert not findings, [str(f) for f in findings]
    assert summary["remat"] == [3, 9]  # fused surface vs expanded replay


def test_verifier_accepts_stamped_replay_rejects_unstamped():
    from paddle_tpu import static
    from paddle_tpu.analysis import check_graph, errors
    from paddle_tpu.static.program import _OpRecord

    def build(stamped):
        prog = static.Program()
        with static.program_guard(prog):
            x = static.data("x", [2, 4], "float32")
            w = static.create_parameter([4, 4], "float32")
            h = paddle.matmul(x, w)
            loss = paddle.mean(h)
        op = prog.ops[0]
        replay = (lambda *a, _fn=op.fn, **k: _fn(*a, **k))
        if stamped:
            replay = rc.remat_replay(replay)
        prog.ops.append(_OpRecord(replay, op.arg_slots, op.kwarg_slots,
                                  op.out_slots, op.name))
        with static.program_guard(prog):
            g = paddle.sum(h)
        return prog, [loss, g]

    prog, targets = build(stamped=True)
    assert not errors(check_graph(prog, targets=targets))
    prog, targets = build(stamped=False)
    bad = errors(check_graph(prog, targets=targets))
    assert any(f.rule == "duplicate-slot-write" for f in bad)

    # a STAMPED op computing from DIFFERENT inputs into the slot is not
    # a rematerialization — the exemption is structural, not name-based
    prog, targets = build(stamped=True)
    replay_op = next(op for op in prog.ops if rc.is_remat_replay(op.fn))
    replay_op.arg_slots = list(reversed(replay_op.arg_slots))
    bad = errors(check_graph(prog, targets=targets))
    assert any(f.rule == "duplicate-slot-write" for f in bad)


def test_raw_remat_lint_rule(tmp_path):
    from paddle_tpu.analysis import lint_source
    p = tmp_path / "model.py"
    p.write_text(
        "import jax\n"
        "from jax import checkpoint as ckpt\n"
        "def forward(x):\n"
        "    return jax.checkpoint(lambda v: v * 2)(x)\n"
        "def forward2(x):\n"
        "    return jax.remat(lambda v: v + 1)(x)\n"
        "def forward3(x):\n"
        "    return ckpt(lambda v: v - 1)(x)\n"
        "@jax.checkpoint\n"
        "def forward4(x):\n"
        "    return x * 3\n")
    found = [f for f in lint_source(paths=[str(p)])
             if f.rule == "raw-remat-outside-policy"]
    assert len(found) == 4  # dotted + remat + bare-import + decorator
    # the default sweep stays clean: the policy surface is the one caller
    assert not [f for f in lint_source()
                if f.rule == "raw-remat-outside-policy"]
    # ... and stays exempt even when named EXPLICITLY
    import os as _os
    repo = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    assert not [f for f in lint_source(
                    paths=[_os.path.join(repo, "paddle_tpu",
                                         "recompute.py")])
                if f.rule == "raw-remat-outside-policy"]


def test_recompute_records_one_fused_op_under_program_guard():
    from paddle_tpu import static
    prog = static.Program()
    with static.program_guard(prog):
        x = static.data("x", [2, 8], "float32")
        blk = nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                            nn.Linear(16, 8))
        h = rc.recompute(blk, x, policy="full")
        loss = paddle.mean(h)
    names = prog.op_names()
    assert names.count("recompute") == 1
    # capture probes must NOT leak into the program
    assert "matmul" not in names[:names.index("recompute")]
    assert not prog.verify(targets=[loss])


def test_mem_view_diff(tmp_path, capsys):
    import json
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import mem_view

    def snap(peak, cat_bytes):
        return {"programs": {"step#0:scan": {
                    **{f"{k}_bytes": 10 for k in
                       ("argument", "output", "temp", "alias",
                        "generated_code")},
                    "peak_bytes": peak}},
                "state": {"categories": {"param": {
                              "bytes": cat_bytes,
                              "global_bytes": cat_bytes * 8,
                              "count": 2}},
                          "total_bytes": cat_bytes,
                          "total_global_bytes": cat_bytes * 8}}

    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps(snap(4 << 20, 1 << 20)))
    b.write_text(json.dumps(snap(3 << 20, 2 << 20)))
    rc_code = mem_view.main(["--diff", str(a), str(b)])
    out = capsys.readouterr().out
    assert rc_code == 0
    assert "d_peak_mb" in out and "-1.000" in out   # program peak fell
    assert "+1.000" in out                          # param bytes rose
    # a budget combined with --diff gates the AFTER side, never no-ops
    assert mem_view.main(["--diff", str(a), str(b), "--budget-mb",
                          "2"]) == 3
    capsys.readouterr()
    assert mem_view.main(["--diff", str(a), str(b), "--budget-mb",
                          "64"]) == 0
    capsys.readouterr()
    with pytest.raises(SystemExit):
        mem_view.main(["--diff", str(a), str(b), "--out",
                       str(tmp_path / "c.json")])
    capsys.readouterr()
