"""ZeRO-1/2/3 sharded data parallelism inside the scan step.

The contract under test: ``to_static(one_step, scan_steps=k,
dp_axis='dp')`` + ``optimizer._zero_enable()`` must be OBSERVABLY
identical to the replicated control — bitwise-equal per-inner-step losses
and final params on the 8-device CPU mesh — while the optimizer state
(and, at stage 3, the parameters themselves) actually lives 1/dp per rank
and the compiled HLO's gradient reduction is bucketed reduce-scatter (+
param all-gather: after the update for stages 1/2, just-in-time before
the forward for stage 3) instead of per-param all-reduce. Gradient
accumulation windows (``accumulate_steps=a``) fire the reduce/update once
per window; the sharded global-norm clip psums per-shard square sums
(tolerance-level parity — the summation order differs from the per-param
control by design)."""
import re

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor, nn
from paddle_tpu.distributed import parallel_env

DP = 8


@pytest.fixture(autouse=True)
def _mesh():
    mesh = parallel_env.make_mesh({"dp": DP})
    parallel_env.set_mesh(mesh)
    yield mesh
    parallel_env.set_mesh(None)
    from paddle_tpu.distributed.fleet.base import topology
    topology.set_hybrid_communicate_group(None)


rng = np.random.RandomState(7)


def _mlp(bf16=False):
    m = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 8))
    if bf16:
        m.to("bfloat16")
    return m


def _build(zero_stage, k, bf16, comm_buffer_mb=None, seed=11,
           accumulate=None, grad_clip=None, prefetch=None):
    paddle.seed(seed)
    m = _mlp(bf16)
    opt = paddle.optimizer.AdamW(parameters=m.parameters(),
                                 learning_rate=0.05,
                                 multi_precision=bf16,
                                 grad_clip=grad_clip)
    if zero_stage:
        opt._zero_enable(axis="dp", stage=zero_stage,
                         comm_buffer_mb=comm_buffer_mb, prefetch=prefetch)

    def one(xb, yb):
        loss = nn.functional.cross_entropy(m(xb), yb)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(one, scan_steps=k, dp_axis="dp",
                                accumulate_steps=accumulate)
    return step, m, opt


def _batches(k, batch=16):
    x = rng.rand(k, batch, 16).astype("float32")
    y = rng.randint(0, 8, (k, batch)).astype("int64")
    return paddle.to_tensor(x), paddle.to_tensor(y)


_CTRL = {}


def _control_run(k, bf16):
    """Replicated-control reference for (k, bf16): batches, first-call
    losses, post-step params, second-call losses. Computed once and
    shared by the three stage parametrizations (same program, same
    data — rebuilding it per stage only burns compile time)."""
    key = (k, bf16)
    if key not in _CTRL:
        x, y = _batches(k)
        s0, m0, _ = _build(0, k, bf16)
        ref1 = s0(x, y).numpy().tobytes()
        params = [np.asarray(p._value).tobytes() for p in m0.parameters()]
        ref2 = s0(x, y).numpy().tobytes()
        _CTRL[key] = (x, y, ref1, params, ref2)
    return _CTRL[key]


@pytest.mark.parametrize("stage", [1, 2, 3])
@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("bf16", [False, True],
                         ids=["fp32", "bf16_master"])
def test_zero_bitwise_matches_replicated_control(stage, k, bf16):
    """Acceptance: zero{1,2,3} × scan_steps {1,4} × {fp32, bf16+master}
    sharded scan losses and final params equal the replicated control
    BITWISE (elementwise update math on a shard == on the whole; stage 3
    reads params through the just-in-time gathered store views)."""
    x, y, ref1, ctrl_params, ref2 = _control_run(k, bf16)
    s1, m1, _ = _build(stage, k, bf16)
    got = s1(x, y).numpy()
    assert ref1 == got.tobytes(), got
    for p1, ctrl in zip(m1.parameters(), ctrl_params):
        assert np.asarray(p1._value).tobytes() == ctrl, p1.name
    # and through the donated carry on a second program call
    assert ref2 == s1(x, y).numpy().tobytes()


def test_zero_state_lives_sharded_1_over_dp():
    """Per-rank optimizer-state bytes shrink ~1/dp: every flat store is
    laid out PartitionSpec('dp', None) and each device holds rows/dp —
    checked through shardcheck's residency verifier (shard shape AND the
    1/dp state-bytes accounting live in one place now)."""
    from paddle_tpu.analysis import check_zero_residency
    k = 2
    s1, _m, opt = _build(1, k, bf16=False)
    x, y = _batches(k)
    s1(x, y)
    stores = [sd[slot] for sd in opt._zero["stores"] for slot in sd]
    assert stores
    assert check_zero_residency(opt) == []
    # spot-check the verifier is looking at real shards, not vacuous
    arr = stores[0].tensor._value
    assert len(arr.sharding.device_set) == DP


def test_zero_hlo_replaces_psum_with_scatter_gather():
    """The compiled program's reduction changes shape: control = every
    param grad all-reduced whole; zero = one reduce-scatter per bucket +
    one all-gather per bucket (plus the scalar loss pmean)."""
    k = 2
    x, y = _batches(k)
    s0, m0, _o0 = _build(0, k, bf16=False)
    s0(x, y)
    s1, _m1, _o1 = _build(1, k, bf16=False)
    s1(x, y)

    ctrl = {s["op"]: s for s in s0.collective_stats()}
    zero = {s["op"]: s for s in s1.collective_stats()}
    # control: per-param psum of every trainable param + the loss pmean.
    # The compiler may combine the five psums into one tuple all-reduce,
    # so hold it to the bytes that cross, not the instruction count
    grad_bytes = sum(int(np.prod(p.shape)) * 4 for p in m0.parameters())
    assert ctrl["all-reduce"]["bytes"] >= grad_bytes + 4
    assert "reduce-scatter" not in ctrl
    # zero: bucketed scatter/gather; only the scalar loss pmean remains
    assert zero["all-reduce"]["bytes"] <= 8  # one f32 scalar
    assert zero["reduce-scatter"]["axis"] == "dp"
    # the exact scatter/gather multiset is shardcheck's budget contract:
    # the compiled per-execution counts must equal the predicted
    # (stage, k, buckets) schedule — no finding means they do
    from paddle_tpu.analysis import check_collective_budget
    assert check_collective_budget(s1) == []

    # exported counters carry the (op, axis) labels
    for c in ('collective_bytes{op="reduce-scatter",axis="dp"}',
              'collective_count{op="reduce-scatter",axis="dp"}'):
        monitor.stat_reset(c)
    s1.export_collective_bytes()
    assert monitor.stat_get(
        'collective_bytes{op="reduce-scatter",axis="dp"}') > 0
    assert monitor.stat_get(
        'collective_count{op="reduce-scatter",axis="dp"}') >= 1


def test_zero_comm_buffer_size_buckets():
    """comm_buffer_mb caps the bucket payload: tiny cap → one bucket per
    param, one reduce-scatter each in the HLO."""
    k = 1
    s1, _m, opt = _build(1, k, bf16=False, comm_buffer_mb=0.0001)
    n_buckets = len(opt._zero["buckets"])
    assert n_buckets == 4  # 2 weights + 2 biases, each over the tiny cap
    x, y = _batches(k)
    first = s1(x, y).numpy()
    # shardcheck reads the bucket count out of the partition provenance
    # and holds the compiled schedule to one rs+ag pair per bucket
    from paddle_tpu.analysis import (check_collective_budget,
                                     infer_zero_layout)
    layout = infer_zero_layout(s1)
    assert layout["stage"] == 1 and layout["n_buckets"] == n_buckets
    assert check_collective_budget(s1) == []
    # bitwise parity holds regardless of bucketing (fresh first calls on
    # both sides — state advances per call)
    s0, _m0, _o0 = _build(0, k, bf16=False)
    assert s0(x, y).numpy().tobytes() == first.tobytes()


def test_zero_partition_and_verifier():
    """The scan partition records the sharded carry and dp axis; the
    static-analysis pass accepts the build."""
    from paddle_tpu import analysis
    k = 2
    s1, _m, opt = _build(1, k, bf16=False)
    x, y = _batches(k)
    s1(x, y)
    part = s1._last_partition
    assert part["dp_axis"] == "dp"
    store_uids = {sd[slot].tensor._state_uid
                  for sd in opt._zero["stores"] for slot in sd}
    # every live store rides the carry as sharded, donated state
    assert store_uids <= set(part["sharded"])
    assert store_uids <= set(part["donated"])
    assert analysis.errors(s1.verify()) == []
    # seeded smell: a sharded store the program silently ignores
    part["skipped"] = list(part["skipped"]) + [sorted(store_uids)[0]]
    bad = s1.verify()
    assert any(f.rule == "sharded-state-skipped" and
               f.severity == "warning" for f in bad)
    # seeded hazard: a sharded grad surviving the dp carry
    part["donated_grads"] = list(part["donated_grads"]) + \
        [sorted(store_uids)[0]]
    bad = s1.verify()
    assert any(f.rule == "sharded-grad-carry" and f.severity == "error"
               for f in bad)


def test_verifier_flags_rank_divergent_bucket_order():
    """Two rank programs whose reduce-scatter sequences agree on op kind
    and axis but not payload (swapped bucket layout) must be flagged —
    that skew cross-matches different buckets on the wire. Swapped
    buckets are a pure permutation of the same collective multiset, so
    the checker diagnoses it as collective-schedule-skew (a
    deterministic reorder, e.g. pipelining enabled on one rank only)
    rather than raw per-position mismatches."""
    from paddle_tpu import analysis, static
    from paddle_tpu.core.dispatch import call_op

    def rank_prog(bucket_bytes):
        prog = static.Program()
        with static.program_guard(prog):
            g = static.data("g", [4], "float32")
            out = g
            for nb in bucket_bytes:
                def _rs(v, _nb=nb):
                    return v
                _rs._collective_axis = "dp"
                _rs._collective_nbytes = nb
                out = call_op(_rs, out, op_name="c_reducescatter")
            paddle.sum(out)
        return prog

    ok = analysis.check_collective_order(
        [rank_prog([4096, 1024]), rank_prog([4096, 1024])],
        mesh_axes=("dp",))
    assert ok == []
    bad = analysis.check_collective_order(
        [rank_prog([4096, 1024]), rank_prog([1024, 4096])],
        mesh_axes=("dp",))
    assert any(f.rule == "collective-schedule-skew" and
               f.severity == "error" for f in bad)
    # a genuinely divergent layout (different payload multiset) still
    # reports the per-position mismatch, not a schedule reorder
    bad2 = analysis.check_collective_order(
        [rank_prog([4096, 1024]), rank_prog([4096, 999])],
        mesh_axes=("dp",))
    assert any(f.rule == "collective-order-mismatch" and
               "bucket" in f.message for f in bad2)
    assert not any(f.rule == "collective-schedule-skew" for f in bad2)


def test_zero_with_grad_scaler_parity():
    """GradScaler + ZeRO: found-inf evaluates over the reduced shard and
    the scaled update still matches the replicated-control scaler run."""
    k = 2
    x, y = _batches(k)

    def build(stage):
        paddle.seed(21)
        m = _mlp()
        opt = paddle.optimizer.AdamW(parameters=m.parameters(),
                                     learning_rate=0.05)
        if stage:
            opt._zero_enable(axis="dp", stage=stage)
        scaler = paddle.amp.GradScaler(init_loss_scaling=128.0)

        def one(xb, yb):
            loss = nn.functional.cross_entropy(m(xb), yb)
            scaler.scale(loss).backward()
            scaler.step(opt)
            opt.clear_grad()
            return loss

        return paddle.jit.to_static(one, scan_steps=k, dp_axis="dp"), m

    s0, m0 = build(0)
    s1, m1 = build(1)
    l0 = s0(x, y).numpy()
    l1 = s1(x, y).numpy()
    np.testing.assert_array_equal(l0, l1)
    for p0, p1 in zip(m0.parameters(), m1.parameters()):
        np.testing.assert_array_equal(np.asarray(p0._value),
                                      np.asarray(p1._value))


@pytest.mark.parametrize("stage", [1, 3])
def test_zero_scaler_accumulation_window_parity(stage):
    """GradScaler across an accumulation window: grads stay scaled until
    the boundary, the found-inf check covers the whole window on the
    reduced shard, and losses/params match the replicated-control run of
    the same window."""
    k, a = 4, 2
    x, y = _batches(k)

    def build(zero):
        paddle.seed(23)
        m = _mlp()
        opt = paddle.optimizer.AdamW(parameters=m.parameters(),
                                     learning_rate=0.05)
        if zero:
            opt._zero_enable(axis="dp", stage=zero)
        scaler = paddle.amp.GradScaler(init_loss_scaling=128.0)

        def one(xb, yb):
            loss = nn.functional.cross_entropy(m(xb), yb)
            scaler.scale(loss).backward()
            scaler.step(opt)
            opt.clear_grad()
            return loss

        return paddle.jit.to_static(one, scan_steps=k, dp_axis="dp",
                                    accumulate_steps=a), m

    s0, m0 = build(0)
    s1, m1 = build(stage)
    l0 = s0(x, y).numpy()
    l1 = s1(x, y).numpy()
    np.testing.assert_allclose(l0, l1, rtol=1e-6)
    for p0, p1 in zip(m0.parameters(), m1.parameters()):
        np.testing.assert_allclose(np.asarray(p0._value),
                                   np.asarray(p1._value), rtol=1e-5,
                                   atol=1e-7, err_msg=p0.name)


def test_scaler_manual_unscale_in_window_rejected():
    """scaler.unscale_ inside an accumulation window would mix unscaled
    and scaled micro gradients (the next backward adds SCALED grads onto
    the unscaled sum) — rejected loudly at trace time on every path."""
    paddle.seed(31)
    m = _mlp()
    opt = paddle.optimizer.AdamW(parameters=m.parameters(),
                                 learning_rate=0.05)
    scaler = paddle.amp.GradScaler(init_loss_scaling=64.0)

    def one(xb, yb):
        loss = nn.functional.cross_entropy(m(xb), yb)
        scaler.scale(loss).backward()
        scaler.unscale_(opt)  # the eager clip workflow — not windowable
        scaler.step(opt)
        opt.clear_grad()
        return loss

    s = paddle.jit.to_static(one, scan_steps=2, dp_axis="dp",
                             accumulate_steps=2)
    x, y = _batches(2)
    with pytest.raises(RuntimeError, match="accumulation window"):
        s(x, y)


def test_zero_decay_fn_row_mask_and_missing_grads():
    """The two row-mask paths through the bound shard_map step: AdamW's
    apply_decay_param_fun becomes a per-row mask, and a param without a
    grad holds still. Losses and the held param are bitwise vs the
    replicated control. The updated params are held to float32 rounding:
    the hold-still `select` changes which multiply-adds LLVM contracts
    into FMAs on XLA:CPU (always allowed there), so from the second
    step — the first with non-zero moments — the two programs round
    `b*m + (1-b)*g` differently by a few ulp (0 with
    --xla_cpu_max_isa=SSE4_2, which has no FMA)."""
    k = 2
    x, y = _batches(k)

    def build(stage):
        paddle.seed(17)
        m = _mlp()
        no_decay = {m[0].bias.name, m[2].bias.name}
        frozen = m[2].bias  # never receives a grad in this step
        opt = paddle.optimizer.AdamW(
            parameters=m.parameters(), learning_rate=0.05,
            apply_decay_param_fun=lambda n: n not in no_decay)
        if stage:
            opt._zero_enable(axis="dp", stage=stage)

        def one(xb, yb):
            loss = nn.functional.cross_entropy(m(xb), yb)
            loss.backward()
            frozen._grad = None  # simulate an unused head this step
            opt.step()
            opt.clear_grad()
            return loss

        return paddle.jit.to_static(one, scan_steps=k, dp_axis="dp"), m

    s0, m0 = build(0)
    s1, m1 = build(1)
    assert s0(x, y).numpy().tobytes() == s1(x, y).numpy().tobytes()
    assert np.asarray(m0[2].bias._value).tobytes() == \
        np.asarray(m1[2].bias._value).tobytes()
    for p0, p1 in zip(m0.parameters(), m1.parameters()):
        np.testing.assert_allclose(np.asarray(p0._value),
                                   np.asarray(p1._value),
                                   rtol=5e-6, atol=1e-7, err_msg=p0.name)


def test_overflow_skips_whole_update_zero_and_control():
    """An inf gradient must leave params AND moments AND masters exactly
    where they were — in the ZeRO shard path (stages 1 and 3, the latter
    through the eager store-view params) and the replicated scaler path
    alike (one poisoned moment NaNs every later step otherwise)."""
    for zero in (0, 1, 3):
        paddle.seed(33)
        m = _mlp()
        opt = paddle.optimizer.AdamW(parameters=m.parameters(),
                                     learning_rate=0.05)
        if zero:
            opt._zero_enable(axis="dp", stage=1)
        scaler = paddle.amp.GradScaler(init_loss_scaling=8.0)
        params = list(m.parameters())
        before_p = [np.asarray(p._value).copy() for p in params]
        loss = nn.functional.cross_entropy(
            m(paddle.to_tensor(rng.rand(8, 16).astype("float32"))),
            paddle.to_tensor(rng.randint(0, 8, 8).astype("int64")))
        scaler.scale(loss).backward()
        params[0]._grad = params[0]._grad.at[0, 0].set(np.inf)
        scaler.step(opt)
        opt.clear_grad()
        for p, old in zip(params, before_p):
            np.testing.assert_array_equal(np.asarray(p._value), old)
        state = opt.state_dict()
        for k, v in state.items():
            if hasattr(v, "numpy"):
                assert np.all(np.isfinite(np.asarray(v.numpy(),
                                                     np.float32))), k
        # and a following finite step still moves the params
        loss = nn.functional.cross_entropy(
            m(paddle.to_tensor(rng.rand(8, 16).astype("float32"))),
            paddle.to_tensor(rng.randint(0, 8, 8).astype("int64")))
        scaler.scale(loss).backward()
        scaler.step(opt)
        opt.clear_grad()
        moved = any(not np.array_equal(np.asarray(p._value), old)
                    for p, old in zip(params, before_p))
        assert moved and all(
            np.all(np.isfinite(np.asarray(p._value, np.float32)))
            for p in params)


def test_zero_enable_conflicting_recall_raises():
    paddle.seed(6)
    m = _mlp()
    opt = paddle.optimizer.Adam(parameters=m.parameters())
    opt._zero_enable(axis="dp", stage=1)
    assert opt._zero_enable(axis="dp", stage=1) == opt._zero["n_sharded"]
    with pytest.raises(RuntimeError, match="already enabled"):
        opt._zero_enable(axis="dp", stage=2)


def test_zero_rejects_unsupported_configs():
    """The remaining rejections stay loud AND name the issue that scoped
    them; ClipGradByGlobalNorm/ByValue and per-param lr are now routed
    through the flat-view path instead of rejected."""
    paddle.seed(5)
    m = _mlp()
    lamb = paddle.optimizer.Lamb(parameters=m.parameters())
    with pytest.raises(NotImplementedError, match="non-elementwise"):
        lamb._zero_enable(axis="dp")
    with pytest.raises(NotImplementedError, match="ISSUE 5"):
        lamb._zero_enable(axis="dp")
    # per-TENSOR-norm clip still can't reassemble on a flat shard
    clip = paddle.nn.ClipGradByNorm(1.0)
    adam = paddle.optimizer.Adam(parameters=m.parameters(), grad_clip=clip)
    with pytest.raises(NotImplementedError, match="ISSUE 5"):
        adam._zero_enable(axis="dp")
    # global-norm and value clip now enable fine
    for ok_clip in (paddle.nn.ClipGradByGlobalNorm(1.0),
                    paddle.nn.ClipGradByValue(1.0)):
        paddle.seed(5)
        m2 = _mlp()
        opt = paddle.optimizer.Adam(parameters=m2.parameters(),
                                    grad_clip=ok_clip)
        assert opt._zero_enable(axis="dp") > 0
    sgd = paddle.optimizer.SGD(parameters=m.parameters())
    with pytest.raises(ValueError, match="no axis"):
        sgd._zero_enable(axis="nope")


def test_zero3_param_residency_and_carry():
    """Stage 3: the flat sharded param store is the ONLY parameter
    residency — live Parameter objects are store views outside the
    framework-state registry, so no full parameter rides the donated
    carry; per-rank optimizer+param state bytes measure ~1/dp."""
    k = 2
    s3, m, opt = _build(3, k, bf16=False)
    x, y = _batches(k)
    before = [np.asarray(p._value).copy() for p in m.parameters()]
    s3(x, y)
    # params converted to views: unregistered, store-backed, readable
    for p, old in zip(m.parameters(), before):
        assert p._state_uid is None
        assert "_value" not in p.__dict__
        assert not np.array_equal(np.asarray(p._value), old), p.name
    pstores = [sd["param"] for sd in opt._zero["stores"]]
    assert pstores
    # shard shape AND the 1/dp state-bytes accounting — moment, master
    # and param stores alike — are shardcheck's residency contract
    from paddle_tpu.analysis import check_zero_residency
    assert check_zero_residency(opt) == []
    # the carry holds the sharded stores, not the params
    part = s3._last_partition
    store_uids = {sd[slot].tensor._state_uid
                  for sd in opt._zero["stores"] for slot in sd
                  if slot != "gacc"}
    assert store_uids <= set(part["donated"])
    assert store_uids <= set(part["sharded"])
    # eager writes round-trip through the store (checkpoint load path)
    p0 = list(m.parameters())[0]
    p0.set_value(np.zeros(p0.shape, np.float32))
    assert np.all(np.asarray(p0._value) == 0.0)
    # the verifier accepts the build (gacc skipping included)
    from paddle_tpu import analysis
    assert analysis.errors(s3.verify()) == []


def test_zero3_hlo_ag_fwd_rs_pattern():
    """Stage-3 compiled HLO, serial schedule (prefetch=False): params
    all-gather JUST-IN-TIME before the forward matmuls, the gradient
    reduce-scatter follows them, and no all-gather trails the update
    (refreshed params stay sharded). The pipelined default moves that
    gather to the tail of the previous iteration — so the body's first
    all-gather lands AFTER the reduce-scatter — without changing the
    per-execution collective counts.

    Deliberately the raw-HLO CANARY: every other collective-count
    assertion in this file rides shardcheck's budget verifier; this one
    keeps matching the compiled text directly so a parser regression in
    hlo_bytes/shardcheck cannot silently blind the whole suite."""
    k = 2
    s3, _m, opt = _build(3, k, bf16=False, prefetch=False)
    x, y = _batches(k)
    s3(x, y)
    hlo = s3.hlo_text()
    body = max((c for c in hlo.split("\n\n") if "reduce-scatter" in c),
               key=len, default=hlo)
    i_ag = body.index("all-gather")
    i_dot = body.index("dot(", i_ag)
    i_rs = body.index("reduce-scatter", i_dot)
    assert i_ag < i_dot < i_rs
    stats = {s["op"]: s for s in s3.collective_stats(per_execution=True)}
    n_buckets = len(opt._zero["buckets"])
    # exactly one gather (forward) + one reduce-scatter per bucket per
    # step — per-execution counts prove it through the scan trip count
    assert stats["all-gather"]["count"] == n_buckets * k
    assert stats["reduce-scatter"]["count"] == n_buckets * k
    assert stats.get("all-reduce", {"bytes": 0})["bytes"] <= 8 * k
    # pipelined twin: the prefetch slot is warmed by a tail gather, so
    # the loop body now ENDS with an all-gather (it feeds the NEXT
    # iteration's forward) while the collective budget stays identical
    sp, _mp, optp = _build(3, k, bf16=False, seed=11)
    sp(x, y)
    hlop = sp.hlo_text()
    bodyp = max((c for c in hlop.split("\n\n") if "reduce-scatter" in c),
                key=len, default=hlop)
    assert bodyp.rindex("all-gather") > bodyp.index("reduce-scatter")
    statsp = {s["op"]: s for s in sp.collective_stats(per_execution=True)}
    assert statsp["all-gather"]["count"] == stats["all-gather"]["count"]
    assert statsp["reduce-scatter"]["count"] == \
        stats["reduce-scatter"]["count"]


def test_accumulation_matches_big_batch():
    """a accumulated micro steps == one step on the a-times batch (up to
    dtype tolerance: the big batch sums losses in one reduction, the
    window sums a per-micro means — fp32 rtol 1e-5)."""
    a, bs = 4, 16
    # dedicated rng: the comparison tolerance is calibrated to THIS data,
    # so the inputs must not shift with whichever tests ran before
    drng = np.random.RandomState(42)
    xs = drng.rand(a, bs, 16).astype("float32")
    ys = drng.randint(0, 8, (a, bs)).astype("int64")
    s_acc, m_acc, _ = _build(0, a, bf16=False, accumulate=a)
    l_acc = s_acc(paddle.to_tensor(xs), paddle.to_tensor(ys)).numpy()

    s_big, m_big, _ = _build(0, 1, bf16=False)
    l_big = s_big(paddle.to_tensor(xs.reshape(1, a * bs, 16)),
                  paddle.to_tensor(ys.reshape(1, a * bs))).numpy()
    np.testing.assert_allclose(l_acc.mean(), l_big[0], rtol=1e-6)
    for p1, p2 in zip(m_acc.parameters(), m_big.parameters()):
        np.testing.assert_allclose(np.asarray(p1._value),
                                   np.asarray(p2._value), rtol=2e-4,
                                   atol=1e-6, err_msg=p1.name)


@pytest.mark.parametrize("stage", [1, 2, 3])
def test_zero_accumulation_matches_accumulating_control(stage):
    """zero{1,2,3} under an accumulation window vs the replicated control
    under the same window: stage 1 accumulates the same per-param local
    sums and reduces once (bitwise); stages 2/3 reduce every micro step
    into the sharded window accumulator — a different summation order, so
    tolerance-level parity."""
    k, a = 4, 2
    x, y = _batches(k)
    s0, m0, _ = _build(0, k, bf16=False, accumulate=a)
    ref = s0(x, y).numpy()
    s1, m1, _ = _build(stage, k, bf16=False, accumulate=a)
    got = s1(x, y).numpy()
    if stage <= 1:
        assert ref.tobytes() == got.tobytes(), (ref, got)
        for p0, p1 in zip(m0.parameters(), m1.parameters()):
            assert np.asarray(p0._value).tobytes() == \
                np.asarray(p1._value).tobytes(), p0.name
    else:
        # per-micro reduction reorders the accumulation sum: parity is
        # tolerance-level (fp32 ulps through AdamW's divide), and losses
        # after the first boundary inherit it
        np.testing.assert_allclose(ref, got, rtol=1e-6)
        for p0, p1 in zip(m0.parameters(), m1.parameters()):
            np.testing.assert_allclose(
                np.asarray(p0._value), np.asarray(p1._value),
                rtol=5e-5, atol=1e-6, err_msg=p0.name)


def test_zero1_accumulation_cuts_collective_bytes():
    """The headline wire saving: with accumulate_steps=a the compiled
    program fires exactly ONE reduce-scatter/all-gather pair per bucket
    per window — per-execution (trip-count-weighted) collective bytes
    drop exactly a× vs the per-step schedule, and the collective_bytes
    counters carry the same numbers."""
    k, a = 4, 4
    x, y = _batches(k)
    s_no, _m0, opt0 = _build(1, k, bf16=False)
    s_no(x, y)
    s_acc, _m1, opt1 = _build(1, k, bf16=False, accumulate=a)
    s_acc(x, y)
    n_buckets = len(opt1._zero["buckets"])
    no = {s["op"]: s for s in s_no.collective_stats(per_execution=True)}
    ac = {s["op"]: s for s in s_acc.collective_stats(per_execution=True)}
    # the a× count drop IS the predicted budget: nb*k per-step vs
    # nb*(k//a) per-window — assert through the predictor so these
    # numbers live in one place, then hold both builds to their budgets
    from paddle_tpu.analysis import (check_collective_budget,
                                     predict_collective_budget)
    per_step = predict_collective_budget(1, scan_steps=k,
                                         n_buckets=n_buckets)
    per_win = predict_collective_budget(1, scan_steps=k,
                                        accumulate_steps=a,
                                        n_buckets=n_buckets)
    for op in ("reduce-scatter", "all-gather"):
        assert no[op]["count"] == per_step[(op, "dp")] == n_buckets * k
        assert ac[op]["count"] == per_win[(op, "dp")] == n_buckets * (k // a)
        assert ac[op]["bytes"] * a == no[op]["bytes"], (op, no[op], ac[op])
    assert check_collective_budget(s_no) == []
    assert check_collective_budget(s_acc) == []
    # static (per-text) counts still see one op per bucket
    static = {s["op"]: s for s in s_acc.collective_stats()}
    assert static["reduce-scatter"]["count"] == n_buckets


def test_zero3_accumulation_uses_sharded_gacc():
    """Stages 2/3 fold every micro step's reduced mean shard into the
    sharded gacc store (no full gradient outlives a micro step); the
    window accumulator returns to zeros once the boundary update fires."""
    import gc
    k, a = 2, 2
    x, y = _batches(k)
    s3, _m, opt = _build(3, k, bf16=False, accumulate=a)
    s3(x, y)
    for sd in opt._zero["stores"]:
        g = np.asarray(sd["gacc"].tensor._value)
        assert g.shape[0] % DP == 0
        assert np.all(g == 0.0)  # consumed by the boundary update
    del s3, _m, opt
    gc.collect()  # drop the first optimizer's registered stores
    # the gacc stores ride the carry only under accumulation: the
    # non-accumulating build skips its OWN gacc without a verifier
    # warning (carry-optional exemption)
    s_plain, _m2, o2 = _build(3, k, bf16=False)
    s_plain(x, y)
    gacc_uids = {sd["gacc"].tensor._state_uid
                 for sd in o2._zero["stores"]}
    part = s_plain._last_partition
    assert gacc_uids <= set(part["skipped"])
    assert gacc_uids <= set(part["carry_optional"])
    from paddle_tpu import analysis
    findings = s_plain.verify()
    # THIS build's gacc stores are exempt from the stale-store warning
    # (other tests' leaked optimizers may legitimately still warn)
    warned_uids = {int(m.group(1)) for f in findings
                   if f.rule == "sharded-state-skipped"
                   for m in [re.search(r"state uid (\d+)", f.message)] if m}
    assert not (warned_uids & gacc_uids)
    assert analysis.errors(findings) == []


@pytest.mark.parametrize("stage", [1, 3])
def test_zero_global_norm_clip_vs_replicated(stage):
    """ClipGradByGlobalNorm over shards: the scale comes from a psum of
    per-shard square sums — same math as the per-param control up to
    summation order, so losses match exactly and params to fp32
    tolerance. ClipGradByValue is elementwise and stays bitwise."""
    k = 2
    x, y = _batches(k)
    s0, m0, _ = _build(0, k, bf16=False,
                       grad_clip=paddle.nn.ClipGradByGlobalNorm(0.02))
    l0 = s0(x, y).numpy()
    s1, m1, _ = _build(stage, k, bf16=False,
                       grad_clip=paddle.nn.ClipGradByGlobalNorm(0.02))
    l1 = s1(x, y).numpy()
    np.testing.assert_allclose(l0, l1, rtol=1e-6)
    for p0, p1 in zip(m0.parameters(), m1.parameters()):
        np.testing.assert_allclose(np.asarray(p0._value),
                                   np.asarray(p1._value), rtol=1e-5,
                                   atol=1e-7, err_msg=p0.name)
    # value clip: elementwise on the shard == elementwise on the whole
    sv0, mv0, _ = _build(0, k, bf16=False,
                         grad_clip=paddle.nn.ClipGradByValue(0.001))
    sv1, mv1, _ = _build(stage, k, bf16=False,
                         grad_clip=paddle.nn.ClipGradByValue(0.001))
    assert sv0(x, y).numpy().tobytes() == sv1(x, y).numpy().tobytes()
    for p0, p1 in zip(mv0.parameters(), mv1.parameters()):
        assert np.asarray(p0._value).tobytes() == \
            np.asarray(p1._value).tobytes(), p0.name


def test_zero_per_param_lr_bitwise():
    """A per-param lr scale becomes a [rows, 1] multiplier over the flat
    shard — bitwise vs the control's scalar per-param lr."""
    k = 2
    x, y = _batches(k)

    def build(stage):
        paddle.seed(13)
        m = _mlp()
        m[0].weight.optimize_attr = {"learning_rate": 0.5}
        opt = paddle.optimizer.AdamW(parameters=m.parameters(),
                                     learning_rate=0.05)
        if stage:
            opt._zero_enable(axis="dp", stage=stage)

        def one(xb, yb):
            loss = nn.functional.cross_entropy(m(xb), yb)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        return paddle.jit.to_static(one, scan_steps=k, dp_axis="dp"), m

    s0, m0 = build(0)
    ref = s0(x, y).numpy()
    for stage in (1, 3):
        s1, m1 = build(stage)
        assert s1(x, y).numpy().tobytes() == ref.tobytes()
        for p0, p1 in zip(m0.parameters(), m1.parameters()):
            assert np.asarray(p0._value).tobytes() == \
                np.asarray(p1._value).tobytes(), (stage, p0.name)


def test_zero3_hook_leaves_unrelated_programs_alone():
    """The stage-3 materialize hook is LAZY: a trace that never reads the
    model's params issues no gathers, so the param/moment stores of a
    live stage-3 optimizer are not threaded into unrelated compiled
    programs (they stay skipped state, not read-only inputs)."""
    k = 1
    s3, _m, opt = _build(3, k, bf16=False)
    x, y = _batches(k)
    s3(x, y)
    store_uids = {sd[slot].tensor._state_uid
                  for sd in opt._zero["stores"] for slot in sd}

    # an independent model's step, traced while opt is alive
    paddle.seed(3)
    other = _mlp()
    oopt = paddle.optimizer.SGD(parameters=other.parameters(),
                                learning_rate=0.1)

    def one(xb, yb):
        loss = nn.functional.cross_entropy(other(xb), yb)
        loss.backward()
        oopt.step()
        oopt.clear_grad()
        return loss

    s_other = paddle.jit.to_static(one, scan_steps=k, dp_axis="dp")
    s_other(x, y)
    part = s_other._last_partition
    assert store_uids.isdisjoint(part["donated"])
    assert store_uids.isdisjoint(part["readonly"])
    assert store_uids <= set(part["skipped"])
    # and the stage-3 program still trains after the unrelated trace
    before = s3(x, y).numpy()
    assert np.isfinite(before).all()


def test_accumulate_steps_validation():
    with pytest.raises(ValueError, match="multiple of"):
        paddle.jit.to_static(lambda x: x, scan_steps=3, dp_axis="dp",
                             accumulate_steps=2)
    with pytest.raises(ValueError, match="scan step"):
        paddle.jit.to_static(lambda x: x, accumulate_steps=2)
    # a=1 degenerates to the plain scan
    sfn = paddle.jit.to_static(lambda x: x, scan_steps=2,
                               accumulate_steps=1)
    assert sfn._accumulate_steps is None


def test_collective_cadence_mismatch_flagged():
    """Window-stamped collectives: ranks agreeing on a per-window cadence
    verify clean; a per-step rank against a per-window rank is flagged as
    a cadence mismatch (not generic divergence) naming both cadences."""
    from paddle_tpu import analysis, static
    from paddle_tpu.core.dispatch import call_op

    def rank_prog(every):
        prog = static.Program()
        with static.program_guard(prog):
            g = static.data("g", [4], "float32")

            def _rs(v):
                return v
            _rs._collective_axis = "dp"
            _rs._collective_nbytes = 16
            _rs._collective_every = every
            out = call_op(_rs, g, op_name="c_reducescatter")
            paddle.sum(out)
        return prog

    ok = analysis.check_collective_order(
        [rank_prog(4), rank_prog(4)], mesh_axes=("dp",))
    assert ok == []
    bad = analysis.check_collective_order(
        [rank_prog(1), rank_prog(4)], mesh_axes=("dp",))
    assert any(f.rule == "collective-cadence-mismatch"
               and "per-window" in f.message for f in bad)


def test_zero3_ladder_twin_verifies_clean():
    """The zero3 analysis ladder twin (ag->fwd + window-gated rs, both
    ranks cadence-stamped) passes the full analyzer."""
    from paddle_tpu.analysis import ladder
    findings, summary = ladder.verify_ladder(["zero3"])
    assert findings == []
    assert summary["zero3"] == [len(p.ops) for p, _ in
                                ladder.LADDER_BUILDERS["zero3"]()]


def test_dp_axis_requires_scan():
    with pytest.raises(ValueError, match="scan step"):
        paddle.jit.to_static(lambda x: x, dp_axis="dp")


# -- eager DataParallel comm-buffer fusion (satellite) ----------------------

def test_dataparallel_eager_bucketed_fusion():
    """DataParallel(comm_buffer_size=...) now actually buckets the eager
    grad fusion: counters record bucket count/bytes and the fused
    round-trip preserves gradients (world of one: allreduce == identity,
    mean divisor == 1)."""
    from paddle_tpu.distributed.parallel import DataParallel
    paddle.seed(9)
    m = _mlp()
    # tiny cap: one bucket per param; generous cap: one bucket total
    for cap_mb, want in ((1e-4, 4), (64, 1)):
        dp = DataParallel(m, comm_buffer_size=cap_mb,
                          last_comm_buffer_size=cap_mb)
        loss = dp(paddle.to_tensor(rng.rand(4, 16).astype("float32"))).sum()
        loss.backward()
        before = {p.name: np.asarray(p._grad).copy()
                  for p in m.parameters() if p._grad is not None}
        monitor.stat_reset("dp_fused_buckets")
        monitor.stat_reset("dp_fused_bytes")
        n = dp.apply_collective_grads()
        assert n == want
        assert monitor.stat_get("dp_fused_buckets") == want
        assert monitor.stat_get("dp_fused_bytes") > 0
        for p in m.parameters():
            if p.name in before:
                np.testing.assert_allclose(np.asarray(p._grad),
                                           before[p.name], rtol=1e-6)
        for p in m.parameters():
            p.clear_grad()


# -- reduce_scatter eager fallback validation (satellite) -------------------

def test_reduce_scatter_rejects_mismatched_shapes():
    import paddle_tpu.distributed as dist
    t = paddle.to_tensor(np.zeros(4, np.float32))
    lst = [paddle.to_tensor(np.zeros(4, np.float32)),
           paddle.to_tensor(np.zeros(5, np.float32))]
    with pytest.raises(ValueError, match="identical per-rank shapes"):
        dist.reduce_scatter(t, lst)
    lst2 = [paddle.to_tensor(np.zeros(4, np.float32)),
            paddle.to_tensor(np.zeros(4, np.int64))]
    with pytest.raises(ValueError, match="identical per-rank shapes"):
        dist.reduce_scatter(t, lst2)


def test_reduce_op_validation():
    import paddle_tpu.distributed as dist
    t = paddle.to_tensor(np.ones(4, np.float32))
    with pytest.raises(ValueError, match="unknown ReduceOp"):
        dist.all_reduce(t, op="bogus")
    with pytest.raises(ValueError, match="unknown ReduceOp"):
        dist.reduce_scatter(t, [t], op="bogus")
    with pytest.raises(NotImplementedError, match="not supported"):
        dist.reduce_scatter(t, [t], op=dist.ReduceOp.MAX)


# -- the narrow gradient exchange -------------------------------------------
# A bucket whose gradients all arrive in one 16-bit float type crosses
# the wire in that type (one all_to_all in the reduce-scatter's place)
# and is summed in float32 where it lands; float32 gradients keep the
# float32 psum_scatter.

COUNTER = "jit_zero_exchanged_buckets"


def _collectives_in(jaxpr):
    """{primitive name: [operand dtype, ...]} of the gradient-reduction
    collectives in a jaxpr, nested regions included (``reduce_scatter``
    is jax's name for ``psum_scatter``)."""
    from paddle_tpu.observability.jaxpr_walk import sub_jaxprs
    found = {}

    def walk(jx):
        for eqn in getattr(jx, "jaxpr", jx).eqns:
            if eqn.primitive.name in ("all_to_all", "reduce_scatter"):
                found.setdefault(eqn.primitive.name, []).append(
                    str(eqn.invars[0].aval.dtype))
            for sub in sub_jaxprs(eqn):
                walk(sub)
    walk(jaxpr)
    return found


def _collectives_traced(step):
    """The same over a step's traced program: XLA:CPU widens a bf16
    all-to-all's operands to float32 in its compiled text, so the dtype
    the PROGRAM puts on the wire is read from the jaxpr
    (tests/test_tpu_compile.py holds the chip's compiler to it)."""
    return _collectives_in(step._last_aux["traced_jaxpr"]())


NARROW_GRID = [(s, a, p) for s in (1, 2, 3) for a in (None, 2)
               for p in (False, True)]


@pytest.mark.parametrize(
    "stage,acc,pf", NARROW_GRID,
    ids=[f"z{s}_a{a or 1}_pf{int(p)}" for s, a, p in NARROW_GRID])
def test_bf16_gradients_are_exchanged_narrow(stage, acc, pf):
    """bf16 parameters give bf16 gradients: the compiled step holds one
    all-to-all a bucket a reduction, its operand bf16, and no
    reduce-scatter at all; `jit_zero_exchanged_buckets` reads the bucket
    count, the partition carries it, and shardcheck's budget — which
    expects the all-to-all in the reduce-scatter's place — is met."""
    from paddle_tpu.analysis import (check_collective_budget,
                                     infer_zero_layout)
    k = 2
    x, y = _batches(k)
    before = monitor.stat_get(COUNTER)
    s, _m, opt = _build(stage, k, bf16=True, comm_buffer_mb=0.003,
                        accumulate=acc, prefetch=pf)
    s(x, y)
    nb = len(opt._zero["buckets"])
    assert nb == 2
    assert monitor.stat_get(COUNTER) - before == nb
    assert infer_zero_layout(s)["exchanged_buckets"] == nb
    # stage 1 reduces once a window, stages 2/3 every micro step
    reductions = nb * (k // (acc or 1) if stage == 1 else k)
    stats = {c["op"]: c for c in s.collective_stats(per_execution=True)}
    assert "reduce-scatter" not in stats
    assert stats["all-to-all"]["count"] == reductions
    assert stats["all-to-all"]["axis"] == "dp"
    assert check_collective_budget(s) == []
    traced = _collectives_traced(s)
    assert "reduce_scatter" not in traced  # jax's name for psum_scatter
    assert set(traced["all_to_all"]) == {"bfloat16"}


@pytest.mark.parametrize(
    "stage,acc,pf", NARROW_GRID,
    ids=[f"z{s}_a{a or 1}_pf{int(p)}" for s, a, p in NARROW_GRID])
def test_float32_gradients_keep_the_reduce_scatter(stage, acc, pf):
    """float32 gradients: no bucket is exchanged — the float32
    psum_scatter per bucket per reduction of before, no all-to-all, the
    counter does not move."""
    k = 2
    x, y = _batches(k)
    before = monitor.stat_get(COUNTER)
    s, _m, opt = _build(stage, k, bf16=False, comm_buffer_mb=0.003,
                        accumulate=acc, prefetch=pf)
    s(x, y)
    nb = len(opt._zero["buckets"])
    assert monitor.stat_get(COUNTER) == before
    assert s._last_partition["zero_exchanged_buckets"] == 0
    reductions = nb * (k // (acc or 1) if stage == 1 else k)
    stats = {c["op"]: c for c in s.collective_stats(per_execution=True)}
    assert "all-to-all" not in stats
    assert stats["reduce-scatter"]["count"] == reductions
    traced = _collectives_traced(s)
    assert "all_to_all" not in traced
    assert set(traced["reduce_scatter"]) == {"float32"}


def test_replicated_step_counts_no_exchanged_bucket():
    """ZeRO off: the counter stays where it was."""
    k = 1
    x, y = _batches(k)
    before = monitor.stat_get(COUNTER)
    s0, _m, _o = _build(0, k, bf16=True)
    s0(x, y)
    assert monitor.stat_get(COUNTER) == before
    assert s0._last_partition["zero_exchanged_buckets"] == 0


def _one_bucket_reduction(dp, param_dtype="bfloat16"):
    """A dp-way mesh, one ZeRO bucket over the MLP's parameters, and a
    jitted `reduce(widen, *per_rank_grads)`: `_zero_reduced_shard` of
    that bucket inside the bound dp axis, the gradients as given or (for
    each `widen` flag) first converted to float32; one shard a flag."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    mesh = parallel_env.make_mesh({"dp": dp})
    parallel_env.set_mesh(mesh)
    paddle.seed(3)
    m = _mlp(bf16=True)
    m.to(param_dtype)
    opt = paddle.optimizer.AdamW(parameters=m.parameters(),
                                 learning_rate=0.05, multi_precision=True)
    opt._zero_enable(axis="dp", stage=1, mesh=mesh)
    (zb,) = opt._zero["buckets"]

    def reduce(widen, *grads):
        def body(*gs):
            out = []
            with parallel_env.dp_axis_ctx("dp"):
                for w in widen:
                    for p, g in zip(zb.params, gs):
                        p._grad = g[0].astype(jnp.float32) if w else g[0]
                    shard, present = opt._zero_reduced_shard(
                        zb, "dp", dp, True, True)
                    assert all(present) and shard.dtype == jnp.float32
                    out.append(shard)
            for p in zb.params:
                p._grad = None
            return tuple(out)
        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
            check_vma=False))

    return zb, reduce


@pytest.mark.parametrize("wire", ["bfloat16", "float16"])
@pytest.mark.parametrize("dp", [2, 4, 8])
def test_exchanged_shard_is_the_float32_sum(dp, wire):
    """What `_zero_reduced_shard` hands back for 16-bit gradients is the
    float32 mean of the same gradients: bitwise the rank-ordered float32
    sum (the order is the program's), bitwise the float32 psum_scatter
    of the widened gradients at dp=2, and within one float32 ulp of the
    summed magnitudes above (a backend may order its all-reduce's adds
    differently). No partial sum is rounded to 16 bits: per-rank values
    that differ by less than a 16-bit ulp of their sum survive."""
    import jax.numpy as jnp
    zb, reduce = _one_bucket_reduction(dp, wire)
    drng = np.random.RandomState(100 + dp)
    # magnitudes spread over six binades, so that low bits of the small
    # ranks' values fall below a 16-bit ulp of the large ones
    grads = [(drng.randn(dp, *shape) * 2.0 ** drng.randint(
                  -6, 1, (dp,) + (1,) * len(shape))).astype(wire)
             for shape in zb.shapes]
    got, want = (np.asarray(a) for a in reduce((False, True))(*grads))
    flat = np.stack([np.asarray(zb.flatten(
        [jnp.asarray(g[r]) for g in grads], dtype=jnp.dtype(wire)))
        for r in range(dp)]).astype(np.float32)
    ordered = flat[0]
    for r in range(1, dp):
        ordered = ordered + flat[r]
    assert (ordered / np.float32(dp)).tobytes() == got.tobytes()
    if dp == 2:
        assert want.tobytes() == got.tobytes()
    else:
        ulp = np.spacing(np.abs(flat).sum(0) / np.float32(dp))
        assert np.all(np.abs(got - want) <= ulp)
    # a 16-bit partial sum would have lost what float32 keeps
    narrow = flat[0].astype(wire)
    for r in range(1, dp):
        narrow = (narrow + flat[r].astype(wire)).astype(wire)
    assert np.any(narrow.astype(np.float32) != ordered)


def test_mixed_gradient_dtypes_keep_the_float32_reduction():
    """The decision is per bucket and from the gradients alone: a bucket
    holding one float32 gradient among bf16 ones is reduced in float32
    (nothing narrow to gain without a second collective)."""
    import jax
    dp = 4
    zb, reduce = _one_bucket_reduction(dp)
    dtypes = ["float32"] + ["bfloat16"] * (len(zb.shapes) - 1)
    grads = [np.ones((dp,) + shape, dt)
             for shape, dt in zip(zb.shapes, dtypes)]
    fn = reduce((False,))
    assert _collectives_in(jax.make_jaxpr(fn)(*grads)) == {
        "reduce_scatter": ["float32"]}
    (got,) = fn(*grads)
    assert np.all(np.asarray(got)[:sum(zb.n_rows)].reshape(-1)
                  [:zb.sizes[0]] == 1.0)
