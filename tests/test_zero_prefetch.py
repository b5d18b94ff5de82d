"""Latency-hiding ZeRO step: the double-buffered bucket prefetch.

The contract: ``_zero_enable(prefetch=True)`` (the default) restructures
the compiled step so collectives are EMITTED with schedulable slack —
bucket i+1's param all-gather rides bucket i's compute, bucket i's grad
reduce-scatter rides bucket i+1's update, and the step's tail re-gathers
bucket 0 into the prefetch carry slot so step N+1's forward starts warm
— while staying BITWISE-equal to the serial (``prefetch=False``)
schedule: per-bucket op order is unchanged, only emission position
moves. The schedulable-overlap meter (``overlap.schedulable_stats``,
sourced from the traced jaxpr — the compiled text's dependency postorder
erases emission structure) is the backend-independent referee that the
pipeline exists; the jaxpr-liveness meter referees its memory price
(one bucket: the carry slot).

Bucket configs here use ``comm_buffer_mb=0.003``: on the 16->32->8 MLP
that is LAYER-ALIGNED (bucket0={w1,b1}, bucket1={w2,b2}), which makes
the serial schedule's score exactly 0.0 — every gather's first consumer
is adjacent. Per-param buckets would give the serial arm a tiny honest
score (a bias gather rides the matmul that only needs the weight), which
is correct but not the 0-vs->0 A/B these tests pin.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.distributed import parallel_env

DP = 8
COMM_MB = 0.003  # layer-aligned buckets on the 16->32->8 MLP


@pytest.fixture(autouse=True)
def _mesh():
    mesh = parallel_env.make_mesh({"dp": DP})
    parallel_env.set_mesh(mesh)
    yield mesh
    parallel_env.set_mesh(None)
    from paddle_tpu.distributed.fleet.base import topology
    topology.set_hybrid_communicate_group(None)


rng = np.random.RandomState(77)


def _mlp(bf16=False):
    m = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 8))
    if bf16:
        m.to("bfloat16")
    return m


def _build(stage, k, bf16=False, prefetch=None, accumulate=None,
           grad_clip=None, seed=11):
    paddle.seed(seed)
    m = _mlp(bf16)
    opt = paddle.optimizer.AdamW(parameters=m.parameters(),
                                 learning_rate=0.05,
                                 multi_precision=bf16,
                                 grad_clip=grad_clip)
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
                                accumulate_steps=accumulate)
    return step, m, opt


def _batches(k, batch=16):
    x = rng.rand(k, batch, 16).astype("float32")
    y = rng.randint(0, 8, (k, batch)).astype("int64")
    return paddle.to_tensor(x), paddle.to_tensor(y)


def _params_bytes(m):
    return [np.asarray(p._value).tobytes() for p in m.parameters()]


# -- bitwise parity matrix -------------------------------------------------

@pytest.mark.parametrize("stage", [1, 3])
@pytest.mark.parametrize("k,acc", [(1, None), (4, None), (4, 2)],
                         ids=["k1", "k4", "k4_acc2"])
@pytest.mark.parametrize("bf16", [False, True], ids=["fp32", "bf16_master"])
def test_prefetch_bitwise_equals_serial(stage, k, acc, bf16):
    """Acceptance bar: the pipelined step is bitwise-equal to the serial
    step across zero{1,3} x scan k x accumulation x dtype — same losses
    on BOTH program calls (the second exercises the warm carry slot
    threaded through the donated state) and identical final params."""
    x, y = _batches(k)
    s_off, m_off, _ = _build(stage, k, bf16, prefetch=False,
                             accumulate=acc)
    s_on, m_on, _ = _build(stage, k, bf16, prefetch=True, accumulate=acc)
    assert s_off(x, y).numpy().tobytes() == s_on(x, y).numpy().tobytes()
    # second call: step N's tail prefetch feeds step N+1's forward
    assert s_off(x, y).numpy().tobytes() == s_on(x, y).numpy().tobytes()
    for b_off, b_on, p in zip(_params_bytes(m_off), _params_bytes(m_on),
                              m_on.parameters()):
        assert b_off == b_on, p.name


@pytest.mark.parametrize("stage", [1, 3])
@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("bf16", [False, True], ids=["fp32", "bf16_master"])
def test_prefetch_bitwise_matches_replicated_control(stage, k, bf16):
    """And the pipelined step vs the replicated (non-ZeRO) control:
    the full transitive chain control == serial == pipelined, bitwise."""
    x, y = _batches(k)
    s0, m0, _ = _build(0, k, bf16)
    s1, m1, _ = _build(stage, k, bf16, prefetch=True)
    assert s0(x, y).numpy().tobytes() == s1(x, y).numpy().tobytes()
    assert s0(x, y).numpy().tobytes() == s1(x, y).numpy().tobytes()
    for b0, b1, p in zip(_params_bytes(m0), _params_bytes(m1),
                         m1.parameters()):
        assert b0 == b1, p.name


def test_prefetch_global_norm_clip_parity():
    """ClipGradByGlobalNorm is a two-pass barrier (every shard's square
    sum before any update): the reduce side stays serial, but the
    forward all-gather pipeline still runs — parity holds at the same
    tolerance as the serial clip path, and the program still scores
    schedulable overlap from the gather side."""
    k = 2
    x, y = _batches(k)
    clip = paddle.nn.ClipGradByGlobalNorm(0.02)
    s_off, m_off, _ = _build(3, k, prefetch=False, grad_clip=clip)
    clip2 = paddle.nn.ClipGradByGlobalNorm(0.02)
    s_on, m_on, _ = _build(3, k, prefetch=True, grad_clip=clip2)
    assert s_off(x, y).numpy().tobytes() == s_on(x, y).numpy().tobytes()
    for b_off, b_on, p in zip(_params_bytes(m_off), _params_bytes(m_on),
                              m_on.parameters()):
        assert b_off == b_on, p.name
    assert s_on.schedulable_stats()["schedulable_overlap"] > 0.0


# -- the schedulable-overlap referee ---------------------------------------

def test_schedulable_overlap_pipelined_vs_serial():
    """The value gate: layer-aligned serial zero3 scores EXACTLY 0.0
    (every collective's first consumer is adjacent in emission order);
    the pipelined program scores > 0, with the prefetched gather, the
    deferred reduce-scatter, and the tail gather each given a real
    compute window."""
    k = 4
    x, y = _batches(k)
    s_off, _, _ = _build(3, k, prefetch=False)
    s_off(x, y)
    s_on, _, _ = _build(3, k, prefetch=True)
    s_on(x, y)
    off = s_off.schedulable_stats()
    on = s_on.schedulable_stats()
    assert off["source"] == on["source"] == "traced-jaxpr"
    assert off["schedulable_overlap"] == 0.0
    assert on["schedulable_overlap"] > 0.0
    # at least: the prefetched next-bucket gather, the bucket-0 tail
    # gather (rides the apply of later buckets), and one reduce-scatter
    # (rides the previous bucket's apply) have non-zero windows
    windowed = [p for p in on["pairs"] if p["available_ns"] > 0]
    assert len(windowed) >= 3, on["pairs"]
    assert any(p["op"] == "all-gather" for p in windowed)
    assert any(p["op"] == "reduce-scatter" for p in windowed)
    # overlap_stats() splices the jaxpr-sourced score into the
    # compiled-text report (the value the bench rows export)
    spliced = s_on.overlap_stats()
    assert spliced["schedulable_overlap"] == on["schedulable_overlap"]
    assert spliced["assumptions"]["schedulable_source"] == "traced-jaxpr"


def test_schedulable_overlap_prices_the_narrow_exchange():
    """bf16 gradients: the all-to-all stands where the reduce-scatter
    stood in the pipeline, its float32 convert-and-add deferred with the
    mean divide — serial scores exactly 0.0, pipelined > 0 with an
    all-to-all given a real compute window, and the cost model prices
    the op."""
    k = 4
    x, y = _batches(k)
    s_off, _, _ = _build(3, k, bf16=True, prefetch=False)
    s_off(x, y)
    s_on, _, _ = _build(3, k, bf16=True, prefetch=True)
    s_on(x, y)
    off = s_off.schedulable_stats()
    on = s_on.schedulable_stats()
    assert off["schedulable_overlap"] == 0.0
    assert on["schedulable_overlap"] > 0.0
    ops = {p["op"] for p in on["pairs"]}
    assert "all-to-all" in ops and "reduce-scatter" not in ops
    assert any(p["op"] == "all-to-all" and p["available_ns"] > 0
               for p in on["pairs"])
    assert all(p["collective_ns"] > 0 for p in on["pairs"]
               if p["op"] == "all-to-all")


def test_schedulable_overlap_accumulation_window():
    """The pipeline composes with accumulation windows: boundary-step
    reduce/update pipelining still scores with accumulate_steps=2."""
    k, a = 4, 2
    x, y = _batches(k)
    s_on, _, _ = _build(3, k, prefetch=True, accumulate=a)
    s_on(x, y)
    assert s_on.schedulable_stats()["schedulable_overlap"] > 0.0


# -- collective schedule shape ---------------------------------------------

def test_prefetch_keeps_collective_counts():
    """Pipelining must not add wire traffic: per-execution collective
    counts and bytes match the serial schedule exactly (the tail gather
    of bucket 0 REPLACES the next step's forward gather — the warm slot
    elides it)."""
    k = 2
    x, y = _batches(k)
    s_off, _, o_off = _build(3, k, prefetch=False)
    s_off(x, y)
    s_on, _, o_on = _build(3, k, prefetch=True)
    s_on(x, y)
    off = {s["op"]: s for s in s_off.collective_stats(per_execution=True)}
    on = {s["op"]: s for s in s_on.collective_stats(per_execution=True)}
    for op in ("all-gather", "reduce-scatter"):
        assert on[op]["count"] == off[op]["count"], (op, off[op], on[op])
        assert on[op]["bytes"] == off[op]["bytes"], (op, off[op], on[op])
    # both schedules sit exactly on shardcheck's predicted budget (the
    # predictor models the warm-slot elision, so prefetch=True is not
    # just "same as serial" but independently priced)
    from paddle_tpu.analysis import check_collective_budget
    assert check_collective_budget(s_off) == []
    assert check_collective_budget(s_on) == []


def test_prefetch_slot_carry_and_verifier():
    """The carry slot is real donated state: it rides the scan carry
    (replicated, carry-optional so prefetch=False builds skip it
    without a verifier warning) and the analysis pass accepts the
    pipelined build."""
    from paddle_tpu import analysis
    k = 2
    s_on, _, opt = _build(3, k, prefetch=True)
    x, y = _batches(k)
    s_on(x, y)
    slot = opt._zero["prefetch_slot"]
    part = s_on._last_partition
    assert slot._state_uid in set(part["donated"])
    assert analysis.errors(s_on.verify()) == []


# -- the memory referee ----------------------------------------------------

def test_prefetch_peak_within_one_bucket():
    """Acceptance bar: the jaxpr-liveness peak of the pipelined step
    stays within ONE bucket's bytes of the serial step's (the carry
    slot is the double-buffer's whole price; the meter models the
    donated-carry aliasing XLA compiles, so the slot's boundary
    crossings don't triple-bill)."""
    k = 4
    x, y = _batches(k)
    s_off, _, _ = _build(3, k, prefetch=False)
    s_off(x, y)
    s_on, _, opt = _build(3, k, prefetch=True)
    s_on(x, y)
    slot = opt._zero["prefetch_slot"]
    slot_bytes = int(np.prod(slot._value.shape)
                     * np.dtype(slot._value.dtype).itemsize)
    off = next(iter(s_off.traced_memory_stats().values()))
    on = next(iter(s_on.traced_memory_stats().values()))
    assert on["alias_io"] and off["alias_io"]
    delta = on["peak_bytes"] - off["peak_bytes"]
    assert 0 <= delta <= slot_bytes, (delta, slot_bytes, off, on)
    # the boundary grows by exactly the slot on each side
    assert on["argument_bytes"] - off["argument_bytes"] == slot_bytes
    assert on["output_bytes"] - off["output_bytes"] == slot_bytes


# -- checkpoint interplay --------------------------------------------------

def test_prefetch_checkpoint_restore_refreshes_slot():
    """restore_optimizer writes the bucket-0 param store directly (no
    flush), so it must re-derive the carry slot — a restored run and an
    uninterrupted run stay bitwise-equal through the prefetched
    forward."""
    from paddle_tpu.checkpoint import state as ckpt_state
    k = 2
    x, y = _batches(k)
    s_a, m_a, o_a = _build(3, k, prefetch=True, seed=19)
    s_a(x, y)
    rec = ckpt_state.loads(ckpt_state.dumps(
        ckpt_state.capture_optimizer(o_a)))
    ref = s_a(x, y).numpy().tobytes()  # uninterrupted second call
    ref_params = _params_bytes(m_a)

    s_b, m_b, o_b = _build(3, k, prefetch=True, seed=19)
    s_b(x, y)
    # poison then restore: the slot must come back from the restored
    # store, not survive as the stale derived cache
    o_b._zero["prefetch_slot"]._value = \
        o_b._zero["prefetch_slot"]._value * 0.0
    ckpt_state.restore_optimizer(o_b, rec)
    assert s_b(x, y).numpy().tobytes() == ref
    for got, want, p in zip(_params_bytes(m_b), ref_params,
                            m_b.parameters()):
        assert got == want, p.name
