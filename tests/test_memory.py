"""HBM memory accounting (ISSUE 10): per-program XLA attribution,
framework-state residency ledger, OOM-classified flight dumps, run-log
rotation and the label-cardinality guard.

The headline contract: ``StaticFunction.memory_stats()`` returns
argument/output/temp/alias/generated-code bytes for every compiled
entry, and the ZeRO-3 ledger proves model-state residency ≈ 1/dp of the
replicated control NUMERICALLY on the 8-device CPU mesh — byte
accounting is backend-deterministic, so these are value assertions, not
pattern matches.
"""
import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor, nn
from paddle_tpu.distributed import parallel_env
from paddle_tpu.observability import export as obs_export
from paddle_tpu.observability import memory

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DP = 8

rng = np.random.RandomState(11)


def _build(zero_stage, k, accumulate=None, feat=64, hidden=128,
           classes=32, seed=5):
    paddle.seed(seed)
    m = nn.Sequential(nn.Linear(feat, hidden), nn.ReLU(),
                      nn.Linear(hidden, classes))
    opt = paddle.optimizer.AdamW(parameters=m.parameters(),
                                 learning_rate=0.05)
    if zero_stage:
        opt._zero_enable(axis="dp", stage=zero_stage)

    def one(xb, yb):
        loss = nn.functional.cross_entropy(m(xb), yb)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(one, scan_steps=k,
                                dp_axis="dp" if zero_stage else None,
                                accumulate_steps=accumulate)
    return step, m, opt


def _batches(k, batch=16, feat=64, classes=32):
    x = rng.rand(k, batch, feat).astype("float32")
    y = rng.randint(0, classes, (k, batch)).astype("int64")
    return paddle.to_tensor(x), paddle.to_tensor(y)


@pytest.fixture
def _mesh():
    mesh = parallel_env.make_mesh({"dp": DP})
    parallel_env.set_mesh(mesh)
    yield mesh
    parallel_env.set_mesh(None)


# -- per-program attribution ----------------------------------------------

@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("zero,acc", [(0, None), (1, None), (3, None),
                                      (3, 2)],
                         ids=["zero0", "zero1", "zero3", "zero3_acc2"])
def test_memory_stats_sharding_matrix(_mesh, k, zero, acc):
    """Every compiled entry across the sharding matrix yields the full
    byte breakdown, and the donated carry shows up as aliased (not
    double-billed) bytes."""
    if acc is not None and k % acc:
        pytest.skip("k must be a multiple of accumulate_steps")
    step, _m, _opt = _build(zero, k, accumulate=acc)
    x, y = _batches(k)
    step(x, y)
    stats = step.memory_stats()
    assert len(stats) == 1
    (label, rec), = stats.items()
    assert ":scan" in label
    for kind in memory.MEMORY_KINDS:
        assert rec[f"{kind}_bytes"] >= 0, kind
    assert rec["peak_bytes"] == memory.peak_bytes(rec)
    # the framework state rides the carry donated: XLA reports the
    # aliased input/output pairs, so peak counts the state once
    assert rec["alias_bytes"] > 0
    assert rec["argument_bytes"] > rec["alias_bytes"]


def test_temp_bytes_scale_with_microbatch_not_k(_mesh):
    """Scan temps are per-step workspace reused across iterations: 4x
    the scan length leaves temp bytes ~flat (xs arguments grow
    instead), while 4x the microbatch grows temps ~linearly — the
    decomposition that makes batch/k tuning a calculation instead of an
    OOM hunt."""
    def temp_of(k, batch):
        step, _m, _opt = _build(0, k)
        x, y = _batches(k, batch=batch)
        step(x, y)
        (rec,) = step.memory_stats().values()
        return rec["temp_bytes"], rec["argument_bytes"]

    t_k1, a_k1 = temp_of(1, 16)
    t_k4, a_k4 = temp_of(4, 16)
    t_b64, _ = temp_of(1, 64)
    assert t_k4 < t_k1 * 2, (t_k1, t_k4)       # temps ~O(1) in k
    # argument growth is exactly the extra xs steps (the carried state
    # is k-invariant): 3 more [16, 64] float32 batches + labels
    xs_step = 16 * 64 * 4
    assert 2 * xs_step <= a_k4 - a_k1 <= 5 * xs_step, (a_k1, a_k4)
    # 4x the microbatch at least doubles temps (activations scale;
    # the param-sized constant workspace dilutes the slope below 4x)
    assert t_b64 >= t_k1 * 2.0, (t_k1, t_b64)


def test_zero3_state_resident_1_over_dp_numerically(_mesh):
    """THE acceptance number: ZeRO-3 model-state residency per rank ==
    rows/dp of the flat layout, and ≈ 1/dp of the analytically-known
    replicated model state (params + both Adam moments) within the
    row-padding slack — the claim the dryrun HLO rows only
    pattern-match, closed with bytes."""
    k = 2
    feat, hidden, classes = 256, 512, 64
    step, m, opt = _build(3, k, feat=feat, hidden=hidden, classes=classes)
    x, y = _batches(k, feat=feat, classes=classes)
    step(x, y)

    # expected per-rank bytes, straight from the flat layout (gacc is a
    # window accumulator with no replicated-control counterpart — the
    # model-state comparison covers param + moment1 + moment2)
    expected = 0
    for zb, sdict in zip(opt._zero["buckets"], opt._zero["stores"]):
        for slot, store in sdict.items():
            if slot == "gacc":
                continue
            itemsize = np.dtype(store.tensor._value.dtype).itemsize
            expected += (zb.rows // zb.degree) * 1024 * itemsize

    measured = 0
    for sdict in opt._zero["stores"]:
        for slot, store in sdict.items():
            if slot == "gacc":
                continue
            _g, r = memory.value_bytes(store.tensor._value)
            measured += r
    assert measured == expected, (measured, expected)

    # vs the replicated control: params + moment1 + moment2, all fp32
    n_elems = sum(int(np.prod(p._value.shape)) for p in m.parameters())
    replicated = 3 * n_elems * 4
    ratio = measured * DP / replicated
    # padding (per-param row alignment + shard-degree pad rows) only
    # ever adds bytes; at this model size the slack is under 10%
    assert 1.0 <= ratio < 1.10, (measured, replicated, ratio)

    # and the ledger's category walk agrees with the direct store walk
    led = memory.state_ledger()
    cat_bytes = sum(led["categories"].get(c, {"bytes": 0})["bytes"]
                    for c in ("zero_param", "zero_moment", "zero_master",
                              "gacc"))
    assert cat_bytes >= measured  # >= : other live tests' stores may add


def test_memory_stats_before_run_raises(_mesh):
    step, _m, _opt = _build(0, 2)
    with pytest.raises(RuntimeError, match="call the step once"):
        step.memory_stats()


def test_export_memory_stats_gauges_and_registry(_mesh):
    step, _m, _opt = _build(0, 2)
    x, y = _batches(2)
    step(x, y)
    step.export_memory_stats()
    gauges = obs_export.gauges()
    keys = [g for g in gauges if g.startswith("program_hbm_bytes{")
            and "one#0:scan" in g]
    kinds = {g.split('kind="')[1].rstrip('"}') for g in keys}
    assert set(memory.MEMORY_KINDS) | {"peak"} <= kinds
    reg = memory.program_memory()
    (entry,) = [e for e in reg if e.startswith("one#0")]
    assert reg[entry]["top_buffers"], "top buffers must ride the registry"
    text = obs_export.prometheus_text()
    assert "program_hbm_bytes{" in text


# -- state ledger ----------------------------------------------------------

def test_state_ledger_categories_and_bytes():
    paddle.seed(0)
    m = nn.Linear(32, 16)
    opt = paddle.optimizer.Adam(parameters=m.parameters(),
                                learning_rate=0.01)
    led = memory.export_state_ledger()
    cats = led["categories"]
    for cat in ("param", "opt_moment", "lr", "rng"):
        assert cat in cats, cats.keys()
    # this model's params: (32*16 + 16) * 4 bytes, replicated resident
    mine = [e for e in led["entries"]
            if e["category"] == "param"
            and e["name"] in {p.name for p in m.parameters()}]
    assert sum(e["bytes"] for e in mine) == (32 * 16 + 16) * 4
    for e in mine:
        assert e["bytes"] == e["global_bytes"]  # replicated
    assert led["total_bytes"] >= sum(e["bytes"] for e in mine)
    gauges = obs_export.gauges()
    assert 'state_resident_bytes{category="param"}' in gauges
    assert "state_resident_bytes_total" in gauges
    del opt  # keep the optimizer alive through the walk above


def test_is_oom_error():
    assert memory.is_oom_error(MemoryError())
    assert memory.is_oom_error(
        RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating "
                     "17179869184 bytes"))
    assert memory.is_oom_error(ValueError("failed to allocate request"))
    assert not memory.is_oom_error(RuntimeError("shape mismatch"))
    assert not memory.is_oom_error(None)


def test_attribute_program_unrecorded_target_raises():
    from paddle_tpu import static
    prog = static.Program()
    with static.program_guard(prog):
        x = static.data("x", [2, 4], "float32")
        y = paddle.mean(x)
    ghost = paddle.to_tensor(np.zeros((1,), np.float32))
    with pytest.raises(memory.MemoryAttributionError):
        memory.attribute_program(prog, [ghost])
    stats = memory.attribute_program(prog, [y])
    assert stats["peak_bytes"] > 0


# -- label-cardinality guard ----------------------------------------------

def test_label_cardinality_guard(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_MAX_LABEL_SETS", "3")
    obs_export.clear_label_sets()
    before = monitor.stat_get("metrics_label_overflow_total")
    admitted = [obs_export.format_labels("guard_test_metric", op=f"op{i}")
                for i in range(3)]
    assert all(f'op="op{i}"' in s for i, s in enumerate(admitted))
    # 4th distinct combination collapses; the admitted ones keep working
    over = obs_export.format_labels("guard_test_metric", op="op3")
    assert over == '{op="__overflow__"}'
    assert monitor.stat_get("metrics_label_overflow_total") == before + 1
    again = obs_export.format_labels("guard_test_metric", op="op1")
    assert again == admitted[1]
    # other metrics are unaffected (per-metric bound)
    other = obs_export.format_labels("guard_other_metric", op="op9")
    assert 'op="op9"' in other
    # metric-less calls (legacy producers) bypass the guard entirely
    free = obs_export.format_labels(op="op77")
    assert 'op="op77"' in free
    obs_export.clear_label_sets()


# -- run-log rotation ------------------------------------------------------

def test_runlog_rotation_parts_and_merge(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_view
    from paddle_tpu.observability import runlog

    log = runlog.start_run(dir=str(tmp_path), run_id="rot", rank=0,
                           max_bytes=4096)
    n_events = 300
    for i in range(n_events):
        runlog.event("tick", i=i, pad="x" * 64)
    runlog.stop_run()

    assert log.part >= 2, "300 padded events must roll a 4KB log"
    assert len(log.paths) == log.part + 1
    for p in log.paths:
        assert os.path.exists(p)
        assert os.path.getsize(p) < 4096 + 4096  # bounded per part
    # continuation manifests chain the parts
    with open(log.paths[1]) as f:
        first = json.loads(f.readline())
    assert first["kind"] == "manifest" and first["part"] == 1
    assert first["continues"] == os.path.basename(log.paths[0])

    # trace_view merges parts transparently: one process track, no
    # event lost
    events, n_bad = trace_view.load_events(log.paths)
    assert n_bad == 0
    ticks = [e for e in events if e.get("event") == "tick"]
    assert len(ticks) == n_events
    assert {e["i"] for e in ticks} == set(range(n_events))
    assert {e["_file"] for e in events} == {log.base_path}
    trace = trace_view.build_chrome_trace(events)
    tracks = [e for e in trace["traceEvents"]
              if e.get("name") == "process_name"]
    assert len(tracks) == 1


def test_runlog_env_max_mb(tmp_path, monkeypatch):
    from paddle_tpu.observability import runlog
    monkeypatch.setenv("PADDLE_TPU_RUNLOG_MAX_MB", "0.01")  # ~10 KB
    log = runlog.start_run(dir=str(tmp_path), run_id="envrot", rank=0)
    assert log.max_bytes == int(0.01 * 1024 * 1024)
    runlog.stop_run()


def test_steptimer_window_boundary_memory_snapshot(tmp_path):
    from paddle_tpu.observability import StepTimer, runlog
    runlog.start_run(dir=str(tmp_path), run_id="memsnap", rank=0)
    t = StepTimer(window=2, tokens_per_step=10, publish_as="memtest")
    for _ in range(5):
        t.step()
    log_path = runlog.log_path()
    runlog.stop_run()
    with open(log_path) as f:
        recs = [json.loads(line) for line in f]
    snaps = [r for r in recs if r.get("event") == "memory_snapshot"]
    # boundaries at total_steps 2 and 4 (first step only anchors)
    assert len(snaps) == 2
    for s in snaps:
        assert "state" in s and "categories" in s["state"]
        assert s["state"]["total_bytes"] >= 0


# -- mem_view --------------------------------------------------------------

def test_mem_view_snapshot_and_budget(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import mem_view

    memory.record_program_memory("mv_test", {
        "argument_bytes": 4 << 20, "output_bytes": 1 << 20,
        "temp_bytes": 8 << 20, "alias_bytes": 2 << 20,
        "generated_code_bytes": 0, "peak_bytes": 11 << 20})
    snap = tmp_path / "snap.json"
    snap.write_text(json.dumps(memory.snapshot()))

    rc = mem_view.main(["--snapshot", str(snap), "--budget-mb", "64"])
    assert rc == 0
    rc = mem_view.main(["--snapshot", str(snap), "--budget-mb", "1"])
    assert rc == 3

    table = mem_view.format_program_table(
        {"mv_test": memory.program_memory()["mv_test"]})
    assert "mv_test" in table and "11.000" in table
    ok, over = mem_view.check_budget(
        {"bad": {"error": "boom"}}, budget_mb=1e9)
    assert not ok and over == [("bad", None)]
    memory.clear_program_memory()


def test_mem_view_flight_dump_source(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import mem_view
    dump = {"reason": "oom", "memory": {
        "programs": {"p": {"argument_bytes": 0, "output_bytes": 0,
                           "temp_bytes": 0, "alias_bytes": 0,
                           "generated_code_bytes": 0,
                           "peak_bytes": 2 << 20}},
        "state": {"categories": {"param": {"bytes": 10, "global_bytes":
                                           10, "count": 1}},
                  "total_bytes": 10, "total_global_bytes": 10}}}
    p = tmp_path / "flight.json"
    p.write_text(json.dumps(dump))
    assert mem_view.main(["--snapshot", str(p)]) == 0
    assert mem_view.main(["--snapshot", str(p), "--budget-mb", "1"]) == 3


# -- serving engine --------------------------------------------------------

def test_serving_engine_per_bucket_memory():
    import paddle_tpu.serving as serving
    from paddle_tpu.jit.to_static import InputSpec

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    model.eval()
    engine = serving.Engine.from_layer(
        model, [InputSpec([None, 8], "float32")], bucket_ladder=(1, 4))
    try:
        stats = engine.memory_stats()
    finally:
        engine.close()
    assert set(stats) == {1, 4}
    for b, rec in stats.items():
        assert rec["peak_bytes"] > 0
        assert rec["argument_bytes"] > 0
    # bigger bucket, bigger activations
    assert stats[4]["peak_bytes"] > stats[1]["peak_bytes"]
    reg = memory.program_memory()
    assert "serving_b1" in reg and "serving_b4" in reg
    memory.clear_program_memory()


# -- OOM-classified flight dump (chaos) ------------------------------------

@pytest.mark.chaos
def test_oom_classified_flight_dump(tmp_path, _mesh):
    """Acceptance: a RESOURCE_EXHAUSTED death produces a dump tagged
    reason="oom" whose memory section carries per-category state bytes
    and the top-N buffers of the recorded programs."""
    from paddle_tpu.observability import flight
    from paddle_tpu.testing import faults

    step, _m, _opt = _build(3, 2)
    x, y = _batches(2)
    step(x, y)
    step.export_memory_stats()  # program + top buffers in the registry

    flight.install(str(tmp_path))
    try:
        faults.inject("jit/step", exc=RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory allocating 17179869184 "
            "bytes (XLA allocator ran out of HBM)"))
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            step(x, y)
    finally:
        faults.reset()
        flight.uninstall()

    path = flight.latest_dump(str(tmp_path))
    assert path is not None
    with open(path) as f:
        dump = json.load(f)
    assert dump["reason"] == "oom"
    assert dump["cause"] == "kill_point"
    assert dump["kill_point"] == "jit/step"
    assert "RESOURCE_EXHAUSTED" in dump["exception"]["message"]
    mem = dump["memory"]
    cats = mem["state"]["categories"]
    assert {"zero_param", "zero_moment"} <= set(cats)
    assert all(c["bytes"] > 0 for k, c in cats.items()
               if k.startswith("zero_"))
    progs = [p for p in mem["programs"] if p.startswith("one#0")]
    assert progs, mem["programs"].keys()
    bufs = mem["programs"][progs[0]]["top_buffers"]
    assert bufs and bufs[0]["bytes"] >= bufs[-1]["bytes"]
    memory.clear_program_memory()


@pytest.mark.chaos
def test_non_oom_kill_point_dump_stays_kill_point(tmp_path):
    from paddle_tpu.observability import flight
    from paddle_tpu.testing import faults

    flight.install(str(tmp_path))
    try:
        faults.inject("jit/step", exc=RuntimeError("plain failure"))
        step, _m, _opt = _build(0, 1)
        # build on the fresh default mesh-less path
        x, y = _batches(1)
        with pytest.raises(RuntimeError, match="plain failure"):
            step(x, y)
    finally:
        faults.reset()
        flight.uninstall()
    with open(flight.latest_dump(str(tmp_path))) as f:
        dump = json.load(f)
    assert dump["reason"] == "kill_point"
    assert "memory" in dump  # every dump carries the section
