"""Unified observability layer tests: spans, counters, exporters,
StepTimer, hot-path instrumentation (executor / jit cache / dataloader /
collectives / PS RPC), and the chip peaks."""
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.observability as obs
from paddle_tpu import _native, monitor, profiler
from paddle_tpu.io import DataLoader
from paddle_tpu.io.dataset import TensorDataset
from paddle_tpu.observability import export as export_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def tracing():
    """Clean tracing session: fresh event buffer + gauges, always
    disabled afterwards (observability state is process-global)."""
    profiler.reset()
    export_mod.clear_gauges()
    obs.enable()
    try:
        yield obs
    finally:
        obs.disable()
        profiler.reset()
        export_mod.clear_gauges()


def _trace_names(tmp_path, name="trace.json"):
    p = str(tmp_path / name)
    obs.export_chrome_trace(p)
    with open(p) as f:
        return [e["name"] for e in json.load(f)["traceEvents"]]


def _reset(*counters):
    for c in counters:
        monitor.stat_reset(c)


# -- span API --------------------------------------------------------------

def test_span_nesting_records_and_exports(tracing, tmp_path):
    with obs.trace_span("outer", cat="user", k=1) as outer:
        assert obs.current_span() is outer
        with obs.trace_span("inner", cat="user") as inner:
            assert obs.current_span() is inner
        assert obs.current_span() is outer
    assert obs.current_span() is None
    names = _trace_names(tmp_path)
    assert "outer" in names and "inner" in names


def test_disabled_tracing_is_guard_only(tmp_path):
    obs.disable()
    profiler.reset()
    # no allocation, no recording: the shared null span comes back and
    # the event buffer stays empty
    s = obs.trace_span("never", cat="user")
    assert s is obs.tracing.NULL_SPAN
    with s:
        pass
    monitor.stat_reset("never_counter")
    obs.count("never_counter")
    assert monitor.stat_get("never_counter") == 0
    assert obs.export_chrome_trace(str(tmp_path / "t.json")) == 0


def test_category_toggle_and_unknown_category(tmp_path):
    profiler.reset()
    obs.enable(categories=["executor"])
    try:
        assert obs.enabled("executor")
        assert not obs.enabled("dataloader")
        assert obs.trace_span("x", cat="dataloader") is obs.tracing.NULL_SPAN
        assert obs.trace_span("y", cat="executor") is not obs.tracing.NULL_SPAN
    finally:
        obs.disable()
    with pytest.raises(ValueError):
        obs.enable(categories=["nonsense"])
    obs.disable()


# -- hot-path instrumentation ---------------------------------------------

def test_jit_cache_counters_and_compile_span(tracing, tmp_path):
    _reset("jit_cache_hit", "jit_cache_miss", "jit_build_ns")
    f = paddle.jit.to_static(lambda x: x * 3.0)
    x = paddle.to_tensor(np.ones((2, 2), np.float32))
    f(x)
    assert monitor.stat_get("jit_cache_miss") == 1
    assert monitor.stat_get("jit_cache_hit") == 0
    assert monitor.stat_get("jit_build_ns") > 0
    f(x)
    assert monitor.stat_get("jit_cache_hit") == 1
    # shape change -> second miss
    f(paddle.to_tensor(np.ones((3, 2), np.float32)))
    assert monitor.stat_get("jit_cache_miss") == 2
    names = _trace_names(tmp_path)
    assert "jit/compile" in names
    assert "executor/step" in names


def test_jax_backend_compile_hook_counts(tracing):
    _reset("jit_backend_compile_ns", "jit_backend_compiles")
    f = paddle.jit.to_static(lambda x: x + 7.0)
    f(paddle.to_tensor(np.ones((4,), np.float32)))
    assert monitor.stat_get("jit_backend_compiles") >= 1
    assert monitor.stat_get("jit_backend_compile_ns") > 0


def test_executor_run_spans_and_compile_counters(tracing, tmp_path):
    _reset("executor_compile_miss", "executor_compile_hit",
           "executor_runs", "program_record_ops")
    main = paddle.static.Program()
    with paddle.static.program_guard(main):
        x = paddle.static.data("x", [2, 3])
        y = paddle.ops.scale(x, 2.0)
    assert monitor.stat_get("program_record_ops") >= 1
    exe = paddle.static.Executor()
    feed = {"x": np.ones((2, 3), np.float32)}
    out1 = exe.run(main, feed=feed, fetch_list=[y])
    out2 = exe.run(main, feed=feed, fetch_list=[y])
    np.testing.assert_allclose(out1[0], np.full((2, 3), 2.0))
    np.testing.assert_allclose(out1[0], out2[0])
    assert monitor.stat_get("executor_runs") == 2
    assert monitor.stat_get("executor_compile_miss") == 1
    assert monitor.stat_get("executor_compile_hit") == 1
    names = _trace_names(tmp_path)
    assert "executor/run" in names
    assert "executor/compile" in names


def test_dataloader_counters_sync_and_prefetch(tracing, tmp_path):
    _reset("dataloader_batches", "dataloader_wait_ns",
           "dataloader_worker_batch_ns")
    ds = TensorDataset([np.arange(8, dtype=np.float32).reshape(8, 1),
                        np.arange(8, dtype=np.int64)])
    n = sum(1 for _ in DataLoader(ds, batch_size=2))
    assert n == 4
    assert monitor.stat_get("dataloader_batches") == 4
    assert monitor.stat_get("dataloader_wait_ns") > 0
    # threaded prefetch path (shared memory off -> _PrefetchIter)
    n = sum(1 for _ in DataLoader(ds, batch_size=2, num_workers=1,
                                  use_shared_memory=False))
    assert n == 4
    assert monitor.stat_get("dataloader_batches") == 8
    assert monitor.stat_get("dataloader_worker_batch_ns") > 0
    names = _trace_names(tmp_path)
    assert "dataloader/batch" in names
    assert "dataloader/wait" in names


def test_collective_counters(tracing):
    import paddle_tpu.distributed as dist
    _reset("collective_all_reduce_calls", "collective_all_reduce_bytes",
           "collective_all_reduce_ns", "collective_broadcast_calls")
    t = paddle.to_tensor(np.ones((8,), np.float32))
    dist.all_reduce(t)
    dist.all_reduce(t)
    dist.broadcast(t, src=0)
    assert monitor.stat_get("collective_all_reduce_calls") == 2
    assert monitor.stat_get("collective_all_reduce_bytes") == 2 * 32
    assert monitor.stat_get("collective_all_reduce_ns") > 0
    assert monitor.stat_get("collective_broadcast_calls") == 1


@pytest.mark.skipif(_native.lib() is None, reason="needs native runtime")
def test_ps_rpc_counters(tracing, tmp_path):
    from paddle_tpu.distributed.ps import PsClient, PsServer, TableConfig
    _reset("ps_client_calls", "ps_client_bytes_out", "ps_client_bytes_in",
           "ps_client_rtt_ns", "ps_client_pull_sparse_calls")
    srv = PsServer([TableConfig(700, "sparse", 4, "sgd", lr=0.1,
                                init_range=0.1, seed=7)], port=0)
    port = srv.start()
    cli = PsClient([f"127.0.0.1:{port}"])
    cli.register_sparse(700, 4)
    try:
        rows = cli.pull_sparse(700, np.array([1, 2, 3], np.uint64))
        assert rows.shape == (3, 4)
        cli.push_sparse_grad(700, np.array([1, 2, 3], np.uint64),
                             np.ones((3, 4), np.float32))
    finally:
        cli.stop_servers()
        srv.stop()
    assert monitor.stat_get("ps_client_pull_sparse_calls") == 1
    assert monitor.stat_get("ps_client_calls") >= 2  # pull + push (+stop)
    assert monitor.stat_get("ps_client_bytes_out") > 0
    assert monitor.stat_get("ps_client_bytes_in") > 0
    assert monitor.stat_get("ps_client_rtt_ns") > 0
    assert "ps/pull_sparse" in _trace_names(tmp_path)


def test_sampled_dispatch_observer(tracing, tmp_path):
    obs.disable()
    profiler.reset()
    monitor.stat_reset("dispatch_sampled_ops")
    obs.enable(categories=["dispatch"], dispatch_sample_rate=1.0)
    try:
        x = paddle.to_tensor(np.ones((2,), np.float32))
        for _ in range(3):
            x = x + x
    finally:
        obs.disable()
    assert monitor.stat_get("dispatch_sampled_ops") >= 3
    assert any(n.startswith("op/") for n in _trace_names(tmp_path))


def test_reenable_without_dispatch_removes_sampler(tmp_path):
    profiler.reset()
    monitor.stat_reset("dispatch_sampled_ops")
    obs.enable(categories=["dispatch"], dispatch_sample_rate=1.0)
    obs.enable()  # default categories: dispatch must be torn down
    try:
        x = paddle.to_tensor(np.ones((2,), np.float32))
        x = x + x
    finally:
        obs.disable()
    assert monitor.stat_get("dispatch_sampled_ops") == 0
    assert not any(n.startswith("op/") for n in _trace_names(tmp_path))


def test_event_buffer_cap_drops_not_grows(tracing, tmp_path):
    old_max = profiler._MAX_EVENTS
    profiler.reset()
    profiler._MAX_EVENTS = 5
    try:
        for i in range(8):
            with obs.trace_span(f"s{i}", cat="user"):
                pass
        # 5 admitted (native or fallback buffer), 3 counted as dropped
        assert profiler.export_chrome_tracing(str(tmp_path / "c.json")) == 5
        assert profiler.dropped_events() == 3
        profiler.reset()  # reset clears the cap accounting too
        assert profiler.dropped_events() == 0
    finally:
        profiler._MAX_EVENTS = old_max
        profiler.reset()


# -- step telemetry --------------------------------------------------------

def test_step_timer_window_rates(tracing):
    _reset("dataloader_wait_ns", "jit_build_ns", "executor_compile_ns",
           "jit_backend_compile_ns")
    timer = obs.StepTimer(window=4, publish_as="ttest").start()
    assert timer.step(tokens=100, examples=10) is not None or True
    for _ in range(3):
        monitor.stat_add("dataloader_wait_ns", 2_000_000)  # 2ms fake wait
        time.sleep(0.01)
        t = timer.step(tokens=100, examples=10)
    assert t["window_steps"] >= 3
    assert t["tokens_per_s"] > 0
    assert t["examples_per_s"] > 0
    assert 0 < t["data_wait_frac"] <= 1
    assert t["step_time_ms"] > 0
    # published onto the gauge board for the scraper
    g = export_mod.gauges()
    assert g["ttest_tokens_per_s"] > 0


def test_step_timer_mfu_estimate():
    timer = obs.StepTimer(window=2, flops_per_step=1e9, peak_flops=1e12)
    t = timer.step()
    assert t is None  # first step() without start() only anchors the window
    time.sleep(0.005)
    t = timer.step()
    assert "mfu" in t and t["mfu"] > 0


def test_step_timer_flops_per_token_override():
    """The per-model flops_per_token override drives MFU from the
    window's actual token throughput and beats the flops_per_step
    estimate when both are given."""
    timer = obs.StepTimer(window=4, flops_per_step=1e20,  # would be absurd
                          flops_per_token=1e6, peak_flops=1e12,
                          publish_as=None).start()
    time.sleep(0.005)
    t = timer.step(tokens=1000)
    # achieved = 1e6 * 1000 / dt; dt >= 5ms -> mfu <= 0.2, far below the
    # absurd flops_per_step estimate (which would exceed 1e4)
    assert 0 < t["mfu"] < 1.0
    # without token counts the override cannot apply; falls back
    timer2 = obs.StepTimer(window=2, flops_per_token=1e6,
                           flops_per_step=1e7, peak_flops=1e12,
                           publish_as=None).start()
    time.sleep(0.002)
    t2 = timer2.step()
    assert t2["mfu"] > 0  # flops_per_step fallback path


# -- exporters -------------------------------------------------------------

def test_prometheus_and_json_exporters(tracing, tmp_path):
    monitor.stat_reset("obs_test_counter")
    monitor.stat_add("obs_test_counter", 5)
    export_mod.publish("obs_test", {"rate": 1.5, "skipme": None})
    text = export_mod.prometheus_text()
    assert "# TYPE paddle_tpu_obs_test_counter counter" in text
    assert "paddle_tpu_obs_test_counter 5" in text
    assert "paddle_tpu_obs_test_rate 1.5" in text
    assert "skipme" not in text
    data = export_mod.write_json(str(tmp_path / "t.json"))
    assert data["counters"]["obs_test_counter"] == 5
    assert data["gauges"]["obs_test_rate"] == 1.5
    on_disk = json.load(open(tmp_path / "t.json"))
    assert on_disk["counters"]["obs_test_counter"] == 5


def test_metrics_http_server(tracing):
    from urllib.request import urlopen
    monitor.stat_reset("obs_http_counter")
    monitor.stat_add("obs_http_counter", 3)
    server = export_mod.start_http_server(port=0)
    try:
        body = urlopen(
            f"http://127.0.0.1:{server.port}/metrics", timeout=10).read()
        assert b"paddle_tpu_obs_http_counter 3" in body
        tele = json.loads(urlopen(
            f"http://127.0.0.1:{server.port}/telemetry.json",
            timeout=10).read())
        assert tele["counters"]["obs_http_counter"] == 3
    finally:
        server.stop()


@pytest.mark.skipif(not _native.AVAILABLE, reason="native runtime not built")
def test_ps_server_per_table_op_latency_export():
    """The native PS server's per-(table, op) service-side latencies show
    up as labeled counters in both exporters, per table."""
    from paddle_tpu.distributed.ps import PsClient, PsServer, TableConfig

    srv = PsServer([TableConfig(41, "sparse", 4, "sgd", lr=0.1,
                                init_range=0.1, seed=1),
                    TableConfig(42, "sparse", 4, "sgd", lr=0.1,
                                init_range=0.1, seed=1)], port=0)
    port = srv.start()
    cli = PsClient([f"127.0.0.1:{port}"])
    try:
        cli.register_sparse(41, 4)
        cli.register_sparse(42, 4)
        keys = np.arange(20, dtype=np.uint64)
        for table in (41, 42):
            rows = cli.pull_sparse(table, keys)
            cli.push_sparse_grad(table, keys, np.ones_like(rows))
        stats = {(r["table"], r["op"]): r for r in srv.stats()}
        for table in (41, 42):
            for op in ("pull_sparse", "push_sparse_grad"):
                r = stats[(table, op)]
                assert r["calls"] >= 1 and r["ns"] > 0
        text = export_mod.prometheus_text()
        assert ('paddle_tpu_ps_server_op_ns{table="41",op="pull_sparse"}'
                in text)
        assert ('paddle_tpu_ps_server_op_calls{table="42",'
                'op="push_sparse_grad"}' in text)
        tele = export_mod.telemetry_dict()
        assert any(k.startswith("ps_server_op_ns") for k in
                   tele["collected"])
    finally:
        cli.stop_servers()
        srv.stop()


def test_collector_errors_do_not_kill_scrape():
    def broken():
        raise RuntimeError("collector exploded")

    export_mod.register_collector("obs_test_broken", broken)
    try:
        text = export_mod.prometheus_text()  # must not raise
        assert "obs_test_broken_collector_errors" in text
    finally:
        export_mod.unregister_collector("obs_test_broken")


# -- chip peaks --------------------------------------------------------------

@pytest.mark.parametrize("peak", ["bf16_flops_per_s", "hbm_bytes_per_s"])
def test_package_peaks_agree_with_the_benchmarks(peak):
    """The package holds each chip peak once; the benchmark's own table
    (`chipbench/peaks.json`, read only) says the same of the same chip."""
    from paddle_tpu.observability import overlap, step
    kind = "TPU v5 lite"
    with open(os.path.join(REPO, "chipbench", "peaks.json")) as f:
        theirs = json.load(f)["devices"][kind][peak]
    ours = {"bf16_flops_per_s": {step.PEAK_BF16_FLOPS[kind],
                                 overlap.DEFAULT_PEAK_FLOPS},
            "hbm_bytes_per_s": {overlap.DEFAULT_HBM_GBPS * 1e9}}[peak]
    assert ours == {theirs}


# -- end-to-end acceptance -------------------------------------------------

def test_fit_three_steps_exports_trace_and_telemetry(tracing, tmp_path):
    """Acceptance: a 3-step hapi.Model.fit with tracing on exports a
    chrome trace holding executor step spans, dataloader spans, and a
    compile-cache event; the Prometheus exporter carries the step
    telemetry (tokens/s, data-wait fraction)."""
    _reset("jit_cache_miss", "dataloader_wait_ns")
    paddle.seed(0)
    xs = np.random.RandomState(0).rand(6, 4).astype(np.float32)
    ys = np.random.RandomState(1).randint(0, 3, (6, 1)).astype(np.int64)
    ds = TensorDataset([xs, ys])
    model = paddle.Model(nn.Linear(4, 3))
    model.prepare(
        optimizer=paddle.optimizer.SGD(learning_rate=0.1,
                                       parameters=model.parameters()),
        loss=nn.CrossEntropyLoss())
    telem = paddle.hapi.callbacks.TelemetryCallback(
        tokens_per_batch=8, examples_per_batch=2, window=4, export_freq=1,
        prom_path=str(tmp_path / "metrics.prom"),
        json_path=str(tmp_path / "telemetry.json"))
    model.fit(ds, batch_size=2, epochs=1, verbose=0, shuffle=False,
              callbacks=[telem])

    names = _trace_names(tmp_path)
    assert "executor/step" in names, names  # compiled train-step runs
    assert any(n.startswith("dataloader/") for n in names), names
    assert any(n in ("jit/compile", "jax/backend_compile")
               for n in names), names  # >=1 compile-cache event
    assert "hapi/train_batch" in names

    # 3 steps -> telemetry window has data; exporter text carries it
    t = telem.last_telemetry
    assert t is not None and t["window_steps"] >= 2
    assert t["tokens_per_s"] > 0
    assert "data_wait_frac" in t
    prom = (tmp_path / "metrics.prom").read_text()
    assert "paddle_tpu_step_tokens_per_s" in prom
    assert "paddle_tpu_step_data_wait_frac" in prom
    tele = json.loads((tmp_path / "telemetry.json").read_text())
    assert tele["gauges"]["step_tokens_per_s"] > 0
    # the run's own counters made it into the same scrape payload
    assert tele["counters"].get("jit_cache_miss", 0) >= 1
