"""Serving engine: bucketed AOT compilation, concurrent dynamic batching,
pass pipeline, SLO telemetry — plus the PR-6 inference satellites.

Mirrors the reference's AnalysisPredictor contracts (`analysis_predictor.cc`
prepare/optimize/run + ZeroCopyTensor semantics) over the StableHLO
artifact: arbitrary ragged traffic must serve through <= len(bucket_ladder)
pre-compiled executables with NO request-path compiles, and padded-batch
outputs must be bitwise-equal (fp32) to per-request unbatched runs.
"""
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.serving as serving
from paddle_tpu import monitor
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.jit.io import save as jit_save
from paddle_tpu.jit.to_static import InputSpec
from paddle_tpu.observability import export as obs_export


def _mlp(in_dim=8, hidden=16, out_dim=4, seed=7):
    paddle.seed(seed)
    m = nn.Sequential(nn.Linear(in_dim, hidden), nn.Tanh(),
                      nn.Linear(hidden, out_dim))
    m.eval()
    return m


def assert_matches_eager(got, want):
    """Against the LIVE model run eagerly — op by op, at the request's
    own row count — the engine's fused bucket program may round a dot
    an ulp or two differently on XLA:CPU (another vector width, another
    FMA contraction). Bitwise equality is the contract with the
    unbatched Predictor, which runs the same exported program; the
    eager model is held to float32 rounding."""
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    """Saved batch-polymorphic StableHLO artifact + the live model."""
    model = _mlp()
    prefix = str(tmp_path_factory.mktemp("serving") / "m")
    jit_save(model, prefix,
             input_spec=[InputSpec([None, 8], "float32", name="feat")])
    return model, prefix


class TestBucketedAOT:
    def test_ragged_batches_bitwise_equal_unbatched(self, artifact):
        """Acceptance: padded-bucket outputs == per-request unbatched
        Predictor runs, bitwise (fp32)."""
        _model, prefix = artifact
        pred = create_predictor(Config(prefix + ".pdmodel",
                                       prefix + ".pdiparams"))
        with serving.Engine(prefix, bucket_ladder=(1, 4, 8),
                            batch_timeout_ms=1.0) as eng:
            rng = np.random.RandomState(0)
            for rows in (1, 2, 3, 4, 5, 7, 8):
                x = rng.randn(rows, 8).astype(np.float32)
                (want,) = pred.run([x])
                (got,) = eng.predict(x)
                assert got.dtype == np.float32
                np.testing.assert_array_equal(got, want)

    def test_bucket_selection(self, artifact):
        _model, prefix = artifact
        with serving.Engine(prefix, bucket_ladder=(1, 4, 8)) as eng:
            assert [eng.bucket_for(r) for r in (1, 2, 4, 5, 8)] == \
                [1, 4, 4, 8, 8]
            with pytest.raises(ValueError, match="exceed"):
                eng.bucket_for(9)

    def test_ladder_executables_no_request_path_compiles(self, artifact):
        """Acceptance: <= len(bucket_ladder) compiled executables, zero
        compiles on the request path after warmup — counter evidence via
        the jax backend-compile hook AND the engine's own AOT counter."""
        import paddle_tpu.observability as obs
        _model, prefix = artifact
        obs.enable()
        try:
            eng = serving.Engine(prefix, bucket_ladder=(1, 4, 8),
                                 batch_timeout_ms=1.0)
            assert eng.aot_compiles == 3 == len(eng.bucket_ladder)
            compiles_after_load = monitor.stats().get(
                "jit_backend_compiles", 0)
            aot_after_load = monitor.stats()["serving_aot_compiles"]
            rng = np.random.RandomState(1)
            for rows in (2, 1, 5, 3, 8, 7, 4, 6):  # every bucket, ragged
                eng.predict(rng.randn(rows, 8).astype(np.float32))
            assert monitor.stats().get("jit_backend_compiles", 0) == \
                compiles_after_load
            assert monitor.stats()["serving_aot_compiles"] == aot_after_load
            assert eng.stats()["executables"] == 3
            eng.close()
        finally:
            obs.disable()

    def test_oversized_request_chunks_transparently(self, artifact):
        model, prefix = artifact
        with serving.Engine(prefix, bucket_ladder=(1, 4),
                            batch_timeout_ms=1.0) as eng:
            x = np.random.RandomState(2).randn(11, 8).astype(np.float32)
            (got,) = eng.predict(x)
            np.testing.assert_array_equal(got, model(Tensor(x)).numpy())
            assert eng.stats()["chunked_requests"] == 1

    def test_input_validation(self, artifact):
        _model, prefix = artifact
        with serving.Engine(prefix, bucket_ladder=(4,)) as eng:
            with pytest.raises(ValueError, match="expected 1 inputs"):
                eng.predict(np.ones((2, 8), np.float32),
                            np.ones((2, 8), np.float32))
            with pytest.raises(ValueError, match="got shape"):
                eng.predict(np.ones((2, 9), np.float32))
            with pytest.raises(ValueError, match="empty request"):
                eng.predict(np.zeros((0, 8), np.float32))

    def test_non_batch_major_output_rejected(self):
        """A fetch whose axis 0 is not the batch can't be sliced back to
        requests — the engine must refuse at load, not serve garbage."""
        paddle.seed(0)
        from paddle_tpu import static
        prog = static.Program()
        with static.program_guard(prog):
            x = static.data("x", [-1, 4], "float32")
            w = static.create_parameter([4, 4], "float32")
            red = paddle.sum(paddle.matmul(x, w))  # batch-reduced
        with pytest.raises(ValueError, match="not batch-major"):
            serving.Engine.from_program(prog, [red], bucket_ladder=(2,))

    def test_unreachable_buckets_not_compiled(self, artifact):
        """max_batch_size caps batch rows, so ladder buckets above it can
        never be selected — compiling them would waste load latency."""
        model, prefix = artifact
        with serving.Engine(prefix, bucket_ladder=(1, 4, 16),
                            max_batch_size=4,
                            batch_timeout_ms=1.0) as eng:
            assert eng.bucket_ladder == (1, 4)
            assert eng.aot_compiles == 2
            x = np.random.RandomState(21).randn(7, 8).astype(np.float32)
            (got,) = eng.predict(x)  # chunks through the 4-bucket
            np.testing.assert_array_equal(got, model(Tensor(x)).numpy())

    def test_fixed_batch_artifact_rejected(self, tmp_path):
        model = _mlp()
        prefix = str(tmp_path / "fixed")
        jit_save(model, prefix, input_spec=[InputSpec([2, 8], "float32")])
        with pytest.raises(ValueError, match="batch-polymorphic"):
            serving.Engine(prefix, bucket_ladder=(1, 4))


class TestConcurrentBatching:
    def test_concurrent_clients_coalesce(self, artifact):
        """N threads of ragged traffic: every future resolves with correct
        rows, and at least one device step served multiple requests."""
        model, prefix = artifact
        with serving.Engine(prefix, bucket_ladder=(1, 4, 16),
                            batch_timeout_ms=20.0) as eng:
            results = {}

            def client(i):
                rng = np.random.RandomState(100 + i)
                for j in range(5):
                    x = rng.randn(1 + (i + j) % 3, 8).astype(np.float32)
                    results[(i, j)] = (x, eng.predict(x))

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            stats = eng.stats()
        assert len(results) == 40
        for x, (out,) in results.values():
            assert out.shape[0] == x.shape[0]
            assert_matches_eager(out, model(Tensor(x)).numpy())
        assert stats["requests"] == 40
        assert stats["multi_request_batches"] >= 1
        assert stats["batches"] < 40  # coalescing actually happened

    def test_timeout_flushes_partial_batch(self, artifact):
        """A lone request must not wait for a full bucket: the
        batch_timeout_ms window flushes it."""
        _model, prefix = artifact
        with serving.Engine(prefix, bucket_ladder=(16,),
                            batch_timeout_ms=30.0) as eng:
            t0 = time.perf_counter()
            (out,) = eng.predict(np.ones((2, 8), np.float32))
            dt = time.perf_counter() - t0
            assert out.shape == (2, 4)
            assert dt < 10.0  # flushed by timeout, not stuck
            assert eng.stats()["padded_rows"] == 14
        g = obs_export.gauges()
        assert g["serving_batch_fill_ratio"] == pytest.approx(2 / 16)

    def test_submit_returns_future(self, artifact):
        _model, prefix = artifact
        with serving.Engine(prefix, bucket_ladder=(4,),
                            batch_timeout_ms=1.0) as eng:
            futs = [eng.submit(np.ones((1, 8), np.float32))
                    for _ in range(6)]
            outs = [f.result(timeout=30) for f in futs]
        assert all(o[0].shape == (1, 4) for o in outs)

    def test_cancelled_future_does_not_poison_batch(self, artifact):
        """A caller cancelling its queued future must not break the
        co-batched requests' results (regression: set_result on the
        cancelled future raised InvalidStateError into the batch)."""
        model, prefix = artifact
        with serving.Engine(prefix, bucket_ladder=(1, 4, 16),
                            batch_timeout_ms=200.0) as eng:
            x = np.random.RandomState(30).randn(2, 8).astype(np.float32)
            f1 = eng.submit(x)  # opens a long coalescing window
            f2 = eng.submit(np.ones((1, 8), np.float32))
            f2.cancel()  # walk away while queued
            (out,) = f1.result(timeout=30)
        np.testing.assert_array_equal(out, model(Tensor(x)).numpy())

    def test_close_rejects_new_requests(self, artifact):
        _model, prefix = artifact
        eng = serving.Engine(prefix, bucket_ladder=(4,))
        eng.close()
        with pytest.raises(RuntimeError, match="closed"):
            eng.predict(np.ones((1, 8), np.float32))


class TestPassPipeline:
    def test_fp32_from_layer_bitwise(self):
        model = _mlp(seed=11)
        x = np.random.RandomState(3).randn(5, 8).astype(np.float32)
        want = model(Tensor(x)).numpy()
        with serving.Engine.from_layer(
                model, [InputSpec([None, 8], "float32")],
                bucket_ladder=(1, 8), batch_timeout_ms=1.0) as eng:
            (got,) = eng.predict(x)
        np.testing.assert_array_equal(got, want)

    def test_bf16_pass_within_tolerance(self):
        model = _mlp(seed=12)
        x = np.random.RandomState(4).randn(6, 8).astype(np.float32)
        want = model(Tensor(x)).numpy()
        with serving.Engine.from_layer(
                model, [InputSpec([None, 8], "float32")],
                bucket_ladder=(8,), passes=("bf16",)) as eng:
            (got,) = eng.predict(x)
        assert got.dtype == np.float32  # cast back at the boundary
        np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)
        assert not np.array_equal(got, want)  # really computed in bf16

    def test_bf16_on_stablehlo_artifact_raises(self, artifact):
        _model, prefix = artifact
        with pytest.raises(ValueError, match="StableHLO"):
            serving.Engine(prefix, passes=("bf16",))

    def test_unknown_pass_raises(self, artifact):
        _model, prefix = artifact
        with pytest.raises(ValueError, match="unknown serving pass"):
            serving.Engine(prefix, passes=("fuse_everything",))

    def test_donate_pass_serves_correctly(self, artifact):
        model, prefix = artifact
        x = np.random.RandomState(5).randn(3, 8).astype(np.float32)
        with serving.Engine(prefix, bucket_ladder=(4,),
                            passes=("donate",)) as eng:
            (got,) = eng.predict(x)
        np.testing.assert_array_equal(got, model(Tensor(x)).numpy())

    def test_output_pruning_subset(self, tmp_path):
        """outputs= serves a fetch subset (reference: prune-to-fetch-set);
        unknown names raise with the valid list."""
        paddle.seed(13)

        class TwoHead(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = nn.Linear(8, 8)
                self.a = nn.Linear(8, 4)
                self.b = nn.Linear(8, 2)

            def forward(self, x):
                h = paddle.tanh(self.fc(x))
                return self.a(h), self.b(h)

        model = TwoHead()
        model.eval()
        prefix = str(tmp_path / "two")
        jit_save(model, prefix, input_spec=[InputSpec([None, 8], "float32")])
        x = np.random.RandomState(6).randn(2, 8).astype(np.float32)
        _wa, wb = model(Tensor(x))
        with serving.Engine(prefix, bucket_ladder=(4,),
                            outputs=["output_1"]) as eng:
            assert eng.output_names == ["output_1"]
            outs = eng.predict(x)
        assert len(outs) == 1
        assert_matches_eager(outs[0], wb.numpy())
        with pytest.raises(ValueError, match="valid output names"):
            serving.Engine(prefix, outputs=["output_9"])

    def test_serving_ladder_twin_registered_and_clean(self):
        from paddle_tpu.analysis import errors, ladder
        assert "serving" in ladder.LADDER_BUILDERS
        findings, summary = ladder.verify_ladder(["serving"])
        assert not findings, [f.message for f in findings]
        assert len(summary["serving"]) == 2  # source + optimized twin


class TestSLOTelemetry:
    def test_percentile_summaries_and_counters_export(self, artifact):
        _model, prefix = artifact
        obs_export.clear_summaries()
        with serving.Engine(prefix, bucket_ladder=(1, 4),
                            batch_timeout_ms=1.0) as eng:
            rng = np.random.RandomState(7)
            for _ in range(12):
                eng.predict(rng.randn(1 + rng.randint(4), 8)
                            .astype(np.float32))
        text = obs_export.prometheus_text()
        assert "# TYPE paddle_tpu_serving_latency_ms summary" in text
        for q in ('quantile="0.5"', 'quantile="0.95"', 'quantile="0.99"'):
            assert f"paddle_tpu_serving_latency_ms{{{q}}}" in text
        assert "paddle_tpu_serving_latency_ms_count" in text
        assert 'paddle_tpu_serving_requests_total{bucket="' in text
        assert "paddle_tpu_serving_batch_fill_ratio" in text
        tele = obs_export.telemetry_dict()
        lat = tele["summaries"]["serving_latency_ms"]
        assert lat["count"] >= 12
        assert lat["p50"] <= lat["p95"] <= lat["p99"]
        assert "serving_queue_wait_ms" in tele["summaries"]
        assert "serving_device_ms" in tele["summaries"]

    def test_empty_summary_serializes_as_valid_json(self):
        """A registered summary with zero observations must not leak the
        invalid-JSON literal NaN into telemetry (strict parsers reject
        it)."""
        import json
        obs_export.clear_summaries()
        obs_export.summary("t_empty")  # get-or-create before any traffic
        try:
            snap = obs_export.summaries()["t_empty"]
            assert snap["p50"] is None and snap["count"] == 0
            text = json.dumps(obs_export.telemetry_dict())
            json.loads(text)  # strict round-trip
            assert "NaN" not in text
        finally:
            obs_export.clear_summaries()

    def test_clear_summaries_keeps_live_engine_exporting(self, artifact):
        """clear_summaries() resets in place: an engine's cached board
        handles must keep exporting afterwards (regression: dropping
        registry entries orphaned live engines' telemetry)."""
        _model, prefix = artifact
        with serving.Engine(prefix, bucket_ladder=(1, 4),
                            batch_timeout_ms=1.0) as eng:
            eng.predict(np.ones((1, 8), np.float32))
            obs_export.clear_summaries()  # mid-life reset
            snap = obs_export.summaries()["serving_latency_ms"]
            assert snap["p50"] is None  # quantile window emptied
            before = snap["count"]  # lifetime count stays monotonic
            eng.predict(np.ones((1, 8), np.float32))
            snap = obs_export.summaries()["serving_latency_ms"]
            assert snap["p50"] is not None  # still wired to the board
            assert snap["count"] == before + 1

    def test_max_batch_size_validated(self, artifact):
        _model, prefix = artifact
        for bad in (0, -3):
            with pytest.raises(ValueError, match="max_batch_size"):
                serving.Engine(prefix, bucket_ladder=(1, 4),
                               max_batch_size=bad)
        with pytest.raises(ValueError, match="exceeds the top bucket"):
            serving.Engine(prefix, bucket_ladder=(1, 4), max_batch_size=9)

    def test_submit_snapshots_caller_buffer(self, artifact):
        """Async contract: mutating the input array after submit() must
        not corrupt the queued request."""
        model, prefix = artifact
        with serving.Engine(prefix, bucket_ladder=(1, 4, 16),
                            batch_timeout_ms=100.0) as eng:
            x = np.random.RandomState(31).randn(2, 8).astype(np.float32)
            want = model(Tensor(x)).numpy()
            fut = eng.submit(x)
            x[:] = 0.0  # caller reuses its buffer while queued
            (out,) = fut.result(timeout=30)
        np.testing.assert_array_equal(out, want)

    def test_summary_quantiles(self):
        s = obs_export.Summary("t_unit", window=128)
        for v in range(1, 101):
            s.observe(float(v))
        q = s.quantiles()
        assert q[0.5] == pytest.approx(50.5, abs=1.0)
        assert q[0.99] == pytest.approx(100.0, abs=2.0)
        assert s.count == 100 and s.sum == pytest.approx(5050.0)

    def test_serving_spans_recorded(self, artifact, tmp_path):
        import json

        import paddle_tpu.observability as obs
        _model, prefix = artifact
        obs.enable(categories=["serving"])
        try:
            from paddle_tpu import profiler
            profiler.reset()
            with serving.Engine(prefix, bucket_ladder=(2,),
                                batch_timeout_ms=1.0) as eng:
                eng.predict(np.ones((1, 8), np.float32))
            trace = str(tmp_path / "trace.json")
            obs.export_chrome_trace(trace)
        finally:
            obs.disable()
        with open(trace) as f:
            names = {e["name"] for e in json.load(f)["traceEvents"]}
        assert "serving/aot_compile" in names
        assert "serving/device_step" in names
        assert "serving/queue_wait" in names
        assert "serving/pad" in names


class TestPredictorDelegation:
    def test_config_enable_serving_engine(self, artifact):
        model, prefix = artifact
        cfg = Config(prefix + ".pdmodel", prefix + ".pdiparams")
        cfg.enable_serving_engine(bucket_ladder=(1, 4), batch_timeout_ms=1.0)
        pred = create_predictor(cfg)
        x = np.random.RandomState(8).randn(3, 8).astype(np.float32)
        pred.get_input_handle("feat").copy_from_cpu(x)
        outs = pred.run()
        np.testing.assert_array_equal(outs[0], model(Tensor(x)).numpy())
        assert pred._engine.stats()["requests"] == 1
        out = pred.get_output_handle("output_0").copy_to_cpu()
        np.testing.assert_array_equal(out, outs[0])
        pred.close()
        assert pred._engine is None  # engine released, thread joined

    def test_delegation_with_output_subset(self, tmp_path):
        """An outputs= subset on the delegated engine must re-map the
        predictor's output names too (regression: get_output_handle used
        to index the stale full-name list into the pruned results)."""
        paddle.seed(14)

        class TwoHead(nn.Layer):
            def __init__(self):
                super().__init__()
                self.a = nn.Linear(8, 4)
                self.b = nn.Linear(8, 2)

            def forward(self, x):
                return self.a(x), self.b(x)

        model = TwoHead()
        model.eval()
        prefix = str(tmp_path / "two")
        jit_save(model, prefix, input_spec=[InputSpec([None, 8], "float32")])
        cfg = Config(prefix + ".pdmodel", prefix + ".pdiparams")
        cfg.enable_serving_engine(bucket_ladder=(4,), batch_timeout_ms=1.0,
                                  outputs=["output_1"])
        pred = create_predictor(cfg)
        assert pred.get_output_names() == ["output_1"]
        x = np.random.RandomState(9).randn(2, 8).astype(np.float32)
        pred.get_input_handle(pred.get_input_names()[0]).copy_from_cpu(x)
        pred.run()
        _wa, wb = model(Tensor(x))
        out = pred.get_output_handle("output_1").copy_to_cpu()
        assert_matches_eager(out, wb.numpy())
        with pytest.raises(ValueError, match="valid output names"):
            pred.get_output_handle("output_0")  # pruned away
        pred.close()

    def test_as_engine_from_predictor(self, artifact):
        model, prefix = artifact
        pred = create_predictor(Config(prefix + ".pdmodel",
                                       prefix + ".pdiparams"))
        with pred.as_engine(bucket_ladder=(2,),
                            batch_timeout_ms=1.0) as eng:
            x = np.ones((2, 8), np.float32)
            np.testing.assert_array_equal(eng.predict(x)[0],
                                          model(Tensor(x)).numpy())

    def test_as_engine_artifact_ignores_input_specs(self, artifact):
        """input_specs on a StableHLO-backed predictor is redundant: it
        must warn and serve, not crash with an opaque TypeError."""
        _model, prefix = artifact
        pred = create_predictor(Config(prefix + ".pdmodel",
                                       prefix + ".pdiparams"))
        with pytest.warns(UserWarning, match="records its own input"):
            eng = pred.as_engine(
                input_specs=[InputSpec([None, 8], "float32")],
                bucket_ladder=(2,), batch_timeout_ms=1.0)
        with eng:
            assert eng.predict(np.ones((1, 8), np.float32))[0].shape == \
                (1, 4)


class TestInferenceSatellites:
    """Regression tests for the PR-6 inference bugfixes."""

    def test_reshape_declares_and_enforces(self, artifact):
        _model, prefix = artifact
        pred = create_predictor(Config(prefix + ".pdmodel",
                                       prefix + ".pdiparams"))
        h = pred.get_input_handle("feat")
        x = np.ones((3, 8), np.float32)
        h.reshape([3, 8])
        h.copy_from_cpu(x)  # exact match ok
        h.reshape([-1, 8])
        h.copy_from_cpu(x)  # wildcard batch ok
        h.reshape([2, 8])
        with pytest.raises(ValueError, match="declared via reshape"):
            h.copy_from_cpu(x)
        # the declaration persists across handle objects (reference: the
        # reshape sizes the predictor's feed tensor, not a local view)
        with pytest.raises(ValueError, match="declared via reshape"):
            pred.get_input_handle("feat").copy_from_cpu(x)
        with pytest.raises(ValueError, match="declared via reshape"):
            h.copy_from_cpu(np.ones((2, 9), np.float32))

    def test_output_handle_bad_name_lists_valid(self, artifact):
        _model, prefix = artifact
        pred = create_predictor(Config(prefix + ".pdmodel",
                                       prefix + ".pdiparams"))
        with pytest.raises(ValueError, match=r"valid output names: "
                                             r"\['output_0'\]"):
            pred.get_output_handle("logits")

    def test_positional_names_still_work_on_named_artifacts(self, tmp_path):
        """Callers using conventional "output_<i>" names against an
        artifact with custom output names keep working (positional alias
        is unambiguous there); typos still raise with the valid list."""
        from paddle_tpu.jit.export import save_exported
        model = _mlp(seed=15)
        prefix = str(tmp_path / "named")
        sd = model.state_dict()
        save_exported(prefix, model.forward, list(sd.items()),
                      [InputSpec([None, 8], "float32", name="feat")],
                      output_names=["logits"])
        pred = create_predictor(Config(prefix + ".pdmodel",
                                       prefix + ".pdiparams"))
        assert pred.get_output_names() == ["logits"]
        x = np.ones((2, 8), np.float32)
        pred.get_input_handle("feat").copy_from_cpu(x)
        pred.run()
        np.testing.assert_array_equal(
            pred.get_output_handle("output_0").copy_to_cpu(),
            pred.get_output_handle("logits").copy_to_cpu())
        with pytest.raises(ValueError, match="valid output names"):
            pred.get_output_handle("output_1")  # out of range
        with pytest.raises(ValueError, match="valid output names"):
            pred.get_output_handle("logit")  # typo

    def test_results_do_not_alias_batch_buffer(self, artifact):
        """Resolved results must be standalone arrays, not views pinning
        the bucket-sized batch output (and its co-batched rows)."""
        _model, prefix = artifact
        with serving.Engine(prefix, bucket_ladder=(16,),
                            batch_timeout_ms=1.0) as eng:
            (out,) = eng.predict(np.ones((2, 8), np.float32))
        assert out.shape == (2, 4)
        assert out.base is None or out.base.shape == out.shape

    def test_legacy_output_handle_validation(self, tmp_path):
        """Legacy artifact (no recorded output names): malformed names
        raise instead of the old bare int() ValueError."""
        model = nn.Sequential(nn.Linear(4, 4))
        prefix = str(tmp_path / "leg")
        with pytest.warns(UserWarning, match="input_spec"):
            jit_save(model, prefix)
        pred = create_predictor(Config(prefix))
        with pytest.raises(ValueError, match="valid output names"):
            pred.get_output_handle("fetch/0")
        pred.run([np.ones((2, 4), np.float32)])
        with pytest.raises(ValueError, match="valid output names"):
            pred.get_output_handle("output_3")  # out of range post-run
        out = pred.get_output_handle("output_0").copy_to_cpu()
        assert out.shape == (2, 4)

    def test_bench_err_not_in_repo(self):
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert not os.path.exists(os.path.join(repo, "bench.err"))
        with open(os.path.join(repo, ".gitignore")) as f:
            assert "*.err" in f.read()
