"""A fourth family through the harness's "new files and entries only"
door: `chipbench/models/lfm2.py` and `chipbench/reference/lfm2.py` are
found by the configuration's `family`, through a fixture manifest of
their own (testdata/lfm2); one tiny cell runs end to end on the CPU, its
float8 control and half-batch fault come out not correct; the new
metrics' files ride readers that exist; `kernel_work` is held to counts
made by hand."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

from chipbench import check, limits, manifest, trace  # noqa: E402
from chipbench import run as run_mod  # noqa: E402
from chipbench.readers import (moe_stat, scope_roofline,  # noqa: E402
                               scope_share)

TESTDATA = os.path.join(BENCH, "testdata", "lfm2")
SEED = 2_147_483_659
CELL = "tiny_lfm2.s32"
REAL = "lfm2_24b_ep8_d5.s8192"
NEW_METRICS = {"conv.proj_share", "conv.mix_share", "conv.mix_roofline",
               "gqa.attention_roofline", "ffn.gated_share",
               "moe.load_max_over_mean_e8", "moe.rows_worked_over_routed"}
JOINED = {"recompute.replay_share", "rope.share", "norm.rms_share",
          "moe.route_share", "moe.experts_roofline", "moe.pairs_per_step"}


@pytest.fixture(scope="module")
def bench():
    return manifest.Manifest(os.path.join(TESTDATA, "BENCHMARK.json"),
                             base=TESTDATA)


def test_the_family_is_found_by_name(bench):
    cfg = bench.config(bench.cell(CELL)["config"])
    assert cfg["family"] == "lfm2"
    model_mod, ref_mod = manifest.family(cfg["family"])
    for name in ("weight_shapes", "stacked_keys", "program_names",
                 "make_batch", "parameter_count", "flops_per_token",
                 "attention_calls", "build_step", "kernel_work"):
        assert callable(getattr(model_mod, name)), name
    assert callable(ref_mod.loss_fn)
    shapes = model_mod.weight_shapes(cfg)
    # every layer's keys are its own, by its kind: a conv layer with the
    # dense FFN, an attention layer with experts, conv layers with
    # experts; the head is the embedding
    assert model_mod.stacked_keys() == ()
    assert {"l0_conv_in", "l0_gate", "l1_q", "l1_k_norm", "l1_router",
            "l2_conv_taps", "l4_e_down", "embed"} <= set(shapes)
    assert not {"head", "l0_router", "l1_conv_in", "l5_conv_in"} & set(shapes)
    with open(ref_mod.__file__) as f:
        assert "paddle_tpu" not in f.read()


def test_the_real_cell_reports_the_new_metrics():
    real = manifest.Manifest()
    traced = {m["name"] for m in real.metrics_of(REAL, True)}
    assert NEW_METRICS | JOINED <= traced
    assert {"step_mfu", "device.idle_share", "head.loss_share",
            "attention.scope_share", "optimizer.update_share",
            "amp.cast_share", "build.window_compiles"} <= traced
    assert not {"flash_attention_roofline", "attention.step_share",
                "moe.load_max_over_mean", "moe.shared_share", "mtp.share",
                "mla.proj_share", "moe.experts_share"} & traced
    assert {m["name"] for m in real.metrics_of(REAL, False)} == {
        "tokens_per_s_chip", "step_ms_p90", "setup_s"}
    for cell in real.cells:  # and no other cell is asked for them
        if cell != REAL:
            assert not NEW_METRICS & {m["name"]
                                      for m in real.metrics_of(cell, True)}
    for spec in real.metrics_of(REAL, True):
        assert callable(manifest.reader(spec["reader"]).read)
    cell = real.cell(REAL)
    assert (cell["recompute"], cell["batch"], cell["seq"], cell["k"]) == (
        "kernels", 4, 8192, 2)
    assert set(cell["limits"]) == set(check.NUMBERS)
    assert len(real.cells[REAL]["why"]) <= 200


@pytest.fixture(scope="module")
def result(bench):
    return run_mod.run_cell(CELL, SEED, 0.3, False, bench=bench,
                            require_tpu=False)


def test_a_tiny_cell_runs_end_to_end_and_is_correct(result, bench):
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 4
    assert result["device"]["platform"] == "cpu"
    assert set(result["compared"]) == set(check.NUMBERS)
    for name, limit in bench.cell(CELL)["limits"].items():
        assert result["compared"][name]["value"] <= limit


def test_the_float8_control_and_the_half_batch_come_out_not_correct(bench):
    got = limits.readings(CELL, SEED, bench=bench, require_tpu=False,
                          which=("control", "bfloat16", "half_batch"))
    cell_limits = bench.cell(CELL)["limits"]
    assert any(got["control"][n] > lim for n, lim in cell_limits.items())
    assert any(got["half_batch"][n] > lim for n, lim in cell_limits.items())
    assert all(got["bfloat16"][n] <= lim for n, lim in cell_limits.items())


def test_kernel_work_counts_shapes_and_pairs_alone():
    real = manifest.Manifest()
    cfg = real.config("lfm2_24b_ep8_d5")
    model_mod, _ref = manifest.family("lfm2")
    work = model_mod.kernel_work(cfg, {"batch": 2, "seq": 4096}, 1000)
    b, s = 2, 4096
    # one attention layer: 32 query heads of 64, half the square
    assert work["flash"]["flops"] == 3 * 2 * 2 * b * 32 * s * s * 64 // 2
    # q, o, do, dq at 2,048 wide and k, v, dk, dv at 512, six passes each
    assert work["flash"]["bytes"] == 6 * b * s * (2048 + 512) * 2
    assert work["experts"]["flops"] == 18 * 2048 * 1536 * 1000
    # four expert layers: 8 experts' three matrices twice read and once
    # written; a pair's row in and out, forward and backward
    assert work["experts"]["bytes"] == (
        4 * 3 * 8 * 3 * 2048 * 1536 * 2 + 1000 * 4 * 2048 * 2)
    # four operators: u (3h) in and h out forward, u and h in and 3h out
    # backward, bf16; 24 operations a channel a token
    assert work["short_conv"]["bytes"] == 4 * b * s * 11 * 2048 * 2
    assert work["short_conv"]["flops"] == 4 * b * s * 24 * 2048
    assert model_mod.attention_calls(cfg, {"batch": 2, "seq": 4096}) == {
        "calls_per_step": 1, "batch": 2, "seq": 4096, "heads": 32,
        "head_dim": 64, "causal": True, "bytes_per_element": 2}


def test_the_new_metrics_read_a_hand_made_run(monkeypatch):
    hlo = '''
  %fusion.1 = bf16[8] fusion(%p0), kind=kLoop, metadata={op_name="jit(f)/pt.layers.0/pt.short_conv/mul"}
  %fusion.2 = bf16[8] fusion(%p0), kind=kLoop, metadata={op_name="jit(f)/pt.layers.0/transpose(jvp(pt.short_conv))/mul"}
  %fusion.3 = bf16[8] fusion(%p0), kind=kOutput, metadata={op_name="jit(f)/pt.layers.0/pt.conv_in/pt.linear/dot_general"}
  %fusion.4 = bf16[8] fusion(%p0), kind=kOutput, metadata={op_name="jit(f)/pt.layers.0/pt.conv_out/pt.linear/dot_general"}
  %fusion.5 = bf16[8] fusion(%p0), kind=kOutput, metadata={op_name="jit(f)/pt.layers.0/pt.gate_proj/pt.linear/dot_general"}
  %custom-call.6 = bf16[8] custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/pt.layers.1/pt.attention/pt.flash/pt.flash_attention/pallas_call"}
  %fusion.7 = bf16[8] fusion(%p0), kind=kLoop, metadata={op_name="jit(f)/pt.optimizer/pt.update/add"}
'''
    ms = 1e6
    events = [["while.1", 0, 100 * ms], ["fusion.1", 0, 4 * ms],
              ["fusion.2", 4 * ms, 6 * ms], ["fusion.3", 10 * ms, 12 * ms],
              ["fusion.4", 22 * ms, 8 * ms], ["fusion.5", 30 * ms, 10 * ms],
              ["custom-call.6", 40 * ms, 20 * ms], ["fusion.7", 60 * ms, 40 * ms]]
    t = {"devices": {"/device:TPU:0": events}, "host": []}
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    run = {"cell": {"config": "lfm2_24b_ep8_d5", "batch": 4, "seq": 8192},
           "peaks": peaks,
           "traced": {"trace": t, "busy": trace.busy(t), "device_steps": 2}}
    work = {"flash": {"flops": 5e9, "bytes": 1e6},        # 5 ms by compute
            "short_conv": {"flops": 1e6, "bytes": 2e8}}   # 2 ms by bytes
    monkeypatch.setattr(scope_share, "registered",
                        lambda: {"table": {}, "hlo": hlo})
    monkeypatch.setattr(scope_roofline, "family_work",
                        lambda name: (lambda cfg, cell, pairs: work, {}))
    monkeypatch.setattr(moe_stat, "routing_stats", lambda: None)
    real = manifest.Manifest()
    specs = {m["name"]: m for m in real.metrics_of(REAL, True)}

    def read(name):
        spec = specs[name]
        return manifest.reader(spec["reader"]).read(run, spec["args"])

    assert read("conv.mix_share") == pytest.approx(100 * 10 / 100)
    assert read("conv.proj_share") == pytest.approx(100 * 20 / 100)
    assert read("ffn.gated_share") == pytest.approx(100 * 10 / 100)
    # forward and backward under the scope: 2 steps x 2 ms over 10 ms
    assert read("conv.mix_roofline") == pytest.approx(100 * 4 / 10)
    assert read("gqa.attention_roofline") == pytest.approx(100 * 10 / 20)
    # a program without the layer's counters: nothing to read, no error
    assert read("moe.rows_worked_over_routed") is None
    assert read("moe.load_max_over_mean_e8") is None


def test_the_counter_metrics_read_a_live_layer():
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.incubate.moe import HeldExpertsLayer

    paddle.seed(3)
    layer = HeldExpertsLayer(32, 24, 64, 4, ep_size=8, ep_rank=0,
                             norm_eps=1e-6)
    x = paddle.to_tensor(np.random.RandomState(0).randn(256, 32).astype(
        "float32"))
    for _ in range(3):
        layer(x)
    real = manifest.Manifest()
    specs = {m["name"]: m for m in real.metrics_of(REAL, True)}
    worked = moe_stat.read({}, specs["moe.rows_worked_over_routed"]["args"])
    assert worked >= 1.0  # the live blocks' rows over the pairs in them
    ratio = moe_stat.read({}, specs["moe.load_max_over_mean_e8"]["args"])
    assert 1.0 <= ratio <= 8.0  # the busiest of the 8 held over their mean
    del layer
