"""A fifth family through the harness's "new files and entries only"
door: `chipbench/models/qwen3_next.py` and `chipbench/reference/
qwen3_next.py` are found by the configuration's `family`, through a
fixture manifest of their own (testdata/qwen3_next); one tiny cell runs
end to end on the CPU, its float8 control and half-batch fault come out
not correct; the cell's cut has the table's parameters; the new
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

TESTDATA = os.path.join(BENCH, "testdata", "qwen3_next")
SEED = 2_147_483_659
CELL = "tiny_qwen3_next.s32"
REAL = "qwen3_next_ep16_d4.s8192"
NEW_METRICS = {"gdn.delta_rule_roofline", "gdn.delta_rule_share",
               "gdn.proj_share"}
JOINED = {"recompute.replay_share", "rope.share", "norm.rms_share",
          "moe.route_share", "moe.experts_roofline", "moe.pairs_per_step",
          "gqa.attention_roofline", "moe.shared_share", "moe.experts_share",
          "moe.rows_worked_over_routed", "moe.rows_folded_over_routed",
          "conv.mix_share", "conv.mix_roofline"}


@pytest.fixture(scope="module")
def bench():
    return manifest.Manifest(os.path.join(TESTDATA, "BENCHMARK.json"),
                             base=TESTDATA)


def test_the_family_is_found_by_name(bench):
    cfg = bench.config(bench.cell(CELL)["config"])
    assert cfg["family"] == "qwen3_next"
    model_mod, ref_mod = manifest.family(cfg["family"])
    for name in ("weight_shapes", "stacked_keys", "program_names",
                 "make_batch", "parameter_count", "flops_per_token",
                 "attention_calls", "build_step", "kernel_work"):
        assert callable(getattr(model_mod, name)), name
    assert callable(ref_mod.loss_fn)
    shapes = model_mod.weight_shapes(cfg)
    # every layer's keys are its own, by its kind: Gated DeltaNet in
    # layers 0-2, attention in layer 3, experts and a shared expert in
    # all four; the head is its own
    assert model_mod.stacked_keys() == ()
    assert {"l0_qkvz", "l0_conv", "l2_A_log", "l3_q", "l3_k_norm",
            "l3_router", "l1_s_expert_gate", "l3_e_down", "embed",
            "head"} <= set(shapes)
    assert not {"l3_qkvz", "l0_q", "l4_router"} & set(shapes)
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
                "conv.proj_share", "mla.proj_share", "mtp.share"} & traced
    assert {m["name"] for m in real.metrics_of(REAL, False)} == {
        "tokens_per_s_chip", "step_ms_p90", "setup_s"}
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
    # a call of k = 2 steps at least; how many fit the window depends on
    # what else the CPU runs
    assert result["attempted"] >= 2
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


def test_the_cut_holds_the_table_s_parameters():
    real = manifest.Manifest()
    cfg = real.config("qwen3_next_ep16_d4")
    model_mod, _ref = manifest.family("qwen3_next")
    assert model_mod.parameter_count(cfg) == 625_667_136
    # 16 bytes a parameter: 9.32 GiB of a v5e's 15.75 before activations
    assert model_mod.parameter_count(cfg) * 16 / 2 ** 30 == pytest.approx(
        9.32, abs=0.005)


def test_kernel_work_counts_shapes_and_pairs_alone():
    real = manifest.Manifest()
    cfg = real.config("qwen3_next_ep16_d4")
    model_mod, _ref = manifest.family("qwen3_next")
    work = model_mod.kernel_work(cfg, {"batch": 2, "seq": 4096}, 1000)
    b, s = 2, 4096
    # one attention layer: 16 query heads of 256, half the square
    assert work["flash"]["flops"] == 3 * 2 * 2 * b * 16 * s * s * 256 // 2
    # q, o, do, dq at 4,096 wide and k, v, dk, dv at 512, six passes each
    assert work["flash"]["bytes"] == 6 * b * s * (4096 + 512) * 2
    assert work["experts"]["flops"] == 18 * 2048 * 512 * 1000
    # four expert layers: 32 experts' three matrices twice read and once
    # written; a pair's row in and out, forward and backward
    assert work["experts"]["bytes"] == (
        4 * 3 * 32 * 3 * 2048 * 512 * 2 + 1000 * 4 * 2048 * 2)
    # three Gated DeltaNet layers: 18 x 128 x 128 a value head a token
    assert work["delta_rule"]["flops"] == 3 * b * s * 18 * 32 * 128 * 128
    assert work["delta_rule"]["bytes"] == 3 * b * s * 2 * (
        3 * 8256 + 2 * 4096)
    assert work["short_conv"]["flops"] == 3 * b * s * 24 * 8192
    assert work["short_conv"]["bytes"] == 3 * b * s * 5 * 8192 * 2
    assert model_mod.attention_calls(cfg, {"batch": 2, "seq": 4096}) == {
        "calls_per_step": 1, "batch": 2, "seq": 4096, "heads": 16,
        "head_dim": 256, "causal": True, "bytes_per_element": 2}


def test_the_new_metrics_read_a_hand_made_run(monkeypatch):
    hlo = '''
  %fusion.1 = bf16[8] fusion(%p0), kind=kLoop, metadata={op_name="jit(f)/pt.layers.0/pt.delta_rule/while/body/dot_general"}
  %fusion.2 = bf16[8] fusion(%p0), kind=kLoop, metadata={op_name="jit(f)/pt.layers.0/transpose(jvp(pt.delta_rule))/while/body/dot_general"}
  %fusion.3 = bf16[8] fusion(%p0), kind=kOutput, metadata={op_name="jit(f)/pt.layers.0/pt.gdn_proj/pt.in_proj_qkvz/pt.linear/dot_general"}
  %fusion.4 = bf16[8] fusion(%p0), kind=kOutput, metadata={op_name="jit(f)/pt.layers.0/pt.gdn_proj/pt.out_proj/pt.linear/dot_general"}
  %fusion.5 = bf16[8] fusion(%p0), kind=kLoop, metadata={op_name="jit(f)/pt.layers.0/pt.short_conv/add"}
  %fusion.6 = bf16[8] fusion(%p0), kind=kLoop, metadata={op_name="jit(f)/pt.optimizer/pt.update/add"}
'''
    ms = 1e6
    events = [["while.1", 0, 100 * ms], ["fusion.1", 0, 4 * ms],
              ["fusion.2", 4 * ms, 16 * ms], ["fusion.3", 20 * ms, 12 * ms],
              ["fusion.4", 32 * ms, 8 * ms], ["fusion.5", 40 * ms, 10 * ms],
              ["fusion.6", 50 * ms, 50 * ms]]
    t = {"devices": {"/device:TPU:0": events}, "host": []}
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    run = {"cell": {"config": "qwen3_next_ep16_d4", "batch": 4,
                    "seq": 8192},
           "peaks": peaks,
           "traced": {"trace": t, "busy": trace.busy(t), "device_steps": 2}}
    work = {"delta_rule": {"flops": 2e9, "bytes": 1e8},  # 2 ms by compute
            "short_conv": {"flops": 1e6, "bytes": 2e8}}  # 2 ms by bytes
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

    assert read("gdn.delta_rule_share") == pytest.approx(100 * 20 / 100)
    assert read("gdn.proj_share") == pytest.approx(100 * 20 / 100)
    # forward and backward under the scope: 2 steps x 2 ms over 20 ms
    assert read("gdn.delta_rule_roofline") == pytest.approx(100 * 4 / 20)
    assert read("conv.mix_roofline") == pytest.approx(100 * 4 / 10)
    # no flash work counted in this made-up table: nothing to read
    assert read("gqa.attention_roofline") is None
