"""A third family through the harness's "new files and entries only"
door: `chipbench/models/joyai.py` and `chipbench/reference/joyai.py` are
found by the configuration's `family`, through a fixture manifest of
their own (testdata/joyai); one tiny cell runs end to end on the CPU;
the two readers the family brings are held to hand-made runs."""
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

TESTDATA = os.path.join(BENCH, "testdata", "joyai")
SEED = 2_147_483_659
CELL = "tiny_joyai.s32"
REAL = "joyai_flash_ep16_d5.s4096"
NEW_METRICS = {"mla.proj_share", "mla.attention_roofline", "moe.route_share",
               "moe.experts_share", "moe.experts_roofline",
               "moe.shared_share", "moe.pairs_per_step",
               "moe.load_max_over_mean", "mtp.share"}


@pytest.fixture(scope="module")
def bench():
    return manifest.Manifest(os.path.join(TESTDATA, "BENCHMARK.json"),
                             base=TESTDATA)


def test_the_family_is_found_by_name(bench):
    cfg = bench.config(bench.cell(CELL)["config"])
    assert cfg["family"] == "joyai"
    model_mod, ref_mod = manifest.family(cfg["family"])
    for name in ("weight_shapes", "stacked_keys", "program_names",
                 "make_batch", "flops_per_token", "attention_calls",
                 "build_step", "kernel_work"):
        assert callable(getattr(model_mod, name)), name
    assert callable(ref_mod.loss_fn)
    shapes = model_mod.weight_shapes(cfg)
    assert not set(shapes) & {"qkv_w", "qkv_b"}
    # every layer's keys are its own: the dense layer, two expert
    # layers, the MTP module's; nothing is stacked over layers
    assert model_mod.stacked_keys() == ()
    assert {"l0_gate", "l1_e_gate", "l2_router", "mtp_e_gate",
            "mtp_eh"} <= set(shapes) and "l3_router" not in shapes
    with open(ref_mod.__file__) as f:
        assert "paddle_tpu" not in f.read()


def test_the_real_cell_reports_the_new_metrics():
    real = manifest.Manifest()
    traced = {m["name"] for m in real.metrics_of(REAL, True)}
    assert NEW_METRICS <= traced
    assert {"step_mfu", "device.idle_share", "head.loss_share",
            "attention.scope_share", "optimizer.update_share",
            "amp.cast_share", "build.window_compiles"} <= traced
    assert not {"flash_attention_roofline", "attention.step_share"} & traced
    assert {m["name"] for m in real.metrics_of(REAL, False)} == {
        "tokens_per_s_chip", "step_ms_p90", "setup_s"}
    for cell in real.cells:  # and no other cell is asked for them
        if cell != REAL:
            assert not NEW_METRICS & {m["name"]
                                      for m in real.metrics_of(cell, True)}
    for spec in real.metrics_of(REAL, True):
        assert callable(manifest.reader(spec["reader"]).read)
    cell = real.cell(REAL)
    assert (cell["recompute"], cell["seq"], cell["k"]) == ("kernels", 4096, 2)
    assert set(cell["limits"]) == set(check.NUMBERS)


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


def test_moe_stat_asks_the_program_and_reads_like_program_stat():
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.incubate.moe import HeldExpertsLayer

    paddle.seed(3)
    layer = HeldExpertsLayer(32, 24, 16, 4, ep_size=4, ep_rank=1)
    x = paddle.to_tensor(np.random.RandomState(0).randn(64, 32).astype(
        "float32"))
    for _ in range(3):
        layer(x)
    pairs = moe_stat.read({}, {"counter": "moe_routed_pairs",
                               "per": "moe_steps", "scale": 3})
    assert 0.5 * 3 * 64 < pairs < 1.6 * 3 * 64  # 3 x 64 tokens x 4 x 4/16
    ratio = moe_stat.read({}, {"counter": "moe_expert_load_max",
                               "per": "moe_routed_pairs", "scale": 4})
    assert 1.0 <= ratio < 4.0
    # a step's total: the mean over applications times the layers the
    # newest step's trace staged (none here: nothing to read, no error)
    from paddle_tpu import monitor
    staged = monitor.stat_get("jit_moe_layers")
    total = moe_stat.read({}, moe_stat.PAIRS_PER_STEP)
    assert total == (pytest.approx(pairs / 3 * staged) if staged else None)
    del layer


def test_the_float8_control_comes_out_not_correct(bench):
    got = limits.readings(CELL, SEED, bench=bench, require_tpu=False,
                          which=("control", "bfloat16", "half_batch"))
    cell_limits = bench.cell(CELL)["limits"]
    assert any(got["control"][n] > lim for n, lim in cell_limits.items())
    assert any(got["half_batch"][n] > lim for n, lim in cell_limits.items())
    assert all(got["bfloat16"][n] <= lim for n, lim in cell_limits.items())


def test_kernel_work_counts_shapes_and_pairs_alone():
    real = manifest.Manifest()
    cfg = real.config("joyai_flash_ep16_d5")
    model_mod, _ref = manifest.family("joyai")
    cell = real.cell(REAL)
    work = model_mod.kernel_work(cfg, cell, 40960)
    b, s = cell["batch"], cell["seq"]
    assert work["flash"]["flops"] == 6 * 3 * 2 * b * 32 * s * s * 160
    # q, k at 192 and v, o at 128: 4 tensors forward, 8 backward
    assert work["flash"]["bytes"] == 6 * 3 * b * s * 32 * 2 * 2 * (192 + 128)
    assert work["experts"]["flops"] == 18 * 2048 * 768 * 40960
    assert work["experts"]["bytes"] == (
        5 * 3 * 16 * 3 * 2048 * 768 * 2 + 40960 * 4 * 2048 * 2)
    twice = model_mod.kernel_work(cfg, cell, 81920)
    assert twice["experts"]["flops"] == 2 * work["experts"]["flops"]
    assert twice["flash"] == work["flash"]


def test_scope_roofline_on_a_hand_made_run(monkeypatch):
    hlo = '''
  %custom-call.1 = bf16[8] custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/pt.layers.1/pt.attention/pt.flash/pt.flash_attention/pallas_call"}
  %fusion.2 = bf16[8] fusion(%p0), kind=kLoop, metadata={op_name="jit(f)/pt.layers.1/pt.attention/pt.flash/pt.flash_attention/transpose"}
  %custom-call.3 = bf16[8] custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/pt.layers.1/pt.moe/pt.held_experts_ffn/while/body/checkpoint/cond/branch_1_fun/pt.experts/ragged_dot_general"}
  %custom-call.4 = bf16[8] custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/pt.layers.1/transpose(jvp(pt.moe/pt.held_experts_ffn))/while/body/rematted_computation/pt.experts/ragged_dot_general"}
  %fusion.5 = bf16[8] fusion(%p0), kind=kLoop, metadata={op_name="jit(f)/pt.optimizer/pt.update/add"}
  %ragged-dot-none.6 = bf16[8] custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
'''
    ms = 1e6
    events = [["while.1", 0, 100 * ms], ["custom-call.1", 0, 20 * ms],
              ["fusion.2", 20 * ms, 5 * ms], ["custom-call.3", 30 * ms, 4 * ms],
              ["custom-call.4", 40 * ms, 6 * ms], ["fusion.5", 50 * ms, 30 * ms],
              ["ragged-dot-none.6", 80 * ms, 10 * ms]]
    t = {"devices": {"/device:TPU:0": events}, "host": []}
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    run = {"cell": {"config": "joyai_flash_ep16_d5", "batch": 4,
                    "seq": 4096},
           "peaks": peaks,
           "traced": {"trace": t, "busy": trace.busy(t), "device_steps": 2}}
    work = {"flash": {"flops": 5e9, "bytes": 1e6},      # 5 ms by compute
            "experts": {"flops": 1e6, "bytes": 2e8}}    # 2 ms by bytes
    monkeypatch.setattr(scope_share, "registered",
                        lambda: {"table": {}, "hlo": hlo})
    monkeypatch.setattr(scope_roofline, "family_work",
                        lambda name: (lambda cfg, cell, pairs: work, {}))
    monkeypatch.setattr(moe_stat, "routing_stats", lambda: None)
    # flash: 2 steps x 5 ms over the 25 ms under the scope (relayout too)
    assert scope_roofline.read(run, {"scopes": ["flash"]}) == pytest.approx(
        100 * 10 / 25)
    # experts: forward and the replayed call, 2 steps x 2 ms over 10 ms
    assert scope_roofline.read(run, {"scopes": ["experts"]}) == pytest.approx(
        100 * 4 / 10)
    # the grouped products the compiler named itself and left unscoped
    # belong to the roofline's scope by their names: 10 ms more, where
    # `scope_share` (`moe.experts_share`) reads them unscoped
    named = {"scopes": ["experts"], "instructions": ["ragged-dot-none"]}
    assert scope_roofline.read(run, named) == pytest.approx(100 * 4 / 20)
    assert scope_share.read(run, {"scopes": ["experts"]}) == pytest.approx(
        100 * 10 / 75)
    # nothing to read is None, never a share of 0 and never an error
    assert scope_roofline.read(run, {"scopes": ["no_such"]}) is None
    monkeypatch.setattr(scope_roofline, "family_work",
                        lambda name: (None, None))
    assert scope_roofline.read(run, {"scopes": ["flash"]}) is None
    monkeypatch.setattr(scope_share, "registered", lambda: None)
    assert scope_roofline.read(run, {"scopes": ["flash"]}) is None
    assert scope_roofline.read({}, {"scopes": ["flash"]}) is None
    # a family with no `kernel_work` (the accepted ones) counts nothing
    monkeypatch.undo()
    assert scope_roofline.family_work("gpt3_1p3b_d8")[0] is None
    assert scope_roofline.family_work("no_such_config") == (None, None)
