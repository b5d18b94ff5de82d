"""A second family comes in through the harness's "new files and entries
only" door: `chipbench/models/ouro.py` and `chipbench/reference/ouro.py`
are found by the configuration's `family`, through a fixture manifest of
their own (testdata/ouro), and one tiny cell runs end to end on the CPU.
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

from chipbench import check, limits, manifest, trace  # noqa: E402
from chipbench import run as run_mod  # noqa: E402
from chipbench.readers import op_name_share, scope_share  # noqa: E402

TESTDATA = os.path.join(BENCH, "testdata", "ouro")
SEED = 2_147_483_659
CELL = "tiny_ouro.s32"
NEW_METRICS = {"loop.body_share", "recompute.replay_share",
               "exit.gate_loss_share", "rope.share", "norm.rms_share",
               "build.rolled_trips"}


@pytest.fixture(scope="module")
def bench():
    return manifest.Manifest(os.path.join(TESTDATA, "BENCHMARK.json"),
                             base=TESTDATA)


def test_the_family_is_found_by_name(bench):
    cfg = bench.config(bench.cell(CELL)["config"])
    assert cfg["family"] == "ouro"
    model_mod, ref_mod = manifest.family(cfg["family"])
    for name in ("weight_shapes", "stacked_keys", "program_names",
                 "make_batch", "flops_per_token", "attention_calls",
                 "build_step"):
        assert callable(getattr(model_mod, name)), name
    assert callable(ref_mod.loss_fn)
    # no stacked key is one the harness would split in three
    assert not set(model_mod.weight_shapes(cfg)) & {"qkv_w", "qkv_b"}
    with open(ref_mod.__file__) as f:
        assert "paddle_tpu" not in f.read()


def test_the_real_cells_report_the_new_metrics():
    real = manifest.Manifest()
    traced = {m["name"] for m in real.metrics_of("ouro_2p6b_d8.s2048", True)}
    assert NEW_METRICS <= traced
    assert {"flash_attention_roofline", "attention.step_share", "step_mfu",
            "head.loss_share", "optimizer.update_share"} <= traced
    assert {m["name"] for m in real.metrics_of(
        "ouro_2p6b_d8.s2048", False)} == {"tokens_per_s_chip",
                                          "step_ms_p90", "setup_s"}
    for cell in real.cells:  # and no other cell is asked for them
        if not cell.startswith("ouro"):
            assert not NEW_METRICS & {m["name"]
                                      for m in real.metrics_of(cell, True)}
    for spec in real.metrics_of("ouro_2p6b_d8.s2048", True):
        assert callable(manifest.reader(spec["reader"]).read)
    cell = real.cell("ouro_2p6b_d8.s2048")
    assert (cell["recompute"], cell["batch"], cell["seq"], cell["k"]) == (
        "kernels", 2, 2048, 2)


STRUCTURE = ("jit_rolled_loop_trips", "jit_recompute_segments")


@pytest.fixture(scope="module")
def result(bench):
    from paddle_tpu import monitor

    # the counters are the process's: another test's build may have run
    for name in STRUCTURE:
        monitor.stat_reset(name)
    out = run_mod.run_cell(CELL, SEED, 0.3, False, bench=bench,
                           require_tpu=False)
    out["structure"] = {name: monitor.stat_get(name) for name in STRUCTURE}
    return out


def test_a_tiny_ouro_cell_runs_end_to_end_and_is_correct(result, bench,
                                                         monkeypatch):
    from paddle_tpu import monitor

    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 4
    assert result["device"]["platform"] == "cpu"
    assert set(result["compared"]) == set(check.NUMBERS)
    for name, limit in bench.cell(CELL)["limits"].items():
        assert result["compared"][name]["value"] <= limit
    # the pass loop was one rolled region, a remat segment a layer
    assert result["structure"] == {"jit_rolled_loop_trips": 4,
                                   "jit_recompute_segments": 2}
    from chipbench.readers import program_stat
    monkeypatch.setattr(monitor, "stat_get", result["structure"].get)
    assert program_stat.read({}, {"counter": "jit_rolled_loop_trips"}) == 4.0


def test_the_float8_control_comes_out_not_correct(bench):
    got = limits.readings(CELL, SEED, bench=bench, require_tpu=False,
                          which=("control", "bfloat16", "half_batch"))
    cell_limits = bench.cell(CELL)["limits"]
    assert any(got["control"][n] > lim for n, lim in cell_limits.items())
    assert any(got["half_batch"][n] > lim for n, lim in cell_limits.items())
    assert all(got["bfloat16"][n] <= lim for n, lim in cell_limits.items())


def test_replay_share_on_a_hand_made_run(monkeypatch):
    hlo = '''
  %fusion.1 = bf16[8] fusion(%p0), kind=kLoop, metadata={op_name="jit(f)/pt.loop/pt.fixed_loop/jvp()/while/body/pt.layers.0/mul"}
  %fusion.2 = bf16[8] fusion(%p0), kind=kLoop, metadata={op_name="jit(f)/pt.loop/pt.fixed_loop/transpose(jvp())/while/body/pt.layers.0/pt.recompute/checkpoint/rematted_computation/pt.ln1/mul"}
  %custom-call.3 = bf16[8] custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/pt.loop/transpose(jvp())/rematted_computation/pt.attention/pt.flash"}
  %fusion.4 = bf16[8] fusion(%p0), kind=kLoop, metadata={op_name="jit(f)/pt.optimizer/pt.update/add"}
'''
    ms = 1e6
    events = [["while.1", 0, 100 * ms], ["fusion.1", 0, 30 * ms],
              ["fusion.2", 30 * ms, 10 * ms], ["custom-call.3", 40 * ms, 10 * ms],
              ["fusion.4", 60 * ms, 30 * ms]]
    t = {"devices": {"/device:TPU:0": events}, "host": []}
    run = {"traced": {"trace": t, "busy": trace.busy(t)}}
    args = {"marks": ["rematted_computation"]}
    monkeypatch.setattr(scope_share, "registered",
                        lambda: {"table": {}, "hlo": hlo})
    assert op_name_share.marked(hlo, args["marks"]) == {"fusion.2",
                                                        "custom-call.3"}
    assert op_name_share.read(run, args) == pytest.approx(100 * 20 / 80)
    # the scope reader on the same run: the loop is 50 of 80 ms
    assert scope_share.read(run, {"scopes": ["loop"]}) == pytest.approx(
        100 * 50 / 80)
    # nothing to read is None, never a share of 0 and never an error
    assert op_name_share.read(run, {"marks": ["no_such_mark"]}) is None
    monkeypatch.setattr(scope_share, "registered", lambda: None)
    assert op_name_share.read(run, args) is None
    monkeypatch.setattr(scope_share, "registered",
                        lambda: {"table": {"stale": True}, "hlo": hlo})
    assert op_name_share.read(run, args) is None
    assert op_name_share.read({}, args) is None
