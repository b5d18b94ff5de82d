"""The benchmark's own tests: the first rehearsal (everything but the
chip, at a tiny size on the CPU), the control and the planted faults.

    python -m pytest chipbench/tests -q

A fixture manifest under chipbench/testdata (tiny widths, the same
layout as the real one) stands for BENCHMARK.json; `require_tpu=False`
is the only steering, and it is an argument of `run_cell`, not an
option of the command.
"""
import gzip
import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from chipbench import check, limits, manifest, trace, window, work  # noqa: E402
from chipbench import run as run_mod  # noqa: E402
from chipbench.models import _common, bert, gpt  # noqa: E402

TESTDATA = os.path.join(BENCH, "testdata")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SEED = 2_147_483_659  # more than 32 signed bits hold


@pytest.fixture(scope="module")
def fixture_bench():
    return manifest.Manifest(os.path.join(TESTDATA, "BENCHMARK.json"),
                             base=TESTDATA)


@pytest.fixture(scope="module")
def real_bench():
    return manifest.Manifest()


# ------------------------------------------------------------ the command

def test_without_a_tpu_the_command_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "gpt3_1p3b_d8.s2048", "--seed", "1", "--seconds", "1", "--trace",
         "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert "metrics" not in out.stdout and "correct" not in out.stdout


# -------------------------------------------------------------- discovery

def test_manifest_names_and_units(real_bench):
    doc = real_bench.doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    names = [e["name"] for sec in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in doc[sec]]
    assert all(NAME.match(n) for n in names), names
    for sec in ("configs", "workloads"):
        sec_names = [e["name"] for e in doc[sec]]
        assert len(set(sec_names)) == len(sec_names)
    metrics = doc["end_to_end"] + doc["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in doc["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in doc["end_to_end"]}
    assert "setup_s" in e2e
    for m in doc["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
    for w in doc["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in doc["workloads"]) <= max(
        1, len(doc["workloads"]) // 4)


@pytest.mark.parametrize("which", ["real", "fixture"])
def test_every_cell_config_and_metric_is_found_by_name(
        which, real_bench, fixture_bench):
    bench = real_bench if which == "real" else fixture_bench
    for name in bench.cells:
        cell = bench.cell(name)
        cfg = bench.config(cell["config"])
        model_mod, ref_mod = manifest.family(cfg["family"])
        assert callable(model_mod.build_step) and callable(ref_mod.loss_fn)
        assert set(cell["limits"]) == set(check.NUMBERS)
        for traced in (False, True):
            specs = bench.metrics_of(name, traced)
            assert specs, (name, traced)
            for spec in specs:
                assert callable(manifest.reader(spec["reader"]).read)
        reported = {s["name"] for s in bench.metrics_of(name, False)}
        assert "setup_s" in reported and len(reported) >= 2


def _config_file(name):
    """A configuration by its file (bert_large's cell is not in the
    manifest yet: PERF.md, Open questions)."""
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_every_width_is_the_sources(real_bench):
    b = _config_file("bert_large")
    assert (b["hidden_size"], b["num_hidden_layers"],
            b["num_attention_heads"], b["intermediate_size"],
            b["max_position_embeddings"]) == (1024, 24, 16, 4096, 512)
    g = real_bench.config("gpt3_1p3b_d8")
    assert (g["hidden_size"], g["num_heads"], g["intermediate_size"],
            g["max_seq_len"], g["num_layers"]) == (2048, 16, 8192, 2048, 8)
    assert g["reduced"] == ["num_layers"] and g["published"][
        "num_layers"] == 24
    assert manifest.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        manifest.peaks("some other chip")


# ------------------------------------------------------------ work counts

def test_flops_per_token_against_hand_counts(real_bench):
    b = _config_file("bert_large")
    per_layer = 4 * 1024 * 1024 + 2 * 1024 * 4096
    want = 6 * (24 * per_layer + 1024 * 1024 + 30720 * 1024) \
        + 12 * 24 * 1024 * 512
    assert bert.flops_per_token(b, 512) == want
    assert 2.15e9 < want < 2.17e9
    g = real_bench.config("gpt3_1p3b_d8")
    per_layer = 4 * 2048 * 2048 + 2 * 2048 * 8192
    want = 6 * (8 * per_layer + 50304 * 2048) + 6 * 8 * 2048 * 2048
    assert gpt.flops_per_token(g, 2048) == want
    assert 3.2e9 < want < 3.3e9


@pytest.mark.parametrize("causal", [False, True])
def test_attention_work_against_hand_counts(causal):
    got = work.attention_work(batch=2, seq=2048, heads=16, head_dim=128,
                              causal=causal)
    square = 2 * 16 * 2048 * 2048 * 128
    # forward QK^T and PV, backward dV dP dQ dK: six products of 2*square
    assert got["flops"] == 6 * 2 * square // (2 if causal else 1)
    assert got["bytes"] == 12 * 2 * 2048 * 16 * 128 * 2
    peaks = manifest.peaks("TPU v5 lite")
    least = work.attention_least_seconds(
        dict(batch=2, seq=2048, heads=16, head_dim=128, causal=causal),
        peaks)
    assert least["bound"] == "compute"
    assert least["seconds"] == got["flops"] / 197e12


# ------------------------------------------------------------------ seeds

def test_same_seed_same_inputs_and_large_seeds(fixture_bench):
    cell = fixture_bench.cell("tiny_bert.s16")
    cfg = fixture_bench.config("tiny_bert")
    a = _common.stack_steps(bert.make_batch, cfg, cell, SEED, 0, 2)
    b = _common.stack_steps(bert.make_batch, cfg, cell, SEED, 0, 2)
    c = _common.stack_steps(bert.make_batch, cfg, cell, SEED + 1, 0, 2)
    assert all((x == y).all() for x, y in zip(a, b))
    assert any((x != y).any() for x, y in zip(a, c))
    ids, _tok, labels, _nsp = a
    masked = (labels != -100).sum(axis=-1)
    assert (masked == max(1, round(0.15 * cell["seq"]))).all()
    assert (labels[labels != -100] == ids[labels != -100]).all()
    w1 = _common.init_weights(bert.weight_shapes(cfg), 0.02, SEED, "bfloat16")
    w2 = _common.init_weights(bert.weight_shapes(cfg), 0.02, SEED, "bfloat16")
    assert all((w1[k] == w2[k]).all() for k in w1)
    assert abs(float(w1["word_emb"].astype("float32").std()) - 0.02) < 2e-3


# ---------------------------------------------- a whole run, on the CPU

@pytest.fixture(scope="module")
def runs(fixture_bench):
    """One untraced run of every fixture cell (shared by the cases)."""
    return {name: run_mod.run_cell(name, SEED, 0.5, False,
                                   bench=fixture_bench, require_tpu=False)
            for name in fixture_bench.cells}


@pytest.mark.parametrize("cell", ["tiny_bert.s16", "tiny_gpt.s32",
                                  "tiny_gpt.zero3_dp4", "tiny_gpt.dp4"])
def test_a_run_is_correct_and_its_last_line_has_the_shape(
        cell, runs, fixture_bench):
    result = runs[cell]
    assert list(result)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(
        result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 4 and result["attempted"] % 2 == 0
    assert result["metrics"]["tokens_per_s_chip"]["value"] > 0
    assert result["metrics"]["setup_s"]["unit"] == "s"
    assert ("step_ms_p90" in result["metrics"]) == (
        "dp4" not in cell and result["attempted"] >= 24)
    assert result["device"]["platform"] == "cpu"  # named for what it is
    assert set(result["compared"]) == set(check.NUMBERS)
    for name, limit in fixture_bench.cell(cell)["limits"].items():
        row = result["compared"][name]
        assert row["limit"] == limit and row["value"] <= limit, (name, row)
    json.dumps(result)


def test_the_reference_agrees_with_the_repos_models(runs):
    """Forward and loss (the losses), gradients (the moment) and one
    call of AdamW (the change) of BERT and GPT at a small size."""
    for cell in ("tiny_bert.s16", "tiny_gpt.s32"):
        compared = runs[cell]["compared"]
        assert compared["loss"]["value"] < 1e-4
        assert compared["moment"]["value"] < 1e-2
        assert compared["change"]["value"] < 2e-2


# -------------------------------------------- the control and the faults

@pytest.mark.parametrize("cell", ["tiny_bert.s16", "tiny_gpt.s32"])
def test_the_control_comes_out_not_correct(cell, fixture_bench):
    """The reference in float8 where the cell states bfloat16, put in
    the program's place, fails a number; rounded to bfloat16 it does
    not."""
    got = limits.readings(cell, SEED, bench=fixture_bench,
                          require_tpu=False, which=("control", "bfloat16"))
    cell_limits = fixture_bench.cell(cell)["limits"]
    assert any(got["control"][n] > lim for n, lim in cell_limits.items())
    assert all(got["bfloat16"][n] <= lim for n, lim in cell_limits.items())


def _broken_run(monkeypatch, fixture_bench, cell, fault):
    import paddle_tpu as paddle
    from paddle_tpu.optimizer import optimizer as opt_mod

    real_build = _common.build_train_step
    if fault == "state_unchanged":
        monkeypatch.setattr(paddle.optimizer.AdamW, "step",
                            lambda self: None)
    elif fault == "half_batch":
        def build(model, forward_loss, training, cell_):
            half = cell_["batch"] // 2
            return real_build(
                model, lambda *batch: forward_loss(*(b[:half]
                                                     for b in batch)),
                training, cell_)
        monkeypatch.setattr(_common, "build_train_step", build)
    elif fault == "no_exchange":
        monkeypatch.setattr(opt_mod.Optimizer, "_reduce_dp_grads",
                            lambda self, axis: None)
    elif fault == "no_scatter_sum":
        # ZeRO's reduce-scatter without the reduce: every chip keeps its
        # own rows' gradient for its shard (times the degree, which the
        # step divides by)
        import jax

        def own_shard(x, axis_name, scatter_dimension=0, tiled=True):
            degree = jax.lax.psum(1, axis_name)
            rows = x.shape[scatter_dimension] // degree
            return degree * jax.lax.dynamic_slice_in_dim(
                x, jax.lax.axis_index(axis_name) * rows, rows,
                scatter_dimension)
        monkeypatch.setattr(jax.lax, "psum_scatter", own_shard)
    return run_mod.run_cell(cell, SEED, 0.2, False, bench=fixture_bench,
                            require_tpu=False)


@pytest.mark.parametrize("cell,fault", [
    ("tiny_bert.s16", "state_unchanged"), ("tiny_bert.s16", "half_batch"),
    ("tiny_gpt.s32", "state_unchanged"), ("tiny_gpt.s32", "half_batch"),
    ("tiny_gpt.dp4", "no_exchange"),
    ("tiny_gpt.zero3_dp4", "no_scatter_sum")])
def test_a_broken_timed_path_comes_out_not_correct(
        cell, fault, monkeypatch, fixture_bench):
    result = _broken_run(monkeypatch, fixture_bench, cell, fault)
    assert result["correct"] is False
    failing = [n for n, row in result["compared"].items()
               if row["limit"] is not None and row["value"] > row["limit"]]
    assert failing
    if fault == "state_unchanged":
        assert result["compared"]["change"]["value"] == pytest.approx(1.0)


# --------------------------------------------------------------- compare

def test_leaf_gaps_and_dead_leaves():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-6, "d": 4.0}
    got = {"a": 1.1, "b": 2.0, "c": 3e-6, "d": 4.0}
    gap, at = check.leaf_gaps(got, ref)[0]
    # the tiny leaf is held against the median (1.5), not its own norm
    assert at == "a" and gap == pytest.approx(0.1 / 1.5)
    assert check.dead_leaves({"a": 1.0, "b": 1.0, "c": 1e-4}) == {"c"}
    gap, _ = check.leaf_gaps({"a": float("nan"), "b": 2.0, "c": 0,
                              "d": 4.0}, ref)[0]
    assert gap == float("inf")


# ----------------------------------------------------------------- window

def test_window_summary_counts_all_work_over_all_time():
    record = {"t_start": 10.0, "t_end": 12.0,
              "done": [10.5, 11.0, 11.5, 12.0],
              "losses": [[1.0, 1.0], [1.0, float("nan")], [1.0, 1.0],
                         [1.0, 1.0]]}
    s = window.summarize(record, k=2, tokens_per_step=100, chips=4)
    assert (s["attempted"], s["failed"]) == (8, 1)
    assert s["tokens_per_s_chip"] == pytest.approx(7 * 100 / 2.0 / 4)
    assert s["step_s"] == pytest.approx([0.25, 0.25, 0.25])


# ------------------------------------------------------------------ trace

def _hand_trace():
    ms = 1e6
    dev = [["while.1", 0, 100 * ms], ["fusion.1", 0, 10 * ms],
           ["custom-call.7", 10 * ms, 20 * ms],
           ["all-gather-start.1", 30 * ms, 1 * ms],
           ["fusion.2", 31 * ms, 9 * ms],
           ["all-gather-done.1", 40 * ms, 10 * ms],
           ["all-reduce.3", 60 * ms, 10 * ms],
           ["fusion.3", 90 * ms, 10 * ms]]
    host = [["chipbench/read", 49 * ms, 12 * ms],
            ["chipbench/dispatch", 69 * ms, 22 * ms]]
    return {"devices": {"/device:TPU:0": dev}, "host": host}


def test_trace_reductions_on_a_hand_made_trace():
    t = _hand_trace()
    b = trace.busy(t)
    assert b["window_s"] == pytest.approx(0.100)
    assert b["busy_s"] == pytest.approx(0.069)  # not the while, not the -start
    assert b["idle_share"] == pytest.approx(0.31)
    assert trace.seconds_of(t, ["custom-call.7"]) == pytest.approx(0.020)
    assert trace.seconds_of(t, ["custom-call.9"]) is None
    # the -done wait and the sync all-reduce, nothing runs beside them
    assert trace.collective_exposed_s(t) == pytest.approx(0.020)
    gaps = trace.idle_gaps(t)
    assert gaps[0] == ["chipbench/dispatch", pytest.approx(0.020)]
    assert gaps[1] == ["chipbench/read", pytest.approx(0.010)]
    assert trace.top_ops(t)[0] == ["fusion", pytest.approx(0.029)]


def test_the_trace_reader_on_a_hand_made_run():
    from chipbench.readers import trace_reduction

    t = _hand_trace()
    calls = dict(calls_per_step=3, batch=1, seq=8, heads=1, head_dim=8,
                 causal=False, bytes_per_element=2)
    run = {"traced": {"trace": t, "busy": trace.busy(t), "device_steps": 2},
           "cell": {"batch": 4, "seq": 100, "chips": 2},
           "flops_per_token": 1e6, "attention_calls": calls,
           "kernel_names": ["custom-call.7"],
           "peaks": {"bf16_flops_per_s": 1e10, "hbm_bytes_per_s": 1e9}}

    def read(quantity):
        return trace_reduction.read(run, {"quantity": quantity})

    # 2 steps of 400 tokens on 2 chips in the device's 0.1 s window
    assert read("step_mfu") == pytest.approx(100 * 4000 * 1e6 / 1e10)
    assert read("idle_share") == pytest.approx(31.0)
    assert read("kernel_step_share") == pytest.approx(100 * 0.020 / 0.069)
    # memory-bound at these shapes: 12 tensors of 128 bytes at 1 GB/s
    assert read("kernel_roofline") == pytest.approx(
        100 * 1536e-9 * 3 * 2 / 0.020)
    assert read("collective_exposed_ms") == pytest.approx(20.0 / 2)
    run["kernel_names"] = ["custom-call.9"]
    assert read("kernel_roofline") is None
    assert trace_reduction.read({}, {"quantity": "step_mfu"}) is None


def test_custom_call_names_from_compiled_text():
    hlo = '''
  %fusion.3 = bf16[2,2048]{1,0} fusion(%p0), kind=kLoop
  %custom-call.12 = bf16[2,16,2048,128]{3,2,1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", operand_layout_constraints={}
  ROOT %custom-call.13 = (f32[2]) custom-call(%c), custom_call_target="tpu_custom_call"
  %custom-call.14 = f32[2] custom-call(%c), custom_call_target="Sharding"
'''
    assert trace.custom_call_names(hlo) == ["custom-call.12",
                                            "custom-call.13"]


def test_trace_reductions_on_the_recorded_trace():
    path = os.path.join(TESTDATA, "trace_gpt3_1p3b_d8.s2048.json.gz")
    with gzip.open(path, "rt") as f:
        recorded = json.load(f)
    t, want = recorded["trace"], recorded["expect"]
    b = trace.busy(t)
    assert b["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < b["busy_s"] <= b["window_s"]
    assert 0 <= b["idle_share"] < 1
    assert trace.seconds_of(t, want["kernel_names"]) == pytest.approx(
        want["kernel_s"], rel=1e-9)
    assert [n for n, _s in trace.top_ops(t)] == want["top_ops"]
