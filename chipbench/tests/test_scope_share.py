"""The two readers of the program's own names and counters
(`scope_share`, `program_stat`) on hand-made input, and the eight
metric files they serve, found by name from the real BENCHMARK.json.

    python -m pytest chipbench/tests/test_scope_share.py -q
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import manifest, trace  # noqa: E402
from chipbench.readers import program_stat, scope_share  # noqa: E402

NEW_METRICS = {
    "optimizer.update_share": ("scope_share", "device_trace"),
    "attention.scope_share": ("scope_share", "device_trace"),
    "head.loss_share": ("scope_share", "device_trace"),
    "amp.cast_share": ("scope_share", "device_trace"),
    "scopes.unattributed_share": ("scope_share", "device_trace"),
    "call.place_ms": ("program_stat", "program_counter"),
    "call.launch_ms": ("program_stat", "program_counter"),
    "build.trace_s": ("program_stat", "program_counter"),
}

HLO = '''HloModule jit_step, is_scheduled=true

%body (c: f32[8]) -> f32[8] {
  %fusion.1 = f32[8]{0} fusion(%c), kind=kLoop, calls=%f.1, metadata={op_name="jit(step)/while/body/pt.gpt/pt.blocks.0/pt.fc1/pt.linear/transpose(jvp())/mul" source_file="a.py"}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%f.2, metadata={op_name="jit(step)/while/body/pt.optimizer/pt.update/pt.cast/convert_element_type"}
  %custom-call.3 = f32[8]{0} custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/while/body/pt.gpt/pt.blocks.0/pt.attention/pt.flash/pt.flash_attention/jvp(pt.inner)/pallas_call"}
  %fusion.4 = f32[8]{0} fusion(%custom-call.3), kind=kOutput, calls=%f.4, metadata={op_name="jit(step)/while/body/pt.GPT/pt.head/pt.matmul/dot_general"}
  %fusion.5 = f32[8]{0} fusion(%fusion.4), kind=kLoop, calls=%f.5, metadata={op_name="jit(step)/while/body/pt.loss/pt.cross_entropy/reduce_sum"}
  %copy.6 = f32[8]{0} copy(%fusion.5)
  %all-gather-start.7 = f32[32]{0} all-gather-start(%copy.6), metadata={op_name="jit(step)/while/body/pt.optimizer/pt.update/pt.zero.gather/all_gather"}
  %reshape.10 = f32[8]{0} reshape(%fusion.2), metadata={op_name="jit(step)/while/body/pt.optimizer/pt.zero.bucket_copy/reshape;pt.loss/pt.other/reshape"}
  %copy.11 = f32[8]{0} copy(%reshape.10)
  %fusion.12 = f32[2]{0} fusion(%copy.11), kind=kCustom, calls=%all-reduce-scatter.clone
  ROOT %add.8 = s32[] add(%i, %one), metadata={op_name="jit(step)/while/body/add"}
}
'''


def _run():
    ms = 1e6
    dev = [["while.9", 0, 100 * ms], ["fusion.1", 0, 10 * ms],
           ["fusion.2", 10 * ms, 20 * ms], ["custom-call.3", 30 * ms, 30 * ms],
           ["fusion.4", 60 * ms, 8 * ms], ["fusion.5", 68 * ms, 2 * ms],
           ["copy.6", 70 * ms, 5 * ms],
           ["all-gather-start.7", 75 * ms, 1 * ms],
           ["add.8", 90 * ms, 5 * ms], ["fusion.12", 95 * ms, 4 * ms]]
    t = {"devices": {"/device:TPU:0": dev, "/device:TPU:1": dev}, "host": []}
    return {"traced": {"trace": t, "busy": trace.busy(t),
                       "device_steps": 2}}


@pytest.fixture
def registry():
    from paddle_tpu.observability import memory
    memory.clear_program_memory()
    yield memory
    memory.clear_program_memory()


def test_paths_by_the_benchmarks_own_regex():
    paths = scope_share.scope_paths(HLO)
    assert paths["fusion.1"] == ["gpt", "blocks.0", "fc1", "linear"]
    assert paths["custom-call.3"] == ["gpt", "blocks.0", "attention",
                                      "flash", "flash_attention", "inner"]
    assert paths["add.8"] == [] and "copy.6" not in paths
    # of names the compiler joined with ';' the first counts; an
    # operation it rewrote without metadata takes its operand's scope
    # (through its own copy, which stays without one)
    assert paths["reshape.10"] == ["optimizer", "zero.bucket_copy"]
    assert paths["fusion.12"] == ["optimizer", "zero.bucket_copy"]
    assert "copy.11" not in paths


def test_shares_of_busy_time_and_the_disjoint_kinds_sum(registry):
    registry.record_program_scopes("step:scan", {"stale": False}, HLO)
    run = _run()
    assert run["traced"]["busy"]["busy_s"] == pytest.approx(0.084)

    def read(**args):
        return scope_share.read(run, args)

    optimizer = read(scopes=["optimizer"])
    attention = read(scopes=["attention"])
    head_loss = read(scopes=["head", "loss"])
    cast = read(scopes=["cast"])
    unscoped = read(unscoped=True)
    # fusion.2 and the rewritten fusion.12, not the -start half
    assert optimizer == pytest.approx(100 * 24 / 84)
    assert attention == pytest.approx(100 * 30 / 84)
    assert head_loss == pytest.approx(100 * 10 / 84)
    assert cast == pytest.approx(100 * 20 / 84)  # inside the optimizer
    assert unscoped == pytest.approx(100 * 10 / 84)  # copy.6 and add.8
    assert optimizer + attention + head_loss + unscoped <= 100
    assert read(scopes=["nothing_like_it"]) == 0.0


def test_none_without_a_table_or_with_a_stale_one(registry, monkeypatch):
    run = _run()
    args = {"scopes": ["optimizer"]}
    assert scope_share.read(run, args) is None  # nothing registered
    registry.record_program_scopes("step:scan", {"stale": True}, HLO)
    assert scope_share.read(run, args) is None
    registry.record_program_scopes("step:scan", {"stale": False},
                                   HLO.replace("pt.", "xx."))
    assert scope_share.read(run, args) is None  # a text that names none
    registry.record_program_scopes("step:scan", {"stale": False}, HLO)
    assert scope_share.read({}, args) is None  # an untraced run
    assert scope_share.read(run, args) is not None
    # a commit whose program keeps no such registry
    monkeypatch.delattr(registry, "program_scopes")
    assert scope_share.read(run, args) is None


def test_program_stat_on_set_counters():
    from paddle_tpu import monitor

    for name in ("t_calls", "t_ns", "t_absent"):
        monitor.stat_reset(name)
    monitor.stat_add("t_calls", 4)
    monitor.stat_add("t_ns", 6_000_000)
    assert program_stat.read({}, {"counter": "t_ns", "per": "t_calls",
                                  "scale": 1e-6}) == pytest.approx(1.5)
    assert program_stat.read({}, {"counter": "t_ns", "scale": 1e-9}) \
        == pytest.approx(0.006)
    assert program_stat.read({}, {"counter": "t_calls"}) == 4.0
    # a commit that keeps no such counter: nothing, not a zero
    assert program_stat.read({}, {"counter": "t_absent"}) is None
    assert program_stat.read({}, {"counter": "t_ns",
                                  "per": "t_absent"}) is None


def test_the_new_metric_files_are_found_by_name():
    bench = manifest.Manifest()
    for cell in bench.cells:
        specs = {s["name"]: s for s in bench.metrics_of(cell, True)}
        for name, (reader, source) in NEW_METRICS.items():
            spec = specs[name]
            assert spec["reader"] == reader and spec["source"] == source
            assert "workloads" not in spec and spec["better"] == "lower"
            assert callable(manifest.reader(reader).read)
        # none of them is an end-to-end metric
        assert not set(NEW_METRICS) & {
            s["name"] for s in bench.metrics_of(cell, False)}
    counters = {s["args"]["counter"] for s in specs.values()
                if s["reader"] == "program_stat"}
    assert counters == {'to_static_call_ns{phase="place"}',
                        'to_static_call_ns{phase="launch"}',
                        "jit_build_ns"}
