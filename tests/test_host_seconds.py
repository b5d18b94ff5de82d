"""The host's seconds, named from inside the program (always-on counters
of `jit/to_static.py` and `jit/compile_cache.py`): a building call's
phases add up to the call, jax's programs are booked to who made them,
a cached call under which jax re-specialised is counted and kept out of
the worst case, the collector's pauses land in `setup` or `steady`, and
with tracing on each of them is a span.
"""
import gc
import glob
import importlib
import json
import logging
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as obs
from paddle_tpu import _native, monitor, profiler
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.jit import compile_cache

ts = importlib.import_module("paddle_tpu.jit.to_static")

BUILD = {p: f'to_static_build_ns{{phase="{p}"}}' for p in ts.BUILD_PHASES}
PROGRAMS = {o: f'jit_programs{{program="{o}"}}'
            for o in ("eager", "step", "introspect")}
PROGRAM_NS = {o: f'jit_program_ns{{program="{o}"}}' for o in PROGRAMS}
MAXIMA = [f'to_static_call_max_ns{{phase="{p}"}}' for p in ("launch", "place")]
# a flag XLA:CPU takes: a flagged program lowers anew for `hlo_text()`
CPU_FLAG = {"xla_cpu_enable_xprof_traceme": True}


def read(names):
    return {n: monitor.stat_get(n) for n in names}


def rose(before):
    """What each counter gained since `before`."""
    return {n: monitor.stat_get(n) - v for n, v in before.items()}


def make_step(width=24, **options):
    """A linear layer's training step, and how often python ran it."""
    layer = paddle.nn.Linear(width, width)
    opt = paddle.optimizer.AdamW(parameters=layer.parameters(),
                                 learning_rate=1e-3)
    ran = []

    def one_step(x):
        ran.append(1)
        loss = (layer(x) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(one_step, **options)
    lead = (options["scan_steps"],) if options.get("scan_steps") else ()
    x = paddle.to_tensor(np.ones(lead + (4, width), np.float32))
    return step, x, ran


@pytest.mark.parametrize("options", [{}, {"scan_steps": 2}],
                         ids=["unrolled", "scan"])
def test_a_first_call_is_one_build_whose_phases_add_up_to_the_call(options):
    step, x, ran = make_step(**options)
    names = [*BUILD.values(), "jit_build_ns", "to_static_calls"]
    before = read(names)
    t0 = time.perf_counter_ns()
    step(x)
    wall = time.perf_counter_ns() - t0
    built = rose(before)
    phases = {p: built[c] for p, c in BUILD.items()}
    assert all(ns > 0 for ns in phases.values()), phases
    assert sum(phases.values()) <= wall, (phases, wall)
    # the analysis trace is what `jit_build_ns` has always timed, and
    # `jit_trace` is jax running the same python a second time: the
    # count a build that traces once will change
    assert built["jit_build_ns"] == phases["analysis_trace"]
    assert len(ran) == 2
    assert built["to_static_calls"] == 0
    mid = read(names)
    step(x).numpy()
    cached = rose(mid)
    assert cached.pop("to_static_calls") == 1
    assert not any(cached.values()), cached
    assert len(ran) == 2


def test_a_program_is_booked_to_who_made_it():
    names = [*PROGRAMS.values(), *PROGRAM_NS.values()]
    step, x, _ran = make_step(width=26, xla_flags=CPU_FLAG)
    before = read(names)
    (paddle.to_tensor(np.ones((3, 5, 7), np.float32)) * 2.5).numpy()
    eager = rose(before)
    assert eager[PROGRAMS["eager"]] >= 1 and eager[PROGRAM_NS["eager"]] > 0
    assert not eager[PROGRAMS["step"]] and not eager[PROGRAMS["introspect"]]
    before = read(names)
    step(x)
    build = rose(before)
    assert build[PROGRAMS["step"]] >= 1 and build[PROGRAM_NS["step"]] > 0
    assert not build[PROGRAMS["eager"]] and not build[PROGRAMS["introspect"]]
    before = read(names)
    # jax holds the lowering and the executable of a program it has
    # just run, and `hlo_text()`'s second compile is then no work at
    # all: the owner shows once jax has forgotten them
    jax.clear_caches()
    assert "HloModule" in step.hlo_text()
    looked = rose(before)
    assert looked[PROGRAMS["introspect"]] >= 1
    assert looked[PROGRAM_NS["introspect"]] > 0
    assert not looked[PROGRAMS["eager"]] and not looked[PROGRAM_NS["eager"]]
    assert not looked[PROGRAMS["step"]]
    assert compile_cache.this_thread.owner == "eager"


@pytest.mark.parametrize("native", [True, False], ids=["native", "python"])
def test_stat_max_never_falls_and_is_listed(native, monkeypatch):
    if native and _native.lib() is None:
        pytest.skip("no native runtime here")
    if not native:
        monkeypatch.setattr(_native, "lib", lambda: None)
    name = f"test_stat_max_{'native' if native else 'python'}"
    for value, kept in ((5, 5), (3, 5), (9, 9), (9, 9), (0, 9)):
        monitor.stat_max(name, value)
        assert monitor.stat_get(name) == kept
    assert monitor.stats()[name] == 9
    monitor.stat_reset(name)
    assert monitor.stat_get(name) == 0


def test_a_cached_call_that_respecialises_is_counted_and_no_worst_case():
    ran = []

    def double(x):
        ran.append(1)
        return x * 2.0

    step = paddle.jit.to_static(double)
    strong, weak = jnp.float32(3.0), jnp.asarray(3.0)
    assert weak.weak_type and not strong.weak_type
    names = ["to_static_calls", "to_static_calls_recompiled",
             'to_static_call_ns{phase="launch"}', *MAXIMA]
    step(Tensor(strong))
    for name in MAXIMA:
        monitor.stat_reset(name)
    before = read(names)
    step(Tensor(strong))
    steady = rose(before)
    assert steady["to_static_calls"] == 1
    assert steady["to_static_calls_recompiled"] == 0
    assert steady[MAXIMA[0]] > 0 and len(ran) == 2
    # same shape and dtype, so the program cache hits; jax keys on the
    # weak type too and traces, lowers and compiles under the call
    for name in MAXIMA:
        monitor.stat_reset(name)
    before = read(names)
    assert float(step(Tensor(weak))) == 6.0
    again = rose(before)
    assert len(ran) == 3
    assert again["to_static_calls"] == 1
    assert again["to_static_calls_recompiled"] == 1
    assert again['to_static_call_ns{phase="launch"}'] > 0
    assert again[MAXIMA[0]] == 0 and again[MAXIMA[1]] == 0


def test_a_call_nested_in_another_hides_no_jax_work_from_the_outer_one():
    names = ["to_static_calls", "to_static_calls_recompiled", MAXIMA[0]]
    outer = ts._CallPhases()
    jax.jit(lambda x: x + 41.0)(jnp.ones((3, 7)))  # jax makes a program
    inner = ts._CallPhases()  # a step called under the outer one's feet
    for phases in (inner, outer):
        phases.ns = dict.fromkeys(ts.CALL_PHASES, 10**12)
    monitor.stat_reset(MAXIMA[0])
    before = read(names)
    inner.commit()
    assert rose(before) == {"to_static_calls": 1, MAXIMA[0]: 10**12,
                            "to_static_calls_recompiled": 0}
    monitor.stat_reset(MAXIMA[0])
    before = read(names)
    outer.commit()
    assert rose(before) == {"to_static_calls": 1, MAXIMA[0]: 0,
                            "to_static_calls_recompiled": 1}


GC = {(kind, during): f'host_gc_{kind}{{during="{during}"}}'
      for kind in ("ns", "collections", "max_ns")
      for during in ("setup", "steady")}


def test_a_collection_lands_in_setup_until_a_cached_call_has_committed(
        monkeypatch):
    monkeypatch.setitem(ts._host_pauses, "during", "setup")
    step, x, _ran = make_step(width=28)
    step(x)  # the build installs the watch
    assert gc.callbacks.count(ts._on_gc) == 1
    before = read(GC.values())
    gc.collect()
    seen = rose(before)
    assert seen[GC["collections", "setup"]] >= 1
    assert seen[GC["ns", "setup"]] > 0
    assert monitor.stat_get(GC["max_ns", "setup"]) > 0
    assert not seen[GC["collections", "steady"]]
    step(x)  # the first cached call commits
    before = read(GC.values())
    gc.collect()
    seen = rose(before)
    assert seen[GC["collections", "steady"]] >= 1
    assert seen[GC["ns", "steady"]] > 0
    assert monitor.stat_get(GC["max_ns", "steady"]) > 0
    assert not seen[GC["collections", "setup"]]


def test_a_long_collection_is_one_warning(monkeypatch, caplog):
    ts._watch_host_pauses()
    ticks = iter(range(0, 10 ** 9, 1))
    monkeypatch.setattr(ts, "_gc_clock",
                        lambda: next(ticks) * 700_000_000)
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.jit"):
        gc.collect()
    monkeypatch.undo()
    lines = [r.getMessage() for r in caplog.records
             if r.name == "paddle_tpu.jit" and "garbage collection" in
             r.getMessage()]
    assert any("0.700 s" in line and "generation 2" in line
               for line in lines), lines
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.jit"):
        caplog.clear()
        gc.collect()  # on the real clock: milliseconds, and no line
    assert not [r for r in caplog.records if r.name == "paddle_tpu.jit"]


def test_the_builds_phases_are_spans_under_the_building_calls_step(tmp_path):
    step, x, _ran = make_step(width=30)
    profiler.reset()
    obs.enable()
    try:
        step(x)
        gc.collect()
    finally:
        obs.disable()
    out = tmp_path / "trace.json"
    obs.export_chrome_trace(str(out))
    events = json.loads(out.read_text())["traceEvents"]
    profiler.reset()

    def named(name):
        return [e for e in events if e["name"] == name]

    def inside(inner, outer):
        return (outer["ts"] <= inner["ts"] and inner["ts"] + inner["dur"]
                <= outer["ts"] + outer["dur"] + 1e-3)

    (root,) = named("executor/step")
    for name, parent in (("jit/compile", "executor/step"),
                         ("jax/jaxpr_trace", "jit/compile"),
                         ("jax/jaxpr_trace", "executor/step/launch"),
                         ("jax/jaxpr_to_mlir_module", "executor/step/launch"),
                         ("jax/backend_compile", "executor/step/launch")):
        assert any(inside(e, p) and inside(p, root)
                   for e in named(name) for p in named(parent)), name
    assert any(e["name"] == "host/gc" for e in events)


def test_a_collection_is_an_annotation_in_a_captured_profile(tmp_path):
    from jax.profiler import ProfileData

    ts._watch_host_pauses()
    obs.enable()
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            gc.collect()
        finally:
            jax.profiler.stop_trace()
    finally:
        obs.disable()
        profiler.reset()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    names = {e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events}
    assert "pt/host/gc" in names


def test_a_capture_pass_is_timed_only_while_a_step_trace_runs():
    layer = paddle.nn.Linear(8, 8)

    def body(h):
        return [layer(h)]

    def looped(x):
        return paddle.nn.fixed_loop(body, [x], trips=3)[0]

    x = paddle.to_tensor(np.ones((2, 8), np.float32))
    before = monitor.stat_get("jit_capture_pass_ns")
    looped(x)  # eagerly a python loop: no capture pass
    assert monitor.stat_get("jit_capture_pass_ns") == before
    paddle.jit.to_static(looped)(x)
    first = monitor.stat_get("jit_capture_pass_ns") - before
    assert first > 0
    assert first < sum(monitor.stat_get(BUILD[p])
                       for p in ("analysis_trace", "jit_trace"))


def test_the_compile_stall_share_reads_the_builds_whole_host_time(
        monkeypatch):
    from paddle_tpu.observability import step as step_mod

    clock = [100.0]
    monkeypatch.setattr(step_mod.time, "perf_counter", lambda: clock[0])
    timer = obs.StepTimer(window=4, publish_as=None).start()
    second = 10 ** 9
    for counter, ns in ((BUILD["analysis_trace"], second // 4),
                        (BUILD["jit_trace"], second),
                        (BUILD["lower"], second // 2),
                        (BUILD["executable"], second // 4),
                        (PROGRAM_NS["eager"], second // 2),
                        (PROGRAM_NS["introspect"], second // 2),
                        # not read (any more): each lies inside the above
                        ("jit_build_ns", 5 * second),
                        ("jit_backend_compile_ns", 5 * second),
                        (PROGRAM_NS["step"], 5 * second)):
        monitor.stat_add(counter, ns)
    clock[0] += 10.0
    assert timer.step()["compile_stall_frac"] == pytest.approx(0.3)
