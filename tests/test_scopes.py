"""Device time gets the program's names (observability/scopes.py).

A tiny GPT through ``to_static(scan_steps=2)`` on the CPU: the scope
table of the compiled step holds every kind the seams enter, the
backward carries the forward's scope, what carries none is listed and
small, the scopes change nothing but metadata, an eager call enters
none, a table asked of an executable without scopes says `stale`; the
step call's phases are counted always and land in a profile on the
profile's own clock.
"""
import contextlib
import functools
import gc
import glob
import os
import re
import statistics
import time

import jax
import jax.lax as lax
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as obs
from paddle_tpu import monitor
from paddle_tpu.jit import compile_cache
from paddle_tpu.jit.to_static import CALL_PHASES
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import memory, scopes

VOCAB, SEQ, K = 128, 16, 2
PHASE_COUNTERS = [f'to_static_call_ns{{phase="{p}"}}' for p in CALL_PHASES]


def build_step(seq=SEQ, zero_stage=None, dp_axis=None, clip=True):
    """The step a user's loop calls (the body of chipbench's
    `build_train_step`), at a tiny size."""
    paddle.seed(7)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=VOCAB, hidden_size=32, num_layers=2, num_heads=2,
        max_seq_len=seq, hidden_dropout=0.0, attention_dropout=0.0))
    opt = paddle.optimizer.AdamW(
        parameters=model.parameters(), learning_rate=1e-3,
        multi_precision=True,
        grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0) if clip else None)
    if zero_stage:
        opt._zero_enable(axis=dp_axis, stage=zero_stage)
    params = list(model.parameters())

    def one_step(ids, labels):
        with paddle.amp.auto_cast(enable=True, dtype="bfloat16"):
            loss = model.loss(model(ids), labels)
        loss.backward()
        withg = [p for p in params if p._grad is not None]
        barred = lax.optimization_barrier(tuple(p._grad for p in withg))
        for p, v in zip(withg, barred):
            p._grad = v
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(one_step, scan_steps=K, dp_axis=dp_axis)
    return step, model, opt, one_step


def batch(rows=4, seq=SEQ, seed=0):
    ids = np.random.RandomState(seed).randint(
        0, VOCAB, (K, rows, seq)).astype("int32")
    return paddle.to_tensor(ids), paddle.to_tensor(ids)


def run_once(**build):
    step, _model, _opt, _fn = build_step(**build)
    losses = step(*batch()).numpy()
    return step.hlo_text(), step.scope_table(), losses


def kinds_of(table):
    return {scopes.kind_of(c) for rec in table["instructions"].values()
            for c in rec["path"].split("/") if c}


def strip_metadata(hlo):
    return re.sub(r", metadata=\{[^}]*\}", "", hlo)


@pytest.fixture(scope="module")
def built():
    """The step with scopes, and the same step with the one thing a
    scope does to the trace patched to nothing (built from the same
    line, so that the stack-frame tables at the end of the text agree)."""
    out = {}
    real = scopes._named_scope
    try:
        for name in ("scoped", "patched"):
            if name == "patched":
                scopes._named_scope = lambda _n: contextlib.nullcontext()
            out[name] = run_once()
    finally:
        scopes._named_scope = real
    return out


def test_every_kind_is_in_the_table(built):
    _hlo, table, losses = built["scoped"]
    assert np.isfinite(losses).all() and not table["stale"]
    kinds = kinds_of(table)
    # layers by the name their parent registered them under, the root by
    # its class; ops by call_op's name; the functional and its path; the
    # optimizer's parts; the two model seams that are no Layer call
    for want in ("GPTForCausalLM", "gpt", "blocks", "qkv", "proj", "fc1",
                 "fc2", "ln1", "ln_f", "wte", "linear", "layer_norm",
                 "gelu", "cross_entropy", "embedding", "matmul", "cast",
                 "attention", "xla", "optimizer", "update", "clip", "head",
                 "loss"):
        assert want in kinds, (want, sorted(kinds))
    paths = {rec["path"] for rec in table["instructions"].values()}
    assert any(p.startswith("GPTForCausalLM/gpt/blocks.1/qkv/linear")
               for p in paths), sorted(paths)
    assert any("/attention/xla/scaled_dot_product_attention" in p
               for p in paths)
    assert "GPTForCausalLM/head/matmul" in paths
    # the four kinds whose shares the benchmark adds up never nest
    disjoint = {"optimizer", "attention", "head", "loss"}
    for p in paths:
        assert len(disjoint & {scopes.kind_of(c)
                               for c in p.split("/")}) <= 1, p


def test_backward_carries_the_forwards_scope(built):
    hlo, table, _ = built["scoped"]
    transposed = re.findall(r'op_name="([^"]*transpose\([^"]*)"', hlo)
    assert len(transposed) > 50
    lost = [n for n in transposed if not scopes.path_of(n)[0]]
    assert not lost, lost[:5]
    backward = [rec["path"] for rec in table["instructions"].values()
                if rec["backward"]]
    assert backward and all(backward)
    for leaf in ("qkv/linear", "fc2/linear", "ln1/layer_norm",
                 "attention/xla/scaled_dot_product_attention",
                 "head/matmul", "loss/cross_entropy"):
        assert any(p.endswith(leaf) for p in backward), leaf
    # and nothing of the optimizer is anybody's backward
    assert not any("optimizer" in p for p in backward)


def test_what_carries_no_scope_is_listed_and_small(built):
    _hlo, table, _ = built["scoped"]
    assert table["nontrivial"] > 100
    # staged by the program outside every scope: the loop's counter, the
    # loss's seed — under a tenth, by count
    assert len(table["unscoped"]) < 0.1 * table["nontrivial"], \
        table["unscoped"]
    # the compiler's own (no op_name at all) are listed apart
    for name in table["unscoped"] + table["unnamed"]:
        assert table["instructions"][name]["path"] == ""


def test_scopes_change_nothing_but_metadata(built):
    hlo, _t, losses = built["scoped"]
    hlo_patched, _tp, losses_patched = built["patched"]
    assert "/pt.optimizer" in hlo and "/pt." not in hlo_patched
    assert strip_metadata(hlo) == strip_metadata(hlo_patched)
    assert (losses == losses_patched).all()


def test_an_executable_without_scopes_reads_stale(built):
    _hlo, table_patched, _ = built["patched"]
    # the step was traced through the seams, its executable names none:
    # what a compile cache warmed before the scopes hands back
    assert table_patched["stale"] is True
    assert not kinds_of(table_patched)
    assert built["scoped"][1]["stale"] is False
    # text that was never traced with scopes is just unscoped
    assert scopes.scope_table(built["patched"][0])["stale"] is False
    with pytest.raises(RuntimeError, match="stale"):
        scopes.device_time_by_scope("/nonexistent", table_patched)


def test_step_programs_are_named_by_the_metadata_schema(built):
    hlo = built["scoped"][0]
    name = compile_cache.program_name("pure_fn2")
    assert name.endswith(compile_cache.METADATA_SCHEMA)
    assert re.search(r"^HloModule jit_" + re.escape(name), hlo, re.M), \
        hlo[:200]


def test_an_eager_step_enters_no_scope():
    _step, _model, _opt, one_step = build_step()
    before = scopes.entered()
    loss = one_step(*(t[0] for t in batch()))
    assert np.isfinite(float(loss))
    assert scopes.entered() == before
    assert scopes.current_path() is None
    assert scopes.scope("x") is scopes.NULL_SCOPE


def test_the_registry_holds_the_newest_table():
    memory.clear_program_memory()
    assert memory.program_scopes() is None
    step, *_ = build_step(clip=False)
    step(*batch())
    table = step.scope_table()
    record = memory.program_scopes()
    assert record["table"] is table and record["hlo"] == step.hlo_text()
    assert record["entry"].endswith(":scan")
    assert "clip" not in kinds_of(table)


def test_cached_calls_are_counted_and_the_building_call_is_not():
    step, *_ = build_step(clip=False)
    args = batch()
    names = ["to_static_calls", "jit_cache_miss", "jit_build_ns",
             *PHASE_COUNTERS]
    before = {n: monitor.stat_get(n) for n in names}
    step(*args)  # builds
    mid = {n: monitor.stat_get(n) for n in names}
    assert mid["to_static_calls"] == before["to_static_calls"]
    assert mid["jit_cache_miss"] == before["jit_cache_miss"] + 1
    assert mid["jit_build_ns"] > before["jit_build_ns"]
    assert all(mid[c] == before[c] for c in PHASE_COUNTERS)
    n = 5
    t0 = time.perf_counter_ns()
    for _ in range(n):
        out = step(*args)
    wall = time.perf_counter_ns() - t0
    out.numpy()
    after = {n_: monitor.stat_get(n_) for n_ in names}
    assert after["to_static_calls"] == mid["to_static_calls"] + n
    assert after["jit_cache_miss"] == mid["jit_cache_miss"]
    spent = [after[c] - mid[c] for c in PHASE_COUNTERS]
    assert all(s > 0 for s in spent)
    assert sum(spent) <= wall


def test_a_span_is_in_the_profile_on_the_profiles_clock(tmp_path):
    from jax.profiler import ProfileData

    obs.enable()
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            starts = []
            for i in range(9):
                with obs.trace_span(f"clock/{i}", cat="user") as span:
                    time.sleep(0.001)
                starts.append(span.t0)
            offset = obs.tracing.epoch_offset_ns()
        finally:
            jax.profiler.stop_trace()
    finally:
        obs.disable()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    data = ProfileData.from_file(path)
    origin = dict(data.find_plane_with_name("Task Environment").stats)[
        "profile_start_time"]
    found = {e.name: e.start_ns for plane in data.planes
             for line in plane.lines for e in line.events
             if e.name.startswith("pt/clock/")}
    assert sorted(found) == [f"pt/clock/{i}" for i in range(9)]
    apart = [abs((origin + found[f"pt/clock/{i}"]) - (t0 + offset))
             for i, t0 in enumerate(starts)]
    # the annotation opens right after the span stamps its t0; the
    # median, because a loaded host may park the thread between the two
    assert statistics.median(apart) < 50_000, apart


def test_the_step_calls_phases_are_spans_under_executor_step(tmp_path):
    step, *_ = build_step(clip=False)
    args = batch()
    step(*args)
    obs.reset()
    obs.enable()
    try:
        step(*args)
    finally:
        obs.disable()
    import json
    out = tmp_path / "trace.json"
    obs.export_chrome_trace(str(out))
    names = [e["name"] for e in json.loads(out.read_text())["traceEvents"]]
    assert "executor/step" in names
    for phase in CALL_PHASES:
        assert f"executor/step/{phase}" in names, names


def test_flash_path_keeps_its_scope_through_the_custom_vjp(monkeypatch):
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.nn.functional import attention

    monkeypatch.setattr(fa, "is_available", lambda: True)
    monkeypatch.setattr(fa, "flash_attention_bshd", functools.partial(
        fa.flash_attention_bshd, interpret=True))
    monkeypatch.setattr(attention, "_FLASH_MIN_SEQ", 128)
    step, *_ = build_step(seq=128, clip=False)
    ids = np.random.RandomState(1).randint(
        0, VOCAB, (K, 1, 128)).astype("int32")
    losses = step(paddle.to_tensor(ids), paddle.to_tensor(ids)).numpy()
    assert np.isfinite(losses).all()
    recs = [rec for rec in step.scope_table()["instructions"].values()
            if "/attention/flash/flash_attention" in rec["path"]]
    assert recs and any(r["backward"] for r in recs) \
        and any(not r["backward"] for r in recs)
    # the custom VJP's backward brings the forward's whole stack along,
    # behind the one the tape re-entered: the path holds it once
    assert all(r["path"].count("flash_attention") == 1 for r in recs), \
        sorted({r["path"] for r in recs})
    assert scopes.path_of(
        "jit(f)/pt.a/pt.b/transpose(pt.a)/pt.b/jvp()/mul") == ("a/b", True)
    assert scopes.path_of(
        "jit(f)/pt.m/pt.a/pt.b/transpose(jvp(pt.a/pt.b))/pad") == (
            "m/a/b", True)
    assert scopes.path_of("pt.a/transpose(jvp(pt.act))/mul") == (
        "a/act", True)
    assert "xla" not in kinds_of(step.scope_table())


@pytest.mark.parametrize("path,fused", [("flash", 2), ("xla", 0)])
def test_a_build_counts_its_fused_flash_backwards(monkeypatch, path, fused):
    """Two attention layers: `jit_flash_fused_backwards` is the number
    of attention backwards the step's trace staged on the one backward
    kernel, and nothing where the gate keeps attention on XLA's path."""
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.nn.functional import attention

    monkeypatch.setattr(fa, "is_available", lambda: path == "flash")
    monkeypatch.setattr(fa, "flash_attention_bshd", functools.partial(
        fa.flash_attention_bshd, interpret=True))
    monkeypatch.setattr(attention, "_FLASH_MIN_SEQ", 128)
    step, *_ = build_step(seq=128, clip=False)
    before = monitor.stat_get("jit_flash_fused_backwards")
    ids = paddle.to_tensor(np.random.RandomState(2).randint(
        0, VOCAB, (K, 1, 128)).astype("int32"))
    for _call in range(2):  # the second call builds nothing
        assert np.isfinite(step(ids, ids).numpy()).all()
    assert monitor.stat_get("jit_flash_fused_backwards") - before == fused
    assert (path in kinds_of(step.scope_table())) and (
        {"flash", "xla"} - {path}).isdisjoint(kinds_of(step.scope_table()))


def test_zero3_on_four_devices_names_its_collectives():
    from paddle_tpu.distributed import parallel_env

    parallel_env.set_mesh(parallel_env.make_mesh(
        {"dp": 4}, devices=jax.devices()[:4]))
    try:
        step, model, opt, _fn = build_step(zero_stage=3, dp_axis="dp",
                                           clip=False)
        losses = step(*batch(rows=8)).numpy()
        assert np.isfinite(losses).all()
        table = step.scope_table()
        assert not table["stale"]
        by_opcode = {}
        for rec in table["instructions"].values():
            by_opcode.setdefault(rec["opcode"], set()).add(rec["path"])
        assert any(p.endswith("optimizer/zero.reduce_scatter")
                   for p in by_opcode["reduce-scatter"]), by_opcode[
                       "reduce-scatter"]
        gathers = set().union(*(paths for op, paths in by_opcode.items()
                                if op.startswith("all-gather")))
        assert any(p.endswith("zero.gather") for p in gathers), gathers
        kinds = kinds_of(table)
        for want in ("zero.bucket_copy", "zero.reduce_scatter",
                     "zero.gather", "optimizer", "update", "attention",
                     "head", "loss"):
            assert want in kinds, (want, sorted(kinds))
        assert len(table["unscoped"]) < 0.1 * table["nontrivial"], \
            table["unscoped"]
    finally:
        del step, model, opt
        gc.collect()
        parallel_env.set_mesh(None)


HAND_HLO = """HloModule jit_step_s2, is_scheduled=true

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %mul.1 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(step)/while/body/pt.gpt/pt.blocks.0/pt.fc1/pt.linear/transpose(jvp())/mul"}
}

%body (c: (s32[], f32[8])) -> (s32[], f32[8]) {
  %c = (s32[], f32[8]{0}) parameter(0)
  %fusion.1 = f32[8]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/while/body/pt.optimizer/pt.update/pt.cast/convert_element_type"}
  %custom-call.3 = f32[8]{0} custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/while/body/pt.gpt/pt.blocks.0/pt.attention/pt.flash/pt.flash_attention/jvp(pt.inner)/pallas_call"}
  %copy.4 = f32[8]{0} copy(%custom-call.3)
  %all-gather-start.5 = f32[32]{0} all-gather-start(%copy.4), metadata={op_name="jit(step)/while/body/pt.gpt/pt.wte/pt.zero.gather/all_gather"}
  %all-gather-done.5 = f32[32]{0} all-gather-done(%all-gather-start.5), metadata={op_name="jit(step)/while/body/pt.gpt/pt.wte/pt.zero.gather/all_gather"}
  %reshape.8 = f32[8]{0} reshape(%fusion.2), metadata={op_name="jit(step)/while/body/pt.optimizer/pt.zero.bucket_copy/reshape;pt.optimizer/pt.zero.bucket_copy/reshape"}
  %copy.9 = f32[8]{0} copy(%reshape.8)
  %fusion.10 = f32[2]{0} fusion(%copy.9), kind=kCustom, calls=%all-reduce-scatter.clone
  ROOT %add.6 = s32[] add(%gte.0, %one), metadata={op_name="jit(step)/while/body/add"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %while.7 = (s32[], f32[8]{0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(step)/while"}
}
"""


def test_table_and_reduction_on_hand_made_text_and_events():
    table = scopes.scope_table(HAND_HLO, traced_with_scopes=True)
    ins = table["instructions"]
    assert "mul.1" not in ins  # inside a fused computation
    # a fusion without metadata of its own takes its root's
    assert ins["fusion.1"] == {"path": "gpt/blocks.0/fc1/linear",
                               "backward": True, "opcode": "fusion"}
    assert ins["fusion.2"]["path"] == "optimizer/update/cast"
    assert ins["custom-call.3"]["path"] == \
        "gpt/blocks.0/attention/flash/flash_attention/inner"
    assert ins["all-gather-done.5"]["opcode"] == "all-gather-done"
    # of names the compiler joined with ';' the first counts
    assert ins["reshape.8"]["path"] == "optimizer/zero.bucket_copy"
    # a collective the compiler rewrote into a fusion without metadata
    # takes its operand's scope, through the compiler's own copy
    assert ins["fusion.10"] == {"path": "optimizer/zero.bucket_copy",
                                "backward": False, "opcode": "fusion",
                                "inherited": True}
    assert ins["copy.9"]["path"] == ""  # the copy itself stays unnamed
    assert table["unnamed"] == [] and table["unscoped"] == []
    assert table["nontrivial"] == 6 and not table["stale"]
    ms = 1e6
    events = [["%while.7 = (s32[], f32[8]) while(...)", 0, 100 * ms],
              ["fusion.1", 0, 10 * ms], ["fusion.2", 10 * ms, 20 * ms],
              ["custom-call.3", 30 * ms, 30 * ms], ["copy.4", 60 * ms, 5 * ms],
              ["all-gather-start.5", 65 * ms, 1 * ms],
              ["all-gather-done.5", 66 * ms, 4 * ms],
              ["add.6", 70 * ms, 1 * ms]]
    got = scopes.time_by_scope({"/device:TPU:0": events,
                                "/device:TPU:1": events}, table)
    assert got["total_s"] == pytest.approx(0.070)  # no while, no -start
    assert got["kinds"]["optimizer"] == pytest.approx(0.020)
    assert got["kinds"]["cast"] == pytest.approx(0.020)
    assert got["kinds"]["attention"] == pytest.approx(0.030)
    assert got["kinds"]["blocks"] == pytest.approx(0.040)
    assert got["kinds"]["zero.gather"] == pytest.approx(0.004)
    assert got["paths"]["gpt/blocks.0/fc1/linear"] == pytest.approx(0.010)
    assert got["backward_s"] == pytest.approx(0.010)
    assert got["unscoped"] == {"copy": pytest.approx(0.005),
                               "add": pytest.approx(0.001)}
    assert got["unscoped_s"] == pytest.approx(0.006)
    text = scopes.format_by_scope(got)
    assert "unscoped 8.6 %" in text and "optimizer" in text
