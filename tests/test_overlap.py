"""Collective overlap observability (ISSUE 16): the HLO schedule
analyzer (``observability.overlap``), the async ``-start``/``-done``
billing contract in ``hlo_bytes``, the per-program XLA flag surface
(``jit.xla_flags``), and ``tools/overlap_view``.

The seeded async-HLO fixtures pin the pairing/interleave math
backend-independently: XLA:CPU never emits async collective pairs, so
these hand-written schedules are the only way the hidden-time path is
exercised on the smoke host — the integration tests then assert the
CPU backend's sync-only schedule is reported honestly (efficiency 0.0,
``backend_sync_schedule=True``), not as an analyzer failure.
"""
import gzip
import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.distributed import parallel_env
from paddle_tpu.jit import xla_flags
from paddle_tpu.observability import export as obs_export
from paddle_tpu.observability import hlo_bytes, overlap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DP = 8

rng = np.random.RandomState(16)


@pytest.fixture
def _mesh():
    mesh = parallel_env.make_mesh({"dp": DP})
    parallel_env.set_mesh(mesh)
    yield mesh
    parallel_env.set_mesh(None)


# -- seeded HLO fixtures ---------------------------------------------------
# hand-written post-scheduling HLO snippets: instruction order is the
# schedule. Payloads are sized so collective time dominates (or not)
# by construction.

SYNC_HLO = """HloModule sync, is_scheduled=true

ENTRY %main (p0: f32[1024]) -> f32[8192] {
  %p0 = f32[1024]{0} parameter(0)
  %mul = f32[1024]{0} multiply(f32[1024]{0} %p0, f32[1024]{0} %p0)
  ROOT %ag = f32[8192]{0} all-gather(f32[1024]{0} %mul), channel_id=1, replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}, use_global_device_ids=true
}
"""

# the dot between start/done costs far more than the 32KB gather moves
ASYNC_FULL_HLO = """HloModule hidden, is_scheduled=true

ENTRY %main (p0: f32[1024], p1: f32[1024,1024]) -> f32[1024] {
  %p0 = f32[1024]{0} parameter(0)
  %p1 = f32[1024,1024]{1,0} parameter(1)
  %ag-start = (f32[1024]{0}, f32[8192]{0}) all-gather-start(f32[1024]{0} %p0), channel_id=1, replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}, use_global_device_ids=true
  %dot = f32[1024]{0} dot(f32[1024]{0} %p0, f32[1024,1024]{1,0} %p1), lhs_contracting_dims={0}, rhs_contracting_dims={0}
  %ag-done = f32[8192]{0} all-gather-done((f32[1024]{0}, f32[8192]{0}) %ag-start)
  ROOT %out = f32[1024]{0} add(f32[1024]{0} %dot, f32[1024]{0} %dot)
}
"""

# only a tiny f32[64] add fits between the pair: a sliver hides
ASYNC_PARTIAL_HLO = """HloModule partial, is_scheduled=true

ENTRY %main (p0: f32[1024], p2: f32[64]) -> f32[8192] {
  %p0 = f32[1024]{0} parameter(0)
  %p2 = f32[64]{0} parameter(1)
  %ag-start = (f32[1024]{0}, f32[8192]{0}) all-gather-start(f32[1024]{0} %p0), channel_id=1, replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}, use_global_device_ids=true
  %small = f32[64]{0} add(f32[64]{0} %p2, f32[64]{0} %p2)
  ROOT %ag-done = f32[8192]{0} all-gather-done((f32[1024]{0}, f32[8192]{0}) %ag-start)
}
"""

# an async pair scheduled back-to-back: nothing between -> fully exposed
ASYNC_ADJACENT_HLO = """HloModule adjacent, is_scheduled=true

ENTRY %main (p0: f32[1024]) -> f32[8192] {
  %p0 = f32[1024]{0} parameter(0)
  %ag-start = (f32[1024]{0}, f32[8192]{0}) all-gather-start(f32[1024]{0} %p0), channel_id=1, replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}, use_global_device_ids=true
  ROOT %ag-done = f32[8192]{0} all-gather-done((f32[1024]{0}, f32[8192]{0}) %ag-start)
}
"""

# sync all-reduce inside a x3 while inside a x4 while: bills 12 per run
NESTED_SCAN_HLO = """HloModule nested, is_scheduled=true

%inner_body (p: (f32[256])) -> (f32[256]) {
  %p = (f32[256]{0}) parameter(0)
  %gte = f32[256]{0} get-tuple-element((f32[256]{0}) %p), index=0
  %ar = f32[256]{0} all-reduce(f32[256]{0} %gte), channel_id=2, replica_groups={{0,1,2,3,4,5,6,7}}, use_global_device_ids=true, to_apply=%sum
  ROOT %t = (f32[256]{0}) tuple(f32[256]{0} %ar)
}

%inner_cond (p: (f32[256])) -> pred[] {
  %p = (f32[256]{0}) parameter(0)
  ROOT %c = pred[] constant(true)
}

%outer_body (q: (f32[256])) -> (f32[256]) {
  %q = (f32[256]{0}) parameter(0)
  %inner = (f32[256]{0}) while((f32[256]{0}) %q), condition=%inner_cond, body=%inner_body, backend_config={"known_trip_count":{"n":"3"}}
  ROOT %t2 = (f32[256]{0}) tuple(f32[256]{0} %inner)
}

%outer_cond (q: (f32[256])) -> pred[] {
  %q = (f32[256]{0}) parameter(0)
  ROOT %c2 = pred[] constant(true)
}

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(f32[] %a, f32[] %b)
}

ENTRY %main (p0: f32[256]) -> (f32[256]) {
  %p0 = f32[256]{0} parameter(0)
  %init = (f32[256]{0}) tuple(f32[256]{0} %p0)
  ROOT %outer = (f32[256]{0}) while((f32[256]{0}) %init), condition=%outer_cond, body=%outer_body, backend_config={"known_trip_count":{"n":"4"}}
}
"""


# -- analyzer: pairing + efficiency math ----------------------------------

def test_sync_schedule_zero_efficiency():
    s = overlap.overlap_stats(SYNC_HLO)
    assert s["collective_overlap_efficiency"] == 0.0
    assert s["exposed_collective_frac"] == 1.0
    assert s["async_pairs_total"] == 0
    assert s["sync_total"] == 1
    assert s["backend_sync_schedule"] is True
    assert s["exposed_ns"] == pytest.approx(s["collective_ns"])
    assert s["collective_ns"] > 0


def test_fully_hidden_async_pair():
    s = overlap.overlap_stats(ASYNC_FULL_HLO)
    assert s["async_pairs_total"] == 1
    assert s["sync_total"] == 0
    assert s["collective_overlap_efficiency"] == pytest.approx(1.0)
    assert s["exposed_ns"] == pytest.approx(0.0)
    assert s["backend_sync_schedule"] is False
    (pair,) = s["pairs"]
    assert pair["phase"] == "async"
    # the dot's compute time exceeds the 32KB gather's wire time
    assert pair["overlap_ns"] > pair["collective_ns"]


def test_partial_interleave_fractional():
    s = overlap.overlap_stats(ASYNC_PARTIAL_HLO)
    assert s["async_pairs_total"] == 1
    eff = s["collective_overlap_efficiency"]
    assert 0.0 < eff < 1.0
    assert s["exposed_collective_frac"] == pytest.approx(1.0 - eff)
    (pair,) = s["pairs"]
    # the hidden sliver is exactly the in-between compute estimate
    assert pair["hidden_ns"] == pytest.approx(pair["overlap_ns"])
    assert pair["hidden_ns"] < pair["collective_ns"]


def test_adjacent_async_pair_fully_exposed():
    s = overlap.overlap_stats(ASYNC_ADJACENT_HLO)
    assert s["async_pairs_total"] == 1
    assert s["collective_overlap_efficiency"] == 0.0
    # async with nothing scheduled between is exposed but NOT a sync
    # schedule — the gauge split must keep the two cases apart
    assert s["backend_sync_schedule"] is False


def test_unmatched_start_counts_sync():
    # strip the -done line: the dangling -start blocks like a sync op
    hlo = "\n".join(l for l in ASYNC_FULL_HLO.splitlines()
                    if "ag-done" not in l)
    s = overlap.overlap_stats(hlo)
    assert s["async_pairs_total"] == 0
    assert s["sync_total"] == 1
    assert s["collective_overlap_efficiency"] == 0.0


def test_nested_scan_trip_count_multiplication():
    s = overlap.overlap_stats(NESTED_SCAN_HLO, per_execution=True)
    # 4 outer trips x 3 inner trips x 1 all-reduce
    assert s["sync_total"] == 12
    static = overlap.overlap_stats(NESTED_SCAN_HLO, per_execution=False)
    assert static["sync_total"] == 1
    assert s["collective_ns"] == pytest.approx(12 * static["collective_ns"])


def test_no_collectives_reports_honestly():
    hlo = """HloModule empty, is_scheduled=true

ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %m = f32[8]{0} multiply(f32[8]{0} %p0, f32[8]{0} %p0)
}
"""
    s = overlap.overlap_stats(hlo)
    assert s["collective_overlap_efficiency"] == 0.0
    assert s["sync_total"] == 0 and s["async_pairs_total"] == 0
    # no collectives is not a "sync schedule" finding
    assert s["backend_sync_schedule"] is False


def test_assumptions_recorded():
    s = overlap.overlap_stats(SYNC_HLO, link_gbps=50.0, hbm_gbps=400.0)
    assert s["assumptions"]["link_gbps"] == 50.0
    assert s["assumptions"]["hbm_gbps"] == 400.0
    # halving the link bandwidth doubles the collective estimate
    base = overlap.overlap_stats(SYNC_HLO)
    assert s["collective_ns"] == pytest.approx(2 * base["collective_ns"])


def test_per_op_split(_mesh):
    # second computation renamed: computations are keyed by name, and
    # two ENTRY %main blocks would collide
    combined = ASYNC_FULL_HLO + SYNC_HLO.replace(
        "HloModule sync, is_scheduled=true", "").replace(
        "ENTRY %main", "%tail")
    s = overlap.overlap_stats(combined, mesh=_mesh)
    assert "all-gather" in s["per_op"]
    (pair,) = [p for p in s["pairs"] if p["phase"] == "async"]
    assert pair["axis"] == "dp"


# -- hlo_bytes: async billing regression (satellite 1) ---------------------

def test_async_pair_bills_bytes_exactly_once():
    stats = hlo_bytes.collective_stats(ASYNC_FULL_HLO)
    assert len(stats) == 1
    (rec,) = stats
    assert rec["op"] == "all-gather"
    assert rec["count"] == 1  # one pair, one op — not two
    # the -start result tuple repeats the operand buffer next to the
    # full result; the payload is the LARGEST shape, once
    assert rec["bytes"] == 8192 * 4


def test_done_line_never_matches_op_regex():
    done_only = ("  %ag-done = f32[8192]{0} all-gather-done("
                 "(f32[1024]{0}, f32[8192]{0}) %ag-start)")
    assert hlo_bytes.collective_stats(done_only) == []
    assert hlo_bytes._OP_RE.search(done_only) is None
    # ... including when an operand NAME carries the op substring
    tricky = ("  %x = f32[8]{0} all-gather-done((f32[1]{0}, f32[8]{0}) "
              "%all-gather-start.1)")
    assert hlo_bytes._OP_RE.search(tricky) is None


# -- hlo_bytes: iota replica-group resolution (satellite 2) ----------------

def test_replica_group_forms_resolve_same_axis(_mesh):
    brace = SYNC_HLO
    iota = SYNC_HLO.replace("replica_groups={{0,1,2,3,4,5,6,7}}",
                            "replica_groups=[8]<=[8]")
    (b,) = hlo_bytes.collective_stats(brace, mesh=_mesh)
    (i,) = hlo_bytes.collective_stats(iota, mesh=_mesh)
    assert b["axis"] == "dp"
    assert i["axis"] == "dp"  # used to fall back to size1
    assert b["bytes"] == i["bytes"]


def test_iota_form_multi_group():
    mesh = parallel_env.make_mesh({"dp": 4, "mp": 2})
    try:
        parallel_env.set_mesh(mesh)
        hlo = SYNC_HLO.replace("replica_groups={{0,1,2,3,4,5,6,7}}",
                               "replica_groups=[4,2]<=[4,2]")
        (rec,) = hlo_bytes.collective_stats(hlo, mesh=mesh)
        assert rec["axis"] == "mp"  # 4 groups of size 2 -> the size-2 axis
        # permuted iota bounds parse the same (dims product, not order)
        hlo2 = SYNC_HLO.replace("replica_groups={{0,1,2,3,4,5,6,7}}",
                                "replica_groups=[2,4]<=[2,4]")
        (rec2,) = hlo_bytes.collective_stats(hlo2, mesh=mesh)
        assert rec2["axis"] == "dp"  # 2 groups of size 4
    finally:
        parallel_env.set_mesh(None)


def test_group_size_parsing_unit():
    assert hlo_bytes._group_size("replica_groups={{0,1,2}}") == 3
    assert hlo_bytes._group_size("replica_groups=[8]<=[8]") == 8
    assert hlo_bytes._group_size("replica_groups=[8]<=[2,4]") == 8
    assert hlo_bytes._group_size("replica_groups=[4,2]<=[8]") == 2
    assert hlo_bytes._group_size("no groups here") is None


# -- jit.xla_flags ---------------------------------------------------------

def test_parse_flags_coercion():
    flags = xla_flags.parse_flags(
        "--xla_a=true xla_b=false xla_c=3 xla_d=1.5 xla_e xla_f=text")
    assert flags == {"xla_a": True, "xla_b": False, "xla_c": 3,
                     "xla_d": 1.5, "xla_e": True, "xla_f": "text"}


def test_resolve_accepts_preset_string_dict():
    preset = xla_flags.resolve("latency-hiding")
    assert preset["xla_tpu_enable_latency_hiding_scheduler"] is True
    parsed = xla_flags.resolve("xla_x=2")
    assert parsed == {"xla_x": 2}
    passthru = xla_flags.resolve({"xla_y": False})
    assert passthru == {"xla_y": False}
    assert xla_flags.resolve(None) == {}
    with pytest.raises(TypeError):
        xla_flags.resolve(42)


def test_env_overlay_wins(monkeypatch):
    monkeypatch.setenv(xla_flags.ENV_VAR, "xla_x=9 xla_z=true")
    flags = xla_flags.resolve({"xla_x": 1, "xla_y": 2})
    assert flags == {"xla_x": 9, "xla_y": 2, "xla_z": True}
    monkeypatch.setenv(xla_flags.ENV_VAR, "no-latency-hiding")
    assert xla_flags.resolve(None) == \
        xla_flags.PRESETS["no-latency-hiding"]


def test_resolve_false_is_hard_off(monkeypatch):
    """False / "none" / "off" mean NO flags — and unlike None, the env
    overlay does not re-arm them (the A/B control arm must stay the
    control even under a runner's PADDLE_TPU_XLA_FLAGS)."""
    assert xla_flags.resolve(False) == {}
    assert xla_flags.resolve("none") == {}
    assert xla_flags.resolve("off") == {}
    monkeypatch.setenv(xla_flags.ENV_VAR, "xla_x=9")
    assert xla_flags.resolve(False) == {}
    assert xla_flags.resolve(None) == {"xla_x": 9}


def test_backend_accepts_probes_once():
    """The scan-default probe: CPU rejects the xla_tpu_* preset (judged
    by one trivial flagged compile), accepts an empty set trivially,
    and caches the verdict per flag set."""
    preset = xla_flags.PRESETS["latency-hiding"]
    assert xla_flags.backend_accepts(preset) is False
    key = tuple(sorted((k, str(v)) for k, v in preset.items()))
    assert xla_flags._BACKEND_ACCEPTS[key] is False
    assert xla_flags.backend_accepts({}) is True
    assert xla_flags.backend_accepts(
        {"xla_cpu_enable_xprof_traceme": True}) is True


def test_flagged_jit_unknown_flag_fallback():
    fj = xla_flags.jit(lambda x: x * 2,
                       xla_flags={"xla_tpu_enable_latency_hiding_scheduler":
                                  True})
    out = fj(np.float32(3.0))
    assert float(out) == 6.0
    assert fj.applied is False
    assert "No such compile option" in fj.fallback_error
    prov = fj.provenance()
    assert prov["applied"] is False and prov["flags"]


def test_flagged_jit_valid_flag_applies():
    fj = xla_flags.jit(lambda x: x + 1,
                       xla_flags={"xla_cpu_enable_xprof_traceme": True})
    assert float(fj(np.float32(1.0))) == 2.0
    assert fj.applied is True
    assert fj.provenance()["fallback_error"] is None


def test_flagged_jit_lower_compile_fallback():
    import jax
    fj = xla_flags.jit(lambda x: x * 3,
                       xla_flags={"xla_tpu_enable_latency_hiding_scheduler":
                                  True})
    compiled = fj.lower(jax.ShapeDtypeStruct((4,), np.float32)).compile()
    assert "f32[4]" in compiled.as_text()
    assert fj.applied is False


def test_flagged_jit_real_error_propagates():
    import jax.numpy as jnp
    fj = xla_flags.jit(lambda x: jnp.dot(x, jnp.zeros((3, 3))),  # shape err
                       xla_flags={"xla_x": True})
    with pytest.raises(Exception) as e:
        fj(np.zeros(4, np.float32))
    assert "No such compile option" not in str(e.value)


# -- StaticFunction surface (zero3 scan, 8-device mesh) --------------------

def _zero3_step(k=2):
    paddle.seed(7)
    m = nn.Sequential(nn.Linear(64, 128), nn.ReLU(), nn.Linear(128, 32))
    opt = paddle.optimizer.AdamW(parameters=m.parameters(),
                                 learning_rate=0.05)
    opt._zero_enable(axis="dp", stage=3)

    def one(xb, yb):
        loss = nn.functional.cross_entropy(m(xb), yb)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss
    x = paddle.to_tensor(rng.rand(k, 16, 64).astype("float32"))
    y = paddle.to_tensor(rng.randint(0, 32, (k, 16)).astype("int64"))
    return one, x, y


def test_static_function_overlap_stats(_mesh):
    one, x, y = _zero3_step()
    step = paddle.jit.to_static(one, scan_steps=2, dp_axis="dp")
    step(x, y)
    s = step.overlap_stats()
    # the zero3 step REALLY issues collectives; CPU schedules them sync
    assert s["sync_total"] > 0
    assert s["backend_sync_schedule"] is True
    assert s["collective_overlap_efficiency"] == 0.0
    assert {"all-gather", "reduce-scatter"} <= set(s["per_op"])
    assert all(p["axis"] == "dp" for p in s["pairs"])


def test_static_function_export_overlap_gauges(_mesh):
    obs_export.clear_gauges()
    one, x, y = _zero3_step()
    step = paddle.jit.to_static(one, scan_steps=2, dp_axis="dp")
    step(x, y)
    step.export_overlap_stats()
    g = obs_export.gauges()
    per_prog = [k for k in g if k.startswith(
        "collective_overlap_efficiency{") and "op=" not in k]
    assert per_prog and g[per_prog[0]] == 0.0
    assert any(k.startswith("exposed_collective_ns_estimate{")
               and 'axis="dp"' in k for k in g)
    assert any(k.startswith("collective_sync_total{") for k in g)
    assert any(k.startswith("collective_async_pairs_total{") for k in g)
    obs_export.clear_gauges()


def test_static_function_xla_flags_provenance(_mesh):
    one, x, y = _zero3_step()
    step = paddle.jit.to_static(one, scan_steps=2, dp_axis="dp",
                                xla_flags="latency-hiding")
    step(x, y)
    prov = step.xla_flags()
    assert prov["flags"] == xla_flags.PRESETS["latency-hiding"]
    assert prov["applied"] is False  # CPU rejects xla_tpu_* options
    assert "No such compile option" in prov["fallback_error"]
    # the fallback still produced a working program + introspection
    assert step.overlap_stats()["sync_total"] > 0


def test_static_function_no_flags_provenance(_mesh):
    one, x, y = _zero3_step()
    # xla_flags=False: the explicit opt-out (scan programs otherwise
    # DEFAULT to the latency-hiding preset where the backend takes it)
    step = paddle.jit.to_static(one, scan_steps=2, dp_axis="dp",
                                xla_flags=False)
    step(x, y)
    prov = step.xla_flags()
    assert prov == {"flags": {}, "applied": False,
                    "fallback_error": None}
    assert step._xla_flags_default_pending is False


def test_scan_default_latency_hiding_preset(_mesh, monkeypatch):
    """A scan program with no xla_flags defaults to the latency-hiding
    preset exactly when the backend registers it: on this CPU host the
    probe says no and the program compiles unflagged; with the probe
    forced to yes the preset attaches and provenance reports it."""
    one, x, y = _zero3_step()
    step = paddle.jit.to_static(one, scan_steps=2, dp_axis="dp")
    assert step._xla_flags_default_pending is True
    step(x, y)  # first build resolves the default via the probe
    assert step._xla_flags_default_pending is False
    assert step.xla_flags()["flags"] == {}  # CPU rejects xla_tpu_*

    monkeypatch.setattr(xla_flags, "backend_accepts", lambda flags: True)
    one2, x2, y2 = _zero3_step()
    step2 = paddle.jit.to_static(one2, scan_steps=2, dp_axis="dp")
    assert step2._xla_flags_default_pending is True
    step2(x2, y2)
    prov = step2.xla_flags()
    assert prov["flags"] == xla_flags.PRESETS["latency-hiding"]
    assert prov["applied"] is False  # ...and the compile still fell back
    # an explicit empty-ish request (False) or env flags suppress it
    step3 = paddle.jit.to_static(lambda v: v, scan_steps=2)
    assert step3._xla_flags_default_pending is True
    monkeypatch.setenv(xla_flags.ENV_VAR, "xla_x=1")
    step4 = paddle.jit.to_static(lambda v: v, scan_steps=2)
    assert step4._xla_flags_default_pending is False
    assert step4._xla_flags == {"xla_x": 1}


# -- tools/overlap_view ----------------------------------------------------

def _overlap_view():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import overlap_view
    return overlap_view


def test_overlap_view_hlo_gantt(tmp_path, capsys):
    ov = _overlap_view()
    hlo = tmp_path / "step.hlo"
    hlo.write_text(ASYNC_FULL_HLO + SYNC_HLO.replace(
        "HloModule sync, is_scheduled=true", "").replace(
        "ENTRY %main", "%tail"))
    rc = ov.main(["--hlo", str(hlo)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "schedule timeline" in out
    assert "#" in out and "=" in out  # hidden + exposed bar cells
    assert "(async)" in out and "(sync)" in out


def test_overlap_view_diff_shape(tmp_path, capsys):
    ov = _overlap_view()
    a = {"programs": {"step": overlap.overlap_stats(SYNC_HLO)}}
    b = {"programs": {"step": overlap.overlap_stats(ASYNC_FULL_HLO)}}
    pa, pb = tmp_path / "off.json", tmp_path / "on.json"
    pa.write_text(json.dumps(a))
    pb.write_text(json.dumps(b))
    rc = ov.main(["--diff", str(pa), str(pb)])
    out = capsys.readouterr().out
    assert rc == 0
    lines = out.splitlines()
    assert "d_eff" in lines[1] and "d_exposed_us" in lines[1]
    row = [l for l in lines if l.startswith("step")][0]
    assert "+1.000" in row  # 0.0 -> 1.0 efficiency
    assert "0->1" in row  # async pair appeared


def test_overlap_view_diff_schedulable_delta(tmp_path, capsys):
    """Seeded prefetch-on/off captures: --diff must surface the
    schedulable-overlap delta per entry — for HLO-priced entries from
    ``schedulable_overlap``, and for ladder-twin entries (identity
    stand-in collectives, nothing priced) from the record-level
    ``sequence_schedulable`` the captures carry."""
    ov = _overlap_view()
    sa = overlap.overlap_stats(SYNC_HLO)
    sb = overlap.overlap_stats(ASYNC_FULL_HLO)
    twin = {"collective_overlap_efficiency": 0.0, "exposed_ns": 0.0,
            "exposed_collective_frac": 1.0, "async_pairs_total": 0,
            "sync_total": 0}
    a = {"programs": {"step": sa,
                      "zero3_twin": dict(twin, sequence_schedulable=0.5)}}
    b = {"programs": {"step": sb,
                      "zero3_twin": dict(twin, sequence_schedulable=1.0)}}
    pa, pb = tmp_path / "off.json", tmp_path / "on.json"
    pa.write_text(json.dumps(a))
    pb.write_text(json.dumps(b))
    rc = ov.main(["--diff", str(pa), str(pb)])
    out = capsys.readouterr().out
    assert rc == 0
    header = out.splitlines()[1]
    assert "sched(A)" in header and "d_sched" in header
    step = [l for l in out.splitlines() if l.startswith("step")][0]
    d = sb["schedulable_overlap"] - sa["schedulable_overlap"]
    assert f"{d:+.3f}" in step
    twin_row = [l for l in out.splitlines()
                if l.startswith("zero3_twin")][0]
    assert "0.500" in twin_row and "1.000" in twin_row
    assert "+0.500" in twin_row
    # the plain table view carries the sched column too
    assert "sched" in ov.format_program_table(
        {"zero3_twin": dict(twin, sequence_schedulable=1.0)})


def test_overlap_view_out_capture_roundtrip(tmp_path, capsys):
    ov = _overlap_view()
    hlo = tmp_path / "step.hlo"
    hlo.write_text(ASYNC_FULL_HLO)
    cap = tmp_path / "cap.json"
    rc = ov.main(["--hlo", str(hlo), "--out", str(cap)])
    capsys.readouterr()
    assert rc == 0
    data = json.loads(cap.read_text())
    (stats,) = data["programs"].values()
    assert stats["collective_overlap_efficiency"] == pytest.approx(1.0)


def test_overlap_view_trace_correlation(tmp_path, capsys):
    ov = _overlap_view()
    prof = tmp_path / "prof" / "plugins" / "profile" / "run1"
    prof.mkdir(parents=True)
    trace = {"traceEvents": [
        {"name": "all-gather-start.1", "dur": 5.0, "ph": "X"},
        {"name": "fusion.7", "dur": 100.0, "ph": "X"},
        {"name": "all-reduce.2", "dur": 2.5, "ph": "X"},
    ]}
    with gzip.open(prof / "host.trace.json.gz", "wt") as f:
        json.dump(trace, f)
    corr = ov.correlate_trace(str(tmp_path / "prof"),
                              {"collective_ns": 1000.0})
    assert corr["events"] == 2
    assert corr["measured_collective_ns"] == pytest.approx(7.5e3)
    assert corr["measured_over_estimate"] == pytest.approx(7.5)
    # empty dir reports "no spans", not a crash
    empty = tmp_path / "empty"
    empty.mkdir()
    assert ov.correlate_trace(str(empty), {"collective_ns": 1.0}) is None
    hlo = tmp_path / "step.hlo"
    hlo.write_text(SYNC_HLO)
    rc = ov.main(["--hlo", str(hlo), "--trace",
                  str(tmp_path / "prof")])
    out = capsys.readouterr().out
    assert rc == 0
    assert "trace correlation: measured collective wall-time" in out


def test_overlap_view_source_validation(capsys):
    ov = _overlap_view()
    with pytest.raises(SystemExit):
        ov.main([])
    capsys.readouterr()


# -- ladder attribution contract -------------------------------------------

@pytest.mark.slow
def test_ladder_attribute_overlap_zero3():
    from paddle_tpu.analysis import ladder
    rows = ladder.attribute_overlap(configs=["zero3"])["zero3"]
    assert rows
    for s in rows:
        assert "error" not in s, s
        # twins use identity stand-in collectives: honest zero report
        assert s["collective_overlap_efficiency"] == 0.0
        assert s["async_pairs_total"] == 0
