"""Multi-host virtual pod runtime (ISSUE 11) + elastic scale-UP
(ISSUE 12).

The contract under test: a pod of REAL localhost processes survives a
REAL SIGKILL of one rank mid-step — the failure is detected within the
configured window and named, the survivors re-form at the smaller world
size, elastically restore from the rank-0-committed multi-process
checkpoint (per-rank shard files, one manifest), continue with losses
within 1e-6 of a single-process control, and `tools/trace_view.py`
merges every rank's run-log — the dead rank's included — into one
trace. ISSUE 12 closes the loop UPWARD: the supervisor RESPAWNS the
reaped rank under a budgeted-backoff RestartPolicy, the replacement
parks in the coordinator's lobby, the survivors' next reform GROWS the
world back, and every rank restores from the latest pod checkpoint —
kill -> shrink -> heal -> grow, generations strictly monotone, losses
still within 1e-6 of the uninterrupted control; three consecutive
kill/heal cycles (one killing a replacement DURING its own restore)
never deadlock. Plus the coordinator/runtime unit semantics
(rendezvous, lobby admission, barrier-with-timeout, lease-expiry +
straggler detection, deterministic allreduce, re-formation up and
down), the pod checkpoint partition/merge, and the satellites
(pod-failure flight dumps, respawn lint, reform timeline, shared
restart policy).
"""
import io
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from paddle_tpu.distributed.pod import (BarrierTimeoutError, PodCoordinator,
                                        PodRuntime, RankFailedError,
                                        RestartPolicy, start_coordinator)
from paddle_tpu.testing import faults
from paddle_tpu.testing.virtual_pod import VirtualPod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "virtual_pod_fixture.py")

sys.path.insert(0, os.path.join(REPO, "tools"))


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


# ---------------------------------------------------------------- unit level

class TestCoordinator:
    """In-process pod semantics: threads as ranks against a real
    coordinator server (the TCP path, minus the process boundary)."""

    def _pod(self, ep, n, r, **kw):
        kw.setdefault("heartbeat_interval", 0.1)
        kw.setdefault("barrier_timeout", 10.0)
        return PodRuntime(ep, n, r, **kw)

    def test_join_is_a_uniqueid_exchange(self):
        coord, ep = start_coordinator(expected=2, lease_ttl=5.0)
        try:
            got, failed = {}, {}

            def run(r):
                try:
                    pod = self._pod(ep, 2, r).init()
                    got[r] = (pod.uid, pod.gen, pod.rank, pod.world_size)
                    pod.shutdown()
                except BaseException as e:  # carried to the main thread
                    failed[r] = e

            ts = [threading.Thread(target=run, args=(r,)) for r in (0, 1)]
            [t.start() for t in ts]
            [t.join(30) for t in ts]
            if failed:
                r = min(failed)
                raise AssertionError(f"rank {r} failed") from failed[r]
            assert not any(t.is_alive() for t in ts), "a join outran 30 s"
            # every rank got the SAME minted uid (the NCCL-uniqueId
            # analog) and a consistent roster
            assert got[0][0] == got[1][0] == coord.uid
            assert got[0][1:] == (0, 0, 2) and got[1][1:] == (0, 1, 2)
        finally:
            coord.close()

    def test_barrier_timeout_names_absent_rank(self):
        coord, ep = start_coordinator(expected=2, lease_ttl=30.0)
        try:
            pods = {}

            def run(r):
                pods[r] = self._pod(ep, 2, r).init()

            ts = [threading.Thread(target=run, args=(r,)) for r in (0, 1)]
            [t.start() for t in ts]
            [t.join(30) for t in ts]
            # rank 1 keeps heartbeating (stays live) but never arrives
            with pytest.raises(BarrierTimeoutError) as ei:
                pods[0].barrier("never", timeout=0.8)
            assert ei.value.waiting == [1]
            assert "never" in str(ei.value)
        finally:
            for p in pods.values():
                p.shutdown()
            coord.close()

    def test_barrier_fails_loudly_on_marked_death(self):
        coord, ep = start_coordinator(expected=2, lease_ttl=30.0)
        try:
            pods = {}

            def run(r):
                pods[r] = self._pod(ep, 2, r).init()

            ts = [threading.Thread(target=run, args=(r,)) for r in (0, 1)]
            [t.start() for t in ts]
            [t.join(30) for t in ts]
            err = {}

            def waiter():
                try:
                    pods[0].barrier("b", timeout=10.0)
                except RankFailedError as e:
                    err["e"] = e

            t = threading.Thread(target=waiter)
            t.start()
            time.sleep(0.2)
            coord.mark_failed(1, "killed by SIGKILL (supervisor)")
            t.join(10)
            assert err["e"].ranks == [1]
            assert "SIGKILL" in str(err["e"])
        finally:
            for p in pods.values():
                p.shutdown()
            coord.close()

    def test_lease_expiry_detection_is_bounded(self):
        """No supervisor: a silently dead rank (heartbeat stops) is
        detected within lease_ttl + one monitor sweep."""
        ttl = 0.8
        coord, ep = start_coordinator(expected=2, lease_ttl=ttl)
        try:
            pods = {}

            def run(r):
                pods[r] = self._pod(ep, 2, r).init()

            ts = [threading.Thread(target=run, args=(r,)) for r in (0, 1)]
            [t.start() for t in ts]
            [t.join(30) for t in ts]
            pods[1]._hb_stop.set()  # the silent death
            t0 = time.time()
            with pytest.raises(RankFailedError) as ei:
                pods[0].barrier("b", timeout=10.0)
            detect = time.time() - t0
            assert ei.value.ranks == [1]
            assert "lease expired" in str(ei.value)
            assert detect < ttl + 1.5, f"detection took {detect:.2f}s"
        finally:
            for p in pods.values():
                p.shutdown()
            coord.close()

    def test_allreduce_rank_sorted_deterministic_sum(self):
        coord, ep = start_coordinator(expected=3, lease_ttl=10.0)
        try:
            out = {}

            def run(r):
                pod = self._pod(ep, 3, r).init()
                out[r] = pod.allreduce(np.full(4, float(r + 1)),
                                       timeout=10.0)
                pod.shutdown()

            ts = [threading.Thread(target=run, args=(r,))
                  for r in (0, 1, 2)]
            [t.start() for t in ts]
            [t.join(30) for t in ts]
            for r in (0, 1, 2):
                np.testing.assert_array_equal(out[r], np.full(4, 6.0))
        finally:
            coord.close()

    def test_reform_shrinks_world_and_redenses_ranks(self):
        coord, ep = start_coordinator(expected=3, lease_ttl=30.0)
        try:
            pods = {}

            def run(r):
                pods[r] = self._pod(ep, 3, r).init()

            ts = [threading.Thread(target=run, args=(r,))
                  for r in (0, 1, 2)]
            [t.start() for t in ts]
            [t.join(30) for t in ts]
            coord.mark_failed(1, "killed")
            views = {}

            def ref(r):
                views[r] = pods[r].reform(timeout=10.0)

            ts = [threading.Thread(target=ref, args=(r,)) for r in (0, 2)]
            [t.start() for t in ts]
            [t.join(30) for t in ts]
            # dense re-rank: survivor 0 stays 0, survivor 2 becomes 1
            assert views[0] == {"gen": 1, "rank": 0, "world_size": 2}
            assert views[2] == {"gen": 1, "rank": 1, "world_size": 2}
            # data re-shards under the new world automatically
            assert pods[2].shard_range(8) == (4, 8)
            # a stale-generation op is rejected, not deadlocked
            resp = coord.handle_req({"op": "barrier", "rank": 0,
                                     "gen": 0, "name": "x",
                                     "timeout": 1.0})
            assert resp == {"ok": False, "error": "stale_gen", "gen": 1}
        finally:
            for p in pods.values():
                p.shutdown()
            coord.close()

    def test_lease_detection_survives_a_reform(self):
        """The re-formed pod must keep lease enforcement at the SMALLER
        world size: a second silent death after the first reform is
        still detected within the ttl (without any supervisor mark)."""
        ttl = 0.8
        coord, ep = start_coordinator(expected=3, lease_ttl=ttl)
        try:
            pods = {}

            def run(r):
                pods[r] = self._pod(ep, 3, r).init()

            ts = [threading.Thread(target=run, args=(r,))
                  for r in (0, 1, 2)]
            [t.start() for t in ts]
            [t.join(30) for t in ts]
            pods[2]._hb_stop.set()  # first silent death
            with pytest.raises(RankFailedError):
                pods[0].barrier("b0", timeout=10.0)
            views = {}

            def ref(r):
                try:
                    pods[r].check_failures()
                except RankFailedError:
                    pass
                views[r] = pods[r].reform(timeout=10.0)

            ts = [threading.Thread(target=ref, args=(r,)) for r in (0, 1)]
            [t.start() for t in ts]
            [t.join(30) for t in ts]
            assert views[0]["world_size"] == views[1]["world_size"] == 2
            pods[1]._hb_stop.set()  # SECOND silent death, post-reform
            t0 = time.time()
            with pytest.raises(RankFailedError) as ei:
                pods[0].barrier("b1", timeout=10.0)
            assert time.time() - t0 < ttl + 1.5
            assert "lease expired" in str(ei.value)
        finally:
            for p in pods.values():
                p.shutdown()
            coord.close()

    def test_join_skew_longer_than_ttl_still_forms(self):
        """Leases must not bind during RENDEZVOUS: a peer that takes
        longer than lease_ttl to start (cold interpreter under CI load)
        must not get the early joiner falsely marked dead — formation
        re-stamps every lease and enforcement starts there."""
        ttl = 0.5
        coord, ep = start_coordinator(expected=2, lease_ttl=ttl)
        try:
            got = {}

            def run(r, delay):
                time.sleep(delay)
                pod = self._pod(ep, 2, r).init()
                pod.barrier("formed", timeout=10.0)
                got[r] = pod.world_size
                pod.shutdown()

            ts = [threading.Thread(target=run, args=(0, 0.0)),
                  threading.Thread(target=run, args=(1, 3 * ttl))]
            [t.start() for t in ts]
            [t.join(30) for t in ts]
            assert got == {0: 2, 1: 2}
            assert coord.state()["failed"] == {}
        finally:
            coord.close()

    def test_from_env(self, monkeypatch):
        monkeypatch.setenv("PADDLE_POD_COORDINATOR", "127.0.0.1:1234")
        monkeypatch.setenv("PADDLE_TRAINERS_NUM", "4")
        monkeypatch.setenv("PADDLE_TRAINER_ID", "2")
        monkeypatch.setenv("PADDLE_POD_BARRIER_TIMEOUT", "12.5")
        monkeypatch.setenv("PADDLE_POD_JOIN_TIMEOUT", "90")
        pod = PodRuntime.from_env()
        assert (pod.coordinator, pod.num_processes, pod.origin,
                pod.barrier_timeout, pod.join_timeout) == \
            ("127.0.0.1:1234", 4, 2, 12.5, 90.0)

    def test_lobby_join_and_reform_up(self):
        """The kill->shrink->heal->grow lifecycle in-process: a
        post-formation joiner parks in the LOBBY (running generation
        undisturbed), survivors see it via pending_joiners(), and the
        next reform GROWS the world — gen+1, the replacement admitted
        at the appended rank, collectives spanning the new world, stale
        generations still rejected loudly."""
        coord, ep = start_coordinator(expected=2, lease_ttl=30.0)
        pods, rep = {}, {}
        try:
            def run(r):
                pods[r] = self._pod(ep, 2, r).init()

            ts = [threading.Thread(target=run, args=(r,)) for r in (0, 1)]
            [t.start() for t in ts]
            [t.join(30) for t in ts]
            coord.mark_failed(1, "killed by SIGKILL (supervisor)")
            with pytest.raises(RankFailedError):
                pods[0].barrier("b", timeout=10.0)
            assert pods[0].reform(timeout=10.0) == {
                "gen": 1, "rank": 0, "world_size": 1}
            assert pods[0].pending_joiners() == []

            # the replacement joins: parked, NOT a member yet, and the
            # survivor's generation does not move
            def join_rep():
                rep["pod"] = self._pod(ep, 2, 1,
                                       join_timeout=30.0).init()

            t = threading.Thread(target=join_rep)
            t.start()
            deadline = time.time() + 10
            while pods[0].pending_joiners() != [1] \
                    and time.time() < deadline:
                time.sleep(0.05)
            assert pods[0].pending_joiners() == [1]
            assert pods[0].gen == 1 and pods[0].world_size == 1
            assert coord.state()["members"] == {0: coord.state()
                                                ["members"][0]}

            # reform-up: the survivor keeps rank 0 (committer stays an
            # incumbent), the joiner appends as rank 1, world grows
            view = pods[0].reform(timeout=10.0)
            t.join(15)
            assert view == {"gen": 2, "rank": 0, "world_size": 2}
            assert (rep["pod"].rank, rep["pod"].world_size,
                    rep["pod"].gen) == (1, 2, 2)
            assert rep["pod"].uid == coord.uid
            assert pods[0].pending_joiners() == []

            out = {}

            def ar(p, r):
                out[r] = p.allreduce(np.full(3, float(r + 1)),
                                     name="healed", timeout=10.0)

            ts = [threading.Thread(target=ar, args=(pods[0], 0)),
                  threading.Thread(target=ar, args=(rep["pod"], 1))]
            [t.start() for t in ts]
            [t.join(15) for t in ts]
            np.testing.assert_array_equal(out[0], np.full(3, 3.0))
            np.testing.assert_array_equal(out[1], np.full(3, 3.0))
            # the shrunk generation is history: its ops are rejected
            resp = coord.handle_req({"op": "barrier", "rank": 0,
                                     "gen": 1, "name": "x",
                                     "timeout": 1.0})
            assert resp == {"ok": False, "error": "stale_gen", "gen": 2}
        finally:
            for p in list(pods.values()) + list(rep.values()):
                p.shutdown()
            coord.close()

    def test_net_new_rank_scales_out_beyond_original_world(self):
        """The lobby is not only for replacements: a NET-NEW origin
        joining a healthy formed pod is admitted at the next reform and
        the world grows past the launch size (scale-out)."""
        coord, ep = start_coordinator(expected=2, lease_ttl=30.0)
        pods, new = {}, {}
        try:
            def run(r):
                pods[r] = self._pod(ep, 2, r).init()

            ts = [threading.Thread(target=run, args=(r,)) for r in (0, 1)]
            [t.start() for t in ts]
            [t.join(30) for t in ts]

            def join_new():
                new["pod"] = self._pod(ep, 2, 7,
                                       join_timeout=30.0).init()

            t = threading.Thread(target=join_new)
            t.start()
            deadline = time.time() + 10
            while pods[0].pending_joiners() != [7] \
                    and time.time() < deadline:
                time.sleep(0.05)
            views = {}

            def ref(r):
                views[r] = pods[r].reform(timeout=10.0)

            ts = [threading.Thread(target=ref, args=(r,)) for r in (0, 1)]
            [t.start() for t in ts]
            [t.join(30) for t in ts]
            t.join(15)
            assert views[0]["world_size"] == views[1]["world_size"] == 3
            assert (new["pod"].rank, new["pod"].world_size,
                    new["pod"].gen) == (2, 3, 1)
            # data re-shards over the grown world
            assert new["pod"].shard_range(9) == (6, 9)
        finally:
            for p in list(pods.values()) + list(new.values()):
                p.shutdown()
            coord.close()

    def test_replacement_joining_before_reform_parks_not_bounces(self):
        """The race the supervisor creates on every fast respawn: the
        dead rank is marked failed but the survivors have NOT reformed
        yet (mid-step), so its origin still sits in the roster. The
        replacement's join must PARK in the lobby (a failed member no
        longer owns its origin) — bouncing it as duplicate_origin would
        burn one RestartPolicy attempt per incarnation until the budget
        dies and the pod stays degraded forever. A single reform then
        does shrink+grow in one transition: dead rank out, replacement
        in, world size preserved."""
        coord, ep = start_coordinator(expected=2, lease_ttl=30.0)
        pods, rep = {}, {}
        try:
            def run(r):
                pods[r] = self._pod(ep, 2, r).init()

            ts = [threading.Thread(target=run, args=(r,)) for r in (0, 1)]
            [t.start() for t in ts]
            [t.join(30) for t in ts]
            coord.mark_failed(1, "killed by SIGKILL (supervisor)")
            # NO reform yet — the dead rank is still in the roster

            def join_rep():
                rep["pod"] = self._pod(ep, 2, 1,
                                       join_timeout=30.0).init()

            t = threading.Thread(target=join_rep)
            t.start()
            deadline = time.time() + 10
            while pods[0].pending_joiners() != [1] \
                    and time.time() < deadline:
                time.sleep(0.05)
            assert pods[0].pending_joiners() == [1]  # parked, not bounced
            # the survivor learns of the death within a heartbeat
            with pytest.raises(RankFailedError):
                while time.time() < deadline:
                    pods[0].check_failures()
                    time.sleep(0.05)
                raise AssertionError("failure never surfaced")
            view = pods[0].reform(timeout=10.0)
            t.join(15)
            assert view == {"gen": 1, "rank": 0, "world_size": 2}
            assert (rep["pod"].rank, rep["pod"].world_size,
                    rep["pod"].gen) == (1, 2, 1)
        finally:
            for p in list(pods.values()) + list(rep.values()):
                p.shutdown()
            coord.close()

    def test_duplicate_origin_rejected_from_lobby(self):
        """A live origin cannot be shadowed by a lobby joiner — only a
        REPLACEMENT (predecessor marked failed) may reuse the id."""
        coord, ep = start_coordinator(expected=2, lease_ttl=30.0)
        pods = {}
        try:
            def run(r):
                pods[r] = self._pod(ep, 2, r).init()

            ts = [threading.Thread(target=run, args=(r,)) for r in (0, 1)]
            [t.start() for t in ts]
            [t.join(30) for t in ts]
            from paddle_tpu.distributed.pod import PodError
            with pytest.raises(PodError, match="duplicate_origin"):
                self._pod(ep, 2, 1, join_timeout=5.0).init()
        finally:
            for p in pods.values():
                p.shutdown()
            coord.close()

    def test_straggler_detection_before_failure(self, tmp_path):
        """A slow-but-alive rank (heartbeat gap past the straggler
        threshold but under the lease ttl) surfaces in stragglers(),
        heartbeat_stats percentiles, pod_rank_heartbeat_ms gauges, and
        an edge-triggered pod_straggler run-log event — BEFORE it ever
        becomes a failure."""
        from paddle_tpu.observability import export, runlog
        log_path = str(tmp_path / "sup.jsonl")
        runlog.start_run(path=log_path, rank=0, run_id="strag")
        coord = PodCoordinator(("127.0.0.1", 0), expected=2,
                               lease_ttl=30.0, monitor_interval=0.1,
                               straggler_threshold=0.3)
        serve = threading.Thread(target=coord.serve_forever, daemon=True)
        serve.start()
        ep = coord.endpoint
        pods = {}
        try:
            def run(r, hb):
                pods[r] = PodRuntime(ep, 2, r, heartbeat_interval=hb,
                                     barrier_timeout=10.0).init()

            ts = [threading.Thread(target=run, args=(0, 0.05)),
                  threading.Thread(target=run, args=(1, 1.2))]
            [t.start() for t in ts]
            [t.join(30) for t in ts]
            # rank 1 beats every 1.2s: its gap spends most of its time
            # past the 0.3s threshold; rank 0 (50ms) never does
            deadline = time.time() + 10
            seen = set()
            while time.time() < deadline:
                seen.update(coord.stragglers())
                if 1 in seen:
                    break
                time.sleep(0.05)
            assert 1 in seen and 0 not in seen
            # the runtime-side query agrees
            assert pods[0].stragglers(threshold=0.3) in ([], [1])
            # gap HISTORY needs rank 1's first (late) heartbeat to land
            stats = coord.heartbeat_stats()
            while "max_ms" not in stats.get(1, {}) \
                    and time.time() < deadline:
                time.sleep(0.05)
                stats = coord.heartbeat_stats()
            assert stats[1]["max_ms"] > 300 > stats[0]["p95_ms"]
            gauges = export.gauges()
            assert any(k.startswith('pod_rank_heartbeat_ms{rank="1"')
                       for k in gauges), sorted(gauges)
            # the lease never expired: no failure, only the warning
            assert coord.state()["failed"] == {}
        finally:
            for p in pods.values():
                p.shutdown()
            coord.close()
            runlog.stop_run()
        with open(log_path) as f:
            events = [json.loads(line) for line in f]
        strag = [e for e in events if e.get("event") == "pod_straggler"]
        assert strag and strag[0]["origin"] == 1
        assert strag[0]["gap_ms"] > 300
        # edge-triggered: at most one event per 1.2s heartbeat episode,
        # NOT one per 0.1s monitor sweep (the sweeps outnumber the
        # episodes ~12:1 — an un-edge-triggered emitter would spam)
        assert len(strag) <= 8


# ------------------------------------------------------- pod checkpointing

class TestPodCheckpoint:
    """Per-rank shard files + rank-0 manifest commit + elastic merge,
    in-process (the subprocess path is covered by the e2e below)."""

    def _train_one(self):
        import paddle_tpu as paddle
        from paddle_tpu import nn
        paddle.seed(3)
        m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
        opt = paddle.optimizer.Momentum(parameters=m.parameters(),
                                        learning_rate=0.05, momentum=0.9)
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.rand(4, 8).astype("float32"))
        y = paddle.to_tensor(rng.rand(4, 1).astype("float32"))
        loss = nn.functional.mse_loss(m(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return m, opt, (x, y)

    def _save_world2(self, root, m, opt, timeout=60.0):
        from paddle_tpu.checkpoint.multihost import PodCheckpointManager
        errs = []

        def save(r):
            try:
                PodCheckpointManager(root, rank=r, world=2,
                                     timeout=timeout).add_model(
                    m).add_optimizer(opt).save(1)
            except Exception as e:  # surfaced by the caller
                errs.append(e)

        t = threading.Thread(target=save, args=(1,))
        t.start()
        save(0)
        t.join(30)
        return errs

    def test_entry_sharded_roundtrip_is_bitwise(self, tmp_path):
        import paddle_tpu as paddle
        from paddle_tpu import nn
        from paddle_tpu.checkpoint import core as ckpt_core
        from paddle_tpu.checkpoint.multihost import (PodCheckpointManager,
                                                     split_pod_payloads)
        root = str(tmp_path)
        m, opt, _ = self._train_one()
        assert self._save_world2(root, m, opt) == []
        ref = [np.asarray(p._value).copy() for p in m.parameters()]

        # the manifest (rank-0 commit) covers BOTH ranks' shard files,
        # and each rank's payload really is a partial shard
        step, payloads, meta = ckpt_core.read_checkpoint(root)
        by_rank = split_pod_payloads(payloads)
        assert sorted(by_rank) == [0, 1]
        assert meta["pod"]["world"] == 2

        # fresh objects at a different seed + SMALLER world: restore
        # merges every rank's shards from the shared filesystem
        paddle.seed(99)
        m2 = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
        opt2 = paddle.optimizer.Momentum(parameters=m2.parameters(),
                                         learning_rate=0.05, momentum=0.9)
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.rand(4, 8).astype("float32"))
        y = paddle.to_tensor(rng.rand(4, 1).astype("float32"))
        loss = nn.functional.mse_loss(m2(x), y)
        loss.backward()
        opt2.step()
        opt2.clear_grad()
        got = PodCheckpointManager(root, rank=0, world=1).add_model(
            m2).add_optimizer(opt2).restore()
        assert got is not None and got["step"] == 1
        for p, want in zip(m2.parameters(), ref):
            np.testing.assert_array_equal(np.asarray(p._value), want)

    @pytest.mark.chaos
    def test_kill_before_commit_never_leaves_torn_checkpoint(self,
                                                             tmp_path):
        """Both ranks' shards written, committer killed BEFORE the
        manifest: restore must see NOTHING (or the previous step), never
        a half-checkpoint; a later re-save of the same step succeeds."""
        from paddle_tpu.checkpoint import core as ckpt_core
        from paddle_tpu.checkpoint.multihost import PodCheckpointError
        root = str(tmp_path)
        m, opt, _ = self._train_one()
        faults.inject("checkpoint/pod_before_commit",
                      exc=PodCheckpointError)
        errs = self._save_world2(root, m, opt, timeout=3.0)
        faults.clear()
        # committer died at the kill-point; the non-committer timed out
        # waiting for a publish that never came — both LOUD
        assert len(errs) == 2
        assert ckpt_core.read_checkpoint(root) is None
        assert ckpt_core.valid_steps(root) == []
        # the pod staging debris does not block a successful retry
        assert self._save_world2(root, m, opt) == []
        assert ckpt_core.valid_steps(root) == [1]

    def test_missing_rank_shard_fails_loudly(self, tmp_path):
        from paddle_tpu.checkpoint import multihost, state
        rec = {"state": {f"p{i}": np.full((2,), i, np.float32)
                         for i in range(5)}, "zero3_params": []}
        parts = [multihost.partition_model(rec, r, 2) for r in (0, 1)]
        merged = multihost.merge_model(parts)
        assert sorted(merged["state"]) == sorted(rec["state"])
        with pytest.raises(state.StateMismatchError, match="missing"):
            multihost.merge_model(parts[:1])  # rank 1's file absent

    def test_zero_store_reflatten_across_rank_files(self, tmp_path):
        """The PR-7 elastic path across the process boundary: a ZeRO
        optimizer's flat stores saved as TWO ranks' row-slices restore
        into a DIFFERENT in-process dp degree bitwise (shards list ->
        state._restore_store concat -> re-pad -> re-place)."""
        import gc

        import jax

        import paddle_tpu as paddle
        from paddle_tpu import nn
        from paddle_tpu.checkpoint import state
        from paddle_tpu.checkpoint.multihost import PodCheckpointManager
        from paddle_tpu.distributed import parallel_env
        root = str(tmp_path)
        K = 2
        rngd = np.random.RandomState(7)
        X = rngd.rand(K, 16, 16).astype("float32")
        Y = rngd.randint(0, 8, (K, 16)).astype("int64")

        def build(dp, seed):
            mesh = parallel_env.make_mesh({"dp": dp},
                                          devices=jax.devices()[:dp])
            parallel_env.set_mesh(mesh)
            paddle.seed(seed)
            m = nn.Sequential(nn.Linear(16, 32), nn.ReLU(),
                              nn.Linear(32, 8))
            opt = paddle.optimizer.AdamW(parameters=m.parameters(),
                                         learning_rate=0.05)
            opt._zero_enable(axis="dp", stage=1)
            return m, opt

        def store_rows(opt):
            out = {}
            for zb, sdict in zip(opt._zero["buckets"],
                                 opt._zero["stores"]):
                for slot, store in sdict.items():
                    sh, _ = state._store_shards(store)
                    full = (np.concatenate(sh, 0) if len(sh) > 1
                            else sh[0])
                    out[(zb.index, slot)] = (
                        full[:zb.rows - zb.pad_rows].copy())
            return out

        try:
            m, opt = build(8, seed=11)

            def one(xb, yb):
                loss = nn.functional.cross_entropy(m(xb), yb)
                loss.backward()
                opt.step()
                opt.clear_grad()
                return loss

            stepf = paddle.jit.to_static(one, scan_steps=K, dp_axis="dp")
            stepf(paddle.to_tensor(X), paddle.to_tensor(Y))
            ref = store_rows(opt)
            errs = []

            def save(r):
                try:
                    PodCheckpointManager(root, rank=r, world=2,
                                         timeout=60.0).add_model(
                        m).add_optimizer(opt).save(5)
                except Exception as e:
                    errs.append(e)

            t = threading.Thread(target=save, args=(1,))
            t.start()
            save(0)
            t.join(60)
            assert errs == []
            del stepf, m, opt
            gc.collect()
            parallel_env.set_mesh(None)

            m2, opt2 = build(4, seed=55)  # ELASTIC: dp8 -> dp4
            meta = PodCheckpointManager(root, rank=0, world=1).add_model(
                m2).add_optimizer(opt2).restore()
            assert meta is not None and meta["step"] == 5
            got = store_rows(opt2)
            assert sorted(got) == sorted(ref)
            for key in ref:
                np.testing.assert_array_equal(got[key], ref[key])
        finally:
            parallel_env.set_mesh(None)
            gc.collect()


# ----------------------------------------------------- process kill-points

def test_process_kill_point_sigkills_this_rank(tmp_path):
    """The cross-process analog of faults.inject: the armed rank
    SIGKILLs itself at the named point's nth hit — uncatchable, leaving
    only the flushed run-log event behind."""
    code = (
        "from paddle_tpu.testing import faults\n"
        "import paddle_tpu.observability as obs\n"
        "obs.start_run(dir=%r, rank=3)\n"
        "faults.kill_point('demo/point')\n"
        "faults.kill_point('demo/point')\n"
        "print('UNREACHABLE')\n" % str(tmp_path))
    env = {**os.environ, "PADDLE_TPU_PROCESS_KILL": "demo/point@3#2",
           "PADDLE_TRAINER_ID": "3", "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=120, cwd=REPO)
    assert r.returncode == -signal.SIGKILL, (r.returncode, r.stderr[-500:])
    assert "UNREACHABLE" not in r.stdout
    logs = [f for f in os.listdir(tmp_path) if f.endswith(".jsonl")]
    assert len(logs) == 1
    with open(os.path.join(tmp_path, logs[0])) as f:
        recs = [json.loads(line) for line in f]
    kills = [rec for rec in recs if rec.get("event") == "process_kill"]
    assert kills and kills[0]["point"] == "demo/point" \
        and kills[0]["rank"] == "3"


def test_process_kill_other_rank_spec_is_inert(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PROCESS_KILL", "demo/p@7#1")
    monkeypatch.setenv("PADDLE_TRAINER_ID", "0")
    faults.reset()  # re-read env
    assert faults.process_kills() == {}
    faults.kill_point("demo/p")  # must not kill the test process
    assert faults.hits("demo/p") >= 1


# -------------------------------------------------------------- satellites

def _spawn_suicide_worker(arg):
    """Module-level for pickling: rank 1 SIGKILLs itself, rank 0 would
    wait forever on a join-like sleep."""
    import os as _os
    import signal as _sig
    import time as _time
    if _os.environ.get("PADDLE_TRAINER_ID") == "1":
        _os.kill(_os.getpid(), _sig.SIGKILL)
    _time.sleep(120)  # the survivor "hangs" on the dead peer
    return arg


def test_spawn_join_reaps_signal_death_quickly():
    """spawn()._Context.join must reap-and-raise (naming the signal)
    when a child dies by signal instead of hanging out the full
    timeout while the survivors deadlock."""
    from paddle_tpu.distributed.spawn import spawn
    t0 = time.time()
    with pytest.raises(RuntimeError, match="SIGKILL"):
        spawn(_spawn_suicide_worker, args=(1,), nprocs=2, backend="cpu",
              timeout=300)
    took = time.time() - t0
    assert took < 60, f"join took {took:.0f}s — it hung instead of reaping"


@pytest.mark.parametrize("who_dies", ["one_rank", "every_rank"])
def test_child_death_before_join_fails_pod_at_once(tmp_path, who_dies):
    """A rank that dies at import or build never joins. The pod must
    fail NOW, with the child's stderr tail — not after its peers have
    sat out a join or lease timeout — and leave nothing running."""
    from paddle_tpu.distributed.pod import PodError
    script = tmp_path / "broken_rank.py"
    script.write_text(
        "import os, time\n"
        f"if {who_dies == 'every_rank'} or "
        "os.environ['PADDLE_TRAINER_ID'] == '1':\n"
        "    raise ImportError('No module named broken_dependency')\n"
        "time.sleep(120)  # a healthy rank, still importing\n")
    pod = VirtualPod(2, str(script), workdir=str(tmp_path / "wd"))
    t0 = time.time()
    with pytest.raises(PodError, match="died before joining") as ei:
        pod.run(timeout=120)
    took = time.time() - t0
    assert took < 20, f"took {took:.0f}s — it waited out a timeout"
    assert "broken_dependency" in str(ei.value)
    assert all(tp.proc.poll() is not None for tp in pod._procs)


def test_watch_local_trainers_grace_lets_sigterm_hook_run(tmp_path):
    """On a trainer death the launcher tears the pod down with SIGTERM +
    grace before SIGKILL — a survivor's SIGTERM hook (the flight
    recorder's dump path) gets to run; the error names the death."""
    from paddle_tpu.distributed import launch
    victim = tmp_path / "victim.py"
    victim.write_text("import os, signal\n"
                      "os.kill(os.getpid(), signal.SIGKILL)\n")
    survivor = tmp_path / "survivor.py"
    survivor.write_text(
        "import os, signal, sys, time\n"
        "def h(sig, frame):\n"
        "    open(os.environ['TERM_PROOF'], 'w').write('dumped')\n"
        "    sys.exit(0)\n"
        "signal.signal(signal.SIGTERM, h)\n"
        "open(os.environ['READY_PROOF'], 'w').write('up')\n"
        "time.sleep(120)\n")
    proof = tmp_path / "term_proof"
    ready = tmp_path / "ready_proof"
    eps = ["127.0.0.1:6470", "127.0.0.1:6471"]
    cluster = launch.get_cluster(["127.0.0.1"], "127.0.0.1", eps, 2)
    # rank 0 runs the survivor script, rank 1 the victim
    wrapper = tmp_path / "main.py"
    wrapper.write_text(
        "import os, runpy\n"
        "r = os.environ['PADDLE_TRAINER_ID']\n"
        "runpy.run_path(%r if r == '0' else %r, run_name='__main__')\n"
        % (str(survivor), str(victim)))
    procs = launch.start_local_trainers(
        cluster, cluster.pods[0], str(wrapper), [],
        envs={"TERM_PROOF": str(proof), "READY_PROOF": str(ready)})
    deadline = time.time() + 30
    while not ready.exists() and time.time() < deadline:
        time.sleep(0.05)
    with pytest.raises(RuntimeError, match="died by signal SIGKILL"):
        while time.time() < deadline:
            procs = launch.watch_local_trainers(procs, grace_s=10.0)
            if not procs:
                break
            time.sleep(0.1)
    assert proof.exists(), \
        "SIGTERM hook never ran — teardown skipped the grace period"


def test_barrier_without_timeout_lint_rule(tmp_path):
    from paddle_tpu.analysis import lint_source
    bad = tmp_path / "bad.py"
    bad.write_text(
        "def sync(pod, client, n):\n"
        "    pod.barrier('step')\n"          # bare -> warning
        "    client.barrier(n)\n"            # bare -> warning
        "    pod.barrier('b', timeout=30)\n"        # kwarg evidence
        "    d = 5.0\n"
        "    deadline = d\n"
        "    client.barrier(n, deadline)\n"  # deadline-named arg\n
    )
    found = [f for f in lint_source(paths=[str(bad)])
             if f.rule == "barrier-without-timeout"]
    assert len(found) == 2
    assert all(f.severity == "warning" for f in found)
    assert {f.loc.rsplit(":", 1)[1] for f in found} == {"2", "3"}
    # the default sweep covers distributed/ and stays clean (the PS
    # barrier call sites carry explicit timeouts now)
    assert [f for f in lint_source()
            if f.rule == "barrier-without-timeout"] == []


class TestRestartPolicy:
    """The shared budgeted-backoff policy (distributed/restart.py) —
    the pod supervisor's respawn pacing and fleet/elastic.py's relaunch
    pacing are this one object."""

    def test_budget_bounds_and_reset_reopens(self):
        p = RestartPolicy(max_restarts=3, base_delay=0.1, jitter=0.0)
        delays = [p.schedule("r1") for _ in range(5)]
        assert all(d is not None for d in delays[:3])
        assert delays[3] is None and delays[4] is None
        assert p.attempts("r1") == 3
        # keys are independent budgets
        assert p.schedule("r2") is not None
        p.reset("r1")
        assert p.schedule("r1") is not None

    def test_exponential_backoff_capped(self):
        p = RestartPolicy(max_restarts=6, base_delay=0.2, factor=2.0,
                          max_delay=1.0, jitter=0.0)
        got = [p.schedule("k") for _ in range(5)]
        assert got == [0.2, 0.4, 0.8, 1.0, 1.0]

    def test_jitter_is_seeded_and_bounded(self):
        a = [RestartPolicy(max_restarts=4, base_delay=1.0, jitter=0.25,
                           seed=7).schedule("k") for _ in range(1)]
        b = RestartPolicy(max_restarts=4, base_delay=1.0, jitter=0.25,
                          seed=7)
        c = RestartPolicy(max_restarts=4, base_delay=1.0, jitter=0.25,
                          seed=8)
        assert a[0] == b.schedule("k")          # same seed replays
        assert b.schedule("k") != c.schedule("k")
        assert 0.75 <= a[0] <= 1.25             # symmetric, bounded

    def test_sliding_window_ages_out_attempts(self):
        p = RestartPolicy(max_restarts=2, base_delay=0.1, jitter=0.0,
                          window_s=10.0)
        assert p.schedule("k", now=0.0) is not None
        assert p.schedule("k", now=1.0) is not None
        assert p.schedule("k", now=5.0) is None     # budget spent
        assert p.schedule("k", now=20.0) is not None  # window aged out


class _FakeProc:
    def __init__(self, rc_script):
        self._rc = rc_script  # callable() -> poll value
        self.terminated = False

    def poll(self):
        return self._rc()

    def terminate(self):
        self.terminated = True


def test_elastic_relaunch_shares_restart_policy(tmp_path):
    """fleet/elastic.py's KV-relaunch path (the reference's
    watch->restart loop) paces itself through the SAME RestartPolicy
    the pod supervisor uses: a dead child is relaunched after backoff,
    a clean exit under stable membership completes, and an exhausted
    budget EXITS instead of crash-looping."""
    from paddle_tpu.distributed.fleet.elastic import (ElasticManager,
                                                      ElasticStatus,
                                                      FileKVStore)
    store = FileKVStore(str(tmp_path))
    mgr = ElasticManager("n1:1", np=1, job_id="j", store=store, ttl=30,
                         heartbeat_interval=0.2)
    mgr.register()
    try:
        spawned = []

        def spawn_dies_then_completes():
            rc = (lambda: 1) if not spawned else (lambda: 0)
            proc = _FakeProc(rc)
            spawned.append(proc)
            return proc

        policy = RestartPolicy(max_restarts=2, base_delay=0.01,
                               jitter=0.0, seed=0)
        status, proc = mgr.relaunch(spawn_dies_then_completes,
                                    policy=policy, watch_interval=0.05)
        assert status == ElasticStatus.COMPLETED
        assert len(spawned) == 2 and proc is spawned[1]
        assert policy.attempts(mgr.endpoint) == 1

        # budget exhaustion: every child dies -> EXIT, bounded spawns
        spawned.clear()

        def spawn_always_dies():
            proc = _FakeProc(lambda: 1)
            spawned.append(proc)
            return proc

        status, proc = mgr.relaunch(
            spawn_always_dies,
            policy=RestartPolicy(max_restarts=2, base_delay=0.01,
                                 jitter=0.0),
            watch_interval=0.05)
        assert status == ElasticStatus.EXIT and proc is None
        assert len(spawned) == 3  # initial + exactly max_restarts
    finally:
        mgr.exit()


def test_pod_failure_triggers_flight_dump(tmp_path):
    """Satellite: RankFailedError and BarrierTimeoutError each leave an
    atomic flight dump (reason="pod_failure") naming the dead/absent
    origin ranks BEFORE any reform — the post-mortem exists even though
    the survivor recovers and keeps running."""
    from paddle_tpu.observability import flight
    flight.install(str(tmp_path))
    coord, ep = start_coordinator(expected=2, lease_ttl=30.0)
    pods = {}
    try:
        def run(r):
            pods[r] = PodRuntime(ep, 2, r, heartbeat_interval=0.1,
                                 barrier_timeout=10.0).init()

        ts = [threading.Thread(target=run, args=(r,)) for r in (0, 1)]
        [t.start() for t in ts]
        [t.join(30) for t in ts]

        # absent rank -> BarrierTimeoutError dump
        with pytest.raises(BarrierTimeoutError):
            pods[0].barrier("never", timeout=0.5)
        with open(flight.latest_dump()) as f:
            dump = json.load(f)
        assert dump["reason"] == "pod_failure"
        assert dump["pod_failure"]["absent_ranks"] == [1]
        assert dump["pod_failure"]["op"] == "never"
        assert dump["exception"]["type"] == "BarrierTimeoutError"

        # dead rank -> RankFailedError dump
        coord.mark_failed(1, "killed by SIGKILL (supervisor)")
        with pytest.raises(RankFailedError):
            pods[0].barrier("b", timeout=10.0)
        with open(flight.latest_dump()) as f:
            dump = json.load(f)
        assert dump["reason"] == "pod_failure"
        assert dump["pod_failure"]["failed_ranks"] == [1]
        assert dump["pod_failure"]["gen"] == 0
        # the survivor reforms and keeps running — the dump persists
        assert pods[0].reform(timeout=10.0)["world_size"] == 1
    finally:
        flight.uninstall()
        for p in pods.values():
            p.shutdown()
        coord.close()


def test_respawn_without_backoff_lint_rule(tmp_path):
    from paddle_tpu.analysis import lint_source
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import subprocess, time\n"
        "def keep_alive(cmd):\n"
        "    while True:\n"                       # unpaced keep-alive
        "        proc = subprocess.Popen(cmd)\n"
        "        proc.wait()\n"
        "def bounded(spawn_fn):\n"
        "    for _ in range(5):\n"                # bounded but unpaced
        "        try:\n"
        "            spawn_fn()\n"
        "        except OSError:\n"
        "            pass\n"
        "def fanout(trainers, spawn_trainer):\n"
        "    for t in trainers:\n"                # one spawn per item
        "        spawn_trainer(t)\n"
        "def good(policy, spawn_fn):\n"
        "    while True:\n"
        "        delay = policy.schedule('k')\n"
        "        if delay is None:\n"
        "            return\n"
        "        time.sleep(delay)\n"
        "        spawn_fn()\n")
    found = [f for f in lint_source(paths=[str(bad)])
             if f.rule == "respawn-without-backoff"]
    assert len(found) == 2
    assert all(f.severity == "error" for f in found)
    assert {f.loc.rsplit(":", 1)[1] for f in found} == {"3", "7"}
    # the default sweep (distributed/ + fleet/elastic.py + the
    # supervisor) is clean: every real respawn loop rides RestartPolicy
    assert [f for f in lint_source()
            if f.rule == "respawn-without-backoff"] == []


def test_trace_view_reform_timeline(tmp_path):
    """Satellite: pod_reform events (direction, worlds, gen) from every
    rank's run-log collapse into one ordered reform timeline in
    trace_view --stats."""
    from paddle_tpu.observability import runlog
    import trace_view

    paths = []
    for r in (0, 1):
        p = str(tmp_path / f"pod.rank{r}.jsonl")
        runlog.start_run(path=p, rank=r, run_id="heal")
        runlog.event("pod_reform", rank=0 if r == 0 else 1, world=1,
                     gen=1, direction="shrink", old_world=2, new_world=1,
                     took_s=0.21)
        if r == 0:
            runlog.event("pod_reform", rank=0, world=2, gen=2,
                         direction="grow", old_world=1, new_world=2,
                         took_s=0.35)
        runlog.stop_run()
        paths.append(p)
    events, n_bad = trace_view.load_events(paths)
    assert n_bad == 0
    timeline = trace_view.reform_timeline(events)
    assert [(e["gen"], e["direction"], e["old_world"], e["new_world"])
            for e in timeline] == [(1, "shrink", 2, 1), (2, "grow", 1, 2)]
    assert timeline[1]["took_s"] == 0.35
    buf = io.StringIO()
    trace_view.print_stats(events, n_bad, file=buf)
    out = buf.getvalue()
    assert "reform timeline:" in out
    assert re.search(r"gen 1: shrink\s+world 2->1", out)
    assert re.search(r"gen 2: grow\s+world 1->2", out)


def test_trace_view_stats_sums_ledger_across_ranks(tmp_path):
    """Satellite: per-rank state-ledger snapshots in each rank's runlog
    sum into a pod-wide residency line in trace_view --stats."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.observability import memory, runlog
    import trace_view

    paddle.seed(0)
    _model = nn.Linear(16, 8)  # some resident state to ledger
    paths = []
    for r in (0, 1):
        p = str(tmp_path / f"pod.rank{r}.jsonl")
        runlog.start_run(path=p, rank=r, run_id="podrun")
        memory.runlog_snapshot(rank=r)
        runlog.stop_run()
        paths.append(p)
    events, n_bad = trace_view.load_events(paths)
    assert n_bad == 0
    cats, n_ranks = trace_view.state_residency(events)
    assert n_ranks == 2
    # both ranks ledger the same process state here: exact 2x one rank
    one = memory.state_ledger()["categories"]["param"]["bytes"]
    assert cats["param"] == 2 * one
    buf = io.StringIO()
    trace_view.print_stats(events, n_bad, file=buf)
    out = buf.getvalue()
    assert "state residency" in out and "summed over 2 rank(s)" in out


# ------------------------------------------------------------- end to end

_CONTROL = {}


def _losses_by_step(text):
    """{step: loss} keeping the LAST occurrence (post-restore re-runs
    supersede pre-crash prints)."""
    out = {}
    for m in re.finditer(r"LOSS (\d+) ([\d.eE+-]+)", text):
        out[int(m.group(1))] = float(m.group(2))
    return out


def _control_losses(tmp_factory):
    """Single-process control of the SAME fixture (one pod rank, no
    kill), cached for the session."""
    if "losses" not in _CONTROL:
        wd = str(tmp_factory.mktemp("pod_control"))
        pod = VirtualPod(1, FIXTURE, workdir=wd,
                         env={"POD_FIX_CKPT_ROOT": os.path.join(wd, "ck")})
        exits = pod.run(timeout=150)
        assert exits[0].returncode == 0, pod.tail_logs()
        _CONTROL["losses"] = _losses_by_step(pod.log(0))
        assert len(_CONTROL["losses"]) == 8
    return _CONTROL["losses"]


def _assert_no_torn_checkpoint(root):
    """Every published step dir must fully validate; staging debris is
    allowed (restore never reads it), torn manifests are not."""
    from paddle_tpu.checkpoint import core as ckpt_core
    steps = [int(m.group(1)) for name in os.listdir(root)
             for m in [re.match(r"^step_(\d+)$", name)] if m]
    for s in steps:
        got = ckpt_core.read_checkpoint(root, step=s)
        assert got is not None, f"step {s} published but torn"


LEASE_TTL = 2.0


def test_pod_sigkill_midstep_elastic_recovery(tmp_path_factory):
    """THE acceptance run: 2 real processes, rank 1 SIGKILLed mid-step
    (step 4, after the step-2 checkpoint), PS pulls crossing the
    process boundary; detection within the window, reform to world 1,
    elastic restore, losses within 1e-6 of control, merged trace with
    the dead rank's track."""
    import jax

    import trace_view
    from paddle_tpu.distributed.ps import PsServer, TableConfig
    jax.config.update("jax_platforms", "cpu")

    control = _control_losses(tmp_path_factory)
    wd = str(tmp_path_factory.mktemp("pod_e2e"))
    root = os.path.join(wd, "ck")
    srv = PsServer([TableConfig(0, "dense", 4)], port=0)
    ps_port = srv.start()
    try:
        pod = VirtualPod(2, FIXTURE, workdir=wd,
                         kill=(1, "pod/mid_step", 5),
                         lease_ttl=LEASE_TTL,
                         env={"POD_FIX_CKPT_ROOT": root,
                              "POD_FIX_PS_ENDPOINT":
                                  f"127.0.0.1:{ps_port}"})
        exits = pod.run(timeout=180)
    finally:
        srv.stop()

    # the kill was real and the survivor finished
    assert exits[1].signal == "SIGKILL", exits
    assert exits[0].returncode == 0, pod.tail_logs()
    log0, log1 = pod.log(0), pod.log(1)

    # cross-process PS demo ran on BOTH ranks
    assert "PS_OK rank=0 n=4" in log0 and "PS_OK rank=1 n=4" in log1

    # detection: named, and within the configured window of the death
    m = re.search(r"FAILURE_DETECTED t=([\d.]+) failed=\[1\] "
                  r"err=(RankFailedError|BarrierTimeoutError)", log0)
    assert m, log0
    detect_delay = float(m.group(1)) - exits[1].t_reaped
    assert detect_delay < LEASE_TTL + 2.0, \
        f"detected {detect_delay:.2f}s after the reap (window {LEASE_TTL}s)"

    # elastic recovery: world shrank, restore resumed from the step-2
    # checkpoint (not from scratch)
    assert "REFORMED rank=0 world=1 gen=1" in log0
    assert re.search(r"RESUME_FROM 3\b", log0)
    assert "DONE rank=0 world=1" in log0
    assert "DONE" not in log1  # the victim never finished

    # losses: every step within 1e-6 of the single-process control —
    # before the kill (dp split across processes) AND after recovery
    got = _losses_by_step(log0)
    assert sorted(got) == sorted(control)
    for s in sorted(control):
        assert abs(got[s] - control[s]) < 1e-6, \
            (s, got[s], control[s])

    # the published checkpoints all validate — no torn manifest
    _assert_no_torn_checkpoint(root)

    # trace merge: every rank's run-log (the DEAD one included) lands
    # on its own process track; the kill left its runlog evidence
    paths = pod.runlog_paths()
    assert len(paths) == 2
    events, _ = trace_view.load_events(paths)
    trace = trace_view.build_chrome_trace(events)
    tracks = {e["args"]["name"] for e in trace["traceEvents"]
              if e.get("ph") == "M"}
    assert len(tracks) == 2 and any("rank1" in t for t in tracks), tracks
    ev_names = {e.get("event") for e in events if e.get("kind") == "event"}
    assert {"process_kill", "pod_reform", "checkpoint_publish",
            "checkpoint_restore"} <= ev_names
    # per-rank ledger snapshots summed in --stats (satellite 1)
    cats, n_ranks = trace_view.state_residency(events)
    assert n_ranks == 2 and cats.get("param", 0) > 0


@pytest.mark.chaos
@pytest.mark.parametrize("victim,point,nth", [
    (0, "pod/before_barrier", 4),
    (1, "checkpoint/pod_shard_written", 2),
])
def test_pod_kill_sweep_2proc(tmp_path, victim, point, nth):
    """Tier-1 chaos subset: SIGKILL each rank id at the remaining named
    points (mid_step rides the acceptance test above) — detection +
    re-formation + elastic restore + no torn checkpoint. The committer
    (rank 0) dying during a checkpoint is the hard case: the survivor
    re-ranks to 0 and becomes the committer."""
    root = os.path.join(str(tmp_path), "ck")
    pod = VirtualPod(2, FIXTURE, workdir=str(tmp_path),
                     kill=(victim, point, nth), lease_ttl=LEASE_TTL,
                     env={"POD_FIX_CKPT_ROOT": root})
    exits = pod.run(timeout=180)
    survivor = 1 - victim
    assert exits[victim].signal == "SIGKILL", exits
    assert exits[survivor].returncode == 0, pod.tail_logs()
    log = pod.log(survivor)
    assert f"FAILURE_DETECTED" in log and f"failed=[{victim}]" in log, log
    assert "REFORMED rank=0 world=1 gen=1" in log
    assert "DONE rank=0 world=1" in log
    _assert_no_torn_checkpoint(root)


def _reformed_transitions(log):
    """[(world, gen, dir)] in print order from a rank's log."""
    return [(int(m.group(1)), int(m.group(2)), m.group(3))
            for m in re.finditer(
                r"REFORMED rank=\d+ world=(\d+) gen=(\d+) dir=(\w+)", log)]


def test_pod_kill_heal_grow_back_to_full_world(tmp_path_factory):
    """THE scale-UP acceptance run: 2 real processes, rank 1 SIGKILLed
    mid-step -> shrink to world 1 -> the supervisor RESPAWNS it
    (RestartPolicy backoff) -> the replacement parks in the lobby ->
    reform-up back to world 2 -> both ranks restore from the latest pod
    checkpoint -> the tail of the run executes at FULL world, and every
    step's loss is within 1e-6 of the uninterrupted control. The merged
    runlogs carry the shrink AND grow pod_reform events (direction,
    worlds, generations strictly monotone)."""
    import trace_view

    control = _control_losses(tmp_path_factory)
    wd = str(tmp_path_factory.mktemp("pod_heal"))
    root = os.path.join(wd, "ck")
    pod = VirtualPod(2, FIXTURE, workdir=wd,
                     kill=(1, "pod/mid_step", 5), lease_ttl=LEASE_TTL,
                     restart=RestartPolicy(max_restarts=2,
                                           base_delay=0.2, seed=0),
                     env={"POD_FIX_CKPT_ROOT": root,
                          "POD_FIX_TARGET_WORLD": "2",
                          "POD_FIX_HEAL_BY_STEP": "6"})
    exits = pod.run(timeout=240)

    # the kill was real — and the LAST incarnation of rank 1 finished
    kills = [e for e in pod.exit_history
             if e.rank == 1 and e.signal == "SIGKILL"]
    assert len(kills) == 1 and kills[0].incarnation == 1
    assert exits[0].returncode == 0, pod.tail_logs()
    assert exits[1].returncode == 0 and exits[1].incarnation == 2, \
        pod.tail_logs()

    log0, log1 = pod.log(0), pod.log(1)
    # detection within the window, then the full lifecycle in order:
    # shrink to 1 (gen 1), grow back to 2 (gen 2)
    m = re.search(r"FAILURE_DETECTED t=([\d.]+) failed=\[1\]", log0)
    assert m, log0
    assert float(m.group(1)) - kills[0].t_reaped < LEASE_TTL + 2.0
    assert _reformed_transitions(log0) == [(1, 1, "shrink"),
                                           (2, 2, "grow")]
    assert "DONE rank=0 world=2" in log0
    # the replacement joined the SAME log (append), re-formed at gen 2,
    # resumed from the shared checkpoint and finished at full world
    assert log1.count("POD_READY rank=1") == 2
    assert "POD_READY rank=1 world=2 gen=2" in log1
    assert "DONE rank=1 world=2" in log1

    # losses: every step within 1e-6 of the single-process control —
    # pre-kill at world 2, degraded at world 1, healed at world 2
    for log in (log0, log1):
        got = _losses_by_step(log)
        for s, v in got.items():
            assert abs(v - control[s]) < 1e-6, (s, v, control[s])
    assert sorted(_losses_by_step(log0)) == sorted(control)
    # the healed tail REALLY ran at world 2: the replacement computed
    # the final steps too
    assert {6, 7} <= set(_losses_by_step(log1))

    _assert_no_torn_checkpoint(root)

    # merged trace: 3 process logs (rank0, rank1, rank1's replacement),
    # reform timeline shrink->grow with strictly monotone generations
    paths = pod.runlog_paths()
    assert len(paths) == 3
    events, _ = trace_view.load_events(paths)
    timeline = trace_view.reform_timeline(events)
    assert [(e["gen"], e["direction"]) for e in timeline] == \
        [(1, "shrink"), (2, "grow")]
    gens = [e["gen"] for e in timeline]
    assert gens == sorted(gens) and len(set(gens)) == len(gens)
    ev_names = {e.get("event") for e in events if e.get("kind") == "event"}
    assert {"process_kill", "pod_reform", "checkpoint_publish",
            "checkpoint_restore", "pod_join"} <= ev_names
    # the replacement's own log records that it came in via the lobby
    lobby_joins = [e for e in events if e.get("event") == "pod_join"
                   and e.get("via") == "lobby"]
    assert lobby_joins and lobby_joins[0]["gen"] == 2


@pytest.mark.slow
@pytest.mark.chaos
def test_pod_three_kill_heal_cycles_monotone_generations(
        tmp_path_factory):
    """Chaos acceptance (slow tier — ISSUE 13's tier-1 budget squeeze:
    ~24 s, the heavier of the two heal-and-grow e2e cases; the single
    kill->shrink->heal->grow lifecycle keeps tier-1 coverage in
    test_pod_kill_heal_grow_back_to_full_world): THREE consecutive
    kill/heal cycles on one pod —
    the original rank 1 killed mid-step, its first replacement killed
    DURING ITS OWN ELASTIC RESTORE (checkpoint/pod_restore), the second
    replacement killed mid-step again, the third replacement finishing
    clean. No deadlock, generations strictly monotone
    (0->1->2->3->4->5->6), no torn checkpoint, and the final losses
    still match the uninterrupted control at every step."""
    control = _control_losses(tmp_path_factory)
    wd = str(tmp_path_factory.mktemp("pod_3cycle"))
    root = os.path.join(wd, "ck")
    pod = VirtualPod(
        2, FIXTURE, workdir=wd,
        kill=(1, "pod/mid_step", 5),
        respawn_kills={1: [("checkpoint/pod_restore", 1),
                           ("pod/mid_step", 2), None]},
        lease_ttl=LEASE_TTL,
        restart=RestartPolicy(max_restarts=4, base_delay=0.2, seed=0),
        env={"POD_FIX_CKPT_ROOT": root, "POD_FIX_TARGET_WORLD": "2",
             "POD_FIX_HEAL_BY_STEP": "6"})
    exits = pod.run(timeout=300)

    kills = [e for e in pod.exit_history
             if e.rank == 1 and e.signal == "SIGKILL"]
    assert [k.incarnation for k in kills] == [1, 2, 3], pod.exit_history
    assert exits[0].returncode == 0, pod.tail_logs()
    assert exits[1].returncode == 0 and exits[1].incarnation == 4

    log0 = pod.log(0)
    trans = _reformed_transitions(log0)
    gens = [g for _w, g, _d in trans]
    assert gens == sorted(gens) and len(set(gens)) == len(gens), trans
    assert gens[-1] == 6, trans  # 3 shrinks + 3 grows
    assert [d for _w, _g, d in trans] == \
        ["shrink", "grow"] * 3, trans
    assert trans[-1][0] == 2  # healed back to full world at the end
    assert "DONE rank=0 world=2" in log0
    assert "DONE rank=1 world=2" in pod.log(1)

    # the mid-restore kill really happened at the restore point
    import trace_view
    events, _ = trace_view.load_events(pod.runlog_paths())
    kill_points = {e.get("point") for e in events
                   if e.get("event") == "process_kill"}
    assert {"pod/mid_step", "checkpoint/pod_restore"} <= kill_points

    _assert_no_torn_checkpoint(root)
    for s, v in _losses_by_step(log0).items():
        assert abs(v - control[s]) < 1e-6, (s, v, control[s])


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.parametrize("victim", [0, 3])
def test_pod_kill_heal_4proc(tmp_path, victim):
    """The 4-process heal sweep (slow tier): kill the committer (0) and
    the last rank (3) mid-step — three survivors shrink to world 3,
    the supervisor respawns the victim, the pod grows back to world 4,
    and all four ranks finish the 8-step trajectory at full world."""
    root = os.path.join(str(tmp_path), "ck")
    pod = VirtualPod(
        4, FIXTURE, workdir=str(tmp_path),
        kill=(victim, "pod/mid_step", 5), lease_ttl=LEASE_TTL,
        restart=RestartPolicy(max_restarts=2, base_delay=0.2, seed=0),
        env={"POD_FIX_CKPT_ROOT": root, "POD_FIX_TARGET_WORLD": "4",
             "POD_FIX_HEAL_BY_STEP": "6"})
    exits = pod.run(timeout=300)
    kills = [e for e in pod.exit_history
             if e.rank == victim and e.signal == "SIGKILL"]
    assert len(kills) == 1 and kills[0].incarnation == 1
    done = 0
    final = {}
    for r in range(4):
        assert exits[r].returncode == 0, pod.tail_logs()
        log = pod.log(r)
        if re.search(r"DONE rank=\d world=4", log):
            done += 1
        losses = _losses_by_step(log)
        if losses:
            final[r] = losses
    assert done == 4, pod.tail_logs()
    survivor = 1 if victim == 0 else 0
    trans = _reformed_transitions(pod.log(survivor))
    assert (3, 1, "shrink") in trans and (4, 2, "grow") in trans, trans
    base = final[survivor]
    assert sorted(base) == list(range(8))
    for r, losses in final.items():
        for s, v in losses.items():
            assert abs(v - base[s]) < 1e-9, (r, s)
    _assert_no_torn_checkpoint(root)


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.parametrize("victim", [0, 1, 2, 3])
@pytest.mark.parametrize("point,nth", [
    ("pod/before_barrier", 4),
    ("pod/mid_step", 5),
    ("checkpoint/pod_shard_written", 2),
])
def test_pod_kill_sweep_4proc(tmp_path, victim, point, nth):
    """The full sweep at world 4: kill EVERY rank id at every named
    point; the three survivors re-form at world 3 (a RAGGED 3/3/2 batch
    split — the sum-allreduce keeps losses exact) and finish within
    1e-6 of the 8-step control trajectory."""
    root = os.path.join(str(tmp_path), "ck")
    pod = VirtualPod(4, FIXTURE, workdir=str(tmp_path),
                     kill=(victim, point, nth), lease_ttl=LEASE_TTL,
                     env={"POD_FIX_CKPT_ROOT": root})
    exits = pod.run(timeout=240)
    assert exits[victim].signal == "SIGKILL", exits
    survivors = [r for r in range(4) if r != victim]
    for r in survivors:
        assert exits[r].returncode == 0, pod.tail_logs()
    done = ranks_reformed = 0
    final = {}
    for r in survivors:
        log = pod.log(r)
        if "REFORMED" in log:
            ranks_reformed += 1
            assert re.search(r"REFORMED rank=\d world=3 gen=1", log), log
        if re.search(r"DONE rank=\d world=3", log):
            done += 1
        losses = _losses_by_step(log)
        if losses:
            final[r] = losses
    assert ranks_reformed == 3 and done == 3
    # survivors agree on the full 8-step trajectory
    base = final[survivors[0]]
    assert sorted(base) == list(range(8))
    for r in survivors[1:]:
        for s, v in final[r].items():
            assert abs(v - base[s]) < 1e-9
    _assert_no_torn_checkpoint(root)
