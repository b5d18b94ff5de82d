"""Multi-process distributed execution (reference: `test_dist_base.py:744`
TestDistBase — spawn real processes on localhost, collect stdout losses,
assert local-vs-distributed loss parity; plus `spawn.py:333`).

These are REAL multi-process tests: each worker runs in its own Python
process with its own XLA runtime, joined through the JAX coordination
service; collectives cross process boundaries (Gloo on the CPU backend).
"""
import os
import re
import subprocess
import sys

import numpy as np
import pytest

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "dist_parity_fixture.py")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _clean_env():
    env = dict(os.environ)
    for k in list(env):
        if k.startswith(("PADDLE_", "JAX_")) or k == "XLA_FLAGS":
            env.pop(k)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _losses(text):
    return [float(m.group(2)) for m in
            re.finditer(r"LOSS (\d+) ([\d.eE+-]+)", text)]


def _run_single():
    env = _clean_env()
    env["JAX_PLATFORMS"] = "cpu"
    script = (
        "import jax; jax.config.update('jax_platforms','cpu');"
        "import runpy; runpy.run_path(%r, run_name='__main__')" % FIXTURE)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=600, cwd=REPO)
    assert r.returncode == 0, r.stderr[-3000:]
    return _losses(r.stdout)


def _run_launcher(nproc, log_dir, mode="dp", port="19850", host_devices=1):
    env = _clean_env()
    env["DIST_FIXTURE_MODE"] = mode
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", str(nproc), "--started_port", port,
         "--host_devices", str(host_devices), "--log_dir", str(log_dir),
         FIXTURE],
        capture_output=True, text=True, env=env, timeout=600, cwd=REPO)
    assert r.returncode == 0, (r.stderr[-2000:] or "") + _tail_logs(log_dir)
    with open(os.path.join(log_dir, "workerlog.0")) as f:
        return _losses(f.read())


def _tail_logs(log_dir):
    out = []
    try:
        for name in sorted(os.listdir(log_dir)):
            with open(os.path.join(log_dir, name)) as f:
                out.append(f"--- {name} ---\n" + f.read()[-2000:])
    except OSError:
        pass
    return "\n".join(out)


class TestDistLossParity:
    """The reference's headline distributed test: same model, same data,
    1 process vs N processes — losses must match."""

    def test_two_proc_matches_single(self, tmp_path):
        single = _run_single()
        dist2 = _run_launcher(2, str(tmp_path))
        assert len(single) == len(dist2) == 5
        np.testing.assert_allclose(single, dist2, rtol=1e-4, atol=1e-6)

    @pytest.mark.slow  # PR 21, ~10 s: the dp case keeps the cross-process path in tier-1
    def test_two_proc_tensor_parallel_matches_single(self, tmp_path):
        """Megatron-sharded weights across two real processes: GSPMD
        collectives cross the process boundary; losses must match the
        unsharded single-process run."""
        single = _run_single()
        mp2 = _run_launcher(2, str(tmp_path), mode="mp", port="19890")
        assert len(mp2) == 5
        np.testing.assert_allclose(single, mp2, rtol=1e-4, atol=1e-6)

    @pytest.mark.slow  # PR 21, ~12 s: the dp case keeps the cross-process path in tier-1
    def test_two_proc_four_dev_hybrid_matches_single(self, tmp_path):
        """Multi-host hybrid mesh: 2 processes x 4 virtual devices = 8
        global devices, dp across the process boundary (DCN analog) and
        megatron mp within each process (ICI analog). Loss parity vs one
        process, one device."""
        single = _run_single()
        hyb = _run_launcher(2, str(tmp_path), mode="hybrid", port="19930",
                            host_devices=4)
        assert len(hyb) == 5
        np.testing.assert_allclose(single, hyb, rtol=1e-4, atol=1e-6)


def _spawn_worker(scale):
    """Module-level so the spawn context can pickle it."""
    import jax
    import jax.numpy as jnp
    assert jax.process_count() == 2
    out = jax.pmap(lambda x: jax.lax.psum(x, "i"), axis_name="i")(
        jnp.ones((jax.local_device_count(),)) * scale * (jax.process_index() + 1))
    return float(np.asarray(out)[0])


class TestSpawn:
    def test_spawn_two_processes_collective(self):
        from paddle_tpu.distributed.spawn import spawn
        ctx = spawn(_spawn_worker, args=(2.0,), nprocs=2, backend="cpu",
                    devices_per_proc=1, timeout=300)
        results = [payload for _, status, payload in ctx.results]
        # psum over both processes: 2*1 + 2*2 = 6 on every rank
        assert results == [6.0, 6.0]

    def test_spawn_single_inprocess(self):
        from paddle_tpu.distributed.spawn import spawn
        ctx = spawn(lambda: 41 + 1, nprocs=1)
        assert ctx.results[0][2] == 42

    def test_spawn_propagates_worker_failure(self):
        from paddle_tpu.distributed.spawn import spawn
        with pytest.raises(RuntimeError, match="rank"):
            spawn(_failing_worker, nprocs=2, backend="cpu", timeout=300)


def _failing_worker():
    raise ValueError("intentional fixture failure")


def _elastic_worker(root, endpoint, die):
    """Register in a shared FileKVStore from a real process; rank comes from
    live membership (reference elastic.py re-rank semantics)."""
    import time
    from paddle_tpu.distributed.fleet.elastic import ElasticManager, \
        FileKVStore
    mgr = ElasticManager(endpoint, np=2, job_id="mp_elastic",
                         store=FileKVStore(root), ttl=3,
                         heartbeat_interval=0.5)
    mgr.register()
    assert mgr.wait_ready(timeout=60)
    r = mgr.rank()
    deadline = time.time() + 60
    if die:
        # rendezvous: don't leave before the survivor has seen us, or the
        # membership change races the survivor's wait_ready
        while time.time() < deadline and mgr.store.get("survivor_saw") is None:
            time.sleep(0.1)
        mgr.exit()  # leaves the membership; lease is gone
        return r
    mgr.store.put("survivor_saw", "1")
    # survivor: wait for the peer to drop out, then re-rank
    while time.time() < deadline and len(mgr.live_nodes()) > 1:
        time.sleep(0.2)
    out = (r, mgr.rank(), len(mgr.live_nodes()))
    mgr.exit()
    return out


class TestElasticAcrossProcesses:
    def test_rerank_after_member_death(self, tmp_path):
        """Two real processes register; one exits; the survivor re-ranks to
        0 — the reference ElasticManager.watch:316 membership behavior,
        exercised across actual process boundaries."""
        import multiprocessing
        ctx = multiprocessing.get_context("spawn")
        root = str(tmp_path)
        with ctx.Pool(2) as pool:
            dead = pool.apply_async(_elastic_worker,
                                    (root, "127.0.0.1:7001", True))
            live = pool.apply_async(_elastic_worker,
                                    (root, "127.0.0.1:7002", False))
            dead_rank = dead.get(timeout=120)
            initial_rank, final_rank, n_live = live.get(timeout=120)
        assert sorted([dead_rank, initial_rank]) == [0, 1]
        assert n_live == 1
        assert final_rank == 0  # survivor re-ranked to 0


class TestEagerCollectives:
    """Eager (non-shard_map) collectives across REAL processes: formerly
    silent identities, now true cross-process ops (reference:
    collective.py broadcast:348/all_reduce:415 work eagerly in dygraph)."""

    def test_two_proc_eager_collectives(self, tmp_path):
        env = _clean_env()
        env["JAX_PLATFORMS"] = "cpu"
        fixture = os.path.join(os.path.dirname(__file__), "fixtures",
                               "eager_collective_fixture.py")
        log_dir = str(tmp_path)
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2", "--started_port", "19970",
             "--log_dir", log_dir, fixture],
            capture_output=True, text=True, env=env, timeout=600, cwd=REPO)
        assert r.returncode == 0, (r.stderr[-2000:] or "") + _tail_logs(log_dir)
        outs = []
        for i in (0, 1):
            with open(os.path.join(log_dir, f"workerlog.{i}")) as f:
                outs.append(f.read())
        for i, out in enumerate(outs):
            # sum over ranks: (1) + (2) = 3 on BOTH ranks
            assert "CHECK allreduce [3.0, 3.0, 3.0]" in out, out[-1500:]
            # broadcast from rank 1: value 10 everywhere
            assert "CHECK broadcast [10.0, 10.0]" in out, out[-1500:]
            assert "CHECK allgather [5.0, 6.0]" in out, out[-1500:]
            # subgroup [0]: rank0 reduces over itself (1.0), rank1 untouched
            want = 1.0 if i == 0 else 2.0
            assert f"CHECK subgroup {want}" in out, out[-1500:]
            assert "CHECK barrier done" in out
            assert "CHECK send raises" in out
