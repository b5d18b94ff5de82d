"""Parameter-server stack tests (reference: `test_dist_base.py:744/867` —
pserver subprocesses + trainer subprocesses on localhost, loss parity
against local runs; plus table-level unit tests).
"""
import os
import re
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "ps_ctr_runner.py")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _clean_env():
    env = dict(os.environ)
    for k in list(env):
        if k.startswith(("PADDLE_", "JAX_", "PS_")) or k == "XLA_FLAGS":
            env.pop(k)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _losses(text):
    return [float(m.group(2)) for m in
            re.finditer(r"LOSS (\d+) ([\d.eE+-]+)", text)]


def _spawn(role, mode, ports, wid=0, n_workers=1, extra=None):
    env = _clean_env()
    if isinstance(ports, int):
        ports = [ports]
    env.update({
        "PS_ROLE": role,
        "PS_MODE": mode,
        "TRAINING_ROLE": "PSERVER" if role == "server" else "TRAINER",
        "PADDLE_PSERVER_ENDPOINTS": ",".join(
            f"127.0.0.1:{p}" for p in ports),
        "PADDLE_PSERVER_ID": str(wid if role == "server" else 0),
        "PADDLE_TRAINER_ID": str(wid),
        "PADDLE_TRAINERS_NUM": str(n_workers),
    })
    if extra:
        env.update(extra)
    script = ("import jax; jax.config.update('jax_platforms','cpu');"
              "import runpy; runpy.run_path(%r, run_name='__main__')"
              % FIXTURE)
    return subprocess.Popen([sys.executable, "-c", script],
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True, env=env, cwd=REPO)


def _run_cluster(mode, n_workers, n_servers=1, extra=None, timeout=420):
    ports = [_free_port() for _ in range(n_servers)]
    servers = [_spawn("server", mode, ports, wid=i, extra=extra)
               for i in range(n_servers)]
    for srv in servers:  # wait for SERVER_READY before starting workers
        line = srv.stdout.readline()
        assert "SERVER_READY" in line, line + srv.stderr.read()[-2000:]
    workers = [_spawn("worker", mode, ports, wid=i, n_workers=n_workers,
                      extra=extra)
               for i in range(n_workers)]
    outs = []
    try:
        for w in workers:
            out, err = w.communicate(timeout=timeout)
            assert w.returncode == 0, f"worker failed:\n{err[-4000:]}"
            outs.append(out)
        for srv in servers:
            srv.wait(timeout=60)
    finally:
        for p in workers + servers:
            if p.poll() is None:
                p.kill()
    return outs


def _run_local(extra=None):
    env = _clean_env()
    env["PS_ROLE"] = "local"
    if extra:
        env.update(extra)
    script = ("import jax; jax.config.update('jax_platforms','cpu');"
              "import runpy; runpy.run_path(%r, run_name='__main__')"
              % FIXTURE)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=420)
    assert r.returncode == 0, r.stderr[-4000:]
    return _losses(r.stdout)


# ---------------------------------------------------------------- unit level

class TestNativeTableService:
    """In-process client/server against the native table store."""

    def _start(self, tables):
        from paddle_tpu.distributed.ps import PsClient, PsServer
        srv = PsServer(tables, port=0)
        port = srv.start()
        cli = PsClient([f"127.0.0.1:{port}"])
        return srv, cli

    def test_sparse_pull_init_matches_python_mirror(self):
        from paddle_tpu.distributed.ps import TableConfig
        from paddle_tpu.distributed.ps.embedding import deterministic_init
        srv, cli = self._start(
            [TableConfig(7, "sparse", 4, "sgd", lr=0.5, init_range=0.2,
                         seed=7)])
        try:
            cli.register_sparse(7, 4)
            keys = np.array([3, 99, 12345], np.uint64)
            got = cli.pull_sparse(7, keys)
            want = deterministic_init(7, keys, 4, 0.2)
            np.testing.assert_allclose(got, want, rtol=1e-6)
            # sgd push applies -lr*g server-side
            g = np.ones((3, 4), np.float32)
            cli.push_sparse_grad(7, keys, g)
            got2 = cli.pull_sparse(7, keys)
            np.testing.assert_allclose(got2, want - 0.5, rtol=1e-5)
            assert cli.sparse_size(7) == 3
        finally:
            cli.stop_servers()
            srv.stop()

    def test_sparse_adam_matches_numpy(self):
        from paddle_tpu.distributed.ps import TableConfig
        srv, cli = self._start(
            [TableConfig(1, "sparse", 3, "adam", lr=0.1, init_range=0.0)])
        try:
            cli.register_sparse(1, 3)
            keys = np.array([5], np.uint64)
            p = np.zeros(3); m = np.zeros(3); v = np.zeros(3)
            for t in range(1, 4):
                g = np.full(3, float(t), np.float32)
                cli.push_sparse_grad(1, keys, g.reshape(1, 3))
                m = 0.9 * m + 0.1 * g
                v = 0.999 * v + 0.001 * g * g
                mh = m / (1 - 0.9 ** t)
                vh = v / (1 - 0.999 ** t)
                p -= 0.1 * mh / (np.sqrt(vh) + 1e-8)
            got = cli.pull_sparse(1, keys)[0]
            np.testing.assert_allclose(got, p, rtol=1e-5)
        finally:
            cli.stop_servers()
            srv.stop()

    def test_dense_init_push_pull_and_delta(self):
        from paddle_tpu.distributed.ps import TableConfig
        srv, cli = self._start(
            [TableConfig(0, "dense", 0, "sgd", lr=0.1)])
        try:
            cli.register_dense(0, 4)
            init = np.arange(4, dtype=np.float32)
            got = cli.pull_dense_init(0, init)
            np.testing.assert_allclose(got, init)
            # second init is ignored (table already initialized)
            got = cli.pull_dense_init(0, np.zeros(4, np.float32))
            np.testing.assert_allclose(got, init)
            cli.push_dense_grad(0, np.ones(4, np.float32))
            np.testing.assert_allclose(cli.pull_dense(0), init - 0.1)
            cli.push_dense_delta(0, np.full(4, 0.5, np.float32))
            np.testing.assert_allclose(cli.pull_dense(0), init + 0.4)
        finally:
            cli.stop_servers()
            srv.stop()

    def test_save_load_roundtrip(self, tmp_path):
        from paddle_tpu.distributed.ps import PsClient, PsServer, TableConfig
        tables = [TableConfig(0, "dense", 0, "sgd", lr=0.1),
                  TableConfig(9, "sparse", 2, "adam", lr=0.05,
                              init_range=0.3, seed=9)]
        srv, cli = self._start(tables)
        keys = np.array([11, 22], np.uint64)
        try:
            cli.register_dense(0, 3)
            cli.register_sparse(9, 2)
            cli.pull_dense_init(0, np.array([1, 2, 3], np.float32))
            cli.push_sparse_grad(9, keys, np.ones((2, 2), np.float32))
            want_sparse = cli.pull_sparse(9, keys)
            want_dense = cli.pull_dense(0)
            cli.save(str(tmp_path / "snap"))
        finally:
            cli.stop_servers()
            srv.stop()
        # fresh server, load the snapshot, state must match (incl. adam t:
        # one more identical push must give identical results server-restart
        # or not)
        srv2, cli2 = self._start(tables)
        try:
            cli2.register_dense(0, 3)
            cli2.register_sparse(9, 2)
            cli2.load(str(tmp_path / "snap"))
            np.testing.assert_allclose(cli2.pull_sparse(9, keys), want_sparse)
            np.testing.assert_allclose(cli2.pull_dense(0), want_dense)
        finally:
            cli2.stop_servers()
            srv2.stop()


# ------------------------------------------------------------ cluster level

class TestPsCluster:
    @pytest.mark.slow  # ~31 s subprocess cluster; geo convergence stays
    def test_geo_single_worker_matches_local(self):  # tier-1-covered by
        """geo k=1, one worker: server state mirrors local SGD exactly
        (the reference's geo-delta semantics)."""  # TestPsGeoMultiWorker
        outs = _run_cluster("geo", 1, extra={"PS_K_STEPS": "1"})
        ps_losses = _losses(outs[0])
        local_losses = _run_local()
        assert len(ps_losses) == len(local_losses) > 0
        np.testing.assert_allclose(ps_losses, local_losses, rtol=2e-3,
                                   atol=2e-4)

    @pytest.mark.slow  # ~26 s; subsumed in tier-1 by the sharded
    def test_sync_two_workers_train(self):  # two-server sync case below
        outs = _run_cluster("sync", 2)
        for out in outs:
            ls = _losses(out)
            assert len(ls) == 200
            assert np.mean(ls[-10:]) < 0.35 < np.mean(ls[:5])

    @pytest.mark.slow  # ~23 s subprocess cluster (PR 11 budget); async
    def test_async_two_workers_train_and_save(self, tmp_path):
        # wire + save coverage stays tier-1 via TestNativeTableService
        # and the Downpour two-thread run
        snap = str(tmp_path / "ps_snap")
        outs = _run_cluster("async", 2, extra={"PS_SAVE": snap})
        for out in outs:
            ls = _losses(out)
            assert len(ls) == 200
            assert np.mean(ls[-10:]) < 0.35 < np.mean(ls[:5])
        assert os.path.exists(snap + ".0")
        m = re.search(r"SPARSE_SIZE (\d+)", outs[0])
        assert m and int(m.group(1)) > 0

    @pytest.mark.slow  # ~26 s subprocess cluster (PR 11 budget); key
    def test_sync_two_workers_two_servers_sharded(self):
        """Sparse keys shard across 2 server processes (key % nservers);
        training still converges and every server holds a partition.
        (Key-range sharding itself stays tier-1 via the async_cache
        write-back range-split tests.)"""
        outs = _run_cluster("sync", 2, n_servers=2)
        for out in outs:
            ls = _losses(out)
            assert len(ls) == 200
            assert np.mean(ls[-10:]) < 0.35 < np.mean(ls[:5])


class TestDownpourTrainer:
    """Multi-threaded DeviceWorker analog (reference: DownpourWorker /
    DistMultiTrainer via train_from_dataset, SURVEY CS5): thread-local
    model replicas over one shared PS client, async push/pull."""

    @pytest.mark.slow  # PR 21, ~11 s: joins the PS cluster runs already in the slow tier
    def test_two_threads_train_from_dataset(self):
        import numpy as np

        import paddle_tpu as paddle
        from paddle_tpu import nn
        from paddle_tpu.distributed import ps
        from paddle_tpu.distributed.ps import (DownpourTrainer, PsClient,
                                               PsServer, TableConfig)

        VOCAB, DIM = 50, 4
        srv = PsServer([
            TableConfig(1000, "sparse", DIM, "sgd", lr=0.2, init_range=0.1,
                        seed=1000),
            TableConfig(0, "dense", 0, "sgd", lr=0.2),
            TableConfig(1, "dense", 0, "sgd", lr=0.2),
            TableConfig(2, "dense", 0, "sgd", lr=0.2),
            TableConfig(3, "dense", 0, "sgd", lr=0.2),
        ], port=0)
        port = srv.start()
        try:
            class Runtime:  # minimal stand-in for PsRuntime on one host
                client = PsClient([f"127.0.0.1:{port}"])

                class role:
                    @staticmethod
                    def worker_num():
                        return 1

            def builder():
                paddle.seed(0)

                class M(nn.Layer):
                    def __init__(self):
                        super().__init__()
                        # EXPLICIT table id: every replica must address
                        # the same server table
                        self.emb = ps.SparseEmbedding([VOCAB, DIM],
                                                      table_id=1000)
                        self.fc1 = nn.Linear(3 * DIM, 8)
                        self.fc2 = nn.Linear(8, 1)

                    def forward(self, ids):
                        e = self.emb(ids)
                        h = paddle.ops.reshape(e, [e.shape[0], 3 * DIM])
                        return self.fc2(
                            paddle.nn.functional.relu(self.fc1(h)))

                return M()

            w_id = np.random.RandomState(42).randn(VOCAB).astype(np.float32)

            def loss_fn(model, batch):
                ids, label = batch
                logits = model(paddle.to_tensor(ids))
                return paddle.nn.functional.\
                    binary_cross_entropy_with_logits(
                        logits, paddle.to_tensor(label))

            def batches(n):
                rng = np.random.RandomState(0)
                for _ in range(n):
                    ids = rng.randint(0, VOCAB, (32, 3)).astype(np.int64)
                    label = (w_id[ids[:, 0]] > 0).astype(
                        np.float32).reshape(-1, 1)
                    yield ids, label

            tr = DownpourTrainer(Runtime, builder, loss_fn, n_threads=2)
            stats = tr.train_from_dataset(batches(250))
            assert stats["batches"] == 250
            assert all(c > 0 for c in stats["per_thread"])  # both worked
            # learned: fresh replica pulled from PS beats chance decisively
            probe = builder()
            from paddle_tpu.distributed.ps import bind_model
            from paddle_tpu.distributed.ps.communicator import SyncCommunicator
            comm = SyncCommunicator(Runtime.client, n_workers=1)
            bind_model(probe, comm)
            comm.pull_dense()
            ids, label = next(batches(1))
            with paddle.no_grad():
                pred = (probe(paddle.to_tensor(ids)).numpy() > 0)
            acc = (pred.ravel() == (label.ravel() > 0.5)).mean()
            assert acc > 0.75, acc
        finally:
            Runtime.client.stop_servers()
            srv.stop()


class TestPsGeoMultiWorker:
    @pytest.mark.slow  # ~24 s subprocess cluster (PR 11 budget); geo
    def test_geo_two_workers_k4_converge(self):  # delta semantics stay
        # tier-1 via the in-process geo wire/communicator unit tests
        """2 workers, geo delta sync every 4 local steps (the reference
        GeoCommunicator's actual operating point): both converge."""
        outs = _run_cluster("geo", 2, extra={"PS_K_STEPS": "4"})
        for out in outs:
            ls = _losses(out)
            assert len(ls) == 200
            assert np.mean(ls[-10:]) < 0.35 < np.mean(ls[:5])


class TestHeterPs:
    """Heterogeneous PS (reference: heter_client.h:67/heter_server.h:151
    + heterxpu_trainer.cc): the worker runs the sparse/embedding stage and
    exchanges activations with a trainer process owning the dense stage;
    activation grads flow back and sparse grads land on the PS."""

    @pytest.mark.slow  # ~12 s two-subprocess pipeline (PR 11 budget);
    def test_heter_worker_trainer_pipeline(self):  # the heter overlap
        # story is tier-1-covered by the async_cache CTR pipeline
        import subprocess
        import sys as _s
        import textwrap

        trainer_code = textwrap.dedent("""
            import jax; jax.config.update('jax_platforms','cpu')
            import numpy as np
            import paddle_tpu as paddle
            from paddle_tpu import nn
            from paddle_tpu.distributed.ps.heter import HeterServer

            paddle.seed(1)
            dense = nn.Sequential(nn.Linear(12, 16), nn.ReLU(),
                                  nn.Linear(16, 1))
            opt = paddle.optimizer.SGD(parameters=dense.parameters(),
                                       learning_rate=0.2)

            def handler(acts, labels):
                a = paddle.to_tensor(acts.astype(np.float32))
                a.stop_gradient = False
                logits = dense(a)
                loss = paddle.nn.functional.\\
                    binary_cross_entropy_with_logits(
                        logits, paddle.to_tensor(labels))
                loss.backward()
                opt.step(); opt.clear_grad()
                return float(loss.numpy()), np.asarray(a.grad.numpy())

            srv = HeterServer(handler, port=int(__import__('sys').argv[1]))
            print("TRAINER_READY", flush=True)
            srv.serve_forever()
        """)
        port = _free_port()
        trainer = subprocess.Popen(
            [_s.executable, "-c", trainer_code, str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
            cwd=REPO, env=_clean_env())
        try:
            line = trainer.stdout.readline()
            assert "TRAINER_READY" in line, line

            # worker side (this process): PS sparse table + embedding stage
            import paddle_tpu as paddle
            from paddle_tpu.distributed import ps
            from paddle_tpu.distributed.ps import (PsClient, PsServer,
                                                   TableConfig)
            from paddle_tpu.distributed.ps.communicator import \
                AsyncCommunicator
            from paddle_tpu.distributed.ps.heter import HeterClient

            VOCAB, DIM = 40, 4
            pss = PsServer([TableConfig(1000, "sparse", DIM, "sgd", lr=0.2,
                                        init_range=0.1, seed=1000)], port=0)
            ps_port = pss.start()
            cli = PsClient([f"127.0.0.1:{ps_port}"])
            comm = AsyncCommunicator(cli, n_workers=1)
            emb = ps.SparseEmbedding([VOCAB, DIM], table_id=1000)
            emb.bind(comm)
            heter = HeterClient(f"127.0.0.1:{port}")

            w_id = np.random.RandomState(42).randn(VOCAB).astype(np.float32)
            rng_l = np.random.RandomState(0)
            losses = []
            for step in range(150):
                ids = rng_l.randint(0, VOCAB, (32, 3)).astype(np.int64)
                labels = (w_id[ids[:, 0]] > 0).astype(
                    np.float32).reshape(-1, 1)
                e = emb(paddle.to_tensor(ids))          # sparse stage (host)
                acts = paddle.ops.reshape(e, [32, 3 * DIM])
                loss, dacts = heter.send_and_recv(
                    np.asarray(acts.numpy()), labels)   # dense stage (trainer)
                acts.backward(paddle.to_tensor(dacts))  # sparse backward
                from paddle_tpu.distributed.ps.embedding import \
                    flush_sparse_grads
                flush_sparse_grads(comm)
                comm.step()
                losses.append(loss)
            assert np.mean(losses[-10:]) < 0.4 < np.mean(losses[:5])
            assert cli.sparse_size(1000) > 0  # sparse grads reached the PS
            heter.stop_server()
            heter.close()
            comm.stop()
            cli.stop_servers()
            pss.stop()
        finally:
            if trainer.poll() is None:
                trainer.kill()


_KILL_SERVER_SCRIPT = """
import sys, time
import jax; jax.config.update('jax_platforms', 'cpu')
from paddle_tpu.distributed.ps import PsServer, TableConfig
tables = [TableConfig(1000, "sparse", 4, "adam", lr=0.05, init_range=0.1,
                      seed=7),
          TableConfig(0, "dense", 0, "adam", lr=0.05)]
srv = PsServer(tables, port=int(sys.argv[1]))
srv.start()
print("SERVER_READY", flush=True)
srv.run()
"""


class TestPsServerKillFaultInjection:
    """Server-side fault injection (reference: brpc_ps_client.cc connect
    retry under FLAGS_pserver_connect_timeout_ms): SIGKILL a pserver
    mid-training, bring up a replacement on the same port, and the worker
    — same PsClient object, never rebuilt — reconnects and resumes from
    the last snapshot. Complements test_launch_elastic_ckpt.py, which
    kills a *worker*."""

    def _spawn_server(self, port):
        srv = subprocess.Popen(
            [sys.executable, "-c", _KILL_SERVER_SCRIPT, str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=_clean_env(), cwd=REPO)
        line = srv.stdout.readline()
        assert "SERVER_READY" in line, line + srv.stderr.read()[-2000:]
        return srv

    def test_worker_reconnects_and_resumes_after_sigkill(self, tmp_path):
        from paddle_tpu.distributed.ps import PsClient
        port = _free_port()
        snap = str(tmp_path / "kill_snap")
        srv = self._spawn_server(port)
        srv2 = None
        cli = PsClient([f"127.0.0.1:{port}"])
        try:
            cli.register_sparse(1000, 4)
            cli.register_dense(0, 6)
            keys = np.array([2, 5, 11], np.uint64)
            rng = np.random.RandomState(3)
            cli.pull_dense_init(0, np.zeros(6, np.float32))
            for _ in range(4):
                cli.push_sparse_grad(1000, keys,
                                     rng.rand(3, 4).astype(np.float32))
                cli.push_dense_grad(0, rng.rand(6).astype(np.float32))
            cli.save(snap)
            trained_sparse = cli.pull_sparse(1000, keys)
            trained_dense = cli.pull_dense(0)

            srv.kill()  # SIGKILL: no graceful shutdown, sockets just die
            srv.wait(timeout=30)
            srv2 = self._spawn_server(port)

            # the SAME client object reconnects: the first pull rides the
            # idempotent retry path over a fresh socket
            fresh = cli.pull_sparse(1000, keys)
            assert not np.allclose(fresh, trained_sparse), \
                "replacement server unexpectedly has trained state"
            cli.load(snap)
            np.testing.assert_allclose(cli.pull_sparse(1000, keys),
                                       trained_sparse)
            np.testing.assert_allclose(cli.pull_dense(0), trained_dense)
            # and training continues against the replacement
            cli.push_dense_grad(0, rng.rand(6).astype(np.float32))
            assert not np.allclose(cli.pull_dense(0), trained_dense)
        finally:
            try:
                cli.stop_servers()
            except (ConnectionError, OSError):
                pass
            cli.close()
            for p in (srv, srv2):
                if p is not None and p.poll() is None:
                    p.kill()

    def test_push_against_dead_server_fails_within_deadline(self):
        """Pushes are idempotent now (request-id dedup server-side), so
        the client MAY retry them — but against a server that never
        comes back the retry budget is bounded: the push fails with a
        ConnectionError subclass (RetriesExhausted/DeadlineExceeded)
        within the policy's deadline instead of hanging or hammering."""
        from paddle_tpu.distributed.ps import PsClient
        from paddle_tpu.distributed.ps.retry import RetryPolicy
        port = _free_port()
        srv = self._spawn_server(port)
        cli = PsClient([f"127.0.0.1:{port}"],
                       retry_policy=RetryPolicy(max_attempts=3,
                                                base_delay_s=0.05,
                                                deadline_s=3.0, seed=5))
        cli.CONNECT_RETRIES = 3
        cli.CONNECT_BACKOFF = 0.05
        try:
            cli.register_dense(0, 6)
            cli.pull_dense_init(0, np.zeros(6, np.float32))  # opens socket
            srv.kill()
            srv.wait(timeout=30)
            t0 = time.monotonic()
            with pytest.raises(ConnectionError):
                cli.push_dense_grad(0, np.ones(6, np.float32))
            assert time.monotonic() - t0 < 10.0  # bounded, not hung
        finally:
            cli.close()
            if srv.poll() is None:
                srv.kill()

    def test_push_retry_across_server_restart_applies_once(self):
        """The push graceful-degradation story end-to-end: the server
        dies, a fresh replacement binds while the client is still inside
        its retry window, and the retried push lands EXACTLY once — the
        replacement's table equals one adam step from zeros (the same
        deterministic reference the original fresh server produced), not
        two."""
        from paddle_tpu.distributed.ps import PsClient
        from paddle_tpu.distributed.ps.retry import RetryPolicy
        port = _free_port()
        srv = self._spawn_server(port)
        srv2 = None
        cli = PsClient([f"127.0.0.1:{port}"],
                       retry_policy=RetryPolicy(max_attempts=20,
                                                base_delay_s=0.2,
                                                max_delay_s=0.5,
                                                deadline_s=60.0, seed=5))
        cli.CONNECT_RETRIES = 40
        cli.CONNECT_BACKOFF = 0.25
        try:
            cli.register_dense(0, 6)
            cli.pull_dense_init(0, np.zeros(6, np.float32))
            cli.push_dense_grad(0, np.ones(6, np.float32))
            base = cli.pull_dense(0)  # one adam step from zeros
            srv.kill()
            srv.wait(timeout=30)

            def revive():
                time.sleep(1.0)
                nonlocal srv2
                srv2 = self._spawn_server(port)

            t = threading.Thread(target=revive)
            t.start()
            # issued while the server is DOWN: rides the retry window
            # until the replacement binds, then applies exactly once on
            # the replacement's fresh (zeros) table
            cli.push_dense_grad(0, np.ones(6, np.float32))
            t.join(timeout=60)
            after = cli.pull_dense(0)
            np.testing.assert_allclose(after, base)
        finally:
            cli.close()
            for p in (srv, srv2):
                if p is not None and p.poll() is None:
                    p.kill()


class TestPsServerRestartResume:
    def test_snapshot_restart_resume_training(self, tmp_path):
        """Server-side fault-tolerance cycle (reference:
        fleet.save_persistables -> server restart -> load -> resume):
        training state survives a full server restart bit-exactly."""
        from paddle_tpu.distributed.ps import (PsClient, PsServer,
                                               TableConfig)
        tables = [TableConfig(1000, "sparse", 4, "adam", lr=0.05,
                              init_range=0.1, seed=1000),
                  TableConfig(0, "dense", 0, "adam", lr=0.05)]
        snap = str(tmp_path / "resume_snap")

        srv = PsServer(tables, port=0)
        port = srv.start()
        cli = PsClient([f"127.0.0.1:{port}"])
        cli.register_sparse(1000, 4)
        cli.register_dense(0, 6)
        keys = np.array([3, 8, 13], np.uint64)
        rng_l = np.random.RandomState(2)
        cli.pull_dense_init(0, np.zeros(6, np.float32))
        for _ in range(5):
            cli.push_sparse_grad(1000, keys,
                                 rng_l.rand(3, 4).astype(np.float32))
            cli.push_dense_grad(0, rng_l.rand(6).astype(np.float32))
        cli.save(snap)
        mid_sparse = cli.pull_sparse(1000, keys)
        mid_dense = cli.pull_dense(0)
        # continue WITHOUT restart: the adam-momentum ground truth
        g_s = rng_l.rand(3, 4).astype(np.float32)
        g_d = rng_l.rand(6).astype(np.float32)
        cli.push_sparse_grad(1000, keys, g_s)
        cli.push_dense_grad(0, g_d)
        want_sparse = cli.pull_sparse(1000, keys)
        want_dense = cli.pull_dense(0)
        cli.stop_servers()
        srv.stop()

        # fresh server process state: load snapshot, apply the SAME next
        # grads — identical result proves optimizer state (m/v/t) resumed
        srv2 = PsServer(tables, port=0)
        port2 = srv2.start()
        cli2 = PsClient([f"127.0.0.1:{port2}"])
        cli2.register_sparse(1000, 4)
        cli2.register_dense(0, 6)
        try:
            cli2.load(snap)
            np.testing.assert_allclose(cli2.pull_sparse(1000, keys),
                                       mid_sparse)
            np.testing.assert_allclose(cli2.pull_dense(0), mid_dense)
            cli2.push_sparse_grad(1000, keys, g_s)
            cli2.push_dense_grad(0, g_d)
            np.testing.assert_allclose(cli2.pull_sparse(1000, keys),
                                       want_sparse, rtol=1e-6)
            np.testing.assert_allclose(cli2.pull_dense(0), want_dense,
                                       rtol=1e-6)
        finally:
            cli2.stop_servers()
            srv2.stop()
