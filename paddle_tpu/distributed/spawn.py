"""spawn (reference: `python/paddle/distributed/spawn.py:333`).

Real N-process spawn on one host — the reference's (and its test suite's)
multi-process-on-localhost strategy. Each child process initializes the JAX
coordination service (`jax.distributed.initialize`) over a free local port;
cross-process collectives then run through XLA's CPU (Gloo) or TPU backends.
With the default nprocs=-1 on a single host the target runs in-process: one
JAX process drives all local chips, and in-host parallelism is the device
mesh, not processes.
"""
import multiprocessing
import os
import socket
import time
import traceback


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_target(func, args, rank, nprocs, port, options, queue):
    try:
        endpoints = [f"127.0.0.1:{port + i}" for i in range(nprocs)]
        os.environ["PADDLE_TRAINER_ID"] = str(rank)
        os.environ["PADDLE_TRAINERS_NUM"] = str(nprocs)
        os.environ["PADDLE_TRAINER_ENDPOINTS"] = ",".join(endpoints)
        os.environ["PADDLE_CURRENT_ENDPOINT"] = endpoints[rank]
        os.environ["JAX_COORDINATOR_ADDRESS"] = endpoints[0]
        os.environ["JAX_NUM_PROCESSES"] = str(nprocs)
        os.environ["JAX_PROCESS_ID"] = str(rank)

        backend = options.get("backend")
        devices_per_proc = int(options.get("devices_per_proc", 1))
        if backend == "cpu":
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count="
                  f"{devices_per_proc}").strip()
            os.environ["JAX_PLATFORMS"] = "cpu"
        elif backend:
            os.environ["JAX_PLATFORMS"] = backend

        from . import parallel_env
        parallel_env.init_parallel_env()
        result = func(*args)
        queue.put((rank, "ok", result))
    except Exception:
        queue.put((rank, "error", traceback.format_exc()))
        raise


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, **options):
    """Start `nprocs` coordinated processes running func(*args).

    `func` must be picklable (module-level). options: backend="cpu" for the
    host-simulated path (the reference test strategy), devices_per_proc=N
    for N XLA host devices per process, timeout=seconds. Each child sets the
    reference env contract (PADDLE_TRAINER_ID/ENDPOINTS) and bootstraps the
    JAX coordination service before calling func.
    """
    if nprocs in (-1, 1):
        result = func(*args)
        return _Context([(0, "ok", result)])

    ctx = multiprocessing.get_context("spawn")
    queue = ctx.Queue()
    port = _free_port()
    procs = []
    for rank in range(nprocs):
        p = ctx.Process(target=_spawn_target,
                        args=(func, args, rank, nprocs, port, options, queue),
                        daemon=daemon)
        p.start()
        procs.append(p)
    context = _Context(None, procs=procs, queue=queue,
                       timeout=options.get("timeout", 300))
    if join:
        context.join()
    return context


class _Context:
    def __init__(self, results, procs=None, queue=None, timeout=300):
        self.results = results
        self._procs = procs or []
        self._queue = queue
        self._timeout = timeout

    @staticmethod
    def _signal_name(exitcode):
        from .launch import signal_name
        return signal_name(exitcode)

    def join(self):
        import queue as _queue_mod

        if self.results is not None:
            return True
        out = {}
        died = None
        signal_deaths = {}
        deadline = time.time() + self._timeout
        try:
            while len(out) + len(signal_deaths) < len(self._procs):
                try:
                    rank, status, payload = self._queue.get(timeout=0.2)
                    out[rank] = (rank, status, payload)
                    continue
                except _queue_mod.Empty:
                    pass
                # reap-and-raise: a child killed by a signal (SIGKILL by
                # the OOM killer, SIGSEGV in native code) never posts a
                # result — without this check the join blocks the full
                # timeout while its peers deadlock on the dead rank's
                # collectives
                for i, p in enumerate(self._procs):
                    if i in out or i in signal_deaths:
                        continue
                    ec = p.exitcode
                    if ec is not None and ec < 0:
                        signal_deaths[i] = self._signal_name(ec)
                if signal_deaths:
                    break
                if time.time() > deadline:
                    # no signal death: distinguish crashed (non-zero
                    # exit), still-running (hang/deadlock), and clean-
                    # exit-without-result, instead of raising a bare
                    # Empty that hides everything we did learn
                    died = [(i, ("alive/hung" if p.is_alive()
                                 else f"exit {p.exitcode}"))
                            for i, p in enumerate(self._procs)]
                    break
            if signal_deaths:
                # drain any results already posted before the death
                while True:
                    try:
                        rank, status, payload = self._queue.get_nowait()
                        out[rank] = (rank, status, payload)
                    except _queue_mod.Empty:
                        break
        finally:
            # signal deaths strand the survivors on dead collectives:
            # reap everyone instead of joining the full timeout
            join_s = 2.0 if signal_deaths else self._timeout
            for p in self._procs:
                p.join(join_s)
                if p.is_alive():
                    p.terminate()
                    # jax.distributed installs a SIGTERM (preemption)
                    # handler: a rank inside it outlives terminate() by
                    # ~100 s unless the reaping escalates
                    p.join(2.0)
                    if p.is_alive():
                        p.kill()
        errors = [f"rank {r} failed:\n{payload}"
                  for r, (_, status, payload) in sorted(out.items())
                  if status == "error"]
        for i, sig in sorted(signal_deaths.items()):
            errors.append(
                f"rank {i} died by {sig} without reporting a result — "
                "an external kill (OOM killer, preemption) or a native "
                "crash; surviving ranks were terminated")
        if died is not None:
            missing = sorted(set(range(len(self._procs))) - set(out))
            states = {i: s for i, s in died}
            detail = ", ".join(f"rank {i}: {states.get(i, 'unknown')}"
                               for i in missing)
            errors.append(
                f"rank(s) {missing} did not report within {self._timeout}s "
                f"({detail}) — 'alive/hung' means a deadlock/slow step "
                "(process was terminated); a non-zero exit suggests a "
                "native crash or OOM kill")
        if errors:
            raise RuntimeError("spawn failed:\n" + "\n".join(errors))
        self.results = [out[r] for r in sorted(out)]
        return True
