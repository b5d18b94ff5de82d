"""Process/mesh environment.

Replaces the reference's env-contract bootstrap (`python/paddle/distributed/
parallel.py:58 init_parallel_env`, PADDLE_TRAINER_* vars, NCCL comm-id TCP
exchange `platform/gen_comm_id_helper.cc`) with the jax picture: one python
process drives all local chips; multi-host uses jax.distributed.initialize
(the coordination service is the comm-id rendezvous analog). The device mesh
(`jax.sharding.Mesh`) is the TPU-native HybridCommunicateGroup substrate.
"""
import os

import jax
import numpy as np
from jax.sharding import Mesh

_mesh = None

# the manual data-parallel axis bound by a to_static(dp_axis=...) trace.
# While a dp-sharded step program is being traced (analysis or real), the
# optimizer/AMP layers consult this to route gradient reduction through
# explicit per-rank collectives (psum / psum_scatter) instead of relying
# on GSPMD's implicit insertion. A plain list cell, not a contextvar: the
# trace is single-threaded and the cell is only set around pure_fn calls.
_dp_axis = [None]


def current_mesh():
    return _mesh


def set_mesh(mesh):
    global _mesh
    _mesh = mesh
    return mesh


def current_dp_axis():
    """The manual dp axis of the to_static step being traced, or None."""
    return _dp_axis[0]


# the gradient-accumulation window of a to_static(accumulate_steps=a) scan
# trace: ("accum", a) while a non-boundary micro step's body is being
# traced (optimizer/scaler updates defer, grads survive clear_grad),
# ("fire", a) while the window-boundary step traces (the update runs once
# over the accumulated gradients, scaled 1/a). None outside accumulation.
_accum = [None]


def current_accum():
    """("accum"|"fire", window_steps) of the scan trace in progress, or
    None when no accumulation window is active."""
    return _accum[0]


class accum_ctx:
    """Bind the accumulation phase for the duration of a micro-step trace."""

    def __init__(self, phase, steps):
        assert phase in ("accum", "fire"), phase
        self.state = (phase, int(steps))
        self._saved = None

    def __enter__(self):
        self._saved = _accum[0]
        _accum[0] = self.state
        return self

    def __exit__(self, *exc):
        _accum[0] = self._saved
        return False


class dp_axis_ctx:
    """Bind the manual dp axis for the duration of a step-program trace."""

    def __init__(self, axis):
        self.axis = axis
        self._saved = None

    def __enter__(self):
        self._saved = _dp_axis[0]
        _dp_axis[0] = self.axis
        return self

    def __exit__(self, *exc):
        _dp_axis[0] = self._saved
        return False


def axis_bound(axis):
    """True when `axis` is a bound named axis here (inside shard_map with
    the axis manual). False in eager code and in abstract analysis traces
    — callers use this to pick real collectives vs shape-preserving
    simulations."""
    if axis is None:
        return False
    try:
        jax.lax.axis_index(axis)
        return True
    except Exception:
        return False


def axis_degree(mesh, axis):
    """Size of a mesh axis (1 when the mesh or axis is absent)."""
    if mesh is None:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis, 1)


def make_mesh(axes, devices=None):
    """axes: dict name->size, e.g. {'dp':2,'mp':2,'pp':2}. -1 infers one axis."""
    devices = devices if devices is not None else jax.devices()
    names = list(axes.keys())
    sizes = list(axes.values())
    n = len(devices)
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        sizes[sizes.index(-1)] = n // known
    total = int(np.prod(sizes))
    assert total <= n, f"mesh {dict(zip(names, sizes))} needs {total} devices, have {n}"
    dev_array = np.asarray(devices[:total]).reshape(sizes)
    return Mesh(dev_array, axis_names=names)


def init_parallel_env():
    """Single-host: nothing to bootstrap (XLA owns the collectives).
    Multi-process under a launcher/spawn: initialize the jax coordination
    service from the env contract (the reference's gen_comm_id TCP
    rendezvous maps to this service)."""
    # NB: no jax.process_count() probe here — any backend-touching call
    # before jax.distributed.initialize would lock the process into a
    # single-process backend
    global _dist_initialized
    if not _dist_initialized and "PADDLE_TRAINER_ENDPOINTS" in os.environ:
        eps = os.environ["PADDLE_TRAINER_ENDPOINTS"].split(",")
        rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
        if len(eps) > 1:
            jax.distributed.initialize(
                coordinator_address=os.environ.get(
                    "JAX_COORDINATOR_ADDRESS", eps[0]),
                num_processes=len(eps),
                process_id=rank)
            _dist_initialized = True
    return ParallelEnv()


_dist_initialized = False


class ParallelEnv:
    """reference: python/paddle/fluid/dygraph/parallel.py:71"""

    @property
    def rank(self):
        return jax.process_index()

    @property
    def world_size(self):
        return jax.process_count()

    @property
    def device_id(self):
        return 0

    local_rank = rank
    nranks = world_size


def get_rank():
    return jax.process_index()


def get_world_size():
    return jax.process_count()
