"""Dygraph MoE layer over parallel.moe (name-compatible with the later
reference releases' paddle.incubate.distributed.models.moe.MoELayer; this
snapshot has no MoE)."""
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dispatch import call_op, unwrap, wrap
from ..nn.layer.layers import Layer
from ..parallel.moe import moe_ffn


class MoELayer(Layer):
    """Switch-FFN mixture of experts.

    d_model -> num_experts x (d_model -> d_hidden -> d_model), top-1
    routed with capacity_factor. Single-device by default; under a mesh,
    annotate the expert parameters with a PartitionSpec over the 'ep'
    axis (`shard_experts`) and the same layer trains expert-parallel.
    The Switch load-balance aux loss accumulates on `self.aux_loss` each
    forward (add it to the training loss)."""

    def __init__(self, d_model, d_hidden, num_experts, capacity_factor=1.25,
                 activation=jax.nn.gelu, name=None):
        super().__init__()
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self._act = activation
        k = 1.0 / np.sqrt(d_model)
        # stable across processes/ranks (python hash() is salted per process
        # and would desync replicated inits in multi-process dp)
        rng = np.random.RandomState(
            zlib.crc32(name.encode()) % (2 ** 31) if name else 0)
        self.gate_weight = self.create_parameter(
            [d_model, num_experts],
            default_initializer=lambda s, d: jnp.asarray(
                rng.uniform(-k, k, s), d))
        self.w1 = self.create_parameter(
            [num_experts, d_model, d_hidden],
            default_initializer=lambda s, d: jnp.asarray(
                rng.uniform(-k, k, s), d))
        self.b1 = self.create_parameter(
            [num_experts, d_hidden],
            default_initializer=lambda s, d: jnp.zeros(s, d))
        self.w2 = self.create_parameter(
            [num_experts, d_hidden, d_model],
            default_initializer=lambda s, d: jnp.asarray(
                rng.uniform(-k, k, s), d))
        self.b2 = self.create_parameter(
            [num_experts, d_model],
            default_initializer=lambda s, d: jnp.zeros(s, d))
        # registered buffer: assignment during a @to_static trace threads
        # through the compiled step instead of stranding a tracer
        self.register_buffer("aux_loss", wrap(jnp.zeros((), jnp.float32)),
                             persistable=False)

    def shard_experts(self, axis="ep"):
        """Annotate expert params for expert parallelism over `axis`."""
        from jax.sharding import PartitionSpec as P
        for p in (self.w1, self.b1, self.w2, self.b2):
            p.pspec = P(axis)
        return self

    def forward(self, x):
        shape = tuple(unwrap(x).shape)
        d = shape[-1]

        def _moe(v, gw, w1, b1, w2, b2):
            flat = v.reshape(-1, d)
            y, aux = moe_ffn(flat, gw, w1, b1, w2, b2,
                             capacity_factor=self.capacity_factor,
                             activation=self._act)
            return y.reshape(shape), aux

        out, aux = call_op(_moe, x, self.gate_weight, self.w1, self.b1,
                           self.w2, self.b2, op_name="moe_ffn")
        self.aux_loss = aux
        return out
