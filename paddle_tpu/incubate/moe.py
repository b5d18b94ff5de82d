"""Dygraph MoE layers over parallel.moe: `MoELayer` (name-compatible with
the later reference releases' paddle.incubate.distributed.models.moe.
MoELayer; this snapshot has no MoE), Switch top-1 routing with a capacity;
and `HeldExpertsLayer`, sigmoid- or softmax-scored top-k routing over a whole
expert set of which this device holds a contiguous share, no pair dropped, with
`routing_stats()` for what its layers counted on the device."""
import weakref
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dispatch import call_op, unwrap, wrap
from ..nn.layer.layers import Layer
from ..parallel.moe import held_experts_ffn, moe_ffn, rows_worked


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


# every live HeldExpertsLayer, for `routing_stats()` (as
# `observability.memory.program_scopes()` keeps the newest program)
_held_layers = weakref.WeakSet()
# a layer's counting buffers and the `monitor` stats they are summed into
_COUNTERS = {"routed_pairs": "moe_routed_pairs", "steps": "moe_steps",
             "load_max": "moe_expert_load_max",
             "rows_worked": "moe_rows_worked",
             "rows_folded": "moe_rows_folded"}


class HeldExpertsLayer(Layer):
    """One device's share of a routed expert layer (DeepSeek-V3's
    `noaux_tc` routing): a router over all `num_experts`, `top_k` of them
    chosen a token by sigmoid score plus a selection bias, gates
    normalised over the chosen (their sum plus `norm_eps`, an argument:
    DeepSeek-V3's 1e-20 by default, LFM2 passes 1e-6) and scaled, and of
    the sum over the chosen experts the part that experts
    `ep_rank * held ..` give, with `held = num_experts // ep_size` gated
    SiLU units of width `d_hidden` stored here. What the experts held
    elsewhere would add is left out (their devices add it); no pair
    routed here is ever dropped.

    `e_score_correction_bias` is a persistable buffer: it enters the
    selection and nothing else, and no rule moves it here (`Layer.to`
    casts it with the rest; the scores it joins are float32).

    With `scoring="softmax"` (Qwen3-Next) the scores are a softmax over
    all `num_experts` and there is no selection bias: the gates are the
    chosen scores over their sum (pass `norm_eps=0`), scaled.

    The routed pairs are worked through by a loop over blocks of sorted
    pair slots whose trip count is read on the device (`parallel.moe.
    held_experts_ffn`): a pass costs what the pairs routed here cost,
    rounded up to a block. Five non-persistable int32 buffers count
    inside a compiled step, with no host sync: `routed_pairs`, `steps`
    (applications of the layer), `load_max` (the busiest held expert's
    pairs, summed over them), `rows_worked` (the rows of the blocks
    the loop ran, summed over them: over `routed_pairs` it is the
    padding the loop pays) and `rows_folded` (the routed pairs summed
    into another row of their token before a block's add, summed over
    them); `routing_stats()` fetches them."""

    def __init__(self, d_model, d_hidden, num_experts, top_k, ep_size=1,
                 ep_rank=0, routed_scaling_factor=1.0, norm_eps=1e-20,
                 scoring="sigmoid"):
        super().__init__()
        if num_experts % ep_size or not 0 <= ep_rank < ep_size:
            raise ValueError(
                f"{num_experts} experts do not split over ep_size "
                f"{ep_size} with ep_rank {ep_rank}")
        held = num_experts // ep_size
        self.num_experts, self.top_k, self.held = num_experts, top_k, held
        self.first_expert = ep_rank * held
        self.scale = float(routed_scaling_factor)
        self.norm_eps = float(norm_eps)
        if scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"unknown scoring {scoring!r}")
        self.scoring = scoring
        from ..nn.initializer import Normal

        normal = Normal(0.0, 0.02)
        self.router_weight = self.create_parameter(
            [d_model, num_experts], default_initializer=normal)
        self.w_gate = self.create_parameter(
            [held, d_model, d_hidden], default_initializer=normal)
        self.w_up = self.create_parameter(
            [held, d_model, d_hidden], default_initializer=normal)
        self.w_down = self.create_parameter(
            [held, d_hidden, d_model], default_initializer=normal)
        if scoring == "sigmoid":
            self.register_buffer(
                "e_score_correction_bias",
                wrap(jnp.zeros((num_experts,), jnp.float32)))
        for name in _COUNTERS:
            self.register_buffer(name, wrap(jnp.zeros((), jnp.int32)),
                                 persistable=False)
        _held_layers.add(self)

    def forward(self, x):
        from ..jit.to_static import note_structure

        shape = tuple(unwrap(x).shape)

        def _moe(v, rw, wg, wu, wd, *bias):
            y, *counts = held_experts_ffn(
                v.reshape(-1, shape[-1]), rw, bias[0] if bias else None,
                wg, wu, wd, top_k=self.top_k, first_expert=self.first_expert,
                scale=self.scale, norm_eps=self.norm_eps,
                scoring=self.scoring)
            # the counts leave the op as float32 (an op's outputs are
            # floating point); a layer application routes < 2**24 pairs
            return (y.reshape(shape),) + tuple(
                count.astype(jnp.float32) for count in counts)

        bias = (self.e_score_correction_bias,) if self.scoring == "sigmoid" \
            else ()
        out, pairs, load, folded = call_op(
            _moe, x, self.router_weight, self.w_gate, self.w_up,
            self.w_down, *bias, op_name="held_experts_ffn")
        routed = unwrap(pairs).astype(jnp.int32)
        slots = int(np.prod(shape[:-1])) * self.top_k
        for name, more in (("routed_pairs", routed),
                           ("load_max", unwrap(load)), ("steps", 1),
                           ("rows_worked", rows_worked(routed, slots)),
                           ("rows_folded", unwrap(folded))):
            setattr(self, name, wrap(unwrap(getattr(self, name))
                                     + jnp.asarray(more, jnp.int32)))
        note_structure("moe_layers")
        note_structure("moe_experts_held", self.held)
        return out


def routing_stats():
    """What the live `HeldExpertsLayer`s have counted so far, fetched in
    one `device_get`: {"moe_routed_pairs", "moe_steps",
    "moe_expert_load_max", "moe_rows_worked", "moe_rows_folded"} as
    python integers, also written to `paddle_tpu.monitor` under those
    names. Call it between steps, never inside one: it waits for the
    device."""
    from .. import monitor

    layers = list(_held_layers)
    fetched = jax.device_get([[unwrap(getattr(layer, name))
                               for name in _COUNTERS] for layer in layers])
    totals = {stat: sum(int(row[i]) for row in fetched)
              for i, stat in enumerate(_COUNTERS.values())}
    for name, value in totals.items():
        monitor.stat_reset(name)
        monitor.stat_add(name, value)
    return totals
