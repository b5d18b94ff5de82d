"""The causal decoder block the language models share, built from what
its configuration says of six things (two of them, `ffn` and
`attention`, a model may give one block otherwise than its
configuration: a leading dense layer, a stack whose `layer_types`
alternate):

    norm        "layer_norm" (weight and bias), "rms_norm" (weight) or
                "zero_centred_rms_norm": rms(x) * (1 + w), w drawn at 0
    sandwich    False: a norm before each sublayer (pre-LN, GPT-2/3);
                True: one before and one after it, the second inside the
                residual branch: x + norm(sublayer(norm(x)))
    rope_theta  None: positions are the model's business (a learned
                table added to the embeddings); a number: rotary
                positions on q and k, rotate-half, that base
    fused_qkv   True: one [h, 3h] projection (`qkv`); False: `q_proj`,
                `k_proj`, `v_proj`. `linear_bias` gives all of the
                block's projections a bias, or none
    ffn         "gelu": fc2(gelu(fc1(x))); "swiglu": a gated unit,
                down_proj(silu(gate_proj(x)) * up_proj(x)); "moe": routed
                experts of which this device holds a share
                (`incubate.moe.HeldExpertsLayer`, registered `moe`,
                scored by `router_scoring`) plus a gated unit every
                token meets (`shared_expert`), with `shared_expert_gate`
                weighted by sigmoid(x w_sg). A model may give one block
                another `ffn` than its configuration's (a leading dense
                layer)
    attention   the block's sequence mixer. "mha": heads of one width
                from `qkv` or `q_proj`, `k_proj`, `v_proj`; with
                `num_key_value_heads` fewer than `num_heads` (separate
                projections only) the heads are grouped-query, query
                head i reading key/value head i // group, and with
                `qk_norm` q and k pass a norm of their own over the
                head's width (`q_norm`, `k_norm`) before the rotation.
                `head_dim` sets the heads' width where it is not
                hidden / heads; `partial_rotary_factor` turns only the
                head's first share of dims; with `attn_output_gate`
                `q_proj` gives each head its query and a gate side by
                side, and the head's output is multiplied by
                sigmoid(gate) before `proj`.
                "conv": no attention but LFM2's gated short convolution,
                    (B | C | z) = x conv_in,  out = (C * conv(B * z))
                    conv_out
                a causal depthwise convolution of `conv_L_cache` taps
                (`conv_taps` [h, taps], `F.gated_short_conv`), no bias.
                "mla": latent attention (DeepSeek-V2)
                    c_q = q_a_norm(x q_a_proj),  q = c_q q_b_proj
                    (c_kv | k_r) = x kv_a_proj,  (k_n | v) =
                    kv_a_norm(c_kv) kv_b_proj
                q and k are `qk_nope_head_dim` wide without position and
                `qk_rope_head_dim` with it (one rotary key for all
                heads), v and the output `v_head_dim`; `o_proj` closes
                "gdn": Gated DeltaNet (Yang et al. 2024), `linear_*`
                key and value heads:
                    (q|k|v|z per key head) = x in_proj_qkvz,
                    (b|a per key head) = x in_proj_ba,
                    (q|k|v) = silu(conv(q|k|v))   (`conv_taps`, causal,
                        depthwise, `linear_conv_kernel_dim` taps)
                    o = gated delta rule (`F.gated_delta_rule`: decay
                        from a, `A_log`, `dt_bias`; write strength b)
                    out = (rms(o) * gdn_norm * silu(z)) out_proj
                the norm over each value head, eps `norm_eps`; the
                two projections' device time goes under `gdn_proj`

Under `GPTConfig` the block registers `ln1 qkv proj ln2 fc1 fc2` and
stages the operations `GPTBlock` always staged, in their order.
"""
from jax.sharding import PartitionSpec as P

from .. import nn, ops
from ..nn import functional as F


class DecoderConfig:
    """The block's choices, as GPT-2/3 makes them. A model's config
    class sets the sizes (`hidden_size`, `num_heads`,
    `intermediate_size`) and overrides what its family changes."""

    norm = "layer_norm"
    norm_eps = 1e-5
    sandwich = False
    rope_theta = None
    fused_qkv = True
    linear_bias = True
    ffn = "gelu"
    attention = "mha"
    rope_interleaved = False
    hidden_dropout = 0.0
    attention_dropout = 0.0
    use_mp = False
    num_key_value_heads = None  # as many as `num_heads`
    qk_norm = False
    conv_L_cache = 3
    n_shared_experts = 1
    shared_expert_gate = False
    router_scoring = "sigmoid"
    router_norm_eps = 1e-20
    head_dim = None  # hidden_size // num_heads
    partial_rotary_factor = 1.0
    attn_output_gate = False


class ZeroCentredRMSNorm(nn.Layer):
    """x / sqrt(mean(x^2) + eps) * (1 + w) over the last axis, w drawn
    at zero (Qwen3-Next's norm). Computed as `F.rms_norm` is, float32
    under autocast, and its device time is `rms_norm`'s."""

    def __init__(self, width, epsilon):
        super().__init__()
        from ..nn.initializer import Constant

        self._epsilon = epsilon
        self.weight = self.create_parameter(
            [width], default_initializer=Constant(0.0))

    def forward(self, x):
        import jax.numpy as jnp

        from ..core.dispatch import call_op

        def _rms(v, w):
            var = jnp.mean(jnp.square(v), axis=-1, keepdims=True)
            return v / jnp.sqrt(var + self._epsilon) * (1.0 + w)

        return call_op(_rms, x, self.weight, op_name="rms_norm")


def make_norm(cfg, width=None):
    width = width or cfg.hidden_size
    if cfg.norm == "layer_norm":
        return nn.LayerNorm(width, epsilon=cfg.norm_eps)
    if cfg.norm == "rms_norm":
        return nn.RMSNorm(width, epsilon=cfg.norm_eps)
    if cfg.norm == "zero_centred_rms_norm":
        return ZeroCentredRMSNorm(width, cfg.norm_eps)
    raise ValueError(f"unknown norm {cfg.norm!r}")


class GatedFFN(nn.Layer):
    """down_proj(silu(gate_proj(x)) * up_proj(x)), no biases."""

    def __init__(self, hidden_size, intermediate_size):
        super().__init__()
        self.gate_proj = nn.Linear(hidden_size, intermediate_size,
                                   bias_attr=False)
        self.up_proj = nn.Linear(hidden_size, intermediate_size,
                                 bias_attr=False)
        self.down_proj = nn.Linear(intermediate_size, hidden_size,
                                   bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


def _by_part(x, heads, *groups):
    """x [..., heads * sum(widths)] holds each head's parts side by side;
    for each group of parts, the parts of every head, part by part (all
    heads' first part, then all heads' second, ..)."""
    import jax.numpy as jnp

    from ..core.dispatch import call_op

    widths = [w for group in groups for w in group]
    per_head = sum(widths)
    starts = [sum(widths[:i]) for i in range(len(widths))]

    def _gather(v):
        out, at = [], 0
        for group in groups:
            out.append(jnp.concatenate(
                [v[..., head * per_head + starts[at + i]:
                   head * per_head + starts[at + i] + width]
                 for i, width in enumerate(group) for head in range(heads)],
                axis=-1))
            at += len(group)
        return tuple(out)

    return call_op(_gather, x, op_name="split")


class DecoderBlock(nn.Layer):
    def __init__(self, cfg, ffn=None, attention=None):
        super().__init__()
        h = cfg.hidden_size
        bias = None if cfg.linear_bias else False
        ffn = ffn or cfg.ffn
        attention = attention or cfg.attention
        self.num_heads = cfg.num_heads
        self.num_kv_heads = cfg.num_key_value_heads or cfg.num_heads
        self.head_dim = cfg.head_dim or h // cfg.num_heads
        self.partial_rotary_factor = cfg.partial_rotary_factor
        self.rope_theta = cfg.rope_theta
        self.rope_interleaved = cfg.rope_interleaved
        self.attn_dropout_p = cfg.attention_dropout
        if ffn not in ("gelu", "swiglu", "moe"):
            raise ValueError(f"unknown ffn {ffn!r}")
        if attention not in ("mha", "mla", "conv", "gdn"):
            raise ValueError(f"unknown attention {attention!r}")
        grouped = self.num_kv_heads != self.num_heads
        if grouped and (cfg.fused_qkv or attention == "mla"
                        or self.num_heads % self.num_kv_heads):
            raise NotImplementedError(
                f"{self.num_kv_heads} key/value heads for {self.num_heads}: "
                f"grouped-query heads divide the query heads and come from "
                f"separate q_proj, k_proj, v_proj")
        self.ln1 = make_norm(cfg)
        if attention == "conv":
            from ..nn.initializer import Normal
            self.conv_in = nn.Linear(h, 3 * h, bias_attr=bias)
            self.conv_taps = self.create_parameter(
                [h, cfg.conv_L_cache], default_initializer=Normal(0.0, 0.02))
            self.conv_out = nn.Linear(h, h, bias_attr=bias)
        elif attention == "gdn":
            from ..nn.initializer import Constant, Normal
            nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
            dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
            if nv % nk:
                raise NotImplementedError(
                    f"{nv} value heads over {nk} key heads: Gated DeltaNet's "
                    f"value heads are a multiple of its key heads")
            self.gdn_dims = (nk, nv, dk, dv)
            self.in_proj_qkvz = nn.Linear(h, 2 * nk * dk + 2 * nv * dv,
                                          bias_attr=bias)
            self.in_proj_ba = nn.Linear(h, 2 * nv, bias_attr=bias)
            self.conv_taps = self.create_parameter(
                [2 * nk * dk + nv * dv, cfg.linear_conv_kernel_dim],
                default_initializer=Normal(0.0, 0.02))
            self.A_log = self.create_parameter(
                [nv], default_initializer=Constant(0.0))
            self.dt_bias = self.create_parameter(
                [nv], default_initializer=Constant(1.0))
            self.gdn_norm = self.create_parameter(
                [dv], default_initializer=Constant(1.0))
            self.gdn_norm_eps = cfg.norm_eps
            self.out_proj = nn.Linear(nv * dv, h, bias_attr=bias)
        elif attention == "mla":
            self.widths = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                           cfg.v_head_dim)
            nope, rope, v = self.widths
            self.q_a_proj = nn.Linear(h, cfg.q_lora_rank, bias_attr=bias)
            self.q_a_norm = make_norm(cfg, cfg.q_lora_rank)
            self.q_b_proj = nn.Linear(
                cfg.q_lora_rank, cfg.num_heads * (nope + rope),
                bias_attr=bias)
            self.kv_a_proj = nn.Linear(h, cfg.kv_lora_rank + rope,
                                       bias_attr=bias)
            self.kv_a_norm = make_norm(cfg, cfg.kv_lora_rank)
            self.kv_b_proj = nn.Linear(
                cfg.kv_lora_rank, cfg.num_heads * (nope + v), bias_attr=bias)
            self.o_proj = nn.Linear(cfg.num_heads * v, h, bias_attr=bias)
        else:
            width = self.num_heads * self.head_dim
            self.attn_output_gate = cfg.attn_output_gate
            if cfg.fused_qkv:
                if width != h or cfg.attn_output_gate:
                    raise NotImplementedError(
                        "the fused qkv projection has hidden-wide heads and "
                        "no output gate")
                self.qkv = nn.Linear(h, 3 * h, bias_attr=bias)
            else:
                kv = self.num_kv_heads * self.head_dim
                self.q_proj = nn.Linear(
                    h, width * (2 if cfg.attn_output_gate else 1),
                    bias_attr=bias)
                self.k_proj = nn.Linear(h, kv, bias_attr=bias)
                self.v_proj = nn.Linear(h, kv, bias_attr=bias)
            if cfg.qk_norm:
                self.q_norm = make_norm(cfg, self.head_dim)
                self.k_norm = make_norm(cfg, self.head_dim)
            self.proj = nn.Linear(width, h, bias_attr=bias)
        if cfg.sandwich:
            self.ln1_post = make_norm(cfg)
        self.ln2 = make_norm(cfg)
        if ffn == "gelu":
            self.fc1 = nn.Linear(h, cfg.intermediate_size, bias_attr=bias)
            self.fc2 = nn.Linear(cfg.intermediate_size, h, bias_attr=bias)
        elif ffn == "moe":
            from ..incubate.moe import HeldExpertsLayer
            self.moe = HeldExpertsLayer(
                h, cfg.moe_intermediate_size, cfg.n_routed_experts,
                cfg.num_experts_per_tok, ep_size=cfg.ep_size,
                ep_rank=cfg.ep_rank,
                routed_scaling_factor=cfg.routed_scaling_factor,
                norm_eps=cfg.router_norm_eps, scoring=cfg.router_scoring)
            if cfg.n_shared_experts:
                self.shared_expert = GatedFFN(
                    h, cfg.n_shared_experts * cfg.moe_intermediate_size)
                if cfg.shared_expert_gate:
                    self.shared_expert_gate = nn.Linear(h, 1, bias_attr=False)
        else:
            self.gate_proj = nn.Linear(h, cfg.intermediate_size,
                                       bias_attr=bias)
            self.up_proj = nn.Linear(h, cfg.intermediate_size,
                                     bias_attr=bias)
            self.down_proj = nn.Linear(cfg.intermediate_size, h,
                                       bias_attr=bias)
        if cfg.sandwich:
            self.ln2_post = make_norm(cfg)
        self.dropout = nn.Dropout(cfg.hidden_dropout)
        if cfg.use_mp:
            if not (cfg.fused_qkv and ffn == "gelu" and cfg.linear_bias
                    and attention == "mha"):
                raise NotImplementedError(
                    "use_mp shards the fused, biased GPT block only")
            self.qkv.weight.pspec = P(None, "mp")
            self.qkv.bias.pspec = P("mp")
            self.proj.weight.pspec = P("mp", None)
            self.fc1.weight.pspec = P(None, "mp")
            self.fc1.bias.pspec = P("mp")
            self.fc2.weight.pspec = P("mp", None)

    def _qkv(self, h, b, s):
        """q, k, v [b, s, heads, head_dim] and the output gate (None
        without one)."""
        if "qkv" in self._sub_layers:
            qkv = ops.reshape(self.qkv(h),
                              [b, s, 3, self.num_heads, self.head_dim])
            return (*ops.unstack(qkv, axis=2), None)
        shape = [b, s, self.num_heads, self.head_dim]
        kv_shape = [b, s, self.num_kv_heads, self.head_dim]
        if self.attn_output_gate:  # each head's query, then its gate
            q, gate = ops.split(ops.reshape(
                self.q_proj(h), shape[:3] + [2 * self.head_dim]), 2, axis=-1)
        else:
            q, gate = ops.reshape(self.q_proj(h), shape), None
        return (q, ops.reshape(self.k_proj(h), kv_shape),
                ops.reshape(self.v_proj(h), kv_shape), gate)

    def _ffn(self, h):
        if "fc1" in self._sub_layers:
            return self.fc2(F.gelu(self.fc1(h)))
        if "shared_expert_gate" in self._sub_layers:
            return self.moe(h) + F.sigmoid(self.shared_expert_gate(h)) \
                * self.shared_expert(h)
        if "shared_expert" in self._sub_layers:
            return self.moe(h) + self.shared_expert(h)
        if "moe" in self._sub_layers:
            return self.moe(h)
        return self.down_proj(F.silu(self.gate_proj(h)) * self.up_proj(h))

    def _rope(self, x):
        d = x.shape[-1]
        turned = int(d * self.partial_rotary_factor)
        if turned == d:
            return F.rotary_embedding(x, theta=self.rope_theta,
                                      interleaved=self.rope_interleaved)
        x_rot, x_pass = ops.split(x, [turned, d - turned], axis=-1)
        return ops.concat([F.rotary_embedding(
            x_rot, theta=self.rope_theta, interleaved=self.rope_interleaved),
            x_pass], axis=-1)

    def _gated_delta_net(self, h, b, s):
        from ..observability.scopes import scope

        nk, nv, dk, dv = self.gdn_dims
        per = nv // nk  # value heads a key head
        with scope("gdn_proj"):
            qkvz = self.in_proj_qkvz(h)
            ba = ops.reshape(self.in_proj_ba(h), [b, s, nk, 2 * per])
        # each key head's (q | k | v | z), gathered part by part as whole
        # columns of the projection: no [b, s, heads, 768] relayout
        qkv, z = _by_part(qkvz, nk, [dk, dk, per * dv], [per * dv])
        gate_b, gate_a = ops.split(ba, 2, axis=-1)
        mixed = F.causal_conv_silu(qkv, self.conv_taps)
        q, k, v = ops.split(mixed, [nk * dk, nk * dk, nv * dv], axis=-1)
        o = F.gated_delta_rule(
            ops.reshape(q, [b, s, nk, dk]), ops.reshape(k, [b, s, nk, dk]),
            ops.reshape(v, [b, s, nv, dv]), ops.reshape(gate_a, [b, s, nv]),
            ops.reshape(gate_b, [b, s, nv]), self.A_log, self.dt_bias)
        o = F.gated_rms_norm(o, ops.reshape(z, [b, s, nv, dv]),
                             self.gdn_norm, self.gdn_norm_eps)
        with scope("gdn_proj"):
            return self.out_proj(ops.reshape(o, [b, s, nv * dv]))

    def _latent_attention(self, h, b, s):
        nope, rope, v_dim = self.widths
        heads = self.num_heads
        q = ops.reshape(self.q_b_proj(self.q_a_norm(self.q_a_proj(h))),
                        [b, s, heads, nope + rope])
        q_n, q_r = ops.split(q, [nope, rope], axis=-1)
        c_kv, k_r = ops.split(self.kv_a_proj(h),
                              [self.kv_a_norm.weight.shape[0], rope], axis=-1)
        kv = ops.reshape(self.kv_b_proj(self.kv_a_norm(c_kv)),
                         [b, s, heads, nope + v_dim])
        k_n, v = ops.split(kv, [nope, v_dim], axis=-1)
        k_r = self._rope(ops.reshape(k_r, [b, s, 1, rope]))
        q = ops.concat([q_n, self._rope(q_r)], axis=-1)
        # the one rotary key, copied to every head
        k = ops.concat([k_n, ops.expand(k_r, [b, s, heads, rope])], axis=-1)
        ctx = F.scaled_dot_product_attention(
            q, k, v, is_causal=True, dropout_p=self.attn_dropout_p,
            training=self.training)
        return self.o_proj(ops.reshape(ctx, [b, s, heads * v_dim]))

    def _attention(self, h, b, s):
        from ..jit.to_static import note_structure

        if "conv_in" in self._sub_layers:
            note_structure("short_conv_layers")
            return self.conv_out(F.gated_short_conv(self.conv_in(h),
                                                    self.conv_taps))
        if "q_a_proj" in self._sub_layers:
            return self._latent_attention(h, b, s)
        if "in_proj_qkvz" in self._sub_layers:
            note_structure("gdn_layers")
            return self._gated_delta_net(h, b, s)
        q, k, v, gate = self._qkv(h, b, s)
        if "q_norm" in self._sub_layers:
            q, k = self.q_norm(q), self.k_norm(k)
        if self.num_kv_heads != self.num_heads:
            note_structure("gqa_attention_layers")
        if self.rope_theta is not None:
            q, k = self._rope(q), self._rope(k)
        ctx = F.scaled_dot_product_attention(
            q, k, v, is_causal=True, dropout_p=self.attn_dropout_p,
            training=self.training)
        if gate is not None:
            ctx = ctx * F.sigmoid(gate)
        return self.proj(ops.reshape(
            ctx, [b, s, self.num_heads * self.head_dim]))

    def forward(self, x):
        b, s = x.shape[0], x.shape[1]
        sandwich = "ln1_post" in self._sub_layers
        a = self._attention(self.ln1(x), b, s)
        if sandwich:
            a = self.ln1_post(a)
        x = x + self.dropout(a)
        m = self._ffn(self.ln2(x))
        if sandwich:
            m = self.ln2_post(m)
        x = x + self.dropout(m)
        return x
