"""The causal decoder block the language models share, built from what
its configuration says of five things:

    norm        "layer_norm" (weight and bias) or "rms_norm" (weight)
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
                down_proj(silu(gate_proj(x)) * up_proj(x))

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
    hidden_dropout = 0.0
    attention_dropout = 0.0
    use_mp = False


def make_norm(cfg):
    if cfg.norm == "layer_norm":
        return nn.LayerNorm(cfg.hidden_size, epsilon=cfg.norm_eps)
    if cfg.norm == "rms_norm":
        return nn.RMSNorm(cfg.hidden_size, epsilon=cfg.norm_eps)
    raise ValueError(f"unknown norm {cfg.norm!r}")


class DecoderBlock(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        h = cfg.hidden_size
        bias = None if cfg.linear_bias else False
        self.num_heads = cfg.num_heads
        self.head_dim = h // cfg.num_heads
        self.rope_theta = cfg.rope_theta
        self.attn_dropout_p = cfg.attention_dropout
        if cfg.ffn not in ("gelu", "swiglu"):
            raise ValueError(f"unknown ffn {cfg.ffn!r}")
        self.ln1 = make_norm(cfg)
        if cfg.fused_qkv:
            self.qkv = nn.Linear(h, 3 * h, bias_attr=bias)
        else:
            self.q_proj = nn.Linear(h, h, bias_attr=bias)
            self.k_proj = nn.Linear(h, h, bias_attr=bias)
            self.v_proj = nn.Linear(h, h, bias_attr=bias)
        self.proj = nn.Linear(h, h, bias_attr=bias)
        if cfg.sandwich:
            self.ln1_post = make_norm(cfg)
        self.ln2 = make_norm(cfg)
        if cfg.ffn == "gelu":
            self.fc1 = nn.Linear(h, cfg.intermediate_size, bias_attr=bias)
            self.fc2 = nn.Linear(cfg.intermediate_size, h, bias_attr=bias)
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
            if not (cfg.fused_qkv and cfg.ffn == "gelu"
                    and cfg.linear_bias):
                raise NotImplementedError(
                    "use_mp shards the fused, biased GPT block only")
            self.qkv.weight.pspec = P(None, "mp")
            self.qkv.bias.pspec = P("mp")
            self.proj.weight.pspec = P("mp", None)
            self.fc1.weight.pspec = P(None, "mp")
            self.fc1.bias.pspec = P("mp")
            self.fc2.weight.pspec = P("mp", None)

    def _qkv(self, h, b, s):
        if "qkv" in self._sub_layers:
            qkv = ops.reshape(self.qkv(h),
                              [b, s, 3, self.num_heads, self.head_dim])
            return ops.unstack(qkv, axis=2)
        shape = [b, s, self.num_heads, self.head_dim]
        return (ops.reshape(self.q_proj(h), shape),
                ops.reshape(self.k_proj(h), shape),
                ops.reshape(self.v_proj(h), shape))

    def _ffn(self, h):
        if "fc1" in self._sub_layers:
            return self.fc2(F.gelu(self.fc1(h)))
        return self.down_proj(F.silu(self.gate_proj(h)) * self.up_proj(h))

    def forward(self, x):
        b, s = x.shape[0], x.shape[1]
        sandwich = "ln1_post" in self._sub_layers
        q, k, v = self._qkv(self.ln1(x), b, s)
        if self.rope_theta is not None:
            q = F.rotary_embedding(q, theta=self.rope_theta)
            k = F.rotary_embedding(k, theta=self.rope_theta)
        ctx = F.scaled_dot_product_attention(
            q, k, v, is_causal=True, dropout_p=self.attn_dropout_p,
            training=self.training)
        a = self.proj(ops.reshape(ctx, [b, s, self.num_heads * self.head_dim]))
        if sandwich:
            a = self.ln1_post(a)
        x = x + self.dropout(a)
        m = self._ffn(self.ln2(x))
        if sandwich:
            m = self.ln2_post(m)
        x = x + self.dropout(m)
        return x
