"""Qwen3-Next (Qwen, `model_type: qwen3_next`, Qwen3-Next-80B-A3B): a
pre-norm decoder whose layers are Gated DeltaNet linear attention three
in four and gated grouped-query attention the fourth, every layer with
softmax-routed experts and one sigmoid-gated shared expert; every norm
zero-centred, rms(x) * (1 + w); the head untied.

    h      = x + mixer_l(rms(x; input_layernorm))
    out    = h + moe_l(rms(h; post_attention_layernorm))
    logits = rms(out_last; norm) W_head

Layer i is full attention where (i + 1) % full_attention_interval == 0,
Gated DeltaNet otherwise. The attention has `head_dim`-wide heads (256,
not hidden / heads), turns the first `partial_rotary_factor` of each
head's dims, norms q and k per head, and multiplies each head's output
by sigmoid of a gate that `q_proj` gives beside its query.

The block is `models/decoder.py`'s, configured `norm =
"zero_centred_rms_norm"`, `ffn = "moe"` with `router_scoring =
"softmax"` and a gated shared expert, and a layer `attention = "gdn"`
or `"mha"` (the block registers `input_layernorm` as `ln1`,
`post_attention_layernorm` as `ln2`, the attention's `o_proj` as `proj`,
the router as `moe.router_weight`, the shared expert's gate as
`shared_expert_gate`). Of each layer's `num_experts` this device holds
`num_experts // ep_size`, those of `ep_rank`; the router keeps its full
width and what the absent experts would add is left out
(`incubate.moe.HeldExpertsLayer`).
"""
from .. import nn, ops
from ..nn import functional as F
from ..observability.scopes import scope
from .decoder import DecoderBlock, DecoderConfig, make_norm


class Qwen3NextConfig(DecoderConfig):
    """The keys of the model's `config.json`, and two that it does not
    ship: `ep_size` and `ep_rank`, over how many devices each layer's
    experts are divided and which share is held here."""

    norm = "zero_centred_rms_norm"
    sandwich = False
    fused_qkv = False
    linear_bias = False
    ffn = "moe"
    qk_norm = True
    attn_output_gate = True
    shared_expert_gate = True
    router_scoring = "softmax"
    router_norm_eps = 0.0
    routed_scaling_factor = 1.0

    def __init__(self, vocab_size=151936, hidden_size=2048,
                 intermediate_size=5120, moe_intermediate_size=512,
                 shared_expert_intermediate_size=512, num_hidden_layers=48,
                 num_attention_heads=16, num_key_value_heads=2, head_dim=256,
                 full_attention_interval=4, linear_num_key_heads=16,
                 linear_num_value_heads=32, linear_key_head_dim=128,
                 linear_value_head_dim=128, linear_conv_kernel_dim=4,
                 num_experts=512, num_experts_per_tok=10,
                 norm_topk_prob=True, decoder_sparse_step=1,
                 mlp_only_layers=(), hidden_act="silu", rms_norm_eps=1e-6,
                 partial_rotary_factor=0.25, rope_theta=10000000.0,
                 rope_scaling=None, max_position_embeddings=262144,
                 use_sliding_window=False, tie_word_embeddings=False,
                 model_type="qwen3_next", ep_size=1, ep_rank=0):
        refused = {
            "model_type": (model_type, "qwen3_next"),
            "use_sliding_window": (use_sliding_window, False),
            "mlp_only_layers": (list(mlp_only_layers), []),
            "decoder_sparse_step": (decoder_sparse_step, 1),
            "norm_topk_prob": (norm_topk_prob, True),
            "hidden_act": (hidden_act, "silu"),
            "rope_scaling": (rope_scaling, None),
            "tie_word_embeddings": (tie_word_embeddings, False),
        }
        for key, (got, can) in refused.items():
            if got != can:
                raise NotImplementedError(
                    f"{key}={got!r} has no path yet (only {can!r})")
        if shared_expert_intermediate_size % moe_intermediate_size:
            raise NotImplementedError(
                f"shared_expert_intermediate_size="
                f"{shared_expert_intermediate_size} has no path yet (only a "
                f"multiple of moe_intermediate_size={moe_intermediate_size})")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        # the dense FFN width: no layer has one (`mlp_only_layers` is [])
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.n_shared_experts = (shared_expert_intermediate_size
                                 // moe_intermediate_size)
        self.num_hidden_layers = num_hidden_layers
        self.num_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.full_attention_interval = full_attention_interval
        self.linear_num_key_heads = linear_num_key_heads
        self.linear_num_value_heads = linear_num_value_heads
        self.linear_key_head_dim = linear_key_head_dim
        self.linear_value_head_dim = linear_value_head_dim
        self.linear_conv_kernel_dim = linear_conv_kernel_dim
        self.n_routed_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_eps = rms_norm_eps
        self.partial_rotary_factor = partial_rotary_factor
        self.rope_theta = float(rope_theta)
        self.max_position_embeddings = max_position_embeddings
        self.ep_size = ep_size
        self.ep_rank = ep_rank

    @property
    def layer_types(self):
        return ["full_attention" if (i + 1) % self.full_attention_interval
                == 0 else "linear_attention"
                for i in range(self.num_hidden_layers)]


class Qwen3NextModel(nn.Layer):
    """Embedding, the stack and the final norm."""

    def __init__(self, cfg):
        super().__init__()
        self.config = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([
            DecoderBlock(cfg, attention="mha" if kind == "full_attention"
                         else "gdn")
            for kind in cfg.layer_types])
        self.norm = make_norm(cfg)

    def forward(self, input_ids):
        h = self.embed_tokens(input_ids)
        for layer in self.layers:
            h = layer(h)
        return self.norm(h)


class Qwen3NextForCausalLM(nn.Layer):
    def __init__(self, cfg=None, **kwargs):
        super().__init__()
        cfg = cfg or Qwen3NextConfig(**kwargs)
        self.config = cfg
        self.model = Qwen3NextModel(cfg)
        self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                 bias_attr=False)

    def enable_layer_recompute(self, policy="full"):
        """Each decoder layer a recompute segment."""
        for layer in self.model.layers:
            layer.enable_recompute(policy)
        return self

    def head(self, h):
        with scope("head"):
            return self.lm_head(h)

    def forward(self, input_ids, labels=None):
        """The logits [b, s, vocab]; with `labels` [b, s] (the ids: the
        loss shifts them), the mean cross-entropy of every position's
        next token."""
        h = self.model(input_ids)
        if labels is None:
            return self.head(h)
        # the last position has no next token and meets no head; the
        # rest go through it as rows
        z = self.head(ops.reshape(h[:, :-1], [-1, self.config.hidden_size]))
        with scope("loss"):
            return F.cross_entropy(z, ops.reshape(labels[:, 1:], [-1]))
