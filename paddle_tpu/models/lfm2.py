"""LFM2-MoE (LiquidAI, `model_type: lfm2_moe`, LFM2-24B-A2B): a pre-norm
RMSNorm decoder whose layers alternate by `layer_types` between a gated
short convolution (three layers in four) and grouped-query attention
with a norm on each q and k head before the rotation; the first
`num_dense_layers` carry a SiLU-gated FFN, the others sigmoid-routed
experts with no shared expert; the head is the embedding, tied.

    h      = x + mixer_l(rms(x; operator_norm))
    out    = h + ffn_l(rms(h; ffn_norm))
    logits = rms(out_last; embedding_norm) E^T

The block is `models/decoder.py`'s, configured `ffn = "moe"`,
`n_shared_experts = 0`, and a layer `attention = "conv"` or `"mha"`
(the block registers `operator_norm` as `ln1`, `ffn_norm` as `ln2`, the
operator's projections as `conv_in` / `conv_out`, the attention's output
projection as `proj`). Of each layer's `num_experts` this device holds
`num_experts // ep_size`, those of `ep_rank`; the router keeps its full
width and what the absent experts would add is left out
(`incubate.moe.HeldExpertsLayer`).
"""
from .. import nn, ops
from ..nn import functional as F
from ..observability.scopes import scope
from .decoder import DecoderBlock, DecoderConfig, make_norm

_MIXERS = {"conv": "conv", "full_attention": "mha"}


class Lfm2MoeConfig(DecoderConfig):
    """The keys of the model's `config.json`, and two that it does not
    ship: `ep_size` and `ep_rank`, over how many devices each layer's
    experts are divided and which share is held here."""

    norm = "rms_norm"
    sandwich = False
    fused_qkv = False
    linear_bias = False
    ffn = "moe"
    qk_norm = True
    n_shared_experts = 0
    router_norm_eps = 1e-6

    def __init__(self, vocab_size=65536, hidden_size=2048,
                 intermediate_size=11776, moe_intermediate_size=1536,
                 num_hidden_layers=40, layer_types=None,
                 num_attention_heads=32, num_key_value_heads=8,
                 num_dense_layers=2, num_experts=64, num_experts_per_tok=4,
                 norm_topk_prob=True, use_expert_bias=True,
                 routed_scaling_factor=1.0, conv_L_cache=3, conv_bias=False,
                 norm_eps=1e-5, rope_parameters=None,
                 max_position_embeddings=128000, model_type="lfm2_moe",
                 tie_word_embeddings=True, ep_size=1, ep_rank=0):
        rope = dict(rope_parameters
                    or {"rope_theta": 1000000.0, "rope_type": "default"})
        if layer_types is None:  # the published period: attention third
            layer_types = ["full_attention" if i % 4 == 2 else "conv"
                           for i in range(num_hidden_layers)]
        refused = {
            "model_type": (model_type, "lfm2_moe"),
            "conv_bias": (conv_bias, False),
            "norm_topk_prob": (norm_topk_prob, True),
            "use_expert_bias": (use_expert_bias, True),
            "rope_type": (rope.get("rope_type", "default"), "default"),
            "tie_word_embeddings": (tie_word_embeddings, True),
        }
        for key, (got, can) in refused.items():
            if got != can:
                raise NotImplementedError(
                    f"{key}={got!r} has no path yet (only {can!r})")
        unknown = sorted(set(layer_types) - set(_MIXERS))
        if unknown or len(layer_types) != num_hidden_layers:
            raise NotImplementedError(
                f"layer_types must name {num_hidden_layers} layers, each "
                f"one of {sorted(_MIXERS)}; got {len(layer_types)} with "
                f"{unknown or 'no'} unknown")
        if not 0 <= num_dense_layers <= num_hidden_layers:
            raise NotImplementedError(
                f"num_dense_layers={num_dense_layers} of "
                f"{num_hidden_layers} layers")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.layer_types = list(layer_types)
        self.num_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.num_dense_layers = num_dense_layers
        self.n_routed_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.routed_scaling_factor = routed_scaling_factor
        self.conv_L_cache = conv_L_cache
        self.norm_eps = norm_eps
        self.rope_theta = float(rope["rope_theta"])
        self.max_position_embeddings = max_position_embeddings
        self.ep_size = ep_size
        self.ep_rank = ep_rank


class Lfm2MoeModel(nn.Layer):
    """Embedding, the stack and `embedding_norm` (registered `norm`)."""

    def __init__(self, cfg):
        super().__init__()
        self.config = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([
            DecoderBlock(cfg, ffn="swiglu" if i < cfg.num_dense_layers
                         else "moe", attention=_MIXERS[kind])
            for i, kind in enumerate(cfg.layer_types)])
        self.norm = make_norm(cfg)

    def forward(self, input_ids):
        h = self.embed_tokens(input_ids)
        for layer in self.layers:
            h = layer(h)
        return self.norm(h)


class Lfm2MoeForCausalLM(nn.Layer):
    def __init__(self, cfg=None, **kwargs):
        super().__init__()
        cfg = cfg or Lfm2MoeConfig(**kwargs)
        self.config = cfg
        self.model = Lfm2MoeModel(cfg)

    def enable_layer_recompute(self, policy="full"):
        """Each decoder layer a recompute segment."""
        for layer in self.model.layers:
            layer.enable_recompute(policy)
        return self

    def head(self, h):
        """h [..., hidden] against the embedding, the tied head."""
        with scope("head"):
            return ops.matmul(h, self.model.embed_tokens.weight,
                              transpose_y=True)

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
