"""JoyAI-LLM-Flash (jdopensource, `model_type: joyai_llm_flash`, 48B-A2.7B;
DeepSeek-V3's key set): a pre-norm RMSNorm decoder with latent attention
(two low-rank projections with a norm inside each, one rotary key shared
by all heads, 192-wide keys and 128-wide values), a leading dense
SiLU-gated layer, then layers of sigmoid-routed experts plus a shared
expert, and a multi-token-prediction module (DeepSeek-V3 report,
arXiv:2412.19437, eqs. 21-25) trained beside the main head:

    h_i   = stack(E[t])_i                                (before norm_f)
    h'_i  = [rms(E[t_{i+1}]) ; rms(h_i)] W_eh            (4096 -> 2048)
    loss  = CE(head(norm_f(h_i)), t_{i+1})
            + lambda * CE(head(norm_mtp(TRM(h')_i)), t_{i+2})

each a mean over its own positions, the embedding and the head shared
by both. The block is `models/decoder.py`'s, configured `attention =
"mla"`, `ffn = "moe"` (layer 0: `"swiglu"`). Of each layer's
`n_routed_experts` this device holds `n_routed_experts // ep_size`,
those of `ep_rank`; the router keeps its full width and what the absent
experts would add is left out (`incubate.moe.HeldExpertsLayer`).
"""
from .. import nn, ops
from ..nn import functional as F
from ..observability.scopes import scope
from .decoder import DecoderBlock, DecoderConfig, make_norm


class JoyAIFlashConfig(DecoderConfig):
    """The keys of the model's `config.json` (`ep_size` among them), and
    two that it does not ship: `ep_rank`, which of the `ep_size` shares
    of every expert layer is held here, and `mtp_loss_weight`, the
    training objective's lambda."""

    norm = "rms_norm"
    sandwich = False
    fused_qkv = False
    linear_bias = False
    ffn = "moe"
    attention = "mla"

    def __init__(self, vocab_size=129280, hidden_size=2048,
                 intermediate_size=7168, moe_intermediate_size=768,
                 num_hidden_layers=40, num_attention_heads=32,
                 num_key_value_heads=None, q_lora_rank=1536,
                 kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, n_routed_experts=256,
                 n_shared_experts=1, num_experts_per_tok=8,
                 first_k_dense_replace=1, moe_layer_freq=1, n_group=1,
                 topk_group=1, norm_topk_prob=True,
                 routed_scaling_factor=2.5, scoring_func="sigmoid",
                 topk_method="noaux_tc", num_nextn_predict_layers=1,
                 hidden_act="silu", attention_bias=False,
                 max_position_embeddings=131072, rms_norm_eps=1e-6,
                 rope_theta=32000000.0, rope_interleave=True,
                 rope_scaling=None, tie_word_embeddings=False, ep_size=1,
                 ep_rank=0, mtp_loss_weight=0.3):
        refused = {
            "num_key_value_heads": (num_key_value_heads
                                    or num_attention_heads,
                                    num_attention_heads),
            "moe_layer_freq": (moe_layer_freq, 1),
            "n_group": (n_group, 1), "topk_group": (topk_group, 1),
            "norm_topk_prob": (norm_topk_prob, True),
            "scoring_func": (scoring_func, "sigmoid"),
            "topk_method": (topk_method, "noaux_tc"),
            "num_nextn_predict_layers": (num_nextn_predict_layers, 1),
            "hidden_act": (hidden_act, "silu"),
            "attention_bias": (attention_bias, False),
            "rope_scaling": (rope_scaling, None),
            "tie_word_embeddings": (tie_word_embeddings, False),
        }
        for key, (got, can) in refused.items():
            if got != can:
                raise NotImplementedError(
                    f"{key}={got!r} has no path yet (only {can!r})")
        if not 0 < first_k_dense_replace < num_hidden_layers:
            raise NotImplementedError(
                f"first_k_dense_replace={first_k_dense_replace} of "
                f"{num_hidden_layers} layers: at least one dense and one "
                f"expert layer")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_heads = num_attention_heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.n_routed_experts = n_routed_experts
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.first_k_dense_replace = first_k_dense_replace
        self.routed_scaling_factor = routed_scaling_factor
        self.max_position_embeddings = max_position_embeddings
        self.norm_eps = rms_norm_eps
        self.rope_theta = float(rope_theta)
        self.rope_interleaved = bool(rope_interleave)
        self.ep_size = ep_size
        self.ep_rank = ep_rank
        self.mtp_loss_weight = mtp_loss_weight


class JoyAIFlashModel(nn.Layer):
    """Embedding and stack; `forward` returns the last layer's output
    BEFORE the final norm (the MTP module reads it there) and `norm` is
    the caller's to apply."""

    def __init__(self, cfg):
        super().__init__()
        self.config = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([
            DecoderBlock(cfg, ffn="swiglu"
                         if i < cfg.first_k_dense_replace else "moe")
            for i in range(cfg.num_hidden_layers)])
        self.norm = make_norm(cfg)

    def forward(self, input_ids):
        h = self.embed_tokens(input_ids)
        for layer in self.layers:
            h = layer(h)
        return h


class MTPModule(nn.Layer):
    """One multi-token-prediction depth: the next token's embedding and
    the main stack's output, each normed, concatenated (embedding
    first) and projected by `eh_proj`; one more expert layer; a final
    norm of its own. The embedding and the head are the main model's,
    handed in at the call."""

    def __init__(self, cfg):
        super().__init__()
        self.enorm = make_norm(cfg)
        self.hnorm = make_norm(cfg)
        self.eh_proj = nn.Linear(2 * cfg.hidden_size, cfg.hidden_size,
                                 bias_attr=False)
        self.block = DecoderBlock(cfg, ffn="moe")
        self.norm = make_norm(cfg)

    def forward(self, h, next_ids, embed, head, keep):
        """h [b, s, hidden] and the ids one to the right of its
        positions -> the logits of the module's first `keep` positions,
        as rows."""
        x = ops.concat([self.enorm(embed(next_ids)), self.hnorm(h)], axis=-1)
        out = self.norm(self.block(self.eh_proj(x)))
        with scope("head"):
            return head(ops.reshape(out[:, :keep], [-1, out.shape[-1]]))


class JoyAIFlashForCausalLM(nn.Layer):
    def __init__(self, cfg=None, **kwargs):
        super().__init__()
        cfg = cfg or JoyAIFlashConfig(**kwargs)
        self.config = cfg
        self.model = JoyAIFlashModel(cfg)
        self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                 bias_attr=False)
        self.mtp = MTPModule(cfg)

    def enable_layer_recompute(self, policy="full"):
        """Each decoder layer a recompute segment, the module's too."""
        for layer in list(self.model.layers) + [self.mtp.block]:
            layer.enable_recompute(policy)
        return self

    def forward(self, input_ids, labels=None):
        """The main head's logits [b, s, vocab]; with `labels` [b, s]
        (the ids: the losses shift them), the training loss."""
        h = self.model(input_ids)
        if labels is None:
            with scope("head"):
                return self.lm_head(self.model.norm(h))
        b, s = input_ids.shape[0], input_ids.shape[1]
        width = self.config.hidden_size
        # the last position has no next token and meets no head; the
        # rest go through it as rows (as Ouro's exits do)
        with scope("head"):
            z = self.lm_head(ops.reshape(self.model.norm(h)[:, :-1],
                                         [-1, width]))
        with scope("loss"):
            main = F.cross_entropy(z, ops.reshape(labels[:, 1:], [-1]))
        # position i of the module sees token i + 1 and predicts token
        # i + 2. It runs over all s positions, the last fed a filler id:
        # attention is causal, so what the last position holds reaches
        # no other, and the last two meet no head
        next_ids = ops.concat([input_ids[:, 1:], input_ids[:, -1:]], axis=1)
        z2 = self.mtp(h, next_ids, self.model.embed_tokens, self.lm_head,
                      s - 2)
        with scope("loss"):
            more = F.cross_entropy(z2, ops.reshape(labels[:, 2:], [-1]))
            return main + self.config.mtp_loss_weight * more
