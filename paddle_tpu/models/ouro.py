"""Ouro (ByteDance, "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741): a decoder stack applied `total_ut_steps`
times over the same weights, a final norm closing every pass, a head and
an exit gate read after each pass, and a training loss over all exits.

    h^0 = E[ids]
    h^t = norm_f(stack(h^(t-1)))                  t = 1..T, shared weights
    z^t = h^t W_head,   lam^t = sigmoid(h^t . w_g + b_g)
    p^t = lam^t prod_{j<t} (1 - lam^j)   (t < T),   p^T = prod_{j<T} (1 - lam^j)
    loss = mean_positions [ sum_t p^t CE(z^t, next token) - beta H(p) ]

The block is `models/decoder.py`'s, configured as the model's published
code does: RMSNorm before and after each sublayer, rotary positions,
separate bias-free projections, a SiLU-gated FFN. The pass loop is
`nn.fixed_loop`: one rolled region in a compiled step (the stack is
traced once; the parameters' gradients are summed over the passes), the
python loop eagerly. `early_exit_threshold` is an inference setting: in
training every token runs every pass.
"""
from .. import nn, ops
from ..nn import functional as F
from ..observability.scopes import scope
from .decoder import DecoderBlock, DecoderConfig, make_norm


class OuroConfig(DecoderConfig):
    """The keys of the model's `config.json`, and `exit_entropy_beta`,
    the training objective's (the paper's stage 1)."""

    norm = "rms_norm"
    sandwich = True
    fused_qkv = False
    linear_bias = False
    ffn = "swiglu"

    def __init__(self, vocab_size=49152, hidden_size=2048,
                 intermediate_size=5632, num_hidden_layers=48,
                 num_attention_heads=16, num_key_value_heads=None,
                 head_dim=None, hidden_act="silu",
                 max_position_embeddings=65536, rms_norm_eps=1e-6,
                 rope_theta=1000000.0, tie_word_embeddings=False,
                 total_ut_steps=4, early_exit_threshold=1.0,
                 exit_entropy_beta=0.05):
        kv_heads = num_key_value_heads or num_attention_heads
        if kv_heads != num_attention_heads:
            raise NotImplementedError(
                f"grouped-query heads ({kv_heads} key-value heads for "
                f"{num_attention_heads}) have no path yet")
        if (head_dim or hidden_size // num_attention_heads) \
                * num_attention_heads != hidden_size:
            raise NotImplementedError(
                "head_dim * num_attention_heads must be hidden_size")
        if hidden_act != "silu":
            raise NotImplementedError(f"hidden_act {hidden_act!r}")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_heads = num_attention_heads
        self.max_position_embeddings = max_position_embeddings
        self.norm_eps = rms_norm_eps
        self.rope_theta = float(rope_theta)
        self.tie_word_embeddings = tie_word_embeddings
        self.total_ut_steps = total_ut_steps
        self.early_exit_threshold = early_exit_threshold
        self.exit_entropy_beta = exit_entropy_beta


class OuroModel(nn.Layer):
    def __init__(self, cfg=None, **kwargs):
        super().__init__()
        cfg = cfg or OuroConfig(**kwargs)
        self.config = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([DecoderBlock(cfg)
                                    for _ in range(cfg.num_hidden_layers)])
        self.norm = make_norm(cfg)

    def one_pass(self, h):
        for layer in self.layers:
            h = layer(h)
        return self.norm(h)

    def forward(self, input_ids):
        """Every pass's output, stacked [total_ut_steps, b, s, h]."""
        (passes,) = nn.fixed_loop(self.one_pass,
                                  [self.embed_tokens(input_ids)],
                                  self.config.total_ut_steps)
        return passes


class OuroForCausalLM(nn.Layer):
    def __init__(self, cfg=None, **kwargs):
        super().__init__()
        cfg = cfg or OuroConfig(**kwargs)
        self.config = cfg
        self.model = OuroModel(cfg)
        if not cfg.tie_word_embeddings:
            self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                     bias_attr=False)
        self.exit_gate = nn.Linear(cfg.hidden_size, 1)

    def enable_layer_recompute(self, policy="full"):
        """Each decoder layer a recompute segment, in every pass."""
        for layer in self.model.layers:
            layer.enable_recompute(policy)
        return self

    def exit(self, h):
        """One pass's output through the head and the gate: logits
        [..., vocab] and the gate's logit [...] in float32."""
        with scope("head"):
            if self.config.tie_word_embeddings:
                z = ops.matmul(h, self.model.embed_tokens.weight,
                               transpose_y=True)
            else:
                z = self.lm_head(h)
        with scope("exit_gate"):
            g = ops.cast(ops.squeeze(self.exit_gate(h), axis=-1), "float32")
        return z, g

    def forward(self, input_ids, labels=None):
        """(logits, gate logits): a list each, one entry a pass —
        [b, s, vocab] and [b, s]. With `labels` [b, s] (the ids: the
        loss shifts them by one), the training loss."""
        passes = ops.unstack(self.model(input_ids), axis=0)
        if labels is None:
            logits, gates = zip(*[self.exit(h) for h in passes])
            return list(logits), list(gates)
        # the last position has no next token and meets no head; the
        # rest go through it as rows (a [b, s - 1, vocab] array costs a
        # relayout to become rows once s - 1 is no multiple of a tile)
        width = self.config.hidden_size
        logits, gates = zip(*[self.exit(ops.reshape(h[:, :-1], [-1, width]))
                              for h in passes])
        return self.loss(logits, gates, labels[:, 1:])

    @staticmethod
    def exit_log_probs(gates):
        """log p^t for each exit from the gates' logits: the chance of
        leaving at t is lam^t times that of not having left before; the
        last exit takes what is left."""
        out, stayed = [], None
        for g in gates[:-1]:
            leave = F.log_sigmoid(g)
            out.append(leave if stayed is None else leave + stayed)
            stay = F.log_sigmoid(-g)
            stayed = stay if stayed is None else stayed + stay
        out.append(gates[-1] * 0.0 if stayed is None else stayed)
        return out

    def loss(self, logits, gates, targets):
        """The expected cross entropy of `targets` [b, n] under the exit
        distribution, less beta times that distribution's entropy, a
        mean over the positions: `logits` and `gates` as `forward`
        returns them, position for position with the targets."""
        v = logits[0].shape[-1]
        targets = ops.reshape(targets, [-1])
        with scope("loss"):
            ce = [F.cross_entropy(ops.reshape(z, [-1, v]), targets,
                                  reduction="none") for z in logits]
        with scope("exit_loss"):
            log_p = self.exit_log_probs([ops.reshape(g, [-1])
                                         for g in gates])
            beta = self.config.exit_entropy_beta
            total = None
            for ce_t, lp in zip(ce, log_p):
                term = ops.exp(lp) * (ce_t + beta * lp)
                total = term if total is None else total + term
            return ops.mean(total)
