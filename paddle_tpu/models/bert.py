"""BERT/ERNIE-style encoder for pretraining
(ERNIE-1.0 / BERT-base pretraining, Fleet DP).

TPU-first: bf16 activations, fused XLA attention (pallas flash for long seq),
GSPMD sharding specs on every parameter (dp-replicated / mp-sharded per the
Megatron pattern when an 'mp' axis is present). The whole train step compiles
to one XLA program via @to_static.

Reference shape: PaddleNLP ernie/bert modeling (the reference repo ships the
framework, model zoos live in PaddleNLP — capability parity means this model
family trains on the framework).
"""
import numpy as np
from jax.sharding import PartitionSpec as P

from .. import nn, ops
from ..nn import functional as F
from ..observability.scopes import scope


class BertConfig:
    def __init__(self, vocab_size=30522, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=3072,
                 max_position_embeddings=512, type_vocab_size=2,
                 hidden_dropout=0.1, attention_dropout=0.1, use_mp=False,
                 hidden_act="gelu_tanh"):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size
        self.max_position_embeddings = max_position_embeddings
        self.type_vocab_size = type_vocab_size
        self.hidden_dropout = hidden_dropout
        self.attention_dropout = attention_dropout
        self.use_mp = use_mp  # annotate weights for the 'mp' mesh axis
        # "gelu_tanh" (default) uses the tanh approximation, standard in
        # BERT/GPT pretraining stacks: tanh is a hardware transcendental.
        # The exact form ("gelu") evaluates its erfc polynomial once a
        # layer since `F.gelu` keeps the factor (PR 30): 0.19 ms over a
        # [4096, 8192] tensor on a v5e, 1.3 % of the GPT-3 1.3B s2048
        # step; left to XLA:TPU it ran in three fusions of the next
        # matmul and cost 6.7 % of that step (chip runs, PR 30; PERF.md)
        self.hidden_act = hidden_act


def _act_fn(cfg):
    act = getattr(cfg, "hidden_act", "gelu_tanh")
    if act in ("gelu_tanh", "gelu_new", "gelu_approx"):
        return lambda v: F.gelu(v, approximate=True)
    if act == "gelu":
        return F.gelu
    if act == "relu":
        return F.relu
    raise ValueError(f"unknown hidden_act {act!r}")


def bert_base(**kw):
    return BertConfig(**kw)


def bert_large(**kw):
    return BertConfig(hidden_size=1024, num_layers=24, num_heads=16,
                      intermediate_size=4096, **kw)


class BertEmbeddings(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.word_embeddings = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.position_embeddings = nn.Embedding(cfg.max_position_embeddings,
                                                cfg.hidden_size)
        self.token_type_embeddings = nn.Embedding(cfg.type_vocab_size,
                                                  cfg.hidden_size)
        self.layer_norm = nn.LayerNorm(cfg.hidden_size)
        self.dropout = nn.Dropout(cfg.hidden_dropout)
        if cfg.use_mp:
            self.word_embeddings.weight.pspec = P("mp", None)

    def forward(self, input_ids, token_type_ids=None):
        seq_len = input_ids.shape[1]
        pos_ids = ops.arange(seq_len, dtype="int32")
        emb = self.word_embeddings(input_ids)
        emb = emb + self.position_embeddings(pos_ids)
        if token_type_ids is not None:
            emb = emb + self.token_type_embeddings(token_type_ids)
        return self.dropout(self.layer_norm(emb))


class BertSelfAttention(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.num_heads = cfg.num_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        h = cfg.hidden_size
        self.qkv = nn.Linear(h, 3 * h)
        self.out = nn.Linear(h, h)
        self.dropout_p = cfg.attention_dropout
        if cfg.use_mp:
            self.qkv.weight.pspec = P(None, "mp")
            self.qkv.bias.pspec = P("mp")
            self.out.weight.pspec = P("mp", None)
            self.out.bias.pspec = P()

    def forward(self, x, attn_mask=None):
        b, s = x.shape[0], x.shape[1]
        qkv = self.qkv(x)
        qkv = ops.reshape(qkv, [b, s, 3, self.num_heads, self.head_dim])
        q, k, v = ops.unstack(qkv, axis=2)
        ctx = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, dropout_p=self.dropout_p,
            training=self.training)
        ctx = ops.reshape(ctx, [b, s, self.num_heads * self.head_dim])
        return self.out(ctx)


class BertLayer(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        h = cfg.hidden_size
        self.attention = BertSelfAttention(cfg)
        self.norm1 = nn.LayerNorm(h)
        self.fc1 = nn.Linear(h, cfg.intermediate_size)
        self.fc2 = nn.Linear(cfg.intermediate_size, h)
        self.norm2 = nn.LayerNorm(h)
        self.dropout = nn.Dropout(cfg.hidden_dropout)
        self.act = _act_fn(cfg)
        if cfg.use_mp:
            self.fc1.weight.pspec = P(None, "mp")
            self.fc1.bias.pspec = P("mp")
            self.fc2.weight.pspec = P("mp", None)
            self.fc2.bias.pspec = P()

    def forward(self, x, attn_mask=None):
        x = self.norm1(x + self.dropout(self.attention(x, attn_mask)))
        x = self.norm2(x + self.dropout(self.fc2(self.act(self.fc1(x)))))
        return x


class BertModel(nn.Layer):
    def __init__(self, cfg=None, **kwargs):
        super().__init__()
        cfg = cfg or BertConfig(**kwargs)
        self.config = cfg
        self.embeddings = BertEmbeddings(cfg)
        self.layers = nn.LayerList([BertLayer(cfg)
                                    for _ in range(cfg.num_layers)])
        self.pooler = nn.Linear(cfg.hidden_size, cfg.hidden_size)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        x = self.embeddings(input_ids, token_type_ids)
        for layer in self.layers:
            x = layer(x, attention_mask)
        pooled = F.tanh(self.pooler(x[:, 0]))
        return x, pooled


class BertPretrainingHeads(nn.Layer):
    def __init__(self, cfg, embedding_weight=None):
        super().__init__()
        h = cfg.hidden_size
        self.transform = nn.Linear(h, h)
        self.layer_norm = nn.LayerNorm(h)
        self.decoder_bias = self.create_parameter(
            [cfg.vocab_size], is_bias=True)
        self._tied = embedding_weight  # weight tying with word embeddings
        self.seq_relationship = nn.Linear(h, 2)
        self.act = _act_fn(cfg)

    def forward(self, sequence_output, pooled_output):
        x = self.layer_norm(self.act(self.transform(sequence_output)))
        with scope("head"):
            logits = ops.matmul(x, self._tied, transpose_y=True)
            # bias joins in the logits dtype: an fp32 bias would promote
            # the [B*S, vocab] logits to fp32 (2x HBM on the biggest
            # tensor)
            logits = logits + ops.cast(self.decoder_bias, logits.dtype)
        nsp = self.seq_relationship(pooled_output)
        return logits, nsp


class BertForPretraining(nn.Layer):
    """MLM + NSP (the ERNIE-1.0/BERT pretraining objective)."""

    def __init__(self, cfg=None, **kwargs):
        super().__init__()
        cfg = cfg or BertConfig(**kwargs)
        self.config = cfg
        self.bert = BertModel(cfg)
        self.cls = BertPretrainingHeads(
            cfg, embedding_weight=self.bert.embeddings.word_embeddings.weight)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        seq, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        return self.cls(seq, pooled)

    def loss(self, prediction_logits, nsp_logits, masked_labels, nsp_labels,
             ignore_index=-100):
        with scope("loss"):
            mlm = F.cross_entropy(prediction_logits, masked_labels,
                                  ignore_index=ignore_index)
            nsp = F.cross_entropy(nsp_logits, nsp_labels)
            return mlm + nsp

    def flops_per_token(self, seq_len=None):
        """Training FLOPs/token ≈ 6*N + attention (for MFU accounting)."""
        cfg = self.config
        n_params = sum(p.size for p in self.parameters())
        s = seq_len or cfg.max_position_embeddings
        attn = 12 * cfg.num_layers * cfg.hidden_size * s  # 2*2*3 * L * h * s
        return 6 * n_params + attn


def synthetic_mlm_batch(batch_size, seq_len, vocab_size=30522, seed=0):
    """Deterministic synthetic pretraining batch (zero-egress environment)."""
    rng = np.random.RandomState(seed)
    input_ids = rng.randint(0, vocab_size, (batch_size, seq_len)).astype("int32")
    token_type = np.zeros((batch_size, seq_len), dtype="int32")
    labels = np.where(rng.rand(batch_size, seq_len) < 0.15,
                      input_ids, -100).astype("int32")
    nsp = rng.randint(0, 2, (batch_size,)).astype("int32")
    return input_ids, token_type, labels, nsp
