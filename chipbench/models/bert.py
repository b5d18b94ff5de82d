"""BERT (Devlin et al. 2018) for pre-training: MLM + NSP."""
import numpy as np

from . import _common


def weight_shapes(cfg):
    h, f, n = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["num_hidden_layers"]
    v = cfg["vocab_size"]
    return {
        "word_emb": ((v, h), "normal"),
        "pos_emb": ((cfg["max_position_embeddings"], h), "normal"),
        "type_emb": ((cfg["type_vocab_size"], h), "normal"),
        "emb_ln_w": ((h,), "ones"), "emb_ln_b": ((h,), "zeros"),
        "qkv_w": ((n, h, 3 * h), "normal"), "qkv_b": ((n, 3 * h), "zeros"),
        "out_w": ((n, h, h), "normal"), "out_b": ((n, h), "zeros"),
        "ln1_w": ((n, h), "ones"), "ln1_b": ((n, h), "zeros"),
        "fc1_w": ((n, h, f), "normal"), "fc1_b": ((n, f), "zeros"),
        "fc2_w": ((n, f, h), "normal"), "fc2_b": ((n, h), "zeros"),
        "ln2_w": ((n, h), "ones"), "ln2_b": ((n, h), "zeros"),
        "pooler_w": ((h, h), "normal"), "pooler_b": ((h,), "zeros"),
        "transform_w": ((h, h), "normal"), "transform_b": ((h,), "zeros"),
        "head_ln_w": ((h,), "ones"), "head_ln_b": ((h,), "zeros"),
        "decoder_b": ((v,), "zeros"),
        "nsp_w": ((h, 2), "normal"), "nsp_b": ((2,), "zeros"),
    }


# stacked key -> the parameter's name inside one `bert.layers.<i>`
_LAYER_NAMES = {
    "qkv_w": "attention.qkv.weight", "qkv_b": "attention.qkv.bias",
    "out_w": "attention.out.weight", "out_b": "attention.out.bias",
    "ln1_w": "norm1.weight", "ln1_b": "norm1.bias",
    "fc1_w": "fc1.weight", "fc1_b": "fc1.bias",
    "fc2_w": "fc2.weight", "fc2_b": "fc2.bias",
    "ln2_w": "norm2.weight", "ln2_b": "norm2.bias",
}
_TOP_NAMES = {
    "word_emb": "bert.embeddings.word_embeddings.weight",
    "pos_emb": "bert.embeddings.position_embeddings.weight",
    "type_emb": "bert.embeddings.token_type_embeddings.weight",
    "emb_ln_w": "bert.embeddings.layer_norm.weight",
    "emb_ln_b": "bert.embeddings.layer_norm.bias",
    "pooler_w": "bert.pooler.weight", "pooler_b": "bert.pooler.bias",
    "transform_w": "cls.transform.weight",
    "transform_b": "cls.transform.bias",
    "head_ln_w": "cls.layer_norm.weight",
    "head_ln_b": "cls.layer_norm.bias",
    "decoder_b": "cls.decoder_bias",
    "nsp_w": "cls.seq_relationship.weight",
    "nsp_b": "cls.seq_relationship.bias",
}


def stacked_keys():
    """The keys whose first axis is the layer."""
    return tuple(_LAYER_NAMES)


def program_names(cfg):
    names = {prog: (key, None) for key, prog in _TOP_NAMES.items()}
    for i in range(cfg["num_hidden_layers"]):
        for key, prog in _LAYER_NAMES.items():
            names[f"bert.layers.{i}.{prog}"] = (key, i)
    return names


def make_batch(cfg, cell, seed, step_index):
    """One step's batch: uniform ids, 15 % of the positions of every row
    masked (the same count in each row, so a mean of per-chip means is
    the global mean), labels -100 elsewhere, a coin per row for NSP."""
    rng = _common.batch_rng(seed, step_index)
    b, s = cell["batch"], cell["seq"]
    n_mask = max(1, round(0.15 * s))
    ids = rng.integers(0, cfg["vocab_size"], (b, s), dtype=np.int32)
    labels = np.full((b, s), -100, np.int32)
    pos = np.argsort(rng.random((b, s)), axis=1)[:, :n_mask]
    rows = np.arange(b)[:, None]
    labels[rows, pos] = ids[rows, pos]
    nsp = rng.integers(0, 2, (b,), dtype=np.int32)
    return ids, np.zeros_like(ids), labels, nsp


def matmul_params(cfg):
    """Parameters that every token multiplies: the layers' matrices,
    the MLM head's transform and the tied decoder once. Embedding
    look-ups, norms and biases multiply nothing, and the pooler and the
    NSP head see one token a row."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = 3 * h * h + h * h + 2 * h * f
    return (cfg["num_hidden_layers"] * per_layer + h * h
            + cfg["vocab_size"] * h)


def flops_per_token(cfg, seq):
    """Required forward+backward operations a token: 6 a matmul
    parameter, and attention's two products at the full square (BERT is
    bidirectional): 2 * 2*s*h forward, three times that with backward."""
    attn = 12 * cfg["num_hidden_layers"] * cfg["hidden_size"] * seq
    return 6 * matmul_params(cfg) + attn


def attention_calls(cfg, cell):
    """BERT's s512 attention runs on XLA's fused path: no kernel call."""
    return None


def build_step(cfg, cell, weights):
    import paddle_tpu as paddle
    from paddle_tpu.models import BertConfig, BertForPretraining

    model = BertForPretraining(BertConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"],
        hidden_dropout=cfg["hidden_dropout_prob"],
        attention_dropout=cfg["attention_probs_dropout_prob"],
        hidden_act=cfg["hidden_act"]))
    model.to(cfg["training"]["param_dtype"])
    _common.set_program_weights(model, program_names(cfg), weights)

    def forward_loss(ids, tok, labels, nsp_labels):
        logits, nsp = model(ids, tok)
        return model.loss(logits, nsp, labels, nsp_labels)

    step, opt = _common.build_train_step(model, forward_loss,
                                         cfg["training"], cell)
    return step, model, opt
