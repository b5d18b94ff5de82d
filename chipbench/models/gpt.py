"""GPT-3 (Brown et al. 2020): a pre-LN causal decoder, tied head."""
import numpy as np

from . import _common


def weight_shapes(cfg):
    h, n, v = cfg["hidden_size"], cfg["num_layers"], cfg["vocab_size"]
    f = cfg["intermediate_size"]
    return {
        "wte": ((v, h), "normal"),
        "wpe": ((cfg["max_seq_len"], h), "normal"),
        "ln1_w": ((n, h), "ones"), "ln1_b": ((n, h), "zeros"),
        "qkv_w": ((n, h, 3 * h), "normal"), "qkv_b": ((n, 3 * h), "zeros"),
        "proj_w": ((n, h, h), "normal"), "proj_b": ((n, h), "zeros"),
        "ln2_w": ((n, h), "ones"), "ln2_b": ((n, h), "zeros"),
        "fc1_w": ((n, h, f), "normal"), "fc1_b": ((n, f), "zeros"),
        "fc2_w": ((n, f, h), "normal"), "fc2_b": ((n, h), "zeros"),
        "lnf_w": ((h,), "ones"), "lnf_b": ((h,), "zeros"),
    }


_BLOCK_NAMES = {
    "ln1_w": "ln1.weight", "ln1_b": "ln1.bias",
    "qkv_w": "qkv.weight", "qkv_b": "qkv.bias",
    "proj_w": "proj.weight", "proj_b": "proj.bias",
    "ln2_w": "ln2.weight", "ln2_b": "ln2.bias",
    "fc1_w": "fc1.weight", "fc1_b": "fc1.bias",
    "fc2_w": "fc2.weight", "fc2_b": "fc2.bias",
}
_TOP_NAMES = {"wte": "gpt.wte.weight", "wpe": "gpt.wpe.weight",
              "lnf_w": "gpt.ln_f.weight", "lnf_b": "gpt.ln_f.bias"}


def stacked_keys():
    """The keys whose first axis is the layer."""
    return tuple(_BLOCK_NAMES)


def program_names(cfg):
    names = {prog: (key, None) for key, prog in _TOP_NAMES.items()}
    for i in range(cfg["num_layers"]):
        for key, prog in _BLOCK_NAMES.items():
            names[f"gpt.blocks.{i}.{prog}"] = (key, i)
    return names


def make_batch(cfg, cell, seed, step_index):
    """One step's batch: uniform ids; the labels are the ids (the loss
    shifts them by one)."""
    rng = _common.batch_rng(seed, step_index)
    ids = rng.integers(0, cfg["vocab_size"], (cell["batch"], cell["seq"]),
                       dtype=np.int32)
    return ids, ids.copy()


def matmul_params(cfg):
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = 3 * h * h + h * h + 2 * h * f
    return cfg["num_layers"] * per_layer + cfg["vocab_size"] * h


def flops_per_token(cfg, seq):
    """6 a matmul parameter (the tied head once; the look-up multiplies
    nothing), and causal attention at half the square: 2 * s*h forward,
    three times that with backward."""
    attn = 6 * cfg["num_layers"] * cfg["hidden_size"] * seq
    return 6 * matmul_params(cfg) + attn


def attention_calls(cfg, cell):
    """The attention calls of one step on one chip, by their shapes:
    what `attention.*` metrics hold the kernel's device time against."""
    rows = cell["batch"] // cell.get("chips", 1)
    return {"calls_per_step": cfg["num_layers"], "batch": rows,
            "seq": cell["seq"], "heads": cfg["num_heads"],
            "head_dim": cfg["hidden_size"] // cfg["num_heads"],
            "causal": True, "bytes_per_element": 2}


def build_step(cfg, cell, weights):
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    model = GPTForCausalLM(GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_seq_len=cfg["max_seq_len"],
        hidden_dropout=cfg["hidden_dropout"],
        attention_dropout=cfg["attention_dropout"]))
    model.to(cfg["training"]["param_dtype"])
    _common.set_program_weights(model, program_names(cfg), weights)

    def forward_loss(ids, labels):
        return model.loss(model(ids), labels)

    step, opt = _common.build_train_step(model, forward_loss,
                                         cfg["training"], cell)
    return step, model, opt
