"""Ouro (ByteDance, arXiv:2510.25741): a sandwich-norm rotary decoder
stack run `total_ut_steps` times over the same weights, a head and an
exit gate after every pass, a loss over all the exits."""
import numpy as np

from . import _common

# of config.json's keys, the ones the program's OuroConfig is built from
_CONFIG_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
                "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "head_dim", "hidden_act",
                "max_position_embeddings", "rms_norm_eps", "rope_theta",
                "tie_word_embeddings", "total_ut_steps",
                "early_exit_threshold")


def weight_shapes(cfg):
    h, n, v = (cfg["hidden_size"], cfg["num_hidden_layers"],
               cfg["vocab_size"])
    f = cfg["intermediate_size"]
    return {
        "embed": ((v, h), "normal"), "head": ((h, v), "normal"),
        "ln1": ((n, h), "ones"), "ln1_post": ((n, h), "ones"),
        "ln2": ((n, h), "ones"), "ln2_post": ((n, h), "ones"),
        "q_w": ((n, h, h), "normal"), "k_w": ((n, h, h), "normal"),
        "v_w": ((n, h, h), "normal"), "o_w": ((n, h, h), "normal"),
        "gate_w": ((n, h, f), "normal"), "up_w": ((n, h, f), "normal"),
        "down_w": ((n, f, h), "normal"),
        "norm_f": ((h,), "ones"),
        "exit_w": ((h, 1), "normal"), "exit_b": ((1,), "zeros"),
    }


_BLOCK_NAMES = {
    "ln1": "ln1.weight", "q_w": "q_proj.weight", "k_w": "k_proj.weight",
    "v_w": "v_proj.weight", "o_w": "proj.weight",
    "ln1_post": "ln1_post.weight", "ln2": "ln2.weight",
    "gate_w": "gate_proj.weight", "up_w": "up_proj.weight",
    "down_w": "down_proj.weight", "ln2_post": "ln2_post.weight",
}
_TOP_NAMES = {"embed": "model.embed_tokens.weight",
              "norm_f": "model.norm.weight", "head": "lm_head.weight",
              "exit_w": "exit_gate.weight", "exit_b": "exit_gate.bias"}


def stacked_keys():
    """The keys whose first axis is the layer."""
    return tuple(_BLOCK_NAMES)


def program_names(cfg):
    names = {prog: (key, None) for key, prog in _TOP_NAMES.items()}
    for i in range(cfg["num_hidden_layers"]):
        for key, prog in _BLOCK_NAMES.items():
            names[f"model.layers.{i}.{prog}"] = (key, i)
    return names


def make_batch(cfg, cell, seed, step_index):
    """One step's batch: uniform ids; the labels are the ids (the loss
    shifts them by one)."""
    rng = _common.batch_rng(seed, step_index)
    ids = rng.integers(0, cfg["vocab_size"], (cell["batch"], cell["seq"]),
                       dtype=np.int32)
    return ids, ids.copy()


def layer_matmul_params(cfg):
    """q, k, v, o and the gated FFN's three: what one application of one
    layer multiplies by."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    return 4 * h * h + 3 * h * f


def parameter_count(cfg):
    h = cfg["hidden_size"]
    per_layer = layer_matmul_params(cfg) + 4 * h  # and its four norms
    return (cfg["num_hidden_layers"] * per_layer
            + 2 * cfg["vocab_size"] * h  # embedding and its own head
            + h + h + 1)  # final norm, gate weight and bias


def flops_per_token(cfg, seq):
    """6 a matmul parameter for every time a token meets it: each layer
    and the head once a pass (the look-up multiplies nothing, the gate's
    h products are left out), and causal attention at half the square in
    every application. The recomputed forward is not required work."""
    passes = cfg["total_ut_steps"]
    applied = passes * (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
                        + cfg["vocab_size"] * cfg["hidden_size"])
    attn = 6 * passes * cfg["num_hidden_layers"] * cfg["hidden_size"] * seq
    return 6 * applied + attn


def attention_calls(cfg, cell):
    """The attention calls of one step on one chip, by their shapes:
    every layer in every pass."""
    rows = cell["batch"] // cell.get("chips", 1)
    return {"calls_per_step": cfg["total_ut_steps"]
            * cfg["num_hidden_layers"], "batch": rows,
            "seq": cell["seq"], "heads": cfg["num_attention_heads"],
            "head_dim": cfg["head_dim"], "causal": True,
            "bytes_per_element": 2}


def build_step(cfg, cell, weights):
    from paddle_tpu.models.ouro import OuroConfig, OuroForCausalLM

    model = OuroForCausalLM(OuroConfig(
        **{key: cfg[key] for key in _CONFIG_KEYS},
        exit_entropy_beta=cfg["training"]["exit_entropy_beta"]))
    model.to(cfg["training"]["param_dtype"])
    _common.set_program_weights(model, program_names(cfg), weights)
    if cell.get("recompute", "none") != "none":
        model.enable_layer_recompute(cell["recompute"])

    def forward_loss(ids, labels):
        return model(ids, labels)

    step, opt = _common.build_train_step(model, forward_loss,
                                         cfg["training"], cell)
    return step, model, opt
