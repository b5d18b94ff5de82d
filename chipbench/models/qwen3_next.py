"""Qwen3-Next-80B-A3B (Qwen, `model_type: qwen3_next`): Gated DeltaNet
linear-attention layers three in four beside gated grouped-query
attention with 256-wide heads, every layer with softmax-routed experts
of which this chip holds one share and a sigmoid-gated shared expert,
every norm zero-centred, the head untied."""
import numpy as np

from chipbench import work

from . import _common

# of config.json's keys, the ones the program's Qwen3NextConfig takes as
# they stand (`num_experts` and `ep_size` come from the share)
_CONFIG_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
                "moe_intermediate_size", "shared_expert_intermediate_size",
                "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "head_dim", "full_attention_interval",
                "linear_num_key_heads", "linear_num_value_heads",
                "linear_key_head_dim", "linear_value_head_dim",
                "linear_conv_kernel_dim", "num_experts_per_tok",
                "norm_topk_prob", "decoder_sparse_step", "mlp_only_layers",
                "hidden_act", "rms_norm_eps", "partial_rotary_factor",
                "rope_theta", "rope_scaling", "max_position_embeddings",
                "use_sliding_window", "tie_word_embeddings", "model_type")


def layer_types(cfg):
    every = cfg["full_attention_interval"]
    return ["full_attention" if (i + 1) % every == 0 else "linear_attention"
            for i in range(cfg["num_hidden_layers"])]


def _gdn_widths(cfg):
    """(key width, value width, the convolution's channels) of a Gated
    DeltaNet layer."""
    key = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    value = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return key, value, 2 * key + value


def _mixer_shapes(cfg, kind):
    h = cfg["hidden_size"]
    norms = {"input_norm": ((h,), "zeros"), "post_norm": ((h,), "zeros")}
    if kind == "linear_attention":
        key, value, channels = _gdn_widths(cfg)
        heads = cfg["linear_num_value_heads"]
        return {**norms, "qkvz": ((h, 2 * key + 2 * value), "normal"),
                "ba": ((h, 2 * heads), "normal"),
                "conv": ((channels, cfg["linear_conv_kernel_dim"]), "normal"),
                "A_log": ((heads,), "zeros"), "dt_bias": ((heads,), "ones"),
                "gdn_norm": ((cfg["linear_value_head_dim"],), "ones"),
                "out": ((value, h), "normal")}
    d = cfg["head_dim"]
    q = cfg["num_attention_heads"] * d
    kv = cfg["num_key_value_heads"] * d
    return {**norms, "q": ((h, 2 * q), "normal"), "k": ((h, kv), "normal"),
            "v": ((h, kv), "normal"), "q_norm": ((d,), "zeros"),
            "k_norm": ((d,), "zeros"), "o": ((q, h), "normal")}


def _moe_shapes(cfg):
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    fs, held = cfg["shared_expert_intermediate_size"], cfg["num_experts"]
    total = held * cfg["deployment"]["ep_size"]
    return {"router": ((h, total), "normal"),
            "e_gate": ((held, h, f), "normal"),
            "e_up": ((held, h, f), "normal"),
            "e_down": ((held, f, h), "normal"),
            "s_gate": ((h, fs), "normal"), "s_up": ((h, fs), "normal"),
            "s_down": ((fs, h), "normal"),
            "s_expert_gate": ((h, 1), "normal")}


def layer_shapes(cfg, i):
    return {**_mixer_shapes(cfg, layer_types(cfg)[i]), **_moe_shapes(cfg)}


def weight_shapes(cfg):
    """Every layer's weights under keys of its own (`l0_*` ..): no key is
    stacked over layers (the layers differ, and the reference's gradient
    of a layer is written where it is kept). The head is its own."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    shapes = {"embed": ((v, h), "normal"), "head": ((h, v), "normal"),
              "norm_f": ((h,), "zeros")}
    for i in range(cfg["num_hidden_layers"]):
        shapes.update({f"l{i}_{key}": spec
                       for key, spec in layer_shapes(cfg, i).items()})
    return shapes


_NAMES = {
    "input_norm": "ln1.weight", "post_norm": "ln2.weight",
    "qkvz": "in_proj_qkvz.weight", "ba": "in_proj_ba.weight",
    "conv": "conv_taps", "A_log": "A_log", "dt_bias": "dt_bias",
    "gdn_norm": "gdn_norm", "out": "out_proj.weight",
    "q": "q_proj.weight", "k": "k_proj.weight", "v": "v_proj.weight",
    "q_norm": "q_norm.weight", "k_norm": "k_norm.weight", "o": "proj.weight",
    "router": "moe.router_weight", "e_gate": "moe.w_gate",
    "e_up": "moe.w_up", "e_down": "moe.w_down",
    "s_gate": "shared_expert.gate_proj.weight",
    "s_up": "shared_expert.up_proj.weight",
    "s_down": "shared_expert.down_proj.weight",
    "s_expert_gate": "shared_expert_gate.weight"}


def stacked_keys():
    """No key's first axis is the layer (see `weight_shapes`)."""
    return ()


def program_names(cfg):
    names = {"model.embed_tokens.weight": ("embed", None),
             "model.norm.weight": ("norm_f", None),
             "lm_head.weight": ("head", None)}
    for i in range(cfg["num_hidden_layers"]):
        for key in layer_shapes(cfg, i):
            names[f"model.layers.{i}.{_NAMES[key]}"] = (f"l{i}_{key}", None)
    return names


def make_batch(cfg, cell, seed, step_index):
    """One step's batch: uniform ids from the vocabulary slice held
    here; the labels are the ids (the loss shifts them)."""
    rng = _common.batch_rng(seed, step_index)
    ids = rng.integers(0, cfg["vocab_size"], (cell["batch"], cell["seq"]),
                       dtype=np.int32)
    return ids, ids.copy()


def parameter_count(cfg):
    return sum(int(np.prod(shape))
               for shape, _kind in weight_shapes(cfg).values())


def _kinds(cfg):
    types = layer_types(cfg)
    return types.count("linear_attention"), types.count("full_attention")


def _delta_rule_flops(cfg):
    """The recurrence's products a token a Gated DeltaNet layer: S^T k,
    the rank-one write and S^T q, d_k x d_v each a value head forward,
    each twice backward (18 d_k d_v operations). The chunked form does
    more; that is not required work."""
    return (18 * cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
            * cfg["linear_value_head_dim"])


def flops_per_token(cfg, seq):
    """6 a matmul parameter for every time a token meets it — the routed
    experts at the uniform expectation (`num_experts_per_tok` times the
    share held: 10 x 32 / 512 of an expert a token), the shared expert,
    its gate and the router in every layer, the head once — causal
    attention over 16 heads of 256 at half the square in the attention
    layers, and the delta rule's recurrence in the Gated DeltaNet layers
    (`_delta_rule_flops`). The convolution's taps, the norms and the
    gates are elementwise work, counted in `kernel_work` where a scope
    has them. The recomputed forward is not required work."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    gdns, attentions = _kinds(cfg)
    key, value, _channels = _gdn_widths(cfg)
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    share = cfg["deployment"]["ep_size"]
    routed = 3 * h * f * cfg["num_experts_per_tok"] // share
    layer_moe = (h * cfg["num_experts"] * share + routed
                 + 3 * h * cfg["shared_expert_intermediate_size"] + h)
    gdn = h * (2 * key + 2 * value) + h * 2 * cfg["linear_num_value_heads"] \
        + value * h
    attention = h * 2 * q + 2 * h * kv + q * h
    met = (gdns * gdn + attentions * attention
           + cfg["num_hidden_layers"] * layer_moe + h * cfg["vocab_size"])
    return (6 * met + attentions * 3 * seq * q * 2
            + gdns * _delta_rule_flops(cfg))


def attention_calls(cfg, cell):
    """The attention calls of one step, by their shapes: the
    `full_attention` layers'. `heads` is the query heads': the products
    run over them; the bytes of the 2-head K and V are `kernel_work`'s
    to count."""
    return {"calls_per_step": _kinds(cfg)[1],
            "batch": cell["batch"] // cell.get("chips", 1),
            "seq": cell["seq"], "heads": cfg["num_attention_heads"],
            "head_dim": cfg["head_dim"], "causal": True,
            "bytes_per_element": 2}


def kernel_work(cfg, cell, pairs_per_step):
    """{scope: {"flops", "bytes"}} one step needs under the scopes
    `delta_rule`, `short_conv`, `flash` and `experts`, from shapes and the
    counted token-expert pairs a step alone.

    Delta rule: `_delta_rule_flops` a token a layer; forward reads q and
    k (the key heads'), v, a and b and writes o, backward reads those
    and do and writes dq, dk, dv, da and db, bf16 — the state stays on
    the chip, as a kernel that keeps it in fast memory would have it.
    Convolution: over the 2 d_k + d_v channels, forward reads x and
    writes silu(conv(x)), backward reads x and the cotangent and writes
    dx (5 elements a channel a token); L multiply-adds a channel forward
    and 2 L backward, 6 L operations a channel a token. Attention:
    `work.attention_work`'s products over the query heads; q, o, do, dq
    (read or written six times in all) at the query heads and k, v, dk,
    dv at the key/value heads. Experts: a routed pair meets three h x f
    matrices forward and twice backward (18 h f operations); the held
    experts' weights are read forward and backward and their gradients
    written once in every layer, and a pair's row goes in and out,
    forward and backward."""
    calls = attention_calls(cfg, cell)
    one = work.attention_work(**calls)
    tokens = calls["batch"] * calls["seq"]
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    gdns, _attentions = _kinds(cfg)
    key, value, channels = _gdn_widths(cfg)
    heads = cfg["linear_num_value_heads"]
    taps = cfg["linear_conv_kernel_dim"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    rule_in = 2 * key + value + 2 * heads  # q, k, v, a, b
    weights = cfg["num_experts"] * 3 * h * f * 2
    return {"delta_rule": {
                "flops": _delta_rule_flops(cfg) * tokens * gdns,
                "bytes": (3 * rule_in + 2 * value) * 2 * tokens * gdns},
            "short_conv": {"flops": 6 * taps * channels * tokens * gdns,
                           "bytes": 5 * channels * 2 * tokens * gdns},
            "flash": {"flops": one["flops"] * calls["calls_per_step"],
                      "bytes": 6 * tokens * (q + kv) * 2
                      * calls["calls_per_step"]},
            "experts": {"flops": 18 * h * f * pairs_per_step,
                        "bytes": 3 * weights * cfg["num_hidden_layers"]
                        + 4 * h * 2 * pairs_per_step}}


def build_step(cfg, cell, weights):
    from paddle_tpu.models.qwen3_next import (Qwen3NextConfig,
                                              Qwen3NextForCausalLM)

    share = cfg["deployment"]
    model = Qwen3NextForCausalLM(Qwen3NextConfig(
        **{key: cfg[key] for key in _CONFIG_KEYS},
        num_experts=cfg["num_experts"] * share["ep_size"],
        ep_size=share["ep_size"], ep_rank=share["ep_rank"]))
    model.to(cfg["training"]["param_dtype"])
    _common.set_program_weights(model, program_names(cfg), weights)
    if cell.get("recompute", "none") != "none":
        model.enable_layer_recompute(cell["recompute"])

    def forward_loss(ids, labels):
        return model(ids, labels)

    step, opt = _common.build_train_step(model, forward_loss,
                                         cfg["training"], cell)
    return step, model, opt
