"""JoyAI-LLM-Flash (jdopensource, DeepSeek-V3's key set): latent
attention, a leading dense layer, layers of sigmoid-routed experts of
which this chip holds one share, a shared expert and a multi-token-
prediction module trained beside the main head."""
import numpy as np

from chipbench import work

from . import _common

# of config.json's keys, the ones the program's JoyAIFlashConfig takes
# as they stand (`n_routed_experts` and `ep_size` come from the share)
_CONFIG_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
                "moe_intermediate_size", "num_hidden_layers",
                "num_attention_heads", "num_key_value_heads", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "n_shared_experts", "num_experts_per_tok",
                "first_k_dense_replace", "moe_layer_freq", "n_group",
                "topk_group", "norm_topk_prob", "routed_scaling_factor",
                "scoring_func", "topk_method", "num_nextn_predict_layers",
                "hidden_act", "attention_bias", "max_position_embeddings",
                "rms_norm_eps", "rope_theta", "rope_interleave",
                "rope_scaling", "tie_word_embeddings")


def _attention_shapes(cfg):
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return {"ln1": ((h,), "ones"), "q_a": ((h, ql), "normal"),
            "q_a_norm": ((ql,), "ones"),
            "q_b": ((ql, heads * (nope + rope)), "normal"),
            "kv_a": ((h, kvl + rope), "normal"),
            "kv_a_norm": ((kvl,), "ones"),
            "kv_b": ((kvl, heads * (nope + v)), "normal"),
            "o": ((heads * v, h), "normal"), "ln2": ((h,), "ones")}


def _sparse_shapes(cfg):
    h, f, held = (cfg["hidden_size"], cfg["moe_intermediate_size"],
                  cfg["n_routed_experts"])
    shared = cfg["n_shared_experts"] * f
    total = held * cfg["deployment"]["ep_size"]
    return {**_attention_shapes(cfg), "router": ((h, total), "normal"),
            "e_gate": ((held, h, f), "normal"),
            "e_up": ((held, h, f), "normal"),
            "e_down": ((held, f, h), "normal"),
            "s_gate": ((h, shared), "normal"), "s_up": ((h, shared), "normal"),
            "s_down": ((shared, h), "normal")}


def sparse_prefixes(cfg):
    """The key prefixes of the expert layers: `l1_` .. of the main
    stack, then `mtp_`, the module's."""
    if cfg["first_k_dense_replace"] != 1:
        raise ValueError("the family's weight table has one leading dense "
                         "layer (`l0_*`)")
    return [f"l{i}_" for i in range(1, cfg["num_hidden_layers"])] + ["mtp_"]


def weight_shapes(cfg):
    """Every layer's weights under keys of its own (`l0_*` the dense
    layer, `l1_*` .. the expert layers, `mtp_*` the module): no key is
    stacked over layers, so that the reference's gradient of a layer is
    written where it is kept and no loop over a stacked array holds a
    second copy of it (PERF.md section 6, PR 33)."""
    h, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    shapes = {"embed": ((v, h), "normal"), "head": ((h, v), "normal"),
              "norm_f": ((h,), "ones"), "mtp_enorm": ((h,), "ones"),
              "mtp_hnorm": ((h,), "ones"), "mtp_eh": ((2 * h, h), "normal"),
              "mtp_norm": ((h,), "ones"),
              "l0_gate": ((h, f), "normal"), "l0_up": ((h, f), "normal"),
              "l0_down": ((f, h), "normal")}
    for key, (shape, kind) in _attention_shapes(cfg).items():
        shapes["l0_" + key] = (shape, kind)
    sparse = _sparse_shapes(cfg)
    for prefix in sparse_prefixes(cfg):
        shapes.update({prefix + key: spec for key, spec in sparse.items()})
    return shapes


_ATTENTION_NAMES = {
    "ln1": "ln1.weight", "q_a": "q_a_proj.weight",
    "q_a_norm": "q_a_norm.weight", "q_b": "q_b_proj.weight",
    "kv_a": "kv_a_proj.weight", "kv_a_norm": "kv_a_norm.weight",
    "kv_b": "kv_b_proj.weight", "o": "o_proj.weight", "ln2": "ln2.weight"}
_DENSE_NAMES = {**_ATTENTION_NAMES, "gate": "gate_proj.weight",
                "up": "up_proj.weight", "down": "down_proj.weight"}
_SPARSE_NAMES = {
    **_ATTENTION_NAMES, "router": "moe.router_weight",
    "e_gate": "moe.w_gate", "e_up": "moe.w_up", "e_down": "moe.w_down",
    "s_gate": "shared_expert.gate_proj.weight",
    "s_up": "shared_expert.up_proj.weight",
    "s_down": "shared_expert.down_proj.weight"}
_TOP_NAMES = {"embed": "model.embed_tokens.weight",
              "norm_f": "model.norm.weight", "head": "lm_head.weight",
              "mtp_enorm": "mtp.enorm.weight", "mtp_hnorm": "mtp.hnorm.weight",
              "mtp_eh": "mtp.eh_proj.weight", "mtp_norm": "mtp.norm.weight"}


def stacked_keys():
    """No key's first axis is the layer (see `weight_shapes`)."""
    return ()


def program_names(cfg):
    names = {prog: (key, None) for key, prog in _TOP_NAMES.items()}
    for key, prog in _DENSE_NAMES.items():
        names[f"model.layers.0.{prog}"] = ("l0_" + key, None)
    for i, prefix in enumerate(sparse_prefixes(cfg)):
        block = "mtp.block" if prefix == "mtp_" else f"model.layers.{i + 1}"
        for key, prog in _SPARSE_NAMES.items():
            names[f"{block}.{prog}"] = (prefix + key, None)
    return names


def make_batch(cfg, cell, seed, step_index):
    """One step's batch: uniform ids from the vocabulary slice held
    here; the labels are the ids (the losses shift them)."""
    rng = _common.batch_rng(seed, step_index)
    ids = rng.integers(0, cfg["vocab_size"], (cell["batch"], cell["seq"]),
                       dtype=np.int32)
    return ids, ids.copy()


def parameter_count(cfg):
    return sum(int(np.prod(shape))
               for shape, _kind in weight_shapes(cfg).values())


def flops_per_token(cfg, seq):
    """6 a matmul parameter for every time a token meets it — the
    routed experts at the uniform expectation (`num_experts_per_tok`
    times the share held: half an expert a token where 16 of 256 are
    held), whatever a run's routing — and causal attention with 192-wide
    scores and 128-wide values at half the square, in the main stack's
    layers and the MTP module's. The recomputed forward is not required
    work."""
    h = cfg["hidden_size"]
    sizes = {k: int(np.prod(shape))
             for k, (shape, _kind) in _sparse_shapes(cfg).items()}
    attention = sum(sizes[k] for k in ("q_a", "q_b", "kv_a", "kv_b", "o"))
    expert = sizes["e_gate"] + sizes["e_up"] + sizes["e_down"]
    held = cfg["n_routed_experts"]
    routed = expert // held * cfg["num_experts_per_tok"] \
        // cfg["deployment"]["ep_size"]
    sparse = (sizes["router"] + sizes["s_gate"] + sizes["s_up"]
              + sizes["s_down"] + routed)
    layers = cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]
    met = (layers * attention + 3 * h * cfg["intermediate_size"]
           + (layers - 1) * sparse + 2 * h * h
           + 2 * h * cfg["vocab_size"])
    widths = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
              + cfg["v_head_dim"])
    return 6 * met + layers * 3 * seq * cfg["num_attention_heads"] * widths


def attention_calls(cfg, cell):
    """The attention calls of one step, by their shapes: every layer of
    the main stack and the MTP module's. `head_dim` is the mean of the
    key width and the value width: `work.attention_work` counts 4 and 8
    tensors of it, which are q, k at the one and v, o at the other, and
    products over both."""
    return {"calls_per_step": cfg["num_hidden_layers"]
            + cfg["num_nextn_predict_layers"],
            "batch": cell["batch"] // cell.get("chips", 1),
            "seq": cell["seq"], "heads": cfg["num_attention_heads"],
            "head_dim": (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                         + cfg["v_head_dim"]) // 2,
            "causal": True, "bytes_per_element": 2}


def kernel_work(cfg, cell, pairs_per_step):
    """{scope: {"flops", "bytes"}} one step needs under the scopes
    `flash` and `experts`, from shapes and the counted token-expert
    pairs a step alone. Attention: `work.attention_work` of every call.
    Experts: a routed pair meets three h x f matrices forward and twice
    backward (18 h f operations); the held experts' weights are read
    forward and backward and their gradients written once in every
    expert layer, and a pair's row goes in and out, forward and
    backward."""
    calls = attention_calls(cfg, cell)
    one = work.attention_work(**calls)
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    layers = len(sparse_prefixes(cfg))
    weights = cfg["n_routed_experts"] * 3 * h * f * 2
    return {"flash": {k: v * calls["calls_per_step"]
                      for k, v in one.items()},
            "experts": {"flops": 18 * h * f * pairs_per_step,
                        "bytes": 3 * weights * layers
                        + 4 * h * 2 * pairs_per_step}}


def build_step(cfg, cell, weights):
    from paddle_tpu.models.joyai import (JoyAIFlashConfig,
                                         JoyAIFlashForCausalLM)

    share = cfg["deployment"]
    model = JoyAIFlashForCausalLM(JoyAIFlashConfig(
        **{key: cfg[key] for key in _CONFIG_KEYS},
        n_routed_experts=cfg["n_routed_experts"] * share["ep_size"],
        ep_size=share["ep_size"], ep_rank=share["ep_rank"],
        mtp_loss_weight=cfg["training"]["mtp_loss_weight"]))
    model.to(cfg["training"]["param_dtype"])
    _common.set_program_weights(model, program_names(cfg), weights)
    if cell.get("recompute", "none") != "none":
        model.enable_layer_recompute(cell["recompute"])

    def forward_loss(ids, labels):
        return model(ids, labels)

    step, opt = _common.build_train_step(model, forward_loss,
                                         cfg["training"], cell)
    return step, model, opt
