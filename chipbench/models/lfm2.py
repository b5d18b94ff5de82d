"""LFM2-24B-A2B (LiquidAI, `model_type: lfm2_moe`): layers that
alternate by `layer_types` between a gated short convolution and
grouped-query attention with normed 64-wide heads, a leading dense
layer, layers of sigmoid-routed experts of which this chip holds one
share and no shared expert, the head tied to the embedding."""
import numpy as np

from chipbench import work

from . import _common

# of config.json's keys, the ones the program's Lfm2MoeConfig takes as
# they stand (`num_experts` and `ep_size` come from the share)
_CONFIG_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
                "moe_intermediate_size", "num_hidden_layers", "layer_types",
                "num_attention_heads", "num_key_value_heads",
                "num_dense_layers", "num_experts_per_tok", "norm_topk_prob",
                "use_expert_bias", "routed_scaling_factor", "conv_L_cache",
                "conv_bias", "norm_eps", "rope_parameters",
                "max_position_embeddings", "model_type")


def _mixer_shapes(cfg, kind):
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    norms = {"operator_norm": ((h,), "ones"), "ffn_norm": ((h,), "ones")}
    if kind == "conv":
        return {**norms, "conv_in": ((h, 3 * h), "normal"),
                "conv_taps": ((h, cfg["conv_L_cache"]), "normal"),
                "conv_out": ((h, h), "normal")}
    d = h // heads
    kv = cfg["num_key_value_heads"] * d
    return {**norms, "q": ((h, h), "normal"), "k": ((h, kv), "normal"),
            "v": ((h, kv), "normal"), "q_norm": ((d,), "ones"),
            "k_norm": ((d,), "ones"), "o": ((h, h), "normal")}


def _ffn_shapes(cfg, dense):
    h = cfg["hidden_size"]
    if dense:
        f = cfg["intermediate_size"]
        return {"gate": ((h, f), "normal"), "up": ((h, f), "normal"),
                "down": ((f, h), "normal")}
    f, held = cfg["moe_intermediate_size"], cfg["num_experts"]
    total = held * cfg["deployment"]["ep_size"]
    return {"router": ((h, total), "normal"),
            "e_gate": ((held, h, f), "normal"),
            "e_up": ((held, h, f), "normal"),
            "e_down": ((held, f, h), "normal")}


def layer_shapes(cfg, i):
    return {**_mixer_shapes(cfg, cfg["layer_types"][i]),
            **_ffn_shapes(cfg, i < cfg["num_dense_layers"])}


def weight_shapes(cfg):
    """Every layer's weights under keys of its own (`l0_*` ..): no key is
    stacked over layers (the layers differ, and the reference's gradient
    of a layer is written where it is kept). The head is the embedding."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    shapes = {"embed": ((v, h), "normal"), "norm_f": ((h,), "ones")}
    for i in range(cfg["num_hidden_layers"]):
        shapes.update({f"l{i}_{key}": spec
                       for key, spec in layer_shapes(cfg, i).items()})
    return shapes


_NAMES = {
    "operator_norm": "ln1.weight", "ffn_norm": "ln2.weight",
    "conv_in": "conv_in.weight", "conv_taps": "conv_taps",
    "conv_out": "conv_out.weight", "q": "q_proj.weight",
    "k": "k_proj.weight", "v": "v_proj.weight", "q_norm": "q_norm.weight",
    "k_norm": "k_norm.weight", "o": "proj.weight",
    "gate": "gate_proj.weight", "up": "up_proj.weight",
    "down": "down_proj.weight", "router": "moe.router_weight",
    "e_gate": "moe.w_gate", "e_up": "moe.w_up", "e_down": "moe.w_down"}


def stacked_keys():
    """No key's first axis is the layer (see `weight_shapes`)."""
    return ()


def program_names(cfg):
    names = {"model.embed_tokens.weight": ("embed", None),
             "model.norm.weight": ("norm_f", None)}
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
    types = cfg["layer_types"]
    return (types.count("conv"), types.count("full_attention"),
            cfg["num_hidden_layers"] - cfg["num_dense_layers"])


def flops_per_token(cfg, seq):
    """6 a matmul parameter for every time a token meets it — the routed
    experts at the uniform expectation (`num_experts_per_tok` times the
    share held: half an expert a token where 8 of 64 are held), whatever
    a run's routing; the tied head once — and causal attention over
    32 heads of 64 at half the square, in the attention layers alone.
    The short convolution's own 3 taps and 2 gates a channel are
    elementwise work, counted in `kernel_work` and not here. The
    recomputed forward is not required work."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    convs, attentions, sparse = _kinds(cfg)
    kv = cfg["num_key_value_heads"] * h // cfg["num_attention_heads"]
    routed = 3 * h * f * cfg["num_experts_per_tok"] \
        // cfg["deployment"]["ep_size"]
    total = cfg["num_experts"] * cfg["deployment"]["ep_size"]
    met = (convs * 4 * h * h + attentions * (2 * h * h + 2 * h * kv)
           + cfg["num_dense_layers"] * 3 * h * cfg["intermediate_size"]
           + sparse * (h * total + routed) + h * cfg["vocab_size"])
    return 6 * met + attentions * 3 * seq * h * 2


def attention_calls(cfg, cell):
    """The attention calls of one step, by their shapes: the
    `full_attention` layers'. `heads` is the query heads': the products
    run over them; the bytes of the 8-head K and V are `kernel_work`'s
    to count."""
    return {"calls_per_step": cfg["layer_types"].count("full_attention"),
            "batch": cell["batch"] // cell.get("chips", 1),
            "seq": cell["seq"], "heads": cfg["num_attention_heads"],
            "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
            "causal": True, "bytes_per_element": 2}


def kernel_work(cfg, cell, pairs_per_step):
    """{scope: {"flops", "bytes"}} one step needs under the scopes
    `flash`, `experts` and `short_conv`, from shapes and the counted
    token-expert pairs a step alone. Attention: `work.attention_work`'s
    products over the query heads; q, o, do, dq (read or written six
    times in all) at the query heads and k, v, dk, dv (six times) at the
    key/value heads. Experts: a routed pair meets three h x f matrices
    forward and twice backward (18 h f operations); the held experts'
    weights are read forward and backward and their gradients written
    once in every expert layer, and a pair's row goes in and out, forward
    and backward. Short convolution: forward `u` [3h a token] is read and
    the gated result [h] written, backward `u` and the cotangent are read
    and `du` written (11 h elements a token an application); a channel's
    3 taps and 2 gates forward and their transposes backward, 24
    operations a channel a token."""
    calls = attention_calls(cfg, cell)
    one = work.attention_work(**calls)
    tokens = calls["batch"] * calls["seq"]
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    kv_width = cfg["num_key_value_heads"] * calls["head_dim"]
    convs, _attentions, sparse = _kinds(cfg)
    weights = cfg["num_experts"] * 3 * h * f * 2
    return {"flash": {"flops": one["flops"] * calls["calls_per_step"],
                      "bytes": 6 * tokens * (h + kv_width) * 2
                      * calls["calls_per_step"]},
            "experts": {"flops": 18 * h * f * pairs_per_step,
                        "bytes": 3 * weights * sparse
                        + 4 * h * 2 * pairs_per_step},
            "short_conv": {"flops": 24 * h * tokens * convs,
                           "bytes": 11 * h * 2 * tokens * convs}}


def build_step(cfg, cell, weights):
    from paddle_tpu.models.lfm2 import Lfm2MoeConfig, Lfm2MoeForCausalLM

    share = cfg["deployment"]
    model = Lfm2MoeForCausalLM(Lfm2MoeConfig(
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
