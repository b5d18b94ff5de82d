"""LFM2-24B-A2B's training loss (LiquidAI/LFM2-24B-A2B, `config.json`,
`model_type: lfm2_moe`), written from the layer equations. With
rms(x; w) = x / sqrt(mean(x^2) + norm_eps) * w, no biases anywhere, for
layer l of type t_l:

    h   = x + mixer_l(rms(x; operator_norm))
    out = h + ffn_l(rms(h; ffn_norm))
    logits = rms(out_last; embedding_norm) E^T        (E the embedding)
    loss = mean over b, i < s-1 of CE(logits_i, t_{i+1})

t_l = conv, the gated short convolution (`conv_L_cache` 3 taps, u the
normed input):

    (B | C | z) = u W_in             2048 -> 3 x 2048, split in that order
    g = B * z
    c_t = w_0 g_{t-2} + w_1 g_{t-1} + w_2 g_t     per channel; g = 0
                                    before a sequence's start
    mixer = (C * c) W_out

t_l = full_attention, 32 query heads over 8 key/value heads, 64 wide:

    q = rms_64(reshape(u W_q); q_norm)   k = rms_64(reshape(u W_k); k_norm)
    v = reshape(u W_v);   q, k = rope(q), rope(k)   rotate-half, theta
    1e6, positions 0..s-1;  query head i attends key/value head i // 4,
    causal, scale 1/8;  mixer = ctx W_o

ffn_l of the first `num_dense_layers` layers: (silu(n W_1) * n W_3) W_2,
11,776 wide. Of the others, with E_i such units 1,536 wide:

    s = sigmoid(n W_r)                   float32, over ALL 64 experts
    sel = top_4(s + expert_bias)         the bias joins the selection only
    g_i = scale * s_i / (sum_{j in sel} s_j + 1e-6)            i in sel
    ffn(n) = sum_{i in sel, i held here} g_i E_i(n)      no shared expert

Departures and assumptions (the configuration file's `assumed` has them
too): the embedding is tied to the head (the family's convention; the
catalog's config has no `tie_word_embeddings`); head width 64 = hidden /
heads; the norms' placement and names; rotate-half pairing;
`expert_bias` held at its zeros (`cfg["expert_bias"]` where a test sets
it); the router's product is float32 at every `precision`; the sum over
experts runs over the share this chip holds (`deployment`: experts
ep_rank * H .. of the published 64) and what the others would add is
left out, as in the program.

For room (469 M float32 weights, both moments and the gradient are
7.5 GB of the chip's 16, and a [4, 8192, 2048] float32 activation is
268 MB): every layer is recomputed in the backward pass; the mixers and
the dense layer's wide unit follow the batch a row at a time (attention
a query head at a time within it, its scores recomputed), the experts
meet all the tokens one expert at a time, and the logits are taken a
block of rows at a time; every layer's weights are arrays of their own
(`l0_*` .. `l4_*`); the float32 masters are rounded to the stored type
where they are used, a layer at a time."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import common as c

CONV = ("operator_norm", "conv_in", "conv_taps", "conv_out", "ffn_norm")
ATTENTION = ("operator_norm", "q", "k", "v", "q_norm", "k_norm", "o",
             "ffn_norm")
DENSE = ("gate", "up", "down")
SPARSE = ("router", "e_gate", "e_up", "e_down")
ROUTER_NORM_EPS = 1e-6
ROW_BLOCK = 1024


def layer_keys(cfg, i):
    mixer = CONV if cfg["layer_types"][i] == "conv" else ATTENTION
    return mixer + (DENSE if i < cfg["num_dense_layers"] else SPARSE)


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * g


def rope_halves(x, theta):
    """x [s, heads, d]: the pair (x_i, x_{i + d/2}) turned by the angle
    position * theta^(-2i/d)."""
    s, d = x.shape[0], x.shape[-1]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    angle = jnp.asarray(np.arange(s, dtype=np.float64)[:, None] * inv,
                        jnp.float32)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def short_conv(precision, u, p):
    """One row u [s, h] through the gated short convolution."""
    mm = functools.partial(c.einsum, precision, "sh,hk->sk")
    b, gate_c, z = jnp.split(mm(u, p["conv_in"]), 3, axis=-1)
    g = b * z
    taps = p["conv_taps"].shape[1]
    mixed = jnp.zeros_like(g)
    for j in range(taps):  # tap j meets the position taps - 1 - j back
        back = taps - 1 - j
        past = jnp.concatenate(
            [jnp.zeros_like(g[:back]), g[:g.shape[0] - back]], axis=0)
        mixed = mixed + p["conv_taps"][:, j] * past
    return mm(gate_c * mixed, p["conv_out"])


def grouped_attention(precision, u, p, cfg):
    """One row u [s, h]: a query head at a time against its group's key
    and value head, the scores recomputed in the backward pass."""
    s = u.shape[0]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // heads
    eps = cfg["norm_eps"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    mm = functools.partial(c.einsum, precision, "sh,hk->sk")
    q = rms(mm(u, p["q"]).reshape(s, heads, d), p["q_norm"], eps)
    k = rms(mm(u, p["k"]).reshape(s, kv_heads, d), p["k_norm"], eps)
    v = mm(u, p["v"]).reshape(s, kv_heads, d)
    q, k = rope_halves(q, theta), rope_halves(k, theta)
    keep = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def head(xs):
        qh, kh, vh = xs  # [s, d] each
        scores = c.einsum(precision, "qd,kd->qk", qh, kh) / np.sqrt(d)
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return c.einsum(precision, "qk,kd->qd", probs, vh)

    group = heads // kv_heads
    of_query = lambda t: jnp.repeat(jnp.moveaxis(t, 1, 0), group,  # noqa: E731
                                    axis=0)  # head i reads head i // group
    ctx = jax.lax.map(head, (jnp.moveaxis(q, 1, 0), of_query(k),
                             of_query(v)))
    return mm(jnp.moveaxis(ctx, 0, 1).reshape(s, heads * d), p["o"])


def gated(precision, n, gate, up, down):
    mm = functools.partial(c.einsum, precision, "sh,hk->sk")
    return mm(jax.nn.silu(mm(n, gate)) * mm(n, up), down)


def held_share(cfg):
    """(first expert held here, how many, how many there are)."""
    ep = cfg["deployment"]
    held = cfg["num_experts"]
    return ep["ep_rank"] * held, held, held * ep["ep_size"]


def routed_experts(precision, n, p, cfg):
    """sum_{i in sel, i held here} g_i E_i(n): the router over all the
    experts, the held ones one at a time, every token through each (the
    gate is 0 where the token did not choose it)."""
    first, held, total = held_share(cfg)
    scores = jax.nn.sigmoid(jnp.einsum(
        "sh,he->se", n, p["router"], precision=jax.lax.Precision.HIGHEST))
    bias = jnp.broadcast_to(jnp.asarray(
        cfg.get("expert_bias", 0.0), jnp.float32), (total,))
    _, sel = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    chosen = jnp.sum(jax.nn.one_hot(sel, total, dtype=scores.dtype), axis=1)
    picked = scores * chosen
    gates = cfg["routed_scaling_factor"] * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + ROUTER_NORM_EPS)
    gates = gates[:, first:first + held]

    @jax.checkpoint
    def expert(xs):
        g, gate, up, down = xs
        return g[:, None] * gated(precision, n, gate, up, down)

    # the sum is outside the recomputed part: a step keeps nothing of it
    y, _ = jax.lax.scan(lambda y, xs: (y + expert(xs), None),
                        jnp.zeros_like(n),
                        (gates.T, p["e_gate"], p["e_up"], p["e_down"]))
    return y


def layer(precision, u, p, cfg):
    """One decoder layer on the batch u [b, s, h]; `p` its weights as
    stored. `conv_in` marks a convolution layer, `router` an expert
    layer: its experts meet all the batch's tokens at once, one expert
    at a time; the mixers and the dense layer's wide unit follow the
    batch a row at a time, each row recomputed in the backward pass."""
    eps = cfg["norm_eps"]
    if "conv_in" in p:
        mixer = functools.partial(short_conv, precision, p=p)
    else:
        mixer = functools.partial(grouped_attention, precision, p=p, cfg=cfg)
    u = jax.lax.map(jax.checkpoint(
        lambda r: r + mixer(rms(r, p["operator_norm"], eps))), u)
    n = rms(u, p["ffn_norm"], eps)
    if "router" not in p:
        return u + jax.lax.map(jax.checkpoint(
            lambda r: gated(precision, r, p["gate"], p["up"], p["down"])), n)
    tokens = n.reshape(-1, n.shape[-1])
    return u + routed_experts(precision, tokens, p, cfg).reshape(u.shape)


def summed_cross_entropy(precision, x, table, labels, param_dtype):
    """sum of -log softmax(x @ table.T)[label] over the rows of x [t, h],
    ROW_BLOCK rows at a time, the logits recomputed in the backward pass
    and the table rounded to the stored type a block at a time."""
    t = x.shape[0]
    pad = (-t) % ROW_BLOCK
    x = jnp.pad(x, ((0, pad), (0, 0)))
    labels = jnp.pad(labels, (0, pad), constant_values=-1)

    @jax.checkpoint
    def block(total, xs):
        xb, lb = xs
        logp = jax.nn.log_softmax(c.einsum(
            precision, "th,vh->tv", xb,
            c.stored({"table": table}, param_dtype)["table"]), axis=-1)
        picked = jnp.take_along_axis(logp, jnp.maximum(lb, 0)[:, None],
                                     axis=-1)[:, 0]
        return total - jnp.sum(jnp.where(lb >= 0, picked, 0.0)), None

    total, _ = jax.lax.scan(
        block, jnp.zeros((), jnp.float32),
        (x.reshape(-1, ROW_BLOCK, x.shape[1]), labels.reshape(-1, ROW_BLOCK)))
    return total


def loss_fn(w, batch, cfg, precision="float32"):
    ids, labels = batch
    param_dtype = cfg["training"]["param_dtype"]
    stored = functools.partial(c.stored, param_dtype=param_dtype)

    @jax.checkpoint
    def rows(u, p):
        """One layer over the batch u [b, s, h]; `p` its float32
        masters."""
        return layer(precision, u, stored(p), cfg)

    h = stored({"rows": w["embed"][ids]})["rows"]
    for i in range(cfg["num_hidden_layers"]):
        h = rows(h, {k: w[f"l{i}_{k}"] for k in layer_keys(cfg, i)})
    x = rms(h[:, :-1], stored({"g": w["norm_f"]})["g"], cfg["norm_eps"])
    targets = labels[:, 1:]
    return summed_cross_entropy(
        precision, x.reshape(-1, x.shape[-1]), w["embed"],
        targets.reshape(-1), param_dtype) / targets.size
