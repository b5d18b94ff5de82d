"""JoyAI-LLM-Flash's training loss (jdopensource/JoyAI-LLM-Flash,
`config.json`; the layer equations are DeepSeek-V2's latent attention,
arXiv:2405.04434 section 2.1, and DeepSeek-V3's routing and multi-token
prediction, arXiv:2412.19437 sections 2.1.2 and 2.2), written from the
equations. With u a layer's input, rms an RMSNorm, no biases anywhere:

    n = rms(u; g1)
    c_q = rms(n W_qa; g_q)            q = c_q W_qb -> per head (q_n | q_r)
    (c_kv | k_r) = n W_kva            c_kv = rms(c_kv; g_kv)
    (k_n | v) = c_kv W_kvb per head   k_r: ONE rotary key for all heads
    q_r, k_r = rope(q_r), rope(k_r)   pairs (2i, 2i+1), by position
    a = softmax_causal([q_n;q_r] [k_n;k_r]^T / sqrt(d_n + d_r)) v W_o
    u = u + a;   n = rms(u; g2);   u = u + ffn(n)

ffn of the leading dense layers: (silu(n W_gate) * n W_up) W_down. Of
the others, with E_i and Shared such gated units:

    s = sigmoid(n W_r)                  float32, over ALL the experts
    sel = top_k(s + b)                  b joins the selection only
    g_i = scale * s_i / (sum_{j in sel} s_j + 1e-20)          i in sel
    ffn(n) = sum_{i in sel, i held here} g_i E_i(n) + Shared(n)

The router's product is float32 at every `precision` (the published
code computes it so), and the sum over experts runs over the share this
chip holds (`deployment`: experts ep_rank * H .. of the published
count) — what the others would add is left out, as in the program.

    h_i  = stack(E[t])_i   (before the final norm)
    h'_i = [rms(E[t_{i+1}]; g_e) ; rms(h_i; g_h)] W_eh       i = 0..s-2
    loss = mean_i CE(rms(h_i; g_f) W_head, t_{i+1})
           + lambda mean_i CE(rms(TRM(h')_i; g_m) W_head, t_{i+2})

For room (680 M float32 weights, both moments and the gradient are
10.9 GB of the chip's 16): every layer is recomputed in the backward
pass; attention and the dense layer's wide unit follow the batch a row
at a time (a block of heads at a time within it), the experts meet all
the tokens one expert at a time, so that an expert's gradient is
written once and never summed over rows, and the logits are taken a
block of rows at a time; every layer's weights are arrays of their own
(`l1_*` .. `l4_*`, no key stacked over layers and no loop over one: a
loop's carried copy of a stacked array and of its gradient was 2.7 GB
by the compiler's count); the float32 masters are rounded to the stored
type where they are used, a layer at a time."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import common as c

ATTENTION = ("ln1", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b",
             "o", "ln2")
DENSE = ATTENTION + ("gate", "up", "down")
SPARSE = ATTENTION + ("router", "e_gate", "e_up", "e_down", "s_gate",
                      "s_up", "s_down")
HEAD_BLOCK = 2
ROW_BLOCK = 1024


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * g


def rope_pairs(x, theta):
    """x [s, heads, d]: each pair (x_2i, x_2i+1) turned by the angle
    position * theta^(-2i/d)."""
    s, d = x.shape[0], x.shape[-1]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    angle = jnp.asarray(np.arange(s, dtype=np.float64)[:, None] * inv,
                        jnp.float32)[:, None, :]
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def causal_attention(precision, q, k, v):
    """q, k [s, heads, d_qk], v [s, heads, d_v] -> [s, heads * d_v],
    HEAD_BLOCK heads at a time (their scores recomputed in the backward
    pass)."""
    s, heads, d = q.shape
    keep = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def block(qkv):
        qb, kb, vb = qkv
        scores = c.einsum(precision, "qnd,knd->nqk", qb, kb) / np.sqrt(d)
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return c.einsum(precision, "nqk,knd->qnd", probs, vb)

    size = min(HEAD_BLOCK, heads)

    def split(t):  # [s, heads, d] -> [blocks, s, size, d]
        return jnp.moveaxis(t.reshape(s, heads // size, size, -1), 1, 0)

    ctx = jax.lax.map(block, (split(q), split(k), split(v)))
    return jnp.moveaxis(ctx, 0, 1).reshape(s, -1)


def latent_attention(precision, n, p, cfg):
    s = n.shape[0]
    heads = cfg["num_attention_heads"]
    nope, rot, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    mm = functools.partial(c.einsum, precision, "sh,hk->sk")
    q = mm(rms(mm(n, p["q_a"]), p["q_a_norm"], eps), p["q_b"])
    q = q.reshape(s, heads, nope + rot)
    kv_a = mm(n, p["kv_a"])
    c_kv, k_r = kv_a[:, :cfg["kv_lora_rank"]], kv_a[:, cfg["kv_lora_rank"]:]
    kv = mm(rms(c_kv, p["kv_a_norm"], eps), p["kv_b"])
    kv = kv.reshape(s, heads, nope + vd)
    k_r = rope_pairs(k_r[:, None, :], theta)
    q = jnp.concatenate([q[..., :nope], rope_pairs(q[..., nope:], theta)],
                        axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r, (s, heads, rot))], axis=-1)
    return mm(causal_attention(precision, q, k, kv[..., nope:]), p["o"])


def gated(precision, n, gate, up, down):
    mm = functools.partial(c.einsum, precision, "sh,hk->sk")
    return mm(jax.nn.silu(mm(n, gate)) * mm(n, up), down)


def held_share(cfg):
    """(first expert held here, how many, how many there are)."""
    ep = cfg["deployment"]
    held = cfg["n_routed_experts"]
    return ep["ep_rank"] * held, held, held * ep["ep_size"]


def routed_experts(precision, n, p, cfg):
    """sum_{i in sel, i held here} g_i E_i(n): the router over all the
    experts, the held ones one at a time, every token through each (the
    gate is 0 where the token did not choose it)."""
    first, held, total = held_share(cfg)
    scores = jax.nn.sigmoid(jnp.einsum(
        "sh,he->se", n, p["router"], precision=jax.lax.Precision.HIGHEST))
    bias = jnp.broadcast_to(jnp.asarray(
        cfg.get("e_score_correction_bias", 0.0), jnp.float32), (total,))
    _, sel = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    chosen = jnp.sum(jax.nn.one_hot(sel, total, dtype=scores.dtype), axis=1)
    picked = scores * chosen
    gates = cfg["routed_scaling_factor"] * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
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


def attention_rows(precision, u, p, cfg):
    """u + attention(rms(u)) over the batch u [b, s, h], a row at a
    time, each row recomputed in the backward pass."""
    def row(r):
        return r + latent_attention(
            precision, rms(r, p["ln1"], cfg["rms_norm_eps"]), p, cfg)

    return jax.lax.map(jax.checkpoint(row), u)


def layer(precision, u, p, cfg):
    """One decoder layer on the batch u [b, s, h]; `p` its weights as
    stored. A layer with a `router` is an expert layer: its experts meet
    all the batch's tokens at once, one expert at a time; the dense
    layer's wide unit follows the batch a row at a time."""
    u = attention_rows(precision, u, p, cfg)
    n = rms(u, p["ln2"], cfg["rms_norm_eps"])
    if "router" not in p:
        return u + jax.lax.map(jax.checkpoint(
            lambda r: gated(precision, r, p["gate"], p["up"], p["down"])), n)
    tokens = n.reshape(-1, n.shape[-1])
    ffn = (routed_experts(precision, tokens, p, cfg)
           + gated(precision, tokens, p["s_gate"], p["s_up"], p["s_down"]))
    return u + ffn.reshape(u.shape)


def summed_cross_entropy(precision, x, head, labels, param_dtype):
    """sum of -log softmax(x @ head)[label] over the rows of x [t, h],
    ROW_BLOCK rows at a time, the logits recomputed in the backward pass
    and the head rounded to the stored type a block at a time."""
    t = x.shape[0]
    pad = (-t) % ROW_BLOCK
    x = jnp.pad(x, ((0, pad), (0, 0)))
    labels = jnp.pad(labels, (0, pad), constant_values=-1)

    @jax.checkpoint
    def block(total, xs):
        xb, lb = xs
        logp = jax.nn.log_softmax(c.einsum(
            precision, "th,hv->tv", xb,
            c.stored({"head": head}, param_dtype)["head"]), axis=-1)
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
    eps = cfg["rms_norm_eps"]
    b, s = ids.shape

    @jax.checkpoint
    def rows(u, p):
        """One layer over the batch u [b, s, h]; `p` its float32
        masters."""
        return layer(precision, u, stored(p), cfg)

    def embed(tokens):
        return stored({"rows": w["embed"][tokens]})["rows"]

    def mean_cross_entropy(h, g, targets):
        x = rms(h, stored({"g": g})["g"], eps)
        return summed_cross_entropy(
            precision, x.reshape(-1, x.shape[-1]), w["head"],
            targets.reshape(-1), param_dtype) / targets.size

    h = rows(embed(ids), {k: w["l0_" + k] for k in DENSE})
    for i in range(1, cfg["num_hidden_layers"]):
        h = rows(h, {k: w[f"l{i}_{k}"] for k in SPARSE})
    main = mean_cross_entropy(h[:, :-1], w["norm_f"], labels[:, 1:])
    top = stored({k: w[k] for k in ("mtp_enorm", "mtp_hnorm", "mtp_eh")})
    merged = jnp.concatenate([rms(embed(ids[:, 1:]), top["mtp_enorm"], eps),
                              rms(h[:, :-1], top["mtp_hnorm"], eps)], axis=-1)
    h2 = rows(c.einsum(precision, "bsh,hk->bsk", merged, top["mtp_eh"]),
              {k: w["mtp_" + k] for k in SPARSE})
    more = mean_cross_entropy(h2[:, :-1], w["mtp_norm"], labels[:, 2:])
    return main + cfg["training"]["mtp_loss_weight"] * more
