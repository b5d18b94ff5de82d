"""Ouro's training loss (ByteDance, "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741; config.json of ByteDance/Ouro-2.6B),
written from the equations:

    h^0 = E[ids]
    for t = 1..T, the same weights every t:
        u = h^(t-1)
        for each layer:
            n = rms(u; g1);  q, k, v = n Wq, n Wk, n Wv   (no biases)
            q, k = rope(q), rope(k)          (rotate-half, by position)
            a = softmax_causal(q k^T / sqrt(d)) v Wo
            u = u + rms(a; g2)               (a norm after the sublayer too)
            n = rms(u; g3);  m = (silu(n Wgate) * (n Wup)) Wdown
            u = u + rms(m; g4)
        h^t = rms(u; gf)
        z^t = h^t Whead;   lam^t = sigmoid(h^t . wg + bg)
    p^t = lam^t prod_{j<t} (1 - lam^j)  for t < T;   p^T = prod_{j<T} (1 - lam^j)
    loss = mean over positions of [ sum_t p^t CE(z^t, next token) - beta H(p) ]

One `lax.scan` over the T x L applications of a layer, application i
running layer i mod L (its float32 masters sliced and rounded to the
stored type inside the step, under `jax.checkpoint`), the final norm
closing a pass where i mod L = L - 1; attention a block of heads at a
time and the logits a block of rows at a time, so that neither the
scores nor the [tokens, vocab] logits exist whole. The flat loop is for
room: beside the weights, both moments and the gradient (9.8 GB at this
size) the chip has 6 GB left, and a scan over passes around a scan over
layers keeps the stacked gradient twice more (19.7 GB by the compiler's
count for a v5e; this form 13.1). What the configuration's `assumed`
lists (norm placement, the per-pass final norm, the gate's form, beta)
is assumed here in the same words."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import common as c

STACKED = ("ln1", "q_w", "k_w", "v_w", "o_w", "ln1_post", "ln2", "gate_w",
           "up_w", "down_w", "ln2_post")
HEAD_BLOCK = 4
ROW_BLOCK = 1024


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * g


def rope(x, theta):
    """x [b, s, heads, d]: each pair (x_i, x_{i + d/2}) turned by the
    angle position * theta^(-2i/d)."""
    s, d = x.shape[1], x.shape[-1]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    angle = jnp.asarray(np.arange(s, dtype=np.float64)[:, None] * inv,
                        jnp.float32)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def causal_attention(precision, q, k, v):
    """q, k, v [b, s, heads, d] -> [b, s, heads * d], HEAD_BLOCK heads
    at a time (their scores recomputed in the backward pass)."""
    b, s, heads, d = q.shape
    keep = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def block(qkv):
        qb, kb, vb = qkv
        scores = c.einsum(precision, "bqnd,bknd->bnqk", qb, kb) / np.sqrt(d)
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return c.einsum(precision, "bnqk,bknd->bqnd", probs, vb)

    size = min(HEAD_BLOCK, heads)

    def split(t):  # [b, s, heads, d] -> [blocks, b, s, size, d]
        return jnp.moveaxis(t.reshape(b, s, heads // size, size, d), 2, 0)

    ctx = jax.lax.map(block, (split(q), split(k), split(v)))
    return jnp.moveaxis(ctx, 0, 2).reshape(b, s, heads * d)


def token_cross_entropy(precision, x, head, labels, param_dtype):
    """-log softmax(x @ head)[label] for each row of x [t, h], ROW_BLOCK
    rows at a time, the logits recomputed in the backward pass."""
    t = x.shape[0]
    pad = (-t) % ROW_BLOCK
    x = jnp.pad(x, ((0, pad), (0, 0)))
    labels = jnp.pad(labels, (0, pad))

    @jax.checkpoint
    def block(xs):
        xb, lb = xs
        logp = jax.nn.log_softmax(c.einsum(
            precision, "th,hv->tv", xb,
            c.stored({"head": head}, param_dtype)["head"]), axis=-1)
        return -jnp.take_along_axis(logp, lb[:, None], axis=-1)[:, 0]

    ce = jax.lax.map(block, (x.reshape(-1, ROW_BLOCK, x.shape[1]),
                             labels.reshape(-1, ROW_BLOCK)))
    return ce.reshape(-1)[:t]


def exit_distribution(lam):
    """lam [T, n] -> p [T, n]."""
    if lam.shape[0] == 1:
        return jnp.ones_like(lam)
    stayed = jnp.cumprod(1.0 - lam[:-1], axis=0)
    before = jnp.concatenate([jnp.ones_like(lam[:1]), stayed[:-1]], axis=0)
    return jnp.concatenate([lam[:-1] * before, stayed[-1:]], axis=0)


def loss_fn(w, batch, cfg, precision="float32"):
    ids, labels = batch
    stored = functools.partial(c.stored,
                               param_dtype=cfg["training"]["param_dtype"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    heads, passes = cfg["num_attention_heads"], cfg["total_ut_steps"]
    beta = cfg["training"]["exit_entropy_beta"]
    b, s = ids.shape
    mm = lambda x, m: c.einsum(precision, "bsh,hk->bsk", x, m)  # noqa: E731

    layers = cfg["num_hidden_layers"]
    top = stored({key: w[key] for key in ("norm_f", "exit_w", "exit_b")})

    @jax.checkpoint
    def apply(u, i):
        """Application i: layer i mod L, and the final norm where it is
        the pass's last."""
        p = stored({key: w[key][i % layers] for key in STACKED})
        n = rms(u, p["ln1"], eps)
        q, k, v = (mm(n, p[key]).reshape(b, s, heads, -1)
                   for key in ("q_w", "k_w", "v_w"))
        a = causal_attention(precision, rope(q, theta), rope(k, theta), v)
        u = u + rms(mm(a, p["o_w"]), p["ln1_post"], eps)
        n = rms(u, p["ln2"], eps)
        m = mm(jax.nn.silu(mm(n, p["gate_w"])) * mm(n, p["up_w"]),
               p["down_w"])
        u = u + rms(m, p["ln2_post"], eps)
        return jnp.where(i % layers == layers - 1,
                         rms(u, top["norm_f"], eps), u)

    def application(carry, i):
        u, exits = carry
        u = apply(u, i)
        t = i // layers  # the pass's exit: its last application's output
        exits = jax.lax.dynamic_update_index_in_dim(
            exits, jnp.where(i % layers == layers - 1, u, exits[t]), t, 0)
        return (u, exits), None

    h0 = stored({"rows": w["embed"][ids]})["rows"]
    (_, exits), _ = jax.lax.scan(
        application, (h0, jnp.zeros((passes,) + h0.shape, h0.dtype)),
        jnp.arange(passes * layers))
    exits = exits[:, :, :-1].reshape(passes, b * (s - 1), -1)
    targets = jnp.tile(labels[:, 1:].reshape(-1), passes)
    ce = token_cross_entropy(precision, exits.reshape(passes * b * (s - 1),
                                                      -1),
                             w["head"], targets,
                             cfg["training"]["param_dtype"]).reshape(
                                 passes, -1)
    lam = jax.nn.sigmoid(
        c.einsum(precision, "tnh,hk->tnk", exits, top["exit_w"])[..., 0]
        + top["exit_b"])
    p = exit_distribution(lam)
    entropy = -jnp.sum(jax.scipy.special.xlogy(p, p), axis=0)
    return jnp.mean(jnp.sum(p * ce, axis=0) - beta * entropy)
