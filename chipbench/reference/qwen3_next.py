"""Qwen3-Next-80B-A3B's training loss (Qwen/Qwen3-Next-80B-A3B-Instruct,
`config.json`, `model_type: qwen3_next`), written from the layer
equations. With zrms(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w) (every
norm of the model but the delta rule's output norm, eps 1e-6), no biases
anywhere, for layer l:

    h   = x + mixer_l(zrms(x; input_norm))
    out = h + moe_l(zrms(h; post_norm))
    logits = zrms(out_last; norm_f) W_head                (untied)
    loss = mean over b, i < s-1 of CE(logits_i, t_{i+1})

mixer_l is full attention where (l + 1) % 4 == 0, Gated DeltaNet
otherwise (Yang et al. 2024), u the normed input:

    per key head (16): (q 128 | k 128 | v 256 | z 256) = u W_qkvz,
                       (b 2 | a 2) = u W_ba
    (q | k | v) = silu(conv(q | k | v))   all 16 q heads, then the 16 k
        heads, then the 32 v heads: 8,192 channels, each its own 4 taps,
        causal, tap 3 on the current position, zeros before the start
    q = l2norm(q) / sqrt(128), k = l2norm(k)    (eps 1e-6); value head i
        reads key head i // 2
    beta = sigmoid(b),  g = -exp(A_log) * softplus(a + dt_bias)
    S_t = exp(g_t) S_{t-1};  S_t += beta_t k_t (v_t - S_t^T k_t)^T;
    o_t = S_t^T q_t                       S [128, 128] a value head, 0 at
                                          a sequence's start
    mixer = (rms(o) * gdn_norm * silu(z)) W_out   the norm over each
                                          128-wide value head, eps 1e-6

full attention, 16 query heads over 2 key/value heads, 256 wide:

    per head: (q 256 | gate 256) = u W_q;  k = u W_k;  v = u W_v
    q, k = zrms_256(q; q_norm), zrms_256(k; k_norm); the first 64 dims
    of each turned by rope (rotate-half, theta 1e7, positions 0..s-1),
    the other 192 as they are; query head i attends key/value head
    i // 8, causal, scale 1/16;  mixer = (ctx * sigmoid(gate)) W_o

moe_l, with E_i gated SiLU units 512 wide:

    s = softmax(n W_r)                   float32, over ALL 512 experts
    sel = top_10(s)                      ties to the lower index
    g_i = s_i / sum_{j in sel} s_j                    (norm_topk_prob)
    moe(n) = sum_{i in sel, i held here} g_i E_i(n)
             + sigmoid(n w_sg) E_shared(n)          E_shared 512 wide

Departures and assumptions (the configuration file's `assumed` has them
too): the norms' placement and names; the q/k/v/z and b/a split order
per key head, after the family's modelling code; the router's product
is float32 at every `precision` (the control rounds the projections,
the delta rule's products, the attention and the experts); the sum
over experts runs over the share this chip holds (`deployment`: experts
ep_rank * H .. of the published 512) and what the others would add is
left out, as in the program.

For room (626 M float32 weights, both moments and the gradient are
10 GB of the chip's 16, and a [4, 8192, 2048] float32 activation is
268 MB): every layer is recomputed in the backward pass; the mixers
follow the batch a row at a time (attention a query head at a time
within it, its scores recomputed; the recurrence a token at a time in
segments of `SEGMENT` tokens, each recomputed, so that only the state
entering a segment is kept), the experts meet all the tokens one expert
at a time, and the logits are taken a block of rows at a time; every
layer's weights are arrays of their own (`l0_*` .. `l3_*`); the float32
masters are rounded to the stored type where they are used, a layer at
a time."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import common as c

GDN = ("input_norm", "qkvz", "ba", "conv", "A_log", "dt_bias", "gdn_norm",
       "out", "post_norm")
ATTENTION = ("input_norm", "q", "k", "v", "q_norm", "k_norm", "o",
             "post_norm")
MOE = ("router", "e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down",
       "s_expert_gate")
ROW_BLOCK = 1024
SEGMENT = 64
HIGHEST = jax.lax.Precision.HIGHEST


def is_attention(cfg, i):
    return (i + 1) % cfg["full_attention_interval"] == 0


def layer_keys(cfg, i):
    return (ATTENTION if is_attention(cfg, i) else GDN) + MOE


def zrms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * (1.0 + w)


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


def l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + eps)


def recurrence(precision, q, k, v, g, beta):
    """The delta rule token by token: q, k [s, H, d_k], v [s, H, d_v],
    g, beta [s, H] -> o [s, H, d_v], its three products (the state
    recalled at k, the rank-one write, the state read at q) rounded to
    `precision`. A segment of SEGMENT tokens is recomputed in the
    backward pass; the state entering it is kept."""
    s, heads, dk = k.shape
    pad = (-s) % SEGMENT

    def padded(x):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((-1, SEGMENT) + x.shape[1:])

    def token(state, xs):
        qt, kt, vt, gt, bt = xs
        state = jnp.exp(gt)[:, None, None] * state
        recalled = c.einsum(precision, "hkv,hk->hv", state, kt)
        state = state + c.einsum(precision, "hk,hv->hkv", kt,
                                 (vt - recalled) * bt[:, None])
        return state, c.einsum(precision, "hkv,hk->hv", state, qt)

    @jax.checkpoint
    def segment(state, xs):
        return jax.lax.scan(token, state, xs)

    state = jnp.zeros((heads, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(segment, state, tuple(
        padded(x) for x in (q, k, v, g, beta)))
    return o.reshape((-1,) + o.shape[2:])[:s]


def gated_delta_net(precision, u, p, cfg):
    """One row u [s, h] through the Gated DeltaNet mixer."""
    s = u.shape[0]
    nk, nv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    per = nv // nk
    mm = functools.partial(c.einsum, precision, "sh,hk->sk")
    qkvz = mm(u, p["qkvz"]).reshape(s, nk, 2 * dk + 2 * per * dv)
    q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + per * dv], axis=-1)
    ba = mm(u, p["ba"]).reshape(s, nk, 2 * per)
    b, a = ba[..., :per].reshape(s, nv), ba[..., per:].reshape(s, nv)
    x = jnp.concatenate([q.reshape(s, -1), k.reshape(s, -1),
                         v.reshape(s, -1)], axis=-1)
    taps = p["conv"].shape[1]
    mixed = jnp.zeros_like(x)
    for j in range(taps):  # tap j meets the position taps - 1 - j back
        back = taps - 1 - j
        past = jnp.concatenate([jnp.zeros_like(x[:back]),
                                x[:x.shape[0] - back]], axis=0)
        mixed = mixed + p["conv"][:, j] * past
    x = jax.nn.silu(mixed)
    q = l2norm(x[:, :nk * dk].reshape(s, nk, dk)) / np.sqrt(dk)
    k = l2norm(x[:, nk * dk:2 * nk * dk].reshape(s, nk, dk))
    v = x[:, 2 * nk * dk:].reshape(s, nv, dv)
    q, k = jnp.repeat(q, per, axis=1), jnp.repeat(k, per, axis=1)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    o = recurrence(precision, q, k, v, g, beta)
    o = (o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                           + cfg["rms_norm_eps"]) * p["gdn_norm"]
         * jax.nn.silu(z.reshape(s, nv, dv)))
    return mm(o.reshape(s, nv * dv), p["out"])


def gated_attention(precision, u, p, cfg):
    """One row u [s, h]: a query head at a time against its group's key
    and value head, the scores recomputed in the backward pass."""
    s = u.shape[0]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    turned = int(d * cfg["partial_rotary_factor"])
    eps = cfg["rms_norm_eps"]
    theta = float(cfg["rope_theta"])
    mm = functools.partial(c.einsum, precision, "sh,hk->sk")
    qg = mm(u, p["q"]).reshape(s, heads, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    q = zrms(q, p["q_norm"], eps)
    k = zrms(mm(u, p["k"]).reshape(s, kv_heads, d), p["k_norm"], eps)
    v = mm(u, p["v"]).reshape(s, kv_heads, d)
    q, k = (jnp.concatenate([rope_halves(t[..., :turned], theta),
                             t[..., turned:]], axis=-1) for t in (q, k))
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
    ctx = jnp.moveaxis(ctx, 0, 1) * jax.nn.sigmoid(gate)
    return mm(ctx.reshape(s, heads * d), p["o"])


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
    scores = jax.nn.softmax(jnp.einsum("sh,he->se", n, p["router"],
                                       precision=HIGHEST), axis=-1)
    _, sel = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    chosen = jnp.sum(jax.nn.one_hot(sel, total, dtype=scores.dtype), axis=1)
    picked = scores * chosen
    gates = (picked / jnp.sum(picked, axis=-1, keepdims=True))[
        :, first:first + held]

    @jax.checkpoint
    def expert(xs):
        g, gate, up, down = xs
        return g[:, None] * gated(precision, n, gate, up, down)

    # the sum is outside the recomputed part: a step keeps nothing of it
    y, _ = jax.lax.scan(lambda y, xs: (y + expert(xs), None),
                        jnp.zeros_like(n),
                        (gates.T, p["e_gate"], p["e_up"], p["e_down"]))
    return y


def shared_expert(precision, n, p):
    weight = jax.nn.sigmoid(c.einsum(precision, "sh,hk->sk", n,
                                     p["s_expert_gate"]))
    return weight * gated(precision, n, p["s_gate"], p["s_up"], p["s_down"])


def layer(precision, u, p, cfg):
    """One decoder layer on the batch u [b, s, h]; `p` its weights as
    stored. `qkvz` marks a Gated DeltaNet layer: the mixer follows the
    batch a row at a time, each row recomputed in the backward pass; the
    experts meet all the batch's tokens at once, one expert at a time."""
    eps = cfg["rms_norm_eps"]
    mixer = gated_delta_net if "qkvz" in p else gated_attention
    u = jax.lax.map(jax.checkpoint(lambda r: r + mixer(
        precision, zrms(r, p["input_norm"], eps), p, cfg)), u)
    n = zrms(u, p["post_norm"], eps).reshape(-1, u.shape[-1])
    return u + (routed_experts(precision, n, p, cfg)
                + shared_expert(precision, n, p)).reshape(u.shape)


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

    @jax.checkpoint
    def rows(u, p):
        """One layer over the batch u [b, s, h]; `p` its float32
        masters."""
        return layer(precision, u, stored(p), cfg)

    h = stored({"rows": w["embed"][ids]})["rows"]
    for i in range(cfg["num_hidden_layers"]):
        h = rows(h, {k: w[f"l{i}_{k}"] for k in layer_keys(cfg, i)})
    x = zrms(h[:, :-1], stored({"g": w["norm_f"]})["g"], cfg["rms_norm_eps"])
    targets = labels[:, 1:]
    return summed_cross_entropy(
        precision, x.reshape(-1, x.shape[-1]), w["head"],
        targets.reshape(-1), param_dtype) / targets.size
