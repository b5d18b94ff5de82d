"""What the plain references share. Written from the papers: layer
normalisation (Ba et al. 2016), scaled dot-product attention (Vaswani et
al. 2017), GELU (Hendrycks & Gimpel 2016), AdamW (Loshchilov & Hutter
2019, in the form Paddle's `adamw` states: epsilon joins the uncorrected
sqrt(v), and the bias corrections fold into the step size).

`precision` selects how the matrix products round:
  "float32"   operands as they are, `highest` precision — the reference;
  "bfloat16"  operands rounded to bfloat16 (what the cells state);
  "float8"    operands scaled to their largest value and rounded to 4
              exponent and 3 mantissa bits (float8 e4m3's grid) — the
              control, one precision below bfloat16.
Sums are float32 in all three; gradients pass the rounding straight
through.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

_F8_MAX = 240.0  # largest finite value of 4 exponent and 3 mantissa bits


def _round_through(x, precision):
    """`reduce_precision` and not a pair of converts: XLA is allowed to
    drop a float32 -> bfloat16 -> float32 round trip (excess precision)
    and on the TPU it does."""
    if precision == "float32":
        return x
    if precision == "bfloat16":
        r = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    elif precision == "float8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _F8_MAX
        r = jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                     mantissa_bits=3) * scale
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return x + jax.lax.stop_gradient(r - x)


def stored(w, param_dtype):
    """The weights as the forward and backward passes see them: the
    float32 masters rounded to the type the configuration stores its
    parameters in, the gradient passing straight through to the master
    (mixed-precision training, Micikevicius et al. 2018). This is part
    of the algorithm, whatever `precision` the products round to: a
    master that moves by less than half a bfloat16 step leaves its
    parameter where it was."""
    if param_dtype == "float32":
        return w
    if param_dtype != "bfloat16":
        raise ValueError(f"unknown param_dtype {param_dtype!r}")
    return {k: _round_through(v, "bfloat16") for k, v in w.items()}


def einsum(precision, spec, a, b):
    return jnp.einsum(spec, _round_through(a, precision),
                      _round_through(b, precision),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def gelu(x, tanh_form):
    if tanh_form:
        return 0.5 * x * (1.0 + jnp.tanh(
            np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))
    return 0.5 * x * (1.0 + jax.lax.erf(x / np.sqrt(2.0)))


def attention(precision, x, qkv_w, qkv_b, heads, causal):
    """Self-attention over x [b, s, h]; the fused projection's columns
    are q, k, v in turn, each [heads, head_dim]."""
    b, s, h = x.shape
    qkv = einsum(precision, "bsh,hk->bsk", x, qkv_w) + qkv_b
    q, k, v = (t.reshape(b, s, heads, h // heads)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = einsum(precision, "bqnd,bknd->bnqk", q, k) / np.sqrt(h // heads)
    if causal:
        keep = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(keep, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = einsum(precision, "bnqk,bknd->bqnd", probs, v)
    return ctx.reshape(b, s, h)


def summed_cross_entropy(precision, x, table, bias, labels, ignore=None,
                         block=1024):
    """Sum of -log softmax(x @ table.T + bias)[label] over the rows of
    x [t, h] whose label is not `ignore`, and their count — in blocks
    of rows, recomputed in the backward pass, so that the [t, vocab]
    logits never exist whole."""
    t = x.shape[0]
    pad = (-t) % block
    skip = -1 if ignore is None else ignore
    x = jnp.pad(x, ((0, pad), (0, 0)))
    labels = jnp.pad(labels, (0, pad), constant_values=skip)

    @jax.checkpoint
    def one(xb, lb):
        logits = einsum(precision, "th,vh->tv", xb, table)
        if bias is not None:
            logits = logits + bias
        valid = lb != skip
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(
            logp, jnp.where(valid, lb, 0)[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(valid, picked, 0.0)), jnp.sum(valid)

    def body(carry, xs):
        s, n = one(*xs)
        return (carry[0] + s, carry[1] + n), None

    (total, count), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        (x.reshape(-1, block, x.shape[1]), labels.reshape(-1, block)))
    return total, count


def adamw_step(w, m, v, g, t, hp):
    """One AdamW step on float32 trees; `t` counts from 1."""
    b1, b2 = hp["beta1"], hp["beta2"]
    t = jnp.asarray(t, jnp.float32)
    lr_t = hp["learning_rate"] * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)

    def one(w, m, v, g):
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * jnp.square(g)
        w = (w - lr_t * m / (jnp.sqrt(v) + hp["epsilon"])
             - hp["learning_rate"] * hp["weight_decay"] * w)
        return w, m, v

    out = {k: one(w[k], m[k], v[k], g[k]) for k in w}
    return tuple({k: o[i] for k, o in out.items()} for i in range(3))


def train(loss_fn, make_w0, batches, hp, reduce):
    """Follow one AdamW step a batch from the float32 weights
    `make_w0()`. `reduce(tree)` is what the caller keeps of a tree (its
    leaves' norms). Returns the per-step losses and `reduce` of the
    first step's gradient, of the first moment after the last step and
    of the weights' change over all steps. One jitted program serves
    every step, the state is donated to it, and the first weights are
    made again at the end instead of being kept."""

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(w, m, v, t, batch):
        loss, g = jax.value_and_grad(loss_fn)(w, batch)
        w, m, v = adamw_step(w, m, v, g, t, hp)
        return w, m, v, loss, reduce(g)

    w = make_w0()
    m = jax.tree_util.tree_map(jnp.zeros_like, w)
    v = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses, first_grad = [], None
    for i, batch in enumerate(batches):
        w, m, v, loss, gnorm = step(w, m, v, i + 1, batch)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = jax.device_get(gnorm)
    moment = jax.device_get(jax.jit(reduce)(m))
    del m, v
    w0 = make_w0()
    change = jax.device_get(jax.jit(
        lambda a, b: reduce({k: a[k] - b[k] for k in a}))(w, w0))
    return {"losses": losses, "grad": first_grad, "moment": moment,
            "change": change}
