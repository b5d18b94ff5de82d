"""Gated DeltaNet's sequence mixing (Yang et al., "Gated Delta Networks:
Improving Mamba2 with Delta Rule", arXiv:2412.06464) after its causal
convolution (`F.causal_conv_silu`): the gated delta rule, a linear
recurrence over a [d_k, d_v] state per value head,

    S_t = exp(g_t) S_{t-1} + beta_t k_t (v_t - exp(g_t) S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

trained in its chunked form (section 3 of the paper: the WY / UT
representation). Not in the reference snapshot.

Within a chunk of C positions, with Q and K its queries and keys
normalized (q / (|q| sqrt(d_k)), k / |k|), G the cumulative sum of g
from the chunk's start (float32), Gamma_ij = exp(G_i - G_j) for j <= i
and 0 above, and K_b = diag(beta) K,

    A   = strictly_lower(K_b K^T * Gamma)     T = (I + A)^-1  (unit lower)
    U   = T diag(beta) V          W = T (K_b * exp(G))
    P   = lower(Q K^T * Gamma)    Q_e = Q * exp(G)    K_d = K * exp(G_C - G)

and across chunks, S the state entering the chunk (float32, zero at a
sequence's start):

    V'  = U - W S
    O   = Q_e S + P V'
    S  <- exp(G_C) S + K_d^T V'

T is formed by matrix products, by block doubling: from T = I (every
1 x 1 diagonal block inverted), each of ceil(log2(C)) levels joins
adjacent s-blocks into 2s-blocks by T <- T - T M T, M being A on the
lower-left s x s quadrant of each 2s diagonal block (exact: T is block
diagonal, so (T M)^2 = 0). Every intermediate T is the inverse of
principal blocks of (I + A), so no entry grows past the final T's, as
the powers of A in a Neumann series would. A chunk that is no power of
two needs no padding: each level's last block is cut short at C, and
is inverted all the same. The derivative is dT = -T dA T (a
`jax.custom_jvp`), so the backward takes two products and does not
replay the doubling. The inverse and its application run at
`Precision.HIGHEST`, float32 accuracy: one bfloat16 pass there would
be another result.

The forward and the backward are one `jax.custom_vjp`, each a sweep over
the chunks whose step forms its chunk's within-chunk part, every batch
row and head at once: the forward keeps the state entering each chunk,
in float32, and nothing else it formed (at 4 x 8,192 tokens and 32
heads of 128: 1 GB); the backward sweeps back with the gradient of the
state, forms the chunk's part again and takes the chunk's gradients
through its vjp. No array a chunk long is kept for every chunk (at 4 x
8,192 tokens and 32 heads of 128 those would be 0.5 GB each). The
decays are float32 products and never enter a matrix product.

The other matrix products take their operands as they are (float32
here) at the backend's default precision: on the TPU one bfloat16 pass
with a float32 sum, as the flash kernel and `experts` multiply.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...core.dispatch import call_op

CHUNK = 64


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def _mm_highest(spec, a, b):
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


@jax.custom_jvp
def _unit_lower_inverse(a):
    """(I + a)^-1 of a strictly lower a [..., C, C] by block doubling
    at `Precision.HIGHEST` (the module's docstring), any C."""
    chunk = a.shape[-1]
    rows, cols = np.arange(chunk)[:, None], np.arange(chunk)[None, :]

    def quadrant(half):
        """A on the lower-left `half` x `half` quadrant of each diagonal
        block 2 `half` wide, 0 elsewhere."""
        return jnp.where((rows // (2 * half) == cols // (2 * half))
                         & (rows % (2 * half) >= half)
                         & (cols % (2 * half) < half), a, 0.0)

    # the first level's T is I, so its T - T M T is I - M
    t = jnp.eye(chunk, dtype=a.dtype) - quadrant(1)
    half = 2
    while half < chunk:
        tm = _mm_highest("...ij,...jk->...ik", t, quadrant(half))
        t = t - _mm_highest("...ij,...jk->...ik", tm, t)
        half *= 2
    return t


@_unit_lower_inverse.defjvp
def _unit_lower_inverse_jvp(primals, tangents):
    (a,), (da,) = primals, tangents
    t = _unit_lower_inverse(a)
    return t, -_mm_highest("...ij,...jk->...ik", t,
                           _mm_highest("...ij,...jk->...ik", da, t))


def _masks(chunk):
    rows = jnp.arange(chunk)[:, None]
    cols = jnp.arange(chunk)[None, :]
    return rows >= cols, rows > cols


def _unit(x):
    """x / sqrt(|x|^2 + 1e-6) over the last axis."""
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + 1e-6)


def _within(q, k, v, g, beta):
    """One chunk's within-chunk part, float32: q, k [B, H_k, C, d_k]
    (normalized here), v [B, H, C, d_v], g, beta [B, H, C] -> (U, W,
    Q_e, P, K_d, exp(G_C)), value head i reading key head i // (H / H_k).
    T = (I + A)^-1 by `_unit_lower_inverse`'s products and applied by
    one more, all at `Precision.HIGHEST`; C need not be a power of two."""
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    q, k = _unit(q) * q.shape[-1] ** -0.5, _unit(k)
    group = v.shape[1] // q.shape[1]
    q, k = (jnp.repeat(x, group, axis=1) for x in (q, k))
    chunk = g.shape[-1]
    lower, strict = _masks(chunk)
    gc = jnp.cumsum(g, axis=-1)
    gl = gc[..., -1:]
    diff = gc[..., :, None] - gc[..., None, :]
    gamma = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    kb = k * beta[..., None]
    a = jnp.where(strict, _mm("...id,...jd->...ij", kb, k) * gamma, 0.0)
    rhs = jnp.concatenate([v * beta[..., None],
                           kb * jnp.exp(gc)[..., None]], axis=-1)
    uw = _mm_highest("...ij,...jd->...id", _unit_lower_inverse(a), rhs)
    u, w = uw[..., :v.shape[-1]], uw[..., v.shape[-1]:]
    p = _mm("...id,...jd->...ij", q, k) * gamma
    qe = q * jnp.exp(gc)[..., None]
    kd = k * jnp.exp(gl - gc)[..., None]
    return u, w, qe, p, kd, jnp.exp(gl)


def _to_chunks(x, chunk):
    """[B, T, H, ...] -> [N, B, H, C, ...], T padded to whole chunks."""
    b, t = x.shape[:2]
    pad = (-t) % chunk
    x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    x = x.reshape((b, (t + pad) // chunk, chunk) + x.shape[2:])
    return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)


def _from_chunks(x, t):
    """[N, B, H, C, ...] -> [B, T, H, ...]."""
    x = jnp.moveaxis(jnp.moveaxis(x, 0, 1), 3, 2)
    b, n, c = x.shape[:3]
    return x.reshape((b, n * c) + x.shape[3:])[:, :t]


def _rule_fwd(q, k, v, g, beta, chunk):
    inputs = (q, k, v, g, beta)
    parts = [_to_chunks(x, chunk) for x in inputs]
    state = jnp.zeros(v.shape[:1] + v.shape[2:3] + (q.shape[-1], v.shape[-1]),
                      jnp.float32)

    def step(s, xs):
        u, w, qe, p, kd, decay = _within(*xs)
        v_new = u - _mm("...ck,...kv->...cv", w, s)
        o = _mm("...ck,...kv->...cv", qe, s) + _mm("...ij,...jv->...iv", p,
                                                    v_new)
        s_next = decay[..., None] * s + _mm("...ck,...cv->...kv", kd, v_new)
        return s_next, (o.astype(v.dtype), s)

    _, (o, states) = jax.lax.scan(step, state, parts)
    return _from_chunks(o, q.shape[1]), (inputs, states)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, g, beta, chunk):
    return _rule_fwd(q, k, v, g, beta, chunk)[0]


def _rule_vjp_bwd(chunk, res, do):
    inputs, states = res
    parts = [_to_chunks(x, chunk) for x in inputs]

    def step(ds, xs):
        """ds: the gradient of the state leaving chunk n."""
        *chunk_in, s, do_n = xs
        (u, w, qe, p, kd, decay), within_vjp = jax.vjp(_within, *chunk_in)
        do_n = do_n.astype(jnp.float32)
        v_new = u - _mm("...ck,...kv->...cv", w, s)
        dv_new = (_mm("...ij,...iv->...jv", p, do_n)
                  + _mm("...ck,...kv->...cv", kd, ds))
        ds_in = (_mm("...ck,...cv->...kv", qe, do_n)
                 + decay[..., None] * ds
                 - _mm("...ck,...cv->...kv", w, dv_new))
        grads = within_vjp((
            dv_new,
            -_mm("...cv,...kv->...ck", dv_new, s),
            _mm("...cv,...kv->...ck", do_n, s),
            _mm("...iv,...jv->...ij", do_n, v_new),
            _mm("...cv,...kv->...ck", v_new, ds),
            jnp.sum(s * ds, axis=(-2, -1))[..., None]))
        return ds_in, grads

    _, grads = jax.lax.scan(
        step, jnp.zeros(states.shape[1:], jnp.float32),
        (*parts, states, _to_chunks(do, chunk)), reverse=True)
    return tuple(_from_chunks(d, do.shape[1]) for d in grads)


_rule.defvjp(_rule_fwd, _rule_vjp_bwd)


def chunked_delta_rule(q, k, v, g, beta, chunk=CHUNK):
    """The gated delta rule over jax arrays q, k [B, T, H_k, d_k], v
    [B, T, H, d_v], g (the log decay, <= 0) and beta [B, T, H], H a
    multiple of H_k and value head i reading key head i // (H / H_k): o
    [B, T, H, d_v] in v's dtype, differentiable in all five, computed a
    chunk of `chunk` positions at a time (T need not be a multiple: the
    sequence is padded with positions that come after it). No state
    enters: each batch row starts from zero. The work is float32 whatever
    the inputs are; the backward keeps the inputs as they are and the
    state entering each chunk in float32, and forms everything else
    again a chunk at a time. The rule reads q / (|q| sqrt(d_k)) and
    k / |k| (|x| = sqrt(sum x^2 + 1e-6) over d_k, Gated DeltaNet's
    normalization), formed in float32 a chunk at a time: no normalized
    copy of the whole sequence is made, forward or backward."""
    if v.shape[2] % q.shape[2] or k.shape[2] != q.shape[2]:
        raise ValueError(
            f"chunked_delta_rule: {v.shape[2]} value heads over "
            f"{q.shape[2]} query and {k.shape[2]} key heads; the value "
            f"heads must be a multiple of the key heads, as many as the "
            f"query's")
    return _rule(q, k, v, g, beta, chunk)


def gated_delta_rule(q, k, v, a, b, a_log, dt_bias, chunk=CHUNK):
    """Gated DeltaNet's recurrence on Tensors as its layer forms them:
    q, k [B, T, H_k, d_k], v [B, T, H_v, d_v] with H_v a multiple of H_k
    (value head i reads key head i // (H_v / H_k)), a and b [B, T, H_v],
    a_log and dt_bias [H_v]. In float32,

        q = l2norm(q) / sqrt(d_k)     k = l2norm(k)        (eps 1e-6)
        beta = sigmoid(b)             g = -exp(a_log) softplus(a + dt_bias)

    and `chunked_delta_rule` of them, which normalizes q and k a chunk
    at a time: o [B, T, H_v, d_v] in v's dtype. The key heads stay H_k
    wide until a chunk's step reads them.
    In a compiled step its device time goes under the scope
    `delta_rule`."""
    def _gdn(q, k, v, a, b, a_log, dt_bias):
        beta = jax.nn.sigmoid(b.astype(jnp.float32))
        g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
            a.astype(jnp.float32) + dt_bias.astype(jnp.float32))
        return chunked_delta_rule(q, k, v, g, beta, chunk)

    return call_op(_gdn, q, k, v, a, b, a_log, dt_bias, op_name="delta_rule")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gated_norm(x, z, w, epsilon):
    return _gated_norm_fwd(x, z, w, epsilon)[0]


def _gated_norm_fwd(x, z, w, epsilon):
    # the barriers keep XLA from turning the casts into float32 copies of
    # x, z and dout made where those are produced (512 MB each at the
    # cell's size), which the norm never needs whole
    x, z = jax.lax.optimization_barrier((x, z))
    x32 = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
                      + epsilon)
    out = x32 * r * w.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    return out.astype(x.dtype), (x, z, w, r)


def _gated_norm_bwd(_epsilon, res, dout):
    x, z, w, r = res
    x, z, dout = jax.lax.optimization_barrier((x, z, dout))
    n = x.astype(jnp.float32) * r
    z32, w32 = z.astype(jnp.float32), w.astype(jnp.float32)
    sig = jax.nn.sigmoid(z32)
    d = dout.astype(jnp.float32)
    dn = d * w32 * z32 * sig
    dx = r * (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True))
    dz = d * n * w32 * sig * (1.0 + z32 * (1.0 - sig))
    dw = jnp.sum(d * n * z32 * sig, axis=tuple(range(x.ndim - 1)))
    return dx.astype(x.dtype), dz.astype(z.dtype), dw.astype(w.dtype)


_gated_norm.defvjp(_gated_norm_fwd, _gated_norm_bwd)


def gated_rms_norm(x, gate, weight, epsilon=1e-6):
    """rms(x) * weight * silu(gate) over the last axis, in float32; the
    result has x's dtype. Gated DeltaNet's output norm, per value head.
    The backward keeps x and the gate as they came and the reciprocal
    norms, and forms the rest again. In a compiled step its device time
    goes under the scope `gated_norm`."""
    return call_op(lambda v, z, w: _gated_norm(v, z, w, epsilon), x, gate,
                   weight, op_name="gated_norm")
