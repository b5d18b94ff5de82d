"""Flash attention — pallas TPU kernel.

The analog of the reference's hand-written fused CUDA attention
(`operators/fused/fused_attention_op.cu` family): online-softmax tiling keeps
the S×S score matrix out of HBM entirely. Forward saves only the logsumexp
row stats; backward recomputes scores blockwise (dq kernel + dkv kernel) with
f32 accumulation. Layout [B, S, H, D] outside (framework attention layout),
[B*H, S, D] inside.

Block sizes 128×128 match the MXU tile; inputs may be bf16 (accumulation is
always f32). Sequence is padded to a 128 multiple by the wrapper; padded key
positions are masked with the true length.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_Q = 128
BLOCK_KV = 128
NEG_INF = -1e30


def is_available():
    """The kernel lowers through Mosaic: TPU backends only."""
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, l_ref, *, kv_len,
                causal, scale, block_kv):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale  # [BQ, D]
    bq, d = q.shape
    q_pos = qi * BLOCK_Q + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    n_kv = pl.cdiv(k_ref.shape[1], block_kv)
    if causal:
        # only blocks whose first key position <= last query position
        n_kv = jnp.minimum(n_kv, (qi * BLOCK_Q + bq + block_kv - 1) // block_kv)

    def body(ki, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(ki * block_kv, block_kv), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(ki * block_kv, block_kv), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        k_pos = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_kv), 1)
        mask = k_pos < kv_len
        if causal:
            mask = mask & (q_pos >= k_pos)
        s = jnp.where(mask, s, NEG_INF)
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_kv, body, (m0, l0, acc0))

    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    l_ref[0] = m + jnp.log(l_safe)  # logsumexp per row, [BQ, 1]


def _flash_fwd(q, k, v, causal, scale, kv_len, interpret):
    """q/k/v: [BH, S, D] (seq padded to BLOCK multiples); kv_len = true
    unpadded key length for masking."""
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    grid = (bh, s_q // BLOCK_Q)
    kernel = functools.partial(
        _fwd_kernel, kv_len=kv_len, causal=causal, scale=scale,
        block_kv=BLOCK_KV)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, BLOCK_Q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, s_k, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, s_k, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, BLOCK_Q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, BLOCK_Q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out, lse


# --------------------------------------------------------------- backward

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, kv_len, causal, scale, block_kv):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]      # [BQ, 1]
    delta = delta_ref[0]  # [BQ, 1]
    bq, d = q.shape
    q_pos = qi * BLOCK_Q + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    n_kv = pl.cdiv(k_ref.shape[1], block_kv)
    if causal:
        n_kv = jnp.minimum(n_kv, (qi * BLOCK_Q + bq + block_kv - 1) // block_kv)

    def body(ki, dq):
        k = k_ref[0, pl.ds(ki * block_kv, block_kv), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(ki * block_kv, block_kv), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        k_pos = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_kv), 1)
        mask = k_pos < kv_len
        if causal:
            mask = mask & (q_pos >= k_pos)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, n_kv, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, q_len, causal, scale, block_q):
    ki = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    bkv, d = k.shape
    k_pos = ki * BLOCK_KV + jax.lax.broadcasted_iota(jnp.int32, (1, bkv), 1)

    n_q = pl.cdiv(q_ref.shape[1], block_q)
    start_q = 0
    if causal:
        start_q = (ki * BLOCK_KV) // block_q  # earlier q blocks are masked

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32) * scale
        do = do_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(qi * block_q, block_q), :]      # [bq, 1]
        delta = delta_ref[0, pl.ds(qi * block_q, block_q), :]  # [bq, 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)
        mask = q_pos < q_len
        if causal:
            mask = mask & (q_pos >= k_pos)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dv_new = dv + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_new = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)
        return dk_new, dv_new

    dk0 = jnp.zeros((bkv, d), jnp.float32)
    dv0 = jnp.zeros((bkv, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(start_q, n_q, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, do, causal, scale, kv_len, q_len,
               interpret):
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [BH, S, 1]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, kv_len=kv_len, causal=causal,
                          scale=scale, block_kv=BLOCK_KV),
        grid=(bh, s_q // BLOCK_Q),
        in_specs=[
            pl.BlockSpec((1, BLOCK_Q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, s_k, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, s_k, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, BLOCK_Q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, BLOCK_Q, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, BLOCK_Q, 1), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_Q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s_q, d), q.dtype),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, q_len=q_len, causal=causal,
                          scale=scale, block_q=BLOCK_Q),
        grid=(bh, s_k // BLOCK_KV),
        in_specs=[
            pl.BlockSpec((1, s_q, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, BLOCK_KV, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, BLOCK_KV, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, s_q, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, s_q, 1), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, s_q, 1), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, BLOCK_KV, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, BLOCK_KV, d), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_k, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s_k, d), v.dtype),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ------------------------------------------------------------- public API

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, q_len, kv_len, interpret):
    out, _ = _flash_fwd(q, k, v, causal, scale, kv_len, interpret)
    return out


def _flash_vjp_fwd(q, k, v, causal, scale, q_len, kv_len, interpret):
    out, lse = _flash_fwd(q, k, v, causal, scale, kv_len, interpret)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, scale, q_len, kv_len, interpret, res, do):
    q, k, v, out, lse = res
    return _flash_bwd(q, k, v, out, lse, do, causal, scale, kv_len, q_len,
                      interpret)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _pad_seq(x, block):
    s = x.shape[1]
    pad = (-s) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x, s


def flash_attention_bshd(q, k, v, causal=False, scale=None, interpret=False):
    """q/k/v: [B, S, H, D] -> [B, S, H, D]."""
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    if causal and s_q != s_k:
        raise NotImplementedError(
            "causal flash attention requires s_q == s_k (top-left aligned "
            "mask); bottom-right cache alignment is not implemented")
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    def to_bhsd(x):
        return jnp.swapaxes(x, 1, 2).reshape(b * h, x.shape[1], d)

    qf, _ = _pad_seq(to_bhsd(q), BLOCK_Q)
    kf, _ = _pad_seq(to_bhsd(k), BLOCK_KV)
    vf, _ = _pad_seq(to_bhsd(v), BLOCK_KV)
    out = _flash(qf, kf, vf, causal, float(scale), s_q, s_k, interpret)
    out = out[:, :s_q]
    return jnp.swapaxes(out.reshape(b, h, s_q, d), 1, 2)
