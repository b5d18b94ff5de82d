"""Flash attention — pallas TPU kernel.

The analog of the reference's hand-written fused CUDA attention
(`operators/fused/fused_attention_op.cu` family): online-softmax tiling keeps
the S×S score matrix out of HBM entirely. Forward saves only the logsumexp
row stats; backward recomputes scores blockwise (dq kernel + dkv kernel).
Layout [B, S, H, D] outside (framework attention layout), [B*H, S, D] inside.
q and k share one width `d_qk` and v, the output and their gradients another,
`d_v` (latent attention: 192-wide keys, 128-wide values); the scores contract
over `d_qk` in one product, `p v`, `do v^T` and `dv` run over `d_v`. With
`d_v == d_qk` the kernels are what they were before the widths could differ.

Grouped-query heads: k and v may have fewer heads than q, `group` query heads
to each (query head i reads key/value head i // group). K and V stay at their
own head count in HBM: the forward and dq kernels find a query head's K and V
through the block index map, and the dkv kernel's grid has a third, innermost
axis over the group's query heads, whose dk and dv meet in the float32 output
block that stays in VMEM across it. With one head each the three calls are
what they were before the counts could differ.

What is multiplied in which dtype: every `dot_general` takes its operands in
the dtype the call's inputs arrive in and accumulates in float32. bf16 inputs
go to the MXU as they are, and `p` (forward PV, backward dV) and `ds` (dQ, dK)
are rounded to bf16 just before their product, as XLA's path does with
`probs`; float32 inputs keep float32 operands. Everything that is not an MXU
operand is float32: the scores (the softmax scale is applied to them, so it
costs no rounding), the running max and sum, `exp`, `lse`, `delta` and the
output accumulators.

Blocks: a score tile is `block x block` with `block` the largest of 512, 256,
128 that divides the sequence padded to a 128 multiple (measured on the v5e,
PR 26: the kernels are bound by the latency of one loop iteration and by
vector spills, not by the MXU, and 512 x 512 runs 2.4x faster than 128 x 128).
The dkv kernel computes its scores transposed (`k q^T`, the row statistics as
rows), so that each of its four products is a plain or a transposed-weights
matmul and nothing is transposed on the vector units.

Which blocks are masked: causal calls mask every block they visit by position
(blocks wholly above the diagonal are not visited); padded key (forward, dq)
or query (dkv) positions are masked with the true length where the wrapper
padded, and a call with neither builds no mask at all.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCKS = (512, 256, 128)
NEG_INF = -1e30

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b


def is_available():
    """The kernel lowers through Mosaic: TPU backends only."""
    return jax.default_backend() == "tpu"


def _block(padded_len):
    return next(b for b in BLOCKS if padded_len % b == 0)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _rows(ref, i, n):
    """Rows [i*n, (i+1)*n) of the [1, S, D] block `ref`."""
    return ref[0, pl.ds(pl.multiple_of(i * n, n), n), :]


def _keep(q_pos, k_pos, causal, padded_pos, true_len):
    """Which scores of a tile count (None: all). `padded_pos` is the side
    the wrapper padded past `true_len`, or None."""
    keep = None if padded_pos is None else padded_pos < true_len
    if causal:
        keep = q_pos >= k_pos if keep is None else keep & (q_pos >= k_pos)
    return keep


def _whole_kv(group):
    """The index map of the whole [1, S, D] K or V of a query head's
    grid step: its own head's, or with grouped heads its group's."""
    if group == 1:
        return lambda b, i: (b, 0, 0)
    return lambda b, i: (b // group, 0, 0)


# ---------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, l_ref, *, kv_len,
                causal, scale, block_kv):
    qi = pl.program_id(1)
    bq, d_v = o_ref.shape[1:]
    q = q_ref[0]
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    s_k = k_ref.shape[1]
    n_kv = s_k // block_kv
    if causal:
        # only blocks whose first key position <= last query position
        n_kv = jnp.minimum(n_kv, pl.cdiv((qi + 1) * bq, block_kv))

    def body(ki, carry):
        m, l, acc = carry
        k = _rows(k_ref, ki, block_kv)
        v = _rows(v_ref, ki, block_kv)
        s = _dot(q, k, _NT) * scale
        k_pos = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_kv), 1)
        keep = _keep(q_pos, k_pos, causal,
                     k_pos if kv_len < s_k else None, kv_len)
        if keep is not None:
            s = jnp.where(keep, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + _dot(p.astype(v.dtype), v, _NN)
        return m_new, l_new, acc_new

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d_v), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_kv, body, (m0, l0, acc0))

    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    l_ref[0] = m + jnp.log(l_safe)  # logsumexp per row, [BQ, 1]


def _flash_fwd(q, k, v, causal, scale, kv_len, interpret):
    """q/k: [BH, S, D_qk], v: [BH, S, D_v] (seq padded to 128 multiples);
    kv_len = true unpadded key length for masking."""
    bh, s_q, d = q.shape
    s_k, d_v = v.shape[1:]
    block_q, block_kv = _block(s_q), _block(s_k)
    whole_kv = _whole_kv(bh // k.shape[0])
    kernel = functools.partial(
        _fwd_kernel, kv_len=kv_len, causal=causal, scale=scale,
        block_kv=block_kv)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, s_q // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, s_k, d), whole_kv),
            pl.BlockSpec((1, s_k, d_v), whole_kv),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d_v), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_q, d_v), q.dtype),
            jax.ShapeDtypeStruct((bh, s_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out, lse


# --------------------------------------------------------------- backward

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, kv_len, causal, scale, block_kv):
    qi = pl.program_id(1)
    bq, d = q_ref.shape[1:]
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0]      # [BQ, 1]
    delta = delta_ref[0]  # [BQ, 1]
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    s_k = k_ref.shape[1]
    n_kv = s_k // block_kv
    if causal:
        n_kv = jnp.minimum(n_kv, pl.cdiv((qi + 1) * bq, block_kv))

    def body(ki, dq):
        k = _rows(k_ref, ki, block_kv)
        v = _rows(v_ref, ki, block_kv)
        p = jnp.exp(_dot(q, k, _NT) * scale - lse)
        k_pos = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_kv), 1)
        keep = _keep(q_pos, k_pos, causal,
                     k_pos if kv_len < s_k else None, kv_len)
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        ds = p * (_dot(do, v, _NT) - delta)
        return dq + _dot(ds.astype(k.dtype), k, _NN)

    dq = jax.lax.fori_loop(0, n_kv, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _dkv_sums(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *, q_len,
              causal, scale, block_q):
    """One key/value block's float32 (dk / scale, dv) over every block of
    one query head. Scores, `p` and `ds` are held transposed, [BKV, BQ];
    `lse_ref` and `delta_ref` are [1, S_Q / BQ, 1, BQ], one row a query
    block."""
    ki = pl.program_id(1)
    bkv, d = k_ref.shape[1:]
    k = k_ref[0]
    v = v_ref[0]
    k_pos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bkv, 1), 0)

    s_q = q_ref.shape[1]
    start_q = (ki * bkv) // block_q if causal else 0  # earlier: all masked

    def body(qi, carry):
        dk, dv = carry
        q = _rows(q_ref, qi, block_q)
        do = _rows(do_ref, qi, block_q)
        p = jnp.exp(_dot(k, q, _NT) * scale - lse_ref[0, qi])
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_q), 1)
        keep = _keep(q_pos, k_pos, causal,
                     q_pos if q_len < s_q else None, q_len)
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        dv_new = dv + _dot(p.astype(do.dtype), do, _NN)
        ds = p * (_dot(v, do, _NT) - delta_ref[0, qi])
        dk_new = dk + _dot(ds.astype(q.dtype), q, _NN)
        return dk_new, dv_new

    dk0 = jnp.zeros((bkv, d), jnp.float32)
    dv0 = dk0 if v.shape == k.shape else jnp.zeros(v.shape, jnp.float32)
    return jax.lax.fori_loop(start_q, s_q // block_q, body, (dk0, dv0))


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale, **static):
    dk, dv = _dkv_sums(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       scale=scale, **static)
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_dkv_grouped_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            dk_ref, dv_ref, *, scale, **static):
    """Grid axis 2 runs over the query heads of this key/value head: the
    float32 output blocks stay where they are across it and take each
    query head's sums."""
    dk, dv = _dkv_sums(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       scale=scale, **static)
    first = pl.program_id(2) == 0

    @pl.when(first)
    def _():
        dk_ref[0] = dk * scale
        dv_ref[0] = dv

    @pl.when(jnp.logical_not(first))
    def _():
        dk_ref[0] += dk * scale
        dv_ref[0] += dv


def _grouped_dkv(q, k, v, do, stats, group, static, interpret):
    """dk and dv of `group` query heads to a key/value head: grid
    (key/value heads, key blocks, the group), the sums float32 until the
    last query head has added its own."""
    (bh, s_q, d), (bkvh, s_k, d_v) = q.shape, v.shape
    block_kv = _block(s_k)
    n_q = s_q // static["block_q"]

    def of_query_head(*block):
        return pl.BlockSpec(block, lambda b, i, g: (b * group + g,)
                            + (0,) * (len(block) - 1))

    def of_kv_block(width):
        return pl.BlockSpec((1, block_kv, width), lambda b, i, g: (b, i, 0))

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_grouped_kernel, **static),
        grid=(bkvh, s_k // block_kv, group),
        in_specs=[
            of_query_head(1, s_q, d), of_kv_block(d), of_kv_block(d_v),
            of_query_head(1, s_q, d_v),
            of_query_head(1, n_q, 1, static["block_q"]),
            of_query_head(1, n_q, 1, static["block_q"]),
        ],
        out_specs=[of_kv_block(d), of_kv_block(d_v)],
        out_shape=[
            jax.ShapeDtypeStruct((bkvh, s_k, d), jnp.float32),
            jax.ShapeDtypeStruct((bkvh, s_k, d_v), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, *stats)
    return dk.astype(k.dtype), dv.astype(v.dtype)


def _flash_bwd(q, k, v, out, lse, do, causal, scale, kv_len, q_len,
               interpret):
    bh, s_q, d = q.shape
    s_k, d_v = v.shape[1:]
    block_q, block_kv = _block(s_q), _block(s_k)
    group = bh // k.shape[0]
    whole_kv = _whole_kv(group)
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [BH, S, 1]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, kv_len=kv_len, causal=causal,
                          scale=scale, block_kv=block_kv),
        grid=(bh, s_q // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, s_k, d), whole_kv),
            pl.BlockSpec((1, s_k, d_v), whole_kv),
            pl.BlockSpec((1, block_q, d_v), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s_q, d), q.dtype),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    n_q = s_q // block_q
    static = dict(q_len=q_len, causal=causal, scale=scale, block_q=block_q)
    stats = (lse.reshape(bh, n_q, 1, block_q),
             delta.reshape(bh, n_q, 1, block_q))
    if group > 1:
        dk, dv = _grouped_dkv(q, k, v, do, stats, group, static, interpret)
        return dq, dk, dv
    stat_spec = pl.BlockSpec((1, n_q, 1, block_q), lambda b, i: (b, 0, 0, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **static),
        grid=(bh, s_k // block_kv),
        in_specs=[
            pl.BlockSpec((1, s_q, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_kv, d_v), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, s_q, d_v), lambda b, i: (b, 0, 0)),
            stat_spec, stat_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, block_kv, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_kv, d_v), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_k, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s_k, d_v), v.dtype),
        ],
        interpret=interpret,
    )(q, k, v, do, *stats)
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
    """q: [B, S, H, D_qk], k: [B, S, H_kv, D_qk], v: [B, S, H_kv, D_v] ->
    [B, S, H, D_v], query head i reading key/value head i // (H / H_kv);
    the default scale is 1 / sqrt(D_qk)."""
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    if v.shape[2] != k.shape[2] or h % k.shape[2]:
        raise NotImplementedError(
            f"flash attention needs as many value heads as key heads and "
            f"the query heads a multiple of them (grouped-query heads), "
            f"got {h} query, {k.shape[2]} key and {v.shape[2]} value heads")
    if k.shape[3] != d or v.shape[1] != s_k:
        raise ValueError(
            f"flash attention: q {q.shape} and k {k.shape} must share "
            f"their last axis, k and v {v.shape} their sequence")
    if causal and s_q != s_k:
        raise NotImplementedError(
            "causal flash attention requires s_q == s_k (top-left aligned "
            "mask); bottom-right cache alignment is not implemented")
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    def to_bhsd(x):
        return jnp.swapaxes(x, 1, 2).reshape(b * x.shape[2], x.shape[1],
                                             x.shape[3])

    qf, _ = _pad_seq(to_bhsd(q), BLOCKS[-1])
    kf, _ = _pad_seq(to_bhsd(k), BLOCKS[-1])
    vf, _ = _pad_seq(to_bhsd(v), BLOCKS[-1])
    out = _flash(qf, kf, vf, causal, float(scale), s_q, s_k, interpret)
    out = out[:, :s_q]
    return jnp.swapaxes(out.reshape(b, h, s_q, v.shape[3]), 1, 2)
