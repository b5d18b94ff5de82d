"""Flash attention — pallas TPU kernel.

The analog of the reference's hand-written fused CUDA attention
(`operators/fused/fused_attention_op.cu` family): online-softmax tiling keeps
the S×S score matrix out of HBM entirely. Two Mosaic kernels: the forward,
which saves only the logsumexp row stats, and one backward, which recomputes
the scores blockwise and feeds dq, dk and dv from them.
Layout [B, S, H, D] outside (framework attention layout), [B*H, S, D] inside.
q and k share one width `d_qk` and v, the output and their gradients another,
`d_v` (latent attention: 192-wide keys, 128-wide values); the scores contract
over `d_qk` in one product, `p v`, `do v^T` and `dv` run over `d_v`.

The backward (PR 36): for each visited (key block, query block) pair the
scores, `p`, the mask, `dp = do v^T` and `ds = p (dp - delta)` are formed
once, and `dv += p^T do`, `dk += ds^T q`, `dq += ds k` all read them: five
products and one `exp` a pair, where a dq kernel and a dkv kernel that each
formed them took seven and two. The grid runs over key blocks with Q and dO
of a query head whole in VMEM; dk and dv of the key block are the loop's
float32 carries. The tile is held transposed (`k q^T`, the row statistics as
rows), so four of the products are plain or transposed-weights matmuls; the
fifth gives dq transposed, `k^T ds^T` ([D_qk, BQ]: it contracts the tile's
rows, so `k`, the small operand, is what the matmul turns and no 512 x 512
tile goes through the transpose unit), added into a float32 [D_qk, S_Q]
scratch that stays in VMEM across the query head's key blocks, is zeroed at
the first and is turned, scaled and rounded once into the head's dq block at
the last. No float32 partial sum leaves VMEM. Measured on the v5e (PR 36,
forward + backward of one call, the two backward kernels -> this one): b2 x
2048 x 16 heads of 128 1.875 -> 1.587 ms; b4 x 4096 x 32 heads of 192 / 128
34.34 -> 26.54 ms; b4 x 8192 x 32-over-8 heads of 64 75.85 -> 56.39 ms.
`ds^T` turned on the transpose unit a tile pair instead reads 1.566, 27.55
and 59.57 ms; writing dq's first visit and not zeroing loses 1.5 % everywhere.

Grouped-query heads: k and v may have fewer heads than q, `group` query heads
to each (query head i reads key/value head i // group). K and V stay at their
own head count in HBM: the forward finds a query head's K and V through the
block index map, and the backward's grid is (key/value heads, the group's
query heads, key blocks): a key/value head's float32 dk and dv stay whole in
VMEM scratch across the two inner axes, the group's first query head writes
them, the others add, the last rounds them out. With one head each there is
no such scratch and a key block's dk and dv leave as they are summed.

VMEM: the backward states its limit from its operands' shapes (Q, dO and the
dq block whole and double-buffered, the float32 sums, 8 MiB for the rest) and
never under the 16 MiB a call gets unasked: 16 MiB at s 2,048, 21 MiB at
4,096 x 192 / 128 (which does not fit 16 at the cell's 128 heads' rows), 30
MiB at 8,192 x 32-over-8 x 64, where 64-wide blocks take 128 lanes.

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

Which blocks are masked: causal calls mask every block they visit by position
(blocks wholly above the diagonal are not visited); padded key (forward) or
query (backward) positions are masked with the true length where the wrapper
padded (the backward needs no key mask: a padded key's row of K is zero, so
what it adds to dq is zero, and its dk and dv rows are sliced off), and a
call with neither builds no mask at all.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCKS = (512, 256, 128)
NEG_INF = -1e30

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


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

def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, *dkv_acc,
                q_len, causal, scale, block_q):
    """One key/value block against every query block of one query head;
    grid (key/value heads, the group's query heads, key blocks). Scores,
    `p` and `ds` are formed once a tile pair and held transposed,
    [BKV, BQ]; `lse_ref` and `delta_ref` are [1, S_Q / BQ, 1, BQ], one row
    a query block. `dq_acc` is the query head's dq / scale, transposed
    ([D_qk, S_Q] float32), across the head's key blocks; `dkv_acc`, with
    grouped heads, the float32 (dk / scale, dv) of the whole key/value
    head across the group's query heads."""
    g, ki = pl.program_id(1), pl.program_id(2)
    bkv = k_ref.shape[1]
    k = k_ref[0]
    v = v_ref[0]
    k_pos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bkv, 1), 0)

    s_q = q_ref.shape[1]
    start_q = (ki * bkv) // block_q if causal else 0  # earlier: all masked

    @pl.when(ki == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

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
        ds = (p * (_dot(v, do, _NT) - delta_ref[0, qi])).astype(q.dtype)
        dk_new = dk + _dot(ds, q, _NN)
        cols = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
        dq_acc[:, cols] += _dot(k, ds, _TN)
        return dk_new, dv_new

    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = dk0 if v.shape == k.shape else jnp.zeros(v.shape, jnp.float32)
    dk, dv = jax.lax.fori_loop(start_q, s_q // block_q, body, (dk0, dv0))

    if dkv_acc:
        dk_acc, dv_acc = dkv_acc
        rows = pl.ds(pl.multiple_of(ki * bkv, bkv), bkv)

        @pl.when(g == 0)
        def _():
            dk_acc[rows, :] = dk
            dv_acc[rows, :] = dv

        @pl.when(g > 0)
        def _():
            dk_acc[rows, :] += dk
            dv_acc[rows, :] += dv

        @pl.when(g == pl.num_programs(1) - 1)
        def _():
            dk_ref[0] = (dk_acc[rows, :] * scale).astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[rows, :].astype(dv_ref.dtype)
    else:
        dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = (dq_acc[...].T * scale).astype(dq_ref.dtype)


def _held_bytes(rows, cols, dtype):
    """A [rows, cols] block as VMEM holds it: lanes padded to 128."""
    return rows * -(-cols // 128) * 128 * jnp.dtype(dtype).itemsize


def _flash_bwd(q, k, v, out, lse, do, causal, scale, q_len, interpret):
    bh, s_q, d = q.shape
    bkvh, s_k, d_v = v.shape
    block_q, block_kv = _block(s_q), _block(s_k)
    group, n_q = bh // bkvh, s_q // block_q
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [BH, S, 1]

    def of_query_head(*block):
        return pl.BlockSpec(block, lambda b, g, i: (b * group + g,)
                            + (0,) * (len(block) - 1))

    def of_kv_block(width):
        return pl.BlockSpec((1, block_kv, width), lambda b, g, i: (b, i, 0))

    def of_kv_sum(width):
        """dk's or dv's output block: with grouped heads the group's last
        query head writes it; the earlier ones stay on the key/value
        head's first block, which nothing writes back before then."""
        if group == 1:
            return of_kv_block(width)
        return pl.BlockSpec(
            (1, block_kv, width),
            lambda b, g, i: (b, jnp.where(g == group - 1, i, 0), 0))

    scratch = [pltpu.VMEM((d, s_q), jnp.float32)]
    if group > 1:
        scratch += [pltpu.VMEM((s_k, d), jnp.float32),
                    pltpu.VMEM((s_k, d_v), jnp.float32)]
    # what stays in VMEM across grid steps: Q, dO and the dq block whole
    # (each buffered twice), the float32 sums; 8 MiB more for the key and
    # value blocks, the row statistics and a tile pair's temporaries. A
    # call that fits the 16 MiB every Mosaic call gets asks for no more.
    resident = sum(_held_bytes(*x.shape, x.dtype) for x in scratch) + 2 * (
        2 * _held_bytes(s_q, d, q.dtype) + _held_bytes(s_q, d_v, q.dtype))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, q_len=q_len, causal=causal,
                          scale=scale, block_q=block_q),
        grid=(bkvh, group, s_k // block_kv),
        in_specs=[
            of_query_head(1, s_q, d), of_kv_block(d), of_kv_block(d_v),
            of_query_head(1, s_q, d_v),
            of_query_head(1, n_q, 1, block_q),
            of_query_head(1, n_q, 1, block_q),
        ],
        out_specs=[of_query_head(1, s_q, d), of_kv_sum(d), of_kv_sum(d_v)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(16 << 20, resident + (8 << 20))),
        interpret=interpret,
    )(q, k, v, do, lse.reshape(bh, n_q, 1, block_q),
      delta.reshape(bh, n_q, 1, block_q))


# ------------------------------------------------------------- public API

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, q_len, kv_len, interpret):
    out, _ = _flash_fwd(q, k, v, causal, scale, kv_len, interpret)
    return out


def _flash_vjp_fwd(q, k, v, causal, scale, q_len, kv_len, interpret):
    out, lse = _flash_fwd(q, k, v, causal, scale, kv_len, interpret)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, scale, q_len, kv_len, interpret, res, do):
    from ..jit.to_static import note_structure

    q, k, v, out, lse = res
    note_structure("flash_fused_backwards")
    return _flash_bwd(q, k, v, out, lse, do, causal, scale, q_len, interpret)


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
