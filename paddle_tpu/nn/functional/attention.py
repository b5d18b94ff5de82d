"""Attention functional.

Not a single op in the reference (composed from matmul+softmax there; the
fused path is `operators/fused/fused_attention_op.cu` in later snapshots).
Here: one fused XLA computation by default, and the pallas flash-attention
kernel (paddle_tpu.kernels.flash_attention) on TPU for long sequences.
Both take grouped-query heads (key and value with fewer heads than the
query, query head i reading key/value head i // group) and values
narrower than keys; neither copies K or V out to the query's head count.
"""
import jax.numpy as jnp

from ...core.dispatch import call_op
from ...observability.scopes import scope

# The kernel under the gate: two Mosaic kernels (forward; one backward) on
# 512x512 score tiles (PR 26), q and k of one width and v and the output of
# another (PR 33: latent attention's 192 and 128; equal widths lower as
# before), O(S) memory. The gate itself is older than that kernel: the
# crossover at ~1k tokens was measured in rounds 2-4 on another chip with a
# 128x128-tile kernel 2.4-2.9x slower, and has not been re-measured
# (ROADMAP S5, "the flash gate").
_FLASH_MIN_SEQ = 1024


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True,
                                 scale=None):
    """q/k/v: [batch, seq, heads, head_dim] (paddle layout); k and v
    may have fewer heads, a divisor of q's. In a compiled step its
    device time goes under the scope `attention`, with the path taken
    beneath it (`flash` / `xla`)."""
    with scope("attention"):
        return _sdpa_dispatch(query, key, value, attn_mask, dropout_p,
                              is_causal, training, scale)


def _sdpa_dispatch(query, key, value, attn_mask, dropout_p, is_causal,
                   training, scale):
    from ...core import random as core_random

    q_shape = query.shape
    seq_len = q_shape[1]
    dropout_inactive = dropout_p == 0.0 or not training
    use_flash = False
    if dropout_inactive and attn_mask is None and seq_len >= _FLASH_MIN_SEQ:
        # imported here (pallas costs ~0.8 s per process) and unguarded: a
        # kernel that fails to import, lower or compile on a TPU raises,
        # nothing hands long sequences back to the XLA path quietly
        from ...kernels import flash_attention as _fa
        use_flash = _fa.is_available()

    if use_flash:

        def _flash(q, k, v):
            return _fa.flash_attention_bshd(q, k, v, causal=is_causal,
                                            scale=scale)

        with scope("flash"):
            return call_op(_flash, query, key, value,
                           op_name="flash_attention")

    drop_key = core_random.next_key() if (dropout_p > 0.0 and training) else None

    def _sdpa(q, k, v, *rest):
        mask = rest[0] if attn_mask is not None else None
        d = q.shape[-1]
        s = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(q.dtype)
        # [B, S, H, D] -> [B, H, S, D]
        qt = jnp.swapaxes(q, 1, 2)
        kt = jnp.swapaxes(k, 1, 2)
        vt = jnp.swapaxes(v, 1, 2)
        group = qt.shape[1] // kt.shape[1]
        if group == 1:
            logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * s
        else:  # query heads [kv head, its group] against their one K
            grouped = (qt.shape[0], kt.shape[1], group) + qt.shape[2:]
            logits = jnp.einsum("bhgqd,bhkd->bhgqk", qt.reshape(grouped),
                                kt).reshape(qt.shape[:3] + kt.shape[2:3]) * s
        if is_causal:
            causal = jnp.tril(jnp.ones((logits.shape[-2], logits.shape[-1]),
                                       dtype=bool))
            logits = jnp.where(causal, logits, jnp.asarray(-1e9, logits.dtype))
        if mask is not None:
            if mask.dtype == jnp.bool_:
                logits = jnp.where(mask, logits, jnp.asarray(-1e9, logits.dtype))
            else:
                logits = logits + mask
        probs = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
        probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
        if drop_key is not None:
            import jax
            keep = jax.random.bernoulli(drop_key, 1.0 - dropout_p, probs.shape)
            probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
        if group == 1:
            out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(vt.dtype), vt)
        else:
            out = jnp.einsum(
                "bhgqk,bhkd->bhgqd",
                probs.astype(vt.dtype).reshape(grouped[:4] + probs.shape[3:]),
                vt).reshape(probs.shape[:3] + vt.shape[3:])
        return jnp.swapaxes(out, 1, 2)  # back to [B, S, H, D]

    args = (query, key, value) + ((attn_mask,) if attn_mask is not None else ())
    with scope("xla"):
        return call_op(_sdpa, *args, op_name="scaled_dot_product_attention")
