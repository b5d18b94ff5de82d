"""Rotary position embedding (Su et al. 2021, RoFormer): the rotate-half
form, and behind `interleaved` the paper's own pairing of neighbours.

Not in the reference snapshot; the position scheme of today's decoder
models. Applied to q and k before `scaled_dot_product_attention`.
"""
import jax.numpy as jnp

from ...core.dispatch import call_op
from ...observability.scopes import scope


def rotary_embedding(x, positions=None, theta=10000.0, interleaved=False):
    """Rotate x [batch, seq, heads, head_dim] by position: with the two
    halves of the last axis x1, x2 and the angle a[s, i] = positions[s]
    * theta ** (-2 i / head_dim),

        out = (x1 cos a - x2 sin a, x2 cos a + x1 sin a)

    With `interleaved` pair i is the neighbours (x[2i], x[2i+1]) and not
    (x[i], x[i + head_dim/2]): the same rotation, the result in x's own
    order (a `rope_interleave: true` checkpoint's layout).
    `positions` ([seq] or [batch, seq], any numeric type) defaults to
    0..seq-1. The angles and the rotation are float32 whatever x is; the
    result has x's dtype. In a compiled step its device time goes under
    the scope `rope`."""
    d = x.shape[-1]
    if d % 2:
        raise ValueError(f"rotary_embedding needs an even head_dim, got {d}")

    def _rope(v, *pos):
        # positions ride through call_op as an operand (a static program
        # records their slot, not the build-time value)
        p = (jnp.asarray(pos[0], jnp.float32) if pos
             else jnp.arange(v.shape[1], dtype=jnp.float32))
        inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        angle = p[..., None, None] * inv  # [(batch,) seq, 1, d/2]
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        if interleaved:
            pairs = v.astype(jnp.float32).reshape(v.shape[:-1] + (d // 2, 2))
            v1, v2 = pairs[..., 0], pairs[..., 1]
            out = jnp.stack([v1 * cos - v2 * sin, v2 * cos + v1 * sin],
                            axis=-1).reshape(v.shape)
            return out.astype(v.dtype)
        v1, v2 = jnp.split(v.astype(jnp.float32), 2, axis=-1)
        out = jnp.concatenate([v1 * cos - v2 * sin, v2 * cos + v1 * sin],
                              axis=-1)
        return out.astype(v.dtype)

    with scope("rope"):
        args = (x,) if positions is None else (x, positions)
        return call_op(_rope, *args, op_name="rotary_embedding")
