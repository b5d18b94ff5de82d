"""The gated short convolution (LiquidAI LFM2's `conv` layers): between
two multiplicative gates, a causal depthwise convolution of a few taps.

Not in the reference snapshot. `F.conv1d(groups=channels)` computes the
same sum as one grouped `conv_general_dilated`, which the MXU cannot
fill (a channel's product is 3 wide); here it is `taps` shifted
multiply-adds: on a TPU, where the shapes allow, the pallas kernels of
`paddle_tpu.kernels.short_conv` (one pass over HBM each way), elsewhere
elementwise work XLA fuses with both gates.
"""
import jax.numpy as jnp

from ...core.dispatch import call_op


def gated_short_conv(u, taps):
    """u [batch, seq, 3 * h] holds (B | C | z) side by side, taps [h, L]:

        g_t = B_t * z_t
        c_t = sum_j taps[:, j] * g_{t - (L - 1 - j)}       (g = 0 before 0)
        out_t = C_t * c_t                                  [batch, seq, h]

    so `taps[:, L - 1]` meets the current position. Nothing crosses from
    one batch row into another. The sums are float32 whatever u is; the
    result has u's dtype. In a compiled step its device time goes under
    the scope `short_conv`."""
    # imported here (pallas costs ~0.8 s per process) and unguarded: a
    # kernel that fails to lower on a TPU raises, as attention's does
    from ...kernels import short_conv as _kernel

    width, length = taps.shape[0], taps.shape[1]
    if u.shape[-1] != 3 * width:
        raise ValueError(
            f"gated_short_conv: u's last axis is {u.shape[-1]}, three "
            f"times the taps' {width} channels were expected")

    if _kernel.is_available() and _kernel.supports(u.shape, taps.shape):
        return call_op(_kernel.short_conv, u, taps, op_name="short_conv")

    def _conv(v, w):
        seq = v.shape[1]
        b, c, z = jnp.split(v.astype(jnp.float32), 3, axis=-1)
        w = w.astype(jnp.float32)
        g = b * z
        mixed = g * w[:, length - 1]
        for back in range(1, min(length, seq)):
            shifted = jnp.pad(g, ((0, 0), (back, 0), (0, 0)))[:, :seq]
            mixed = mixed + shifted * w[:, length - 1 - back]
        return (c * mixed).astype(v.dtype)

    return call_op(_conv, u, taps, op_name="short_conv")
