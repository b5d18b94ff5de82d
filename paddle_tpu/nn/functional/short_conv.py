"""Short causal depthwise convolutions of a few taps: gated, between two
multiplicative gates (LiquidAI LFM2's `conv` layers), and followed by a
SiLU (Gated DeltaNet's convolution over q, k and v).

Not in the reference snapshot. `F.conv1d(groups=channels)` computes the
same sum as one grouped `conv_general_dilated`, which the MXU cannot
fill (a channel's product is 3 wide); here it is `taps` shifted
multiply-adds: on a TPU, where the shapes allow, the pallas kernels of
`paddle_tpu.kernels.short_conv` (one pass over HBM each way), elsewhere
elementwise work XLA fuses with both gates.
"""
import jax
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

    width = taps.shape[0]
    if u.shape[-1] != 3 * width:
        raise ValueError(
            f"gated_short_conv: u's last axis is {u.shape[-1]}, three "
            f"times the taps' {width} channels were expected")

    if _kernel.is_available() and _kernel.supports(u.shape, taps.shape):
        return call_op(_kernel.short_conv, u, taps, op_name="short_conv")

    def _conv(v, w):
        b, c, z = jnp.split(v.astype(jnp.float32), 3, axis=-1)
        return (c * _taps_sum(b * z, w)).astype(v.dtype)

    return call_op(_conv, u, taps, op_name="short_conv")


def causal_conv_silu(x, taps):
    """silu of a causal depthwise convolution: x [batch, seq, C], taps
    [C, L],

        out_t = silu(sum_j taps[:, j] * x_{t - (L - 1 - j)})  (x = 0 before 0)

    so `taps[:, L - 1]` meets the current position (a `Conv1d(C, C, L,
    groups=C, padding=L - 1)` truncated to the sequence, no bias).
    Nothing crosses from one batch row into another. The sums are
    float32 whatever x is; the result has x's dtype. On a TPU, where the
    shapes allow, the SiLU form of the same pallas kernels. In a compiled
    step its device time goes under the scope `short_conv`."""
    from ...kernels import short_conv as _kernel

    if x.shape[-1] != taps.shape[0]:
        raise ValueError(f"causal_conv_silu: x has {x.shape[-1]} channels, "
                         f"the taps {taps.shape[0]}")

    if _kernel.is_available() and _kernel.supports(x.shape, taps.shape):
        return call_op(_kernel.silu_conv, x, taps, op_name="short_conv")

    def _conv(v, w):
        y = _taps_sum(v.astype(jnp.float32), w)
        return (y * jax.nn.sigmoid(y)).astype(v.dtype)

    return call_op(_conv, x, taps, op_name="short_conv")


def _taps_sum(g, w):
    """sum_j w[:, j] * g_{t - (L - 1 - j)} along axis 1 of g [batch, seq,
    C], float32, g = 0 before the start."""
    seq, length = g.shape[1], w.shape[1]
    w = w.astype(jnp.float32)
    mixed = g * w[:, length - 1]
    for back in range(1, min(length, seq)):
        shifted = jnp.pad(g, ((0, 0), (back, 0), (0, 0)))[:, :seq]
        mixed = mixed + shifted * w[:, length - 1 - back]
    return mixed
