"""The short causal depthwise convolution — pallas TPU kernels, in two
forms: gated (`F.gated_short_conv`: B * z in, C times the sum out) and
SiLU (`F.causal_conv_silu`: x in, silu of the sum out).

Each form's arithmetic (see there) in one pass over HBM each
way: XLA's own lowering of the shifted multiply-adds writes the gated
product `B * z` out in float32 before it shifts it and reads it back
once a tap, several times the bytes the operator needs. Here a grid step
takes `BLOCK` positions of one batch row, all channels, with the 16
positions before them (forward: the taps reach back) or before and after
them (backward: the cotangent's taps reach ahead) as a second view of
the same operand, and shifts along the sequence with `pltpu.roll`; the
rows a roll wraps round are replaced by the neighbour block's. Forward
reads `u` and writes the result; backward reads `u` and the cotangent,
writes `du` and adds the taps' gradient into one float32 block that stays
in VMEM for the whole grid. The SiLU form's backward also forms the sums
at the first positions after the block, from the after view and the
block's own tail: their cotangents reach back into it. Everything is
float32 between the loads and the stores; the channels are worked
through `_CHUNK` at a time so that a step's temporaries stay small.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 128   # positions a grid step
_HALO = 16    # positions of the neighbour block a step sees: one bf16 tile
_CHUNK = 512  # channels worked at a time inside a step


def is_available():
    """The kernels lower through Mosaic: TPU backends only."""
    return jax.default_backend() == "tpu"


def supports(u_shape, taps_shape):
    """Whole blocks of positions, whole lane tiles of channels, taps
    that reach no further back than the neighbour view."""
    width, length = taps_shape
    return (u_shape[1] % BLOCK == 0 and width % 128 == 0
            and 1 <= length <= _HALO)


def _row(x, r):
    """Row `r` of the [_HALO, C] value `x` as [1, C] (a masked sum: no
    slice off the tiling)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], 1), 0)
    return jnp.sum(jnp.where(rows == r, x, 0.0), axis=0, keepdims=True)


def _shifted(x, steps, neighbour):
    """x_{t - steps} over the block's rows (steps > 0: back; < 0: ahead),
    the rows that reach outside it taken from `neighbour` [_HALO, C]: the
    positions just before the block, or just after it."""
    n = x.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    out = pltpu.roll(x, steps % n, 0)
    for k in range(abs(steps)):
        if steps > 0:  # row k holds position k - steps: neighbour's tail
            out = jnp.where(rows == k, _row(neighbour, _HALO - steps + k), out)
        else:  # row n - 1 - k holds position n - 1 - k - steps: its head
            out = jnp.where(rows == n - 1 - k, _row(neighbour, -steps - 1 - k),
                            out)
    return out


def _chunks(width):
    size = _CHUNK if width % _CHUNK == 0 else 128
    return [(lo, size) for lo in range(0, width, size)]


def _load(ref, part, lo, size, width):
    """Channels [lo, lo + size) of part 0 (B), 1 (C) or 2 (z), float32."""
    return ref[0, :, pl.ds(part * width + lo, size)].astype(jnp.float32)


def _gated(ref, lo, size, width, outside):
    """g = B * z of the view `ref`; zeros where the view lies `outside`
    the sequence (the first block's positions before, say)."""
    g = _load(ref, 0, lo, size, width) * _load(ref, 2, lo, size, width)
    return g if outside is None else jnp.where(outside, 0.0, g)


def _taps_sum(w_ref, cols, length, g, g_before):
    """sum_j w_j g_{t - (L - 1 - j)} over the rows of g, the positions
    before them taken from `g_before` [_HALO, C]."""
    mixed = w_ref[length - 1, :, cols] * g
    for steps in range(1, length):
        mixed = mixed + (w_ref[length - 1 - steps, :, cols]
                         * _shifted(g, steps, g_before))
    return mixed


def _fwd_kernel(u_ref, before_ref, w_ref, o_ref, *, length):
    width = o_ref.shape[2]
    first = pl.program_id(1) == 0
    for lo, size in _chunks(width):
        g = _gated(u_ref, lo, size, width, None)
        g_before = _gated(before_ref, lo, size, width, first)
        mixed = _taps_sum(w_ref, pl.ds(lo, size), length, g, g_before)
        o_ref[0, :, pl.ds(lo, size)] = (
            _load(u_ref, 1, lo, size, width) * mixed).astype(o_ref.dtype)


def _bwd_kernel(u_ref, before_ref, after_ref, do_ref, do_after_ref, w_ref,
                du_ref, dw_ref, *, length):
    width = do_ref.shape[2]
    i = pl.program_id(1)
    first, last = i == 0, i == pl.num_programs(1) - 1

    @pl.when(jnp.logical_and(pl.program_id(0) == 0, first))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    for lo, size in _chunks(width):
        cols = pl.ds(lo, size)
        b, c, z = (_load(u_ref, part, lo, size, width) for part in range(3))
        do = do_ref[0, :, cols].astype(jnp.float32)
        g = b * z
        g_before = _gated(before_ref, lo, size, width, first)
        dm = do * c  # the cotangent of the taps' sum
        dm_after = jnp.where(
            last, 0.0, do_after_ref[0, :, cols].astype(jnp.float32)
            * _load(after_ref, 1, lo, size, width))
        mixed, dg = 0.0, 0.0
        for steps in range(length):
            w = w_ref[length - 1 - steps, :, cols]
            g_back = _shifted(g, steps, g_before) if steps else g
            mixed = mixed + w * g_back
            dg = dg + w * (_shifted(dm, -steps, dm_after) if steps else dm)
            dw_ref[length - 1 - steps, :, cols] += jnp.sum(
                dm * g_back, axis=0, keepdims=True)
        for part, value in enumerate((dg * z, do * mixed, dg * b)):
            du_ref[0, :, pl.ds(part * width + lo, size)] = value.astype(
                du_ref.dtype)


def _sigmoid(y):
    return 1.0 / (1.0 + jnp.exp(-y))


def _silu_fwd_kernel(x_ref, before_ref, w_ref, o_ref, *, length):
    width = o_ref.shape[2]
    first = pl.program_id(1) == 0
    for lo, size in _chunks(width):
        before = jnp.where(first, 0.0, _load(before_ref, 0, lo, size, width))
        y = _taps_sum(w_ref, pl.ds(lo, size), length,
                      _load(x_ref, 0, lo, size, width), before)
        o_ref[0, :, pl.ds(lo, size)] = (y * _sigmoid(y)).astype(o_ref.dtype)


def _silu_bwd_kernel(x_ref, before_ref, after_ref, do_ref, do_after_ref,
                     w_ref, dx_ref, dw_ref, *, length):
    width = do_ref.shape[2]
    i = pl.program_id(1)
    first, last = i == 0, i == pl.num_programs(1) - 1

    @pl.when(jnp.logical_and(pl.program_id(0) == 0, first))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def d_sum(y, do):
        """The cotangent of the taps' sum y: silu'(y) do."""
        sig = _sigmoid(y)
        return do * sig * (1.0 + y * (1.0 - sig))

    for lo, size in _chunks(width):
        cols = pl.ds(lo, size)
        x = _load(x_ref, 0, lo, size, width)
        x_before = jnp.where(first, 0.0, _load(before_ref, 0, lo, size, width))
        dm = d_sum(_taps_sum(w_ref, cols, length, x, x_before),
                   do_ref[0, :, cols].astype(jnp.float32))
        # the sums at the positions just after the block reach back into it
        tail = x_ref[0, pl.ds(BLOCK - _HALO, _HALO), cols].astype(jnp.float32)
        y_after = _taps_sum(w_ref, cols, length,
                            _load(after_ref, 0, lo, size, width), tail)
        dm_after = jnp.where(last, 0.0, d_sum(
            y_after, do_after_ref[0, :, cols].astype(jnp.float32)))
        dx = 0.0
        for steps in range(length):
            w = w_ref[length - 1 - steps, :, cols]
            dx = dx + w * (_shifted(dm, -steps, dm_after) if steps else dm)
            x_back = _shifted(x, steps, x_before) if steps else x
            dw_ref[length - 1 - steps, :, cols] += jnp.sum(
                dm * x_back, axis=0, keepdims=True)
        dx_ref[0, :, cols] = dx.astype(dx_ref.dtype)


def _block_spec(x):
    """`BLOCK` positions of one batch row of x [batch, seq, channels]."""
    return pl.BlockSpec((1, BLOCK, x.shape[2]), lambda b, i: (b, i, 0))


def _neighbour_spec(x, before):
    """The `_HALO` positions just before a grid step's block, or just
    after it (clamped inside x: the kernels zero what lies outside)."""
    per = BLOCK // _HALO
    last = x.shape[1] // _HALO - 1
    if before:
        where = lambda b, i: (b, jnp.maximum(i * per - 1, 0), 0)  # noqa: E731
    else:
        where = lambda b, i: (b, jnp.minimum((i + 1) * per, last), 0)  # noqa: E731
    return pl.BlockSpec((1, _HALO, x.shape[2]), where)


def _taps_spec(taps):
    return pl.BlockSpec((taps.shape[1], 1, taps.shape[0]),
                        lambda b, i: (0, 0, 0))


def _as_rows(taps):
    """taps [h, L] -> float32 [L, 1, h]: a tap's channels one lane row."""
    return taps.astype(jnp.float32).T[:, None, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _short_conv(u, taps, interpret):
    batch, seq, _ = u.shape
    return pl.pallas_call(
        functools.partial(_fwd_kernel, length=taps.shape[1]),
        grid=(batch, seq // BLOCK),
        in_specs=[_block_spec(u), _neighbour_spec(u, before=True),
                  _taps_spec(taps)],
        out_specs=pl.BlockSpec((1, BLOCK, taps.shape[0]),
                               lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, seq, taps.shape[0]), u.dtype),
        interpret=interpret,
    )(u, u, _as_rows(taps))


def _vjp_fwd(u, taps, interpret):
    return _short_conv(u, taps, interpret), (u, taps)


def _vjp_bwd(interpret, saved, dout):
    u, taps = saved
    batch, seq, _ = u.shape
    du, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, length=taps.shape[1]),
        grid=(batch, seq // BLOCK),
        in_specs=[_block_spec(u), _neighbour_spec(u, before=True),
                  _neighbour_spec(u, before=False), _block_spec(dout),
                  _neighbour_spec(dout, before=False), _taps_spec(taps)],
        out_specs=[_block_spec(u), _taps_spec(taps)],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct((taps.shape[1], 1, taps.shape[0]),
                                        jnp.float32)],
        interpret=interpret,
    )(u, u, u, dout, dout, _as_rows(taps))
    return du, dw[:, 0, :].T.astype(taps.dtype)


_short_conv.defvjp(_vjp_fwd, _vjp_bwd)


def short_conv(u, taps, interpret=False):
    """u [batch, seq, 3 * h] = (B | C | z), taps [h, L] -> [batch, seq, h]
    in u's dtype, differentiable in both; `supports` says which shapes."""
    if not supports(u.shape, taps.shape) or u.shape[2] != 3 * taps.shape[0]:
        raise ValueError(
            f"short_conv kernel: u {u.shape} and taps {taps.shape} are not "
            f"whole blocks of {BLOCK} positions over three lane-tiled parts")
    return _short_conv(u, taps, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _silu_conv(x, taps, interpret):
    batch, seq, _ = x.shape
    return pl.pallas_call(
        functools.partial(_silu_fwd_kernel, length=taps.shape[1]),
        grid=(batch, seq // BLOCK),
        in_specs=[_block_spec(x), _neighbour_spec(x, before=True),
                  _taps_spec(taps)],
        out_specs=_block_spec(x),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, x, _as_rows(taps))


def _silu_vjp_fwd(x, taps, interpret):
    return _silu_conv(x, taps, interpret), (x, taps)


def _silu_vjp_bwd(interpret, saved, dout):
    x, taps = saved
    batch, seq, _ = x.shape
    dx, dw = pl.pallas_call(
        functools.partial(_silu_bwd_kernel, length=taps.shape[1]),
        grid=(batch, seq // BLOCK),
        in_specs=[_block_spec(x), _neighbour_spec(x, before=True),
                  _neighbour_spec(x, before=False), _block_spec(dout),
                  _neighbour_spec(dout, before=False), _taps_spec(taps)],
        out_specs=[_block_spec(x), _taps_spec(taps)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((taps.shape[1], 1, taps.shape[0]),
                                        jnp.float32)],
        interpret=interpret,
    )(x, x, x, dout, dout, _as_rows(taps))
    return dx, dw[:, 0, :].T.astype(taps.dtype)


_silu_conv.defvjp(_silu_vjp_fwd, _silu_vjp_bwd)


def silu_conv(x, taps, interpret=False):
    """x [batch, seq, h], taps [h, L] -> silu of the causal sum [batch,
    seq, h] in x's dtype, differentiable in both; `supports` says which
    shapes."""
    if not supports(x.shape, taps.shape) or x.shape[2] != taps.shape[0]:
        raise ValueError(
            f"short_conv kernel: x {x.shape} and taps {taps.shape} are not "
            f"whole blocks of {BLOCK} positions over lane-tiled channels")
    return _silu_conv(x, taps, interpret)
