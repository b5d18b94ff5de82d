"""Rows written over scattered rows of an array in HBM — a pallas TPU
kernel.

`put_rows(carry, dest, rows)` is `carry.at[dest].set(rows)` for indices
that do not repeat: XLA:TPU's scatter writes a row at a time whatever it
is told of its indices (~0.3 us a 8 KB row, set or add), where one DMA a
row, HBM to HBM and all of them in flight at once, writes one in
~0.06 us. The kernel starts a copy of each row to its place, spread over
`_QUEUES` semaphores, and waits once on each for its share of the bytes.
The carry is aliased to the result and never read here, and nothing
orders the copies: where two rows share an index, either may be left
(a caller sends the rows it has nothing for to one spare row past its
data, and drops that row).

A row is one slab of the carry's leading dimension: [N, n, L] with the
lane width L a whole tile (128) is what a DMA may cut; a [N, n * L] array
is cut row by row only in whole (8, 128) tiles.
"""
import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_QUEUES = 4  # semaphores the copies are spread over


def is_available():
    """The kernel lowers through Mosaic: TPU backends only."""
    return jax.default_backend() == "tpu"


def supports(carry_shape, rows_shape):
    """Rows whole lane tiles, as many as the queues share evenly."""
    return (len(carry_shape) == 3 and carry_shape[1:] == rows_shape[1:]
            and carry_shape[2] % 128 == 0 and rows_shape[0] % _QUEUES == 0)


def _kernel(dest_ref, rows_ref, carry_ref, out_ref, sems):
    del carry_ref  # the same buffer as out_ref
    share = rows_ref.shape[0] // _QUEUES

    def start(i, carry):
        for queue in range(_QUEUES):
            row = queue * share + i
            pltpu.make_async_copy(rows_ref.at[row], out_ref.at[dest_ref[row]],
                                  sems.at[queue]).start()
        return carry

    jax.lax.fori_loop(0, share, start, 0)
    for queue in range(_QUEUES):
        # one wait for the bytes of the queue's `share` rows
        part = rows_ref.at[pl.ds(queue * share, share)]
        pltpu.make_async_copy(part, part, sems.at[queue]).wait()


def put_rows(carry, dest, rows, interpret=False):
    """carry [N, n, L] with rows [B, n, L] written over carry[dest[i]]:
    the result, in carry's buffer. dest [B] int32 in [0, N); `supports`
    says which shapes."""
    if not supports(carry.shape, rows.shape):
        raise ValueError(
            f"put_rows kernel: rows {rows.shape} into {carry.shape} are not "
            f"whole lane tiles in a multiple of {_QUEUES}")
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct(carry.shape, carry.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA((_QUEUES,))]),
        input_output_aliases={2: 0},
        interpret=pltpu.InterpretParams() if interpret else False,
    )(dest, rows, carry)
