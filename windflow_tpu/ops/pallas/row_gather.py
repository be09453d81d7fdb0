"""Pallas TPU kernel: a block of window rows gathered from a column by DMA.

A user's window function runs over a [rows, w_pad] tile of each of its
columns, row ``i`` the ``w_pad`` elements from ``starts[i]``
(ops/window_compute._custom_program).  Built as a vmapped
``lax.dynamic_slice``, XLA's TPU backend makes that a loop of one row an
iteration: on a TPU v5e a block of 4,096 rows of 4,096 takes 3.9 ms of
device time in the median cell and 9.1 ms alone, where reading and
writing its 64 MiB at the chip's 819 GB/s takes 0.16 ms.  This kernel
takes 0.38 ms alone (PERF.md section 6).

Each grid step puts the DMAs of 64 rows (32 at the widest) in flight at
once, from the column left in HBM into VMEM.  A DMA moves whole
lane-rows of 128 (the column's HBM layout is tiled, and a copy cannot
start inside a tile): the column is read as [rows, 128], and row ``i``
fetches the ``w_pad / 128 + 1`` lane-rows from ``starts[i] // 128``.  A
lane rotate by ``starts[i] % 128`` and a select between neighbouring
lane-rows then move its elements into place.  Eight rows at a time go
into a scratch by strided stores, row ``q``'s lane-row ``i`` at sublane
``q`` of tile ``i``, and each tile is copied whole into the output
block.  The tile is the slice's element for element, including what
lies beyond a window's end (the column's next elements).  Off the TPU
the kernel runs in interpret mode (the tests).

The starts come in by scalar prefetch: at most ``2**24 / 128`` rows a
block (window_compute.fn_rows), 512 KiB of the 1 MiB of SMEM.
"""
from __future__ import annotations

import functools

from ..backend import jax_modules
from .window_sum import LANES, interpret_off_tpu

# a grid step's rows: about 1 MiB of output block (Pallas holds it twice)
# and as much again of fetched lane-rows
_STEP_BYTES = 1 << 20
_MAX_ROWS = 64
# the widest row the kernel takes: the copy of a row's lane-rows out is
# unrolled, 64 at most
MAX_W = 1 << 13


def engages(backend: str, w_pad: int) -> bool:
    """Whether a block's tile is gathered by the kernel: on the TPU
    backend, for rows of whole lane-rows up to :data:`MAX_W`.  Everywhere
    else the program keeps the vmapped dynamic slice."""
    return backend == "tpu" and LANES <= w_pad <= MAX_W


def rows_a_step(w_pad: int) -> int:
    """64 rows a grid step, 32 at the widest: whole groups of 8."""
    return min(_MAX_ROWS, _STEP_BYTES // (4 * w_pad))


@functools.lru_cache(maxsize=None)
def _build(n: int, w_pad: int, interpret: bool):
    jax, jnp = jax_modules()
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k = w_pad // LANES
    R = rows_a_step(w_pad)
    assert w_pad % LANES == 0 and n % R == 0

    def kernel(starts_ref, col_ref, out_ref, fetched, sem, tiles):
        g = pl.program_id(0)

        def copy(r):
            return pltpu.make_async_copy(
                col_ref.at[pl.ds(starts_ref[g * R + r] // LANES, k + 1)],
                fetched.at[r], sem)

        def start(r, carry):
            copy(r).start()
            return carry

        def wait(r, carry):
            copy(r).wait()
            return carry

        # every row's copy in flight before the first is waited on
        jax.lax.fori_loop(0, R, start, 0)
        jax.lax.fori_loop(0, R, wait, 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (k, LANES), 1)

        def eight_rows(j, carry):
            r0 = pl.multiple_of(8 * j, 8)
            for q in range(8):
                b = starts_ref[g * R + r0 + q] % LANES
                # lane-row i of the row: the tail of fetched lane-row i
                # from lane b, then the head of lane-row i + 1; it goes
                # to sublane q of the eight rows' tile i
                x = pltpu.roll(fetched[r0 + q], (LANES - b) % LANES, 1)
                tiles[pl.ds(q, k, stride=8), :] = jnp.where(
                    lane < LANES - b, x[:k], x[1:])
            out = out_ref.at[pl.ds(r0, 8)]
            for i in range(k):
                out[:, i * LANES:(i + 1) * LANES] = tiles[8 * i:8 * i + 8, :]
            return carry

        jax.lax.fori_loop(0, R // 8, eight_rows, 0)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n, w_pad), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // R,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((R, w_pad), lambda g, s: (g, 0)),
            scratch_shapes=[pltpu.VMEM((R, k + 1, LANES), jnp.float32),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.VMEM((8 * k, LANES), jnp.float32)]),
        interpret=interpret,
        name="row_gather",
    )


def row_tiles(col, starts, w_pad: int, interpret: bool = None):
    """[len(starts), w_pad] float32: row ``i`` is ``col[starts[i]:
    starts[i] + w_pad]``, as ``jax.vmap(lax.dynamic_slice)`` gives it.
    Traceable.  ``col`` is float32 of whole lane-rows and reaches a lane-row
    beyond every row's last (``128 * (starts[i] // 128) + w_pad + 128``
    elements); ``len(starts)`` is a multiple of :func:`rows_a_step`."""
    if interpret is None:
        interpret = interpret_off_tpu()
    call = _build(starts.shape[0], w_pad, bool(interpret))
    return call(starts, col.reshape(-1, LANES))
