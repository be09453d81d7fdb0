"""Pallas TPU kernel: batched window reduction over a flat buffer.

The direct TPU analogue of the reference's grid-stride
``ComputeBatch_Kernel`` (win_seq_gpu.hpp:61-84): one grid program per
block of ``ROWS`` fired windows instead of one CUDA thread per window.
Window extents arrive via scalar prefetch (SMEM); the flat buffer sits
whole in VMEM (the engine's gate caps it at 2 MiB) and each window
walks only the lane-rows its extent touches, lanes outside the extent
masked.  The output is blocked ``(ROWS, 128)`` per program, so its VMEM
footprint does not grow with the batch.

This is the hand-scheduled alternative to the XLA cumsum path in
ops/window_compute.py -- profitable when windows are short relative to
the buffer (e.g. after pane pre-reduction) because it avoids
materializing the prefix scan, and when results feed further device
work without a host round trip.  Off the TPU the kernel runs in
interpret mode, so tests exercise the same code on the CPU backend.
"""
from __future__ import annotations

import functools

import numpy as np

from ..backend import jax_modules

LANES = 128
# windows per grid program = sublanes of one f32 output tile
ROWS = 8


def interpret_off_tpu() -> bool:
    """The kernels compile for the TPU and run interpreted everywhere
    else (what the tests ask for on the CPU backend)."""
    jax, _ = jax_modules()
    return jax.default_backend() != "tpu"


@functools.lru_cache(maxsize=None)
def _build(n_rows: int, n_windows: int, interpret: bool):
    jax, jnp = jax_modules()
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    assert n_windows % ROWS == 0

    def kernel(starts_ref, ends_ref, values_ref, out_ref):
        g = pl.program_id(0)
        # every vector stays 2-D (1, LANES): Mosaic has no 1-D iota and
        # lays 1-D vectors out awkwardly
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
        for r in range(ROWS):
            start = starts_ref[g * ROWS + r]
            end = ends_ref[g * ROWS + r]
            first_row = start // LANES
            last_row = jax.lax.max(end - 1, 0) // LANES

            def body(row, acc, start=start, end=end):
                vals = values_ref[pl.ds(row, 1), :]
                idx = row * LANES + lane
                return acc + jnp.where((idx >= start) & (idx < end),
                                       vals, 0.0)

            acc = jax.lax.fori_loop(first_row, last_row + 1, body,
                                    jnp.zeros((1, LANES), jnp.float32))
            # one lane-row per window (the host reads column 0)
            out_ref[r:r + 1, :] = jnp.broadcast_to(
                jnp.sum(acc, axis=1, keepdims=True), (1, LANES))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_windows // ROWS,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((ROWS, LANES), lambda g, s, e: (g, 0)),
    )

    @jax.jit
    def run(starts, ends, values2d):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((n_windows, LANES), jnp.float32),
            grid_spec=grid_spec,
            interpret=interpret,
        )(starts, ends, values2d)

    return run


def pad_extents(starts, ends) -> np.ndarray:
    """[2, B'] int32 (starts; ends), B' the next multiple of ROWS;
    padding windows are empty."""
    B = len(starts)
    se = np.zeros((2, -(-B // ROWS) * ROWS), np.int32)
    se[0, :B] = starts
    se[1, :B] = ends
    return se


def window_sums(values: np.ndarray, starts: np.ndarray,
                ends: np.ndarray, interpret: bool = None):
    """out[b] = sum(values[starts[b]:ends[b]]) via the Pallas kernel.

    values is padded to a multiple of 128 lanes, the extents to a
    multiple of ``ROWS`` windows; starts/ends are int32.
    """
    T = len(values)
    n_rows = max(1, -(-T // LANES))
    padded = np.zeros(n_rows * LANES, np.float32)
    padded[:T] = values
    se = pad_extents(starts, ends)
    out = window_sums_device(padded, se[0], se[1], interpret)
    return np.asarray(out)[:len(starts), 0]


def window_sums_device(values, starts, ends, interpret: bool = None):
    """Async variant for the engine's dispatch path: returns the
    on-device [B, LANES] output (column 0 holds the sums) without a
    host round trip.  ``values`` must already be padded to a multiple
    of LANES and the extents to a multiple of ROWS; starts/ends int32
    device-or-host arrays."""
    _, jnp = jax_modules()
    if interpret is None:
        interpret = interpret_off_tpu()
    n_rows = values.shape[0] // LANES
    run = _build(n_rows, len(starts), bool(interpret))
    return run(starts, ends, jnp.asarray(values).reshape(n_rows, LANES))
