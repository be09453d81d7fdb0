"""Pallas TPU kernel: batched FlatFAT range queries.

The TPU twin of the reference's ``ComputeResults_Kernel``
(flatfat_gpu.hpp:92-135): there, one CUDA thread per window walks the
device-resident aggregator tree with the bit-trick range decomposition;
here, one grid program per block of ``ROWS`` windows performs the same
O(log n) walk with the whole heap-layout tree resident in VMEM (it is
at most 2 x t_pad floats; the engine's gate caps it at 4 MiB).  The
output is blocked ``(ROWS, 128)`` per program, so its VMEM footprint
does not grow with the batch.

The walk keeps separate left/right partial accumulators so the combine
order is preserved oldest->newest, which makes the kernel correct for
non-commutative combines -- same contract as the XLA query in
ops/flatfat_jax.py, against which the tests diff this kernel.

Tree layout: flat [2n] heap (root at 1, leaves at [n, 2n)), reshaped to
(2n / 128, 128) lane-rows.  Scalar tree loads become a dynamic-sublane
row load plus a one-hot lane extract -- the TPU-shaped substitute for
the scalar ``fat[i]`` indexing of the CUDA kernel.  Node values travel
as lane-uniform (1, 128) vectors: the combine runs on the VPU, and the
walk's scalar predicates reach it as int32 splats (Mosaic splats an
int32 scalar, not a bool).

Build/update stay XLA level sweeps (flatfat_jax.py): they are
bandwidth-bound strided combines XLA already fuses optimally; only the
per-window query has the irregular access pattern worth hand-scheduling.
"""
from __future__ import annotations

import functools
from typing import Callable

import numpy as np

from ..backend import jax_modules
from .window_sum import LANES, ROWS, interpret_off_tpu, pad_extents


@functools.lru_cache(maxsize=None)
def _build(n_leaves: int, n_windows: int, combine: Callable,
           neutral: float, interpret: bool):
    jax, jnp = jax_modules()
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    levels = int(np.log2(n_leaves))
    assert 1 << levels == n_leaves, "FlatFAT capacity must be a power of two"
    assert n_windows % ROWS == 0

    def kernel(starts_ref, ends_ref, tree_ref, out_ref):
        g = pl.program_id(0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
        neutral_row = jnp.full((1, LANES), neutral, jnp.float32)

        def tload(idx):
            """tree[idx] on every lane: dynamic row load, one-hot lane
            extract, lane reduce."""
            rowvec = tree_ref[pl.ds(idx // LANES, 1), :]
            hit = jnp.where(lane == idx % LANES, rowvec, 0.0)
            return jnp.broadcast_to(
                jnp.sum(hit, axis=1, keepdims=True), (1, LANES))

        def select(pred, a, b):
            return jnp.where(
                jnp.full((1, LANES), pred.astype(jnp.int32)) != 0, a, b)

        def body(_, carry):
            lo, hi, left, right = carry
            take_l = (lo < hi) & ((lo & 1) == 1)
            # an empty extent at the buffer's end starts at heap slot 2n:
            # clamp the (discarded) load inside the tree
            lval = tload(jax.lax.min(lo, 2 * n_leaves - 1))
            left = select(take_l, combine(left, lval), left)
            lo = jnp.where(take_l, lo + 1, lo)
            take_r = (lo < hi) & ((hi & 1) == 1)
            rval = tload(jax.lax.max(hi - 1, 0))
            right = select(take_r, combine(rval, right), right)
            hi = jnp.where(take_r, hi - 1, hi)
            return lo >> 1, hi >> 1, left, right

        for r in range(ROWS):
            lo = starts_ref[g * ROWS + r] + n_leaves
            hi = ends_ref[g * ROWS + r] + n_leaves
            _lo, _hi, left, right = jax.lax.fori_loop(
                0, levels + 1, body, (lo, hi, neutral_row, neutral_row))
            # one lane-row per window (the caller reads column 0)
            out_ref[r:r + 1, :] = select(hi > lo, combine(left, right),
                                         neutral_row)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_windows // ROWS,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((ROWS, LANES), lambda g, s, e: (g, 0)),
    )

    @jax.jit
    def run(starts, ends, tree2d):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((n_windows, LANES), jnp.float32),
            grid_spec=grid_spec,
            interpret=interpret,
        )(starts, ends, tree2d)

    return run


def pad_tree_rows(tree, neutral: float):
    """Pad a [2n] heap tree to a LANES multiple and reshape to the
    (rows, LANES) layout the kernel expects.  jnp-traceable."""
    _, jnp = jax_modules()
    tree = jnp.asarray(tree, jnp.float32)
    two_n = tree.shape[0]
    if two_n % LANES:
        tree = jnp.concatenate(
            [tree, jnp.full((LANES - two_n % LANES,), neutral,
                            jnp.float32)])
    return tree.reshape(-1, LANES)


def flatfat_query_ranges(tree, starts, ends, combine: Callable,
                         neutral: float, interpret: bool = None):
    """out[b] = fold(combine, tree leaves [starts[b], ends[b]))  using
    the heap tree (shape [2n], root at 1) built by flatfat_jax.

    ``combine`` must be a jax-traceable binary fn forming a monoid with
    ``neutral``; starts/ends index the leaf axis.  Returns float32 [B].
    """
    _, jnp = jax_modules()
    if interpret is None:
        interpret = interpret_off_tpu()
    tree = jnp.asarray(tree, jnp.float32)
    n_leaves = tree.shape[0] // 2
    se = pad_extents(starts, ends)
    run = _build(n_leaves, se.shape[1], combine, float(neutral),
                 bool(interpret))
    out = run(se[0], se[1], pad_tree_rows(tree, neutral))
    return np.asarray(out)[:len(starts), 0]
