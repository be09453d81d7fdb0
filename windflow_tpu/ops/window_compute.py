"""Batched window computation on device: the XLA replacement for the
reference's per-window CUDA kernels.

The reference assembles a batch of fired windows in pinned host memory
and launches ``ComputeBatch_Kernel`` -- one CUDA thread per window
running the user functor (win_seq_gpu.hpp:61-84, :552-610).  A TPU is
not a scalar-thread machine, so the design is different:

* windows over each key live in one contiguous **flat buffer** (ragged
  concatenation of per-key series); window extents are [start, end)
  index pairs into it.  Windows never span keys, so segment math works
  on the flat buffer directly.
* **sums** (sum / mean, and count over pane partials) add each window's
  OWN elements and nobody else's, so a window's result does not depend
  on the buffer's length or the window's place in it: windows of up to
  32 elements gather a masked [B, w_pad] tile; wider ones (3,600 panes a
  window in SABER's SG2) go through the **blocked sum**: the buffer in
  rows of 128, the rows' sums in rows of 128 again, and a window is the
  tail of its first row, the head of its last and the whole rows
  between, read a level up -- two row gathers a level, O(T + 256 B) work
  a level, every partial a sum of elements of the window.  One float32
  running sum over the whole buffer and ``c[end] - c[start]`` -- what
  served wide windows before -- carries the rounding of the BUFFER's
  magnitude into every window: past 2**24 (a few plugs' worth of
  thousand-watt panes) every later sum is off in its low digits.
  ``count`` over a store's own tuples is ``end - start``.
* **semigroup combines** (max/min) use a sparse table (log-sweep of
  strided combines) + two gathers per window -- the classic O(1) range
  query, a TPU-shaped replacement for FlatFAT's per-window tree walk.
  **argmax** runs the same table over (value, index) pairs in int32:
  each window's maximum and the first element at it (ops/argmax.py has
  the host's half: the ids at it).
* **custom window functions** gather padded [rows, W_pad] tiles a
  block of rows at a time and vmap the user's JAX function over each
  (the analogue of the reference's arbitrary ``__host__ __device__``
  functor path): :func:`_custom_program`; a sort in the function is
  lowered to a Pallas sorting network on the TPU (:func:`_fn_sorts`).

Every launch but the opt-in Pallas kernels' hands the device ONE host
buffer, inside the jitted call (:func:`pack_launch`, :func:`_packed`;
:func:`_custom_program` reads its own layout): the padded value columns and
the extents in one pooled int32 array, the float32 sections written
through a float32 view of the same memory and cast back bit for bit by
the program (argmax's one section is int32, written as it is and
turned back bit for bit by its program).
Nothing is converted to a device array on the dispatcher's thread:
JAX's call path takes a host array itself, and the Python in front of a
``jnp.asarray`` bought nothing (docs/RUNTIME.md 5c).

All shapes are bucketed to powers of two so XLA compiles a small, cached
set of programs (the reference instead reallocates pinned buffers
adaptively, win_seq_gpu.hpp:574-592).  Dispatch is async: results come
back as handles whose ``.block()`` materializes on host -- the
double-buffering protocol of ``waitAndFlush`` (win_seq_gpu.hpp:267-297)
falls out of JAX's asynchronous dispatch.
"""
from __future__ import annotations

import functools
import os
import threading
import time
from typing import Any, Callable, Dict

import numpy as np

from ..telemetry.spans import HANDLE_COUNTS
from .backend import jax_modules as _jax
from .pallas import row_gather, window_sort

BUILTIN_KINDS = ("sum", "count", "mean", "max", "min", "argmax")

# pane-partial pair kinds: cols carry a second buffer alongside "value"
# (the native engine's MEAN staging ships per-pane sums + counts)
PAIR_KINDS = ("mean_panes",)

def next_pow2(n: int) -> int:
    return 1 << (max(1, int(n)) - 1).bit_length()


# ---------------------------------------------------------------------------
# what a launch hands the device: one packed buffer
# ---------------------------------------------------------------------------

def packed_len(n_cols: int, t_pad: int, b_pad: int,
               n_rows: int = 2) -> int:
    """int32 words of one packed launch: ``n_cols`` value sections of
    ``t_pad``, then ``n_rows`` sections of ``b_pad`` (starts and ends,
    and behind them what else a launch carries a row)."""
    return n_cols * t_pad + n_rows * b_pad


def pack_launch(buf: np.ndarray, cols, fill, starts, ends,
                t_pad: int, b_pad: int, rows=()) -> np.ndarray:
    """Lay one launch out in ``buf`` (int32, :func:`packed_len` long):
    ``[cols[0] | cols[1] ... | starts | ends | rows[0] ...]``.  The value
    sections are written through a float32 view of the same memory, each
    element once:
    ``f32[:T] = v`` is the one float64 -> float32 conversion, the tail
    takes ``fill`` (the combine's neutral), padding extents are (0, 0).
    :func:`_packed` is the device's side of the same layout.  An int32
    column is written as it is (argmax, ops/argmax.int32_values); a
    section of ``rows`` (a user's function's window ids) is cast to
    int32 and padded with 0 as the extents are."""
    f32 = buf.view(np.float32)
    o = 0
    for v in cols:
        T = len(v)
        sec = buf if v.dtype == np.int32 else f32
        sec[o:o + T] = v
        sec[o + T:o + t_pad] = fill
        o += t_pad
    B = len(starts)
    for x in (starts, ends, *rows):
        buf[o:o + B] = x
        buf[o + B:o + b_pad] = 0
        o += b_pad
    return buf


def _packed(body: Callable, n_cols: int, t_pad: int, b_pad: int):
    """``body(*value columns, se)`` jitted over the one packed launch
    buffer: static slices at :func:`pack_launch`'s offsets, the value
    sections turned back into float32 bit for bit, the extents as one
    int32 [2, b_pad] array (padding rows are (0, 0): they read 0 and the
    host slice drops them anyway)."""
    jax, jnp = _jax()

    @jax.jit
    def run(buf):
        cols = [jax.lax.bitcast_convert_type(
            buf[j * t_pad:(j + 1) * t_pad], jnp.float32)
            for j in range(n_cols)]
        return body(*cols, buf[n_cols * t_pad:].reshape(2, b_pad))

    return run


# ---------------------------------------------------------------------------
# jitted programs (cached per bucketed shape)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _count_program(b_pad: int):
    """Tuples a window over the store's own buffer: ``end - start``; the
    launch carries no value section."""
    _, jnp = _jax()

    def run(se):
        return (se[1] - se[0]).astype(jnp.float32)

    return _packed(run, 0, 0, b_pad)


@functools.lru_cache(maxsize=None)
def _tile_sum_program(w_pad: int, t_pad: int, b_pad: int):
    """Window sums via a masked [B, w_pad] gather-tile reduction, for
    windows of up to ``_TILE_MAX_W`` elements: the tile sums only the
    window's own panes -- exact to within-window rounding -- and for
    w_pad this small one gather is the cheapest way to read them.  Wider
    windows take :func:`_block_sum_program`, which keeps the same
    promise with two row gathers a level instead of ``w_pad`` element
    gathers a window."""
    _, jnp = _jax()

    def run(values, se):
        starts, ends = se[0], se[1]
        T = values.shape[0]
        idx = starts[:, None] + jnp.arange(w_pad)[None, :]
        mask = idx < ends[:, None]
        idx = jnp.clip(idx, 0, T - 1)
        return jnp.where(mask, values[idx], 0).sum(axis=1)

    return _packed(run, 1, t_pad, b_pad)


@functools.lru_cache(maxsize=None)
def _tile_mean_program(w_pad: int, t_pad: int, b_pad: int):
    _, jnp = _jax()

    def run(values, counts, se):
        starts, ends = se[0], se[1]
        T = values.shape[0]
        idx = starts[:, None] + jnp.arange(w_pad)[None, :]
        mask = idx < ends[:, None]
        idx = jnp.clip(idx, 0, T - 1)
        s = jnp.where(mask, values[idx], 0).sum(axis=1)
        n = jnp.where(mask, counts[idx], 0).sum(axis=1)
        return jnp.stack([s, n])     # the host divides (DeviceBatchHandle)

    return _packed(run, 2, t_pad, b_pad)


# max pane extent (already padded to a power of two) served by the
# gather-tile programs; wider windows take the blocked sum
_TILE_MAX_W = 32
# the blocked sum's row: one vector register's lanes
_BLOCK = 128


def _block_levels(w_pad: int) -> int:
    """Levels the blocked sum needs for windows of up to ``w_pad``
    elements: the smallest ``n`` with ``_BLOCK ** n >= w_pad`` (a window
    of up to 128 elements touches two rows and none between; each level
    up serves 128 times the extent)."""
    n, reach = 1, _BLOCK
    while reach < w_pad:
        n, reach = n + 1, reach * _BLOCK
    return n


@functools.lru_cache(maxsize=None)
def _block_sum_program(kind: str, n_levels: int, t_pad: int, b_pad: int):
    """Sums over wide windows, each from the window's own elements.

    Level 0 is the flat buffer in rows of ``_BLOCK``; level ``l + 1``
    holds the sums of level ``l``'s rows, in rows of ``_BLOCK`` again.  A
    window [lo, hi) at a level is: of the row ``lo`` lies in, the lanes
    from ``lo`` on; of the row ``hi - 1`` lies in, the lanes up to it
    (one masked row where the two are the same row); and the whole rows
    strictly between, which are a window a level up.  ``n_levels``
    (:func:`_block_levels`) is what the widest window of the launch
    needs, after which nothing is left between.  Two row gathers a
    level and column, [B, 128] each; no running sum anywhere, so every
    partial is a sum of elements of the one window: where those are
    integers whose sum float32 holds (pane counts; whole watts under
    2**24), the result is exact, whatever the buffer's length and the
    window's place in it, and for any floats the rounding is that of
    adding the window's own elements pairwise.

    ``kind``: ``sum`` (one column); ``mean`` (one column over the
    store's own tuples: the sum over ``hi - lo``); ``mean_panes`` (pane
    sums and pane counts, the native engine's MEAN staging: the sum of
    pane sums over the sum of pane counts; a windowed mean is NOT the
    mean of pane means).  A mean comes back as the pair [sums; counts]
    and the host takes the quotient (:class:`DeviceBatchHandle`)."""
    _, jnp = _jax()
    lane = np.arange(_BLOCK, dtype=np.int32)[None, :]
    shift = _BLOCK.bit_length() - 1

    def rows_of(flat):
        pad = -flat.shape[0] % _BLOCK
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        return flat.reshape(-1, _BLOCK)

    def span_sums(cols, lo, hi):
        """[len(cols), B]: each column's sum over every [lo, hi)."""
        levels = [rows_of(c) for c in cols]
        out = [jnp.zeros(lo.shape, c.dtype) for c in cols]
        live = hi > lo
        for level in range(n_levels):
            last = hi - 1
            r_lo, r_hi = lo >> shift, last >> shift
            same = r_lo == r_hi
            head = live[:, None] & (lane >= (lo & (_BLOCK - 1))[:, None]) \
                & (~same[:, None] | (lane <= (last & (_BLOCK - 1))[:, None]))
            tail = (live & ~same)[:, None] \
                & (lane <= (last & (_BLOCK - 1))[:, None])
            for j, rows in enumerate(levels):
                top = rows.shape[0] - 1
                out[j] = out[j] \
                    + jnp.where(head, rows[jnp.clip(r_lo, 0, top)],
                                0).sum(axis=1) \
                    + jnp.where(tail, rows[jnp.clip(r_hi, 0, top)],
                                0).sum(axis=1)
            if level + 1 < n_levels:
                levels = [rows_of(rows.sum(axis=1)) for rows in levels]
                lo, hi = r_lo + 1, r_hi
                live = live & (hi > lo)
        return out

    if kind == "mean_panes":
        def run(values, counts, se):
            s, n = span_sums((values, counts), se[0], se[1])
            return jnp.stack([s, n])
    else:
        def run(values, se):
            s, = span_sums((values,), se[0], se[1])
            if kind == "sum":
                return s
            return jnp.stack([s, (se[1] - se[0]).astype(values.dtype)])

    return _packed(run, 2 if kind == "mean_panes" else 1, t_pad, b_pad)


@functools.lru_cache(maxsize=None)
def _sparse_table_program(kind: str, t_pad: int, b_pad: int):
    """Range-min/max via log-sweep sparse table: level j holds the
    combine over [i, i + 2^j), one level a bit of ``t_pad``.  Result =
    combine(table[j][start], table[j][end - 2^j]) with j =
    floor(log2(len)) per window."""
    _, jnp = _jax()
    neutral = -np.inf if kind == "max" else np.inf
    comb = jnp.maximum if kind == "max" else jnp.minimum
    n_levels = t_pad.bit_length()

    def run(values, se):
        starts, ends = se[0], se[1]
        T = values.shape[0]
        levels = [values]
        v = values
        for j in range(1, n_levels):
            shift = 1 << (j - 1)
            shifted = jnp.concatenate(
                [v[shift:], jnp.full((shift,), neutral, v.dtype)])
            v = comb(v, shifted)
            levels.append(v)
        table = jnp.stack(levels)  # [L, T]
        length = jnp.maximum(ends - starts, 1)
        j = jnp.floor(jnp.log2(length.astype(jnp.float32))).astype(jnp.int32)
        j = jnp.clip(j, 0, n_levels - 1)
        hi = jnp.clip(ends - (1 << j), 0, T - 1)
        lo = jnp.clip(starts, 0, T - 1)
        out = comb(table[j, lo], table[j, hi])
        # padding rows ((0,0) extents) may hold +-inf; zero them so the
        # host-side result buffer stays finite
        return jnp.where(se[1] > se[0], out, 0)

    return _packed(run, 1, t_pad, b_pad)


@functools.lru_cache(maxsize=None)
def _argmax_program(t_pad: int, b_pad: int):
    """Each window's maximum and the offset of the first element at it
    from the window's start, in int32 (ops/argmax.py): the sparse table
    of :func:`_sparse_table_program` over (value, index) pairs, a larger
    value winning and of equal ones the lower index (two overlapping
    halves of a query then agree on the first).  A window that holds
    nothing reads (INT32_NEUTRAL, 0)."""
    jax, jnp = _jax()
    from .argmax import INT32_NEUTRAL
    n_levels = t_pad.bit_length()

    def comb(va, ia, vb, ib):
        take_a = (va > vb) | ((va == vb) & (ia <= ib))
        return jnp.where(take_a, va, vb), jnp.where(take_a, ia, ib)

    def run(values, se):
        starts, ends = se[0], se[1]
        T = values.shape[0]
        # the section is int32 (pack_launch): _packed's float32 view
        # turned back, bit for bit (the two casts fold away)
        v = jax.lax.bitcast_convert_type(values, jnp.int32)
        i = jnp.arange(T, dtype=jnp.int32)
        vs, is_ = [v], [i]
        for j in range(1, n_levels):
            shift = 1 << (j - 1)
            v, i = comb(v, i, jnp.concatenate(
                [v[shift:], jnp.full((shift,), INT32_NEUTRAL, v.dtype)]),
                jnp.concatenate([i[shift:], jnp.full((shift,), T,
                                                     i.dtype)]))
            vs.append(v)
            is_.append(i)
        tv, ti = jnp.stack(vs), jnp.stack(is_)      # [L, T]
        length = jnp.maximum(ends - starts, 1)
        j = jnp.floor(jnp.log2(length.astype(jnp.float32))).astype(jnp.int32)
        j = jnp.clip(j, 0, n_levels - 1)
        hi = jnp.clip(ends - (1 << j), 0, T - 1)
        lo = jnp.clip(starts, 0, T - 1)
        m, at = comb(tv[j, lo], ti[j, lo], tv[j, hi], ti[j, hi])
        held = ends > starts
        return jnp.stack([jnp.where(held, m, INT32_NEUTRAL),
                          jnp.where(held, at - starts, 0)])

    return _packed(run, 1, t_pad, b_pad)


# A user's window function runs over its tile a block of rows at a time,
# a block being about this many elements (64 MiB of float32): a launch of
# 65,875 windows of up to 4,096 readings is 1 GiB of tile, and twice that
# again in gather indices and sort space, where a block of 4,096 rows
# holds a few hundred MiB whatever the launch's length
_FN_BLOCK_ELEMS = 1 << 24


def fn_rows(n_rows: int, w_pad: int):
    """``(b_pad, block)`` of a launch of ``n_rows`` windows of up to
    ``w_pad`` elements handed to a user's function.  ``b_pad``, the rows
    the launch's buffer has room for, is a power of eight (4,096 at
    least): the launches of a steady stream, whose row counts wander
    round one value (63,750 or 65,875 a chunk in SABER's SG2 shape, on
    either side of 2**16), share one program.  The function runs over
    the filled blocks of ``block`` rows alone (:func:`_custom_program`),
    so the rows beyond the launch's cost their ids and extents (12 bytes
    a row) and never a row of the tile."""
    b_pad = 4096
    while b_pad < n_rows:
        b_pad <<= 3
    return b_pad, min(b_pad, max(1, _FN_BLOCK_ELEMS // w_pad))


@functools.lru_cache(maxsize=None)
def _fn_sorts(fn: Callable, w_pad: int, col_names: tuple, block: int,
              backend: str):
    """The user's ``fn`` vmapped over a block, with each sort that
    ``pallas.window_sort`` takes lowered to its kernel
    (``window_sort.lower_sorts``: traced once on the block's shapes), or
    None where the function has no such sort and runs as it is."""
    jax, jnp = _jax()
    tile = jax.ShapeDtypeStruct((block, w_pad), jnp.float32)
    return window_sort.lower_sorts(
        jax.vmap(fn), (jax.ShapeDtypeStruct((block,), jnp.int32),
                       {name: tile for name in col_names},
                       jax.ShapeDtypeStruct((block, w_pad), jnp.bool_)),
        backend)


@functools.lru_cache(maxsize=None)
def _custom_program(fn: Callable, w_pad: int, col_names: tuple, t_pad: int,
                    b_pad: int, block: int, dma: bool, backend: str):
    """A user's ``fn(gwid, cols, mask)`` over every window of one packed
    launch (:func:`pack_launch`: the columns, then starts, ends and the
    window ids, then the row count in the buffer's last word): each block
    of ``block`` rows takes its [block, w_pad] tile of every column, row
    ``i`` the ``w_pad`` elements from its start, with the mask of the
    window's own elements, and vmaps the function over it, as the user
    wrote it.  With ``dma`` (``pallas.row_gather.engages``: the TPU, a
    row of whole lane-rows) the Pallas kernel copies each row by DMA
    (``ops/pallas/row_gather.py``); else a row is a dynamic slice, which
    XLA's TPU backend runs as a loop of one row an iteration.  On a TPU
    v5e a block of 4,096 rows of 4,096 took 120 ms by element gather;
    alone, with a real launch's starts, 9.1 ms of device time by the slice
    and 0.38 ms by the kernel (PERF.md section 6).  The column's
    ``t_pad`` leaves ``w_pad`` behind the last window's start, so no row
    is moved to fit (:func:`fn_rows`' caller); the kernel's view of a
    column reaches one lane-row further, into the next section of the
    buffer.  As many blocks run as the launch fills (a
    loop whose count is read from the buffer); the rows behind read 0.

    Where the function sorts a window and ``pallas.window_sort`` takes
    the sort on ``backend`` (:func:`_fn_sorts`), the sort is its kernel
    and the result carries one more word: the rows of the launch whose
    every sort the kernel made (int32, bit for bit), counted on the
    device since a block that holds -0.0, a NaN or a subnormal takes
    ``lax.sort``.  Else the program is the function's as traced."""
    jax, jnp = _jax()
    n_cols = len(col_names)
    n_blocks = b_pad // block
    reach = row_gather.LANES if dma else 0
    sorts = _fn_sorts(fn, w_pad, col_names, block, backend)

    @jax.jit
    def run(buf):
        cols = [jax.lax.bitcast_convert_type(
            buf[j * t_pad:(j + 1) * t_pad + reach], jnp.float32)
            for j in range(n_cols)]
        rows = buf[n_cols * t_pad:n_cols * t_pad + 3 * b_pad]
        blocks = rows.reshape(3, n_blocks, block)
        n_rows = buf[-1]
        lane = jnp.arange(w_pad, dtype=jnp.int32)[None, :]

        def rows_of(c, starts):
            if dma:
                return row_gather.row_tiles(c, starts, w_pad)
            return jax.vmap(
                lambda s: jax.lax.dynamic_slice(c, (s,), (w_pad,)))(starts)

        def block_of(b):
            starts, ends, gwids = (blocks[k, b] for k in range(3))
            mask = lane < (ends - starts)[:, None]
            win = {name: rows_of(c, starts)
                   for name, c in zip(col_names, cols)}
            return gwids, win, mask

        out = jnp.zeros((n_blocks, block), jnp.float32)
        n_filled = (n_rows + block - 1) // block
        if sorts is None:
            def one(b, out):
                got = jax.vmap(fn)(*block_of(b))
                return out.at[b].set(got.astype(out.dtype))

            return jax.lax.fori_loop(0, n_filled, one, out).reshape(b_pad)

        def one(b, carry):
            out, n_sorted = carry
            got, taken = sorts(*block_of(b))
            filled = jnp.minimum(n_rows - b * block, block)
            return (out.at[b].set(got.astype(out.dtype)),
                    n_sorted + jnp.where(taken, filled, 0))

        out, n_sorted = jax.lax.fori_loop(0, n_filled, one,
                                          (out, jnp.int32(0)))
        return jnp.concatenate([
            out.reshape(b_pad),
            jax.lax.bitcast_convert_type(n_sorted, jnp.float32)[None]])

    return run


@functools.lru_cache(maxsize=None)
def _ffat_program(combine: Callable, neutral: float, t_pad: int,
                  b_pad: int):
    """FlatFAT path: build the device aggregator tree over the flat
    buffer, then answer every window with a vectorized range query --
    the Win_SeqFFAT_GPU pipeline (flatfat_gpu.hpp kernels) in one jitted
    chain."""
    from .flatfat_jax import _programs
    _, jnp = _jax()
    build, _update, query = _programs(combine, neutral, t_pad)

    def run(values, se):
        starts, ends = se[0], se[1]
        valid = ends > starts
        tree = build(values)
        out = query(tree, starts, ends, valid)
        return jnp.where(valid, out, 0)

    return _packed(run, 1, t_pad, b_pad)


# Largest shapes the opt-in Pallas kernels take; anything larger keeps
# the XLA path whatever the flag says.  The flat buffer (window sum) or
# the 2 * t_pad heap tree (FFAT query) is mapped whole into VMEM:
# 2 * 2^19 f32 = 4 MiB.  The extents of a batch travel by scalar
# prefetch: 2 * 2^15 int32 = 256 KiB of SMEM.
_PALLAS_MAX_T = 1 << 19
_PALLAS_MAX_B = 1 << 15


def _use_pallas(flag: str, t_pad: int, b_pad: int) -> bool:
    """Pallas kernel gate: env opt-in only (``WINDFLOW_PALLAS_FFAT`` /
    ``WINDFLOW_PALLAS_WINSUM``).  The A/B on a real chip
    (docs/PARITY.md "Pallas vs XLA") measured the FFAT bit-walk kernel
    at parity with the XLA query for short extents and up to 5.5x
    BEHIND at the extents the engine actually produces for custom
    combines (extent ~ win_len: no pane pre-reduction there), so the
    default is the XLA path on every backend.  Once opted in there is
    no second path: a kernel that fails to lower raises."""
    return (os.environ.get(flag) in ("1", "on")
            and t_pad <= _PALLAS_MAX_T and b_pad <= _PALLAS_MAX_B)


@functools.lru_cache(maxsize=None)
def _ffat_pallas_program(combine: Callable, neutral: float, t_pad: int,
                         b_pad: int):
    """XLA tree build + Pallas bit-walk range query (the hand-scheduled
    ComputeResults_Kernel twin, ops/pallas/flatfat_query.py)."""
    from .flatfat_jax import _programs
    from .pallas.flatfat_query import _build as _pallas_build
    from .pallas.window_sum import interpret_off_tpu
    jax, jnp = _jax()
    build, _update, _query = _programs(combine, neutral, t_pad)
    pq = _pallas_build(t_pad, b_pad, combine, float(neutral),
                       interpret_off_tpu())

    @jax.jit
    def run(values, se):
        starts, ends = se[0], se[1]
        valid = ends > starts
        tree = build(values)
        from .pallas.flatfat_query import pad_tree_rows
        out = pq(starts, ends, pad_tree_rows(tree, neutral))[:b_pad, 0]
        return jnp.where(valid, out, 0)

    return run


class DeviceBatchHandle:
    """Async result of one batched window computation (the PJRT-future
    analogue of the reference's in-flight CUDA kernel).

    The device-to-host copy is started asynchronously at construction
    (``copy_to_host_async``), the cudaMemcpyAsync-D2H analogue
    (win_seq_gpu.hpp:610).  A caller with nothing else to do sleeps in
    ``wait()`` until the device computation has finished (the GIL is
    released meanwhile); ``ready()`` asks the same without waiting.
    After either, ``block()`` is the end of the copy to the host and
    near-free; entered before, it waits for the computation too.

    An ARGMAX comes back as the int32 pair [maxima; offsets] (``pair``),
    both rows of it for ops/argmax.rows.  A MEAN comes back as the pair
    [sums; counts] (``pair``) and the quotient is taken here, on the
    host, in float32: a TPU v5e's float32 divide is not the correctly
    rounded one (a third of 4 M integer pairs came out one or two units
    in the last place off; PR 34), and a mean that is the IEEE quotient
    of an exact sum and an exact count can be checked bit for bit.
    65,536 quotients take some 20 us.

    The handle carries the launch's ``spans.HANDLE_COUNTS``, given as
    keywords (0 where not given; ``buffers_in`` 1, as on the packed
    paths), but for ``fn_rows_sorted``: :meth:`block` reads that off the
    result's last word where the program carries it (``counted``).
    ``t_packed`` and ``t_called`` are the engine's two ``perf_counter``
    stamps of the launch (``spans.Launch``): the host had finished
    preparing what it hands the runtime; the jitted call had returned,
    before the copy back is started here."""

    __slots__ = ("_dev", "_n", "_pair", "_counted", "t_packed", "t_called",
                 *HANDLE_COUNTS)

    def __init__(self, dev_array, n_valid: int, t_packed: float,
                 pair: bool = False, counted: bool = False, **counts):
        self.t_called = time.perf_counter()
        self.t_packed = t_packed
        self._dev = dev_array
        self._n = n_valid
        self._pair = pair
        self._counted = counted
        counts = {"buffers_in": 1, **counts}
        for name in HANDLE_COUNTS:
            setattr(self, name, counts.pop(name, 0))
        if counts:
            raise TypeError(f"not a launch count: {sorted(counts)}")
        dev_array.copy_to_host_async()

    def ready(self) -> bool:
        """True when the device computation has finished (block() will
        not stall)."""
        return bool(self._dev.is_ready())

    def wait(self) -> None:
        """Sleep until ``ready()`` would read true."""
        self._dev.block_until_ready()

    def block(self) -> np.ndarray:
        out = np.asarray(self._dev)
        if self._counted:
            self.fn_rows_sorted = int(out[-1:].view(np.int32)[0])
        if not self._pair:
            return out[: self._n]
        if out.dtype == np.int32:   # argmax: [maxima; offsets]
            return out[:, : self._n]
        return out[0, : self._n] / np.maximum(out[1, : self._n], 1)


class WindowComputeEngine:
    """Executes batches of window extents against a flat value buffer.

    ``kind`` is a builtin combine name or a JAX callable
    ``fn(gwid, cols: dict[str, f32[W]], mask: bool[W]) -> f32``
    (the TPU twin of the GPU functor signature, API:104/118).
    """

    def __init__(self, kind: Any = "sum", value_col: str = "value"):
        # kind may also be ("ffat", combine_fn, neutral): device FlatFAT
        # tree over the flat buffer (Win_SeqFFAT_GPU analogue)
        is_ffat = isinstance(kind, tuple) and len(kind) == 3 \
            and kind[0] == "ffat"
        if not (callable(kind) or kind in BUILTIN_KINDS
                or kind in PAIR_KINDS or is_ffat):
            raise ValueError(f"unknown window combine kind: {kind!r}")
        self.kind = kind
        self.is_ffat = is_ffat
        self.value_col = value_col
        # one in-flight dispatch per ENGINE: farm replicas overlap
        # their launches, one engine's launches stay ordered
        self._lock = threading.Lock()
        # the buffers a launch hands the device, re-lent once the
        # transfer has let go of them (by refcount: the runtime holds the
        # host array it was called with until then, and on the CPU
        # backend, which may alias it, until the program has run).  A
        # fresh 67 MB buffer costs its page faults every launch: 39 ms a
        # column of 33 MB on the chip's host, a quarter of it the copy
        from ..core.tuples import ColumnPool
        self._padded = ColumnPool()

    def compute(self, cols: Dict[str, np.ndarray], starts: np.ndarray,
                ends: np.ndarray, gwids: np.ndarray) -> DeviceBatchHandle:
        """Launch one batch; returns an async handle."""
        with self._lock:
            return self._compute(cols, starts, ends, gwids)

    def _compute(self, cols: Dict[str, np.ndarray], starts: np.ndarray,
                 ends: np.ndarray, gwids: np.ndarray) -> DeviceBatchHandle:
        B = len(starts)
        T = len(next(iter(cols.values())))
        # floor the shape buckets: padding a small launch to 2048 costs
        # ~16-32 KB of transfer (noise next to a launch's fixed cost)
        # and collapses the set of distinct compiled programs to a
        # handful, so steady-state launches never hit a mid-stream XLA
        # compile
        T_pad = next_pow2(max(T, 2048))
        if callable(self.kind):
            return self._compute_fn(cols, starts, ends, gwids)
        B_pad = next_pow2(max(B, 2048))
        if (self.is_ffat and _use_pallas(
                "WINDFLOW_PALLAS_FFAT", T_pad, B_pad)) or (
                self.kind == "sum" and _use_pallas(
                    "WINDFLOW_PALLAS_WINSUM", T_pad, B_pad)):
            return self._compute_unpacked(cols, starts, ends, gwids,
                                          T_pad, B_pad)
        # every builtin combine: ONE packed buffer, handed to the jitted
        # program as the numpy array it is (pack_launch has the layout)
        values, fill = (cols[self.value_col],), 0
        if self.kind == "argmax":
            from .argmax import INT32_NEUTRAL, int32_values
            buf = pack_launch(
                self._padded.take(packed_len(1, T_pad, B_pad), np.int32),
                (int32_values(values[0]),), INT32_NEUTRAL, starts, ends,
                T_pad, B_pad)
            t_packed = time.perf_counter()
            return DeviceBatchHandle(_argmax_program(T_pad, B_pad)(buf), B,
                                     t_packed, pair=True)
        if self.is_ffat:
            _, comb, fill = self.kind
            prog = _ffat_program(comb, fill, T_pad, B_pad)
        elif self.kind in ("max", "min"):
            fill = -np.inf if self.kind == "max" else np.inf
            prog = _sparse_table_program(self.kind, T_pad, B_pad)
        elif self.kind == "count":
            # over the buffer as the store staged it: the extents alone
            values, T_pad = (), 0
            prog = _count_program(B_pad)
        else:
            # the sums: the widest window of the launch picks the program
            wp = next_pow2(max(int((ends - starts).max()) if B else 1, 2))
            if self.kind == "mean_panes":
                values += (cols["count"],)
                prog = (_tile_mean_program(wp, T_pad, B_pad)
                        if wp <= _TILE_MAX_W else _block_sum_program(
                            "mean_panes", _block_levels(wp), T_pad, B_pad))
            elif self.kind == "sum" and wp <= _TILE_MAX_W:
                prog = _tile_sum_program(wp, T_pad, B_pad)
            else:
                # a wide sum, or a mean over the store's own tuples
                prog = _block_sum_program(self.kind, _block_levels(wp),
                                          T_pad, B_pad)
        buf = pack_launch(
            self._padded.take(packed_len(len(values), T_pad, B_pad),
                              np.int32),
            values, fill, starts, ends, T_pad, B_pad)
        t_packed = time.perf_counter()
        # a mean comes back as [sums; counts] and is divided on the host
        return DeviceBatchHandle(prog(buf), B, t_packed,
                                 self.kind in ("mean", "mean_panes"))

    def _compute_fn(self, cols, starts, ends, gwids) -> DeviceBatchHandle:
        """A user's window function: its columns (the set is the user's,
        in name order), the extents and the window ids in ONE packed
        buffer, and the row count in its last word (:func:`fn_rows`,
        :func:`_custom_program`).  A column's section holds ``w_pad``
        more than its elements, so that every window's row is a slice
        of the section."""
        B = len(starts)
        T = len(next(iter(cols.values())))
        w_pad = next_pow2(int((ends - starts).max()) if B else 1)
        T_pad = next_pow2(max(T + w_pad, 2048))
        B_pad, block = fn_rows(B, w_pad)
        names = tuple(sorted(cols))
        buf = self._padded.take(packed_len(len(names), T_pad, B_pad, 3) + 1,
                                np.int32)
        pack_launch(buf[:-1], [cols[c] for c in names], 0, starts, ends,
                    T_pad, B_pad, rows=(gwids,))
        buf[-1] = B
        t_packed = time.perf_counter()
        backend = _jax()[0].default_backend()
        dma = row_gather.engages(backend, w_pad)
        prog = _custom_program(self.kind, w_pad, names, T_pad, B_pad, block,
                               dma, backend)
        return DeviceBatchHandle(
            prog(buf), B, t_packed, fn_elems=int((ends - starts).sum()),
            tile_elems=-(-B // block) * block * w_pad,
            fn_rows_dma=B if dma else 0,
            counted=_fn_sorts(self.kind, w_pad, names, block,
                              backend) is not None)

    def _compute_unpacked(self, cols, starts, ends, gwids, T_pad: int,
                          B_pad: int) -> DeviceBatchHandle:
        """The launches that keep their own columns: the two opt-in
        Pallas kernels.  Host arrays go into the jitted call as they are
        here too."""
        B = len(starts)
        T = len(next(iter(cols.values())))
        se = np.zeros((2, B_pad), dtype=np.int32)
        se[0, :B] = starts
        se[1, :B] = ends

        def pad_col(v, fill=0):
            out = self._padded.take(T_pad, np.float32)
            out[:T] = v
            out[T:] = fill
            return out

        if self.is_ffat:
            _, comb, neutral = self.kind
            prog = _ffat_pallas_program(comb, neutral, T_pad, B_pad)
            operands = (pad_col(cols[self.value_col], neutral), se)
        elif self.kind == "sum":
            # hand-scheduled Pallas alternative to the XLA sum paths
            # (the ComputeBatch_Kernel twin).  T_pad/B_pad are powers
            # of two >= 2048, so the lane/row alignment holds by
            # construction.
            from .pallas.window_sum import window_sums_device

            def prog(*operands):
                return window_sums_device(*operands)[:, 0]
            operands = (pad_col(cols[self.value_col]), se[0], se[1])
        t_packed = time.perf_counter()
        return DeviceBatchHandle(prog(*operands), B, t_packed,
                                 buffers_in=len(operands))
