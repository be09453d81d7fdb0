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
* **invertible combines** (sum/count/mean) use one prefix scan over the
  flat buffer + two gathers per window: O(T + B) work, no [B, W]
  materialization, pure VPU-friendly code XLA fuses well.
* **semigroup combines** (max/min) use a sparse table (log-sweep of
  strided combines) + two gathers per window -- the classic O(1) range
  query, a TPU-shaped replacement for FlatFAT's per-window tree walk.
* **custom window functions** gather padded [B, W_pad] tiles and vmap
  the user's JAX function over the batch (the analogue of the
  reference's arbitrary ``__host__ __device__`` functor path).

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
from typing import Any, Callable, Dict

import numpy as np

from .backend import jax_modules as _jax

BUILTIN_KINDS = ("sum", "count", "mean", "max", "min")

# pane-partial pair kinds: cols carry a second buffer alongside "value"
# (the native engine's MEAN staging ships per-pane sums + counts)
PAIR_KINDS = ("mean_panes",)

def next_pow2(n: int) -> int:
    p = 1
    while p < max(1, n):
        p <<= 1
    return p


# ---------------------------------------------------------------------------
# jitted programs (cached per bucketed shape)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _scan_program(kind: str):
    """``se`` packs [starts; ends] as one int32 [2, B] array: a single
    host->device transfer instead of three (padding rows are (0, 0), so
    their range sums are 0 and the host slice drops them anyway)."""
    jax, jnp = _jax()

    @jax.jit
    def run(values, se):
        starts, ends = se[0], se[1]
        c = jnp.concatenate([jnp.zeros((1,), values.dtype),
                             jnp.cumsum(values)])
        s = c[ends] - c[starts]
        n = (ends - starts).astype(values.dtype)
        if kind == "sum":
            out = s
        elif kind == "count":
            out = n
        else:  # mean
            out = s / jnp.maximum(n, 1)
        return out

    return run


@functools.lru_cache(maxsize=None)
def _tile_sum_program(w_pad: int):
    """Window sums via a masked [B, w_pad] gather-tile reduction.
    Used instead of the prefix scan when every window spans few panes:
    the scan's c[end]-c[start] differencing carries the f32 rounding of
    the WHOLE buffer's magnitude into each window (catastrophic for
    small windows late in the buffer), while the tile sums only the
    window's own panes -- exact to within-window rounding, and for
    w_pad this small the gather is cheaper than the scan anyway."""
    jax, jnp = _jax()

    @jax.jit
    def run(values, se):
        starts, ends = se[0], se[1]
        T = values.shape[0]
        idx = starts[:, None] + jnp.arange(w_pad)[None, :]
        mask = idx < ends[:, None]
        idx = jnp.clip(idx, 0, T - 1)
        return jnp.where(mask, values[idx], 0).sum(axis=1)

    return run


@functools.lru_cache(maxsize=None)
def _tile_mean_program(w_pad: int):
    jax, jnp = _jax()

    @jax.jit
    def run(values, counts, se):
        starts, ends = se[0], se[1]
        T = values.shape[0]
        idx = starts[:, None] + jnp.arange(w_pad)[None, :]
        mask = idx < ends[:, None]
        idx = jnp.clip(idx, 0, T - 1)
        s = jnp.where(mask, values[idx], 0).sum(axis=1)
        n = jnp.where(mask, counts[idx], 0).sum(axis=1)
        return s / jnp.maximum(n, 1)

    return run


# max pane extent (already padded to a power of two) served by the
# gather-tile programs; wider windows take the prefix scan
_TILE_MAX_W = 32


@functools.lru_cache(maxsize=None)
def _scan_pair_program():
    """Mean over pane partials: per-window sum of pane sums divided by
    sum of pane counts (the native engine's MEAN staging ships both
    buffers; a windowed mean is NOT the mean of pane means)."""
    jax, jnp = _jax()

    @jax.jit
    def run(values, counts, se):
        starts, ends = se[0], se[1]
        cv = jnp.concatenate([jnp.zeros((1,), values.dtype),
                              jnp.cumsum(values)])
        cc = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)])
        s = cv[ends] - cv[starts]
        n = cc[ends] - cc[starts]
        return s / jnp.maximum(n, 1)

    return run


@functools.lru_cache(maxsize=None)
def _sparse_table_program(kind: str, n_levels: int):
    """Range-min/max via log-sweep sparse table: level j holds the
    combine over [i, i + 2^j).  Result = combine(table[j][start],
    table[j][end - 2^j]) with j = floor(log2(len)) per window."""
    jax, jnp = _jax()
    neutral = -np.inf if kind == "max" else np.inf
    comb = jnp.maximum if kind == "max" else jnp.minimum

    @jax.jit
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

    return run


@functools.lru_cache(maxsize=None)
def _custom_program(fn: Callable, w_pad: int, col_names: tuple):
    jax, jnp = _jax()

    @jax.jit
    def run(gwids, starts, ends, valid, *cols):
        T = cols[0].shape[0]
        idx = starts[:, None] + jnp.arange(w_pad)[None, :]
        mask = idx < ends[:, None]
        idx = jnp.clip(idx, 0, T - 1)
        win_cols = {name: c[idx] for name, c in zip(col_names, cols)}
        out = jax.vmap(fn)(gwids, win_cols, mask)
        return jnp.where(valid, out, 0)

    return run


@functools.lru_cache(maxsize=None)
def _ffat_program(combine: Callable, neutral: float, t_pad: int):
    """FlatFAT path: build the device aggregator tree over the flat
    buffer, then answer every window with a vectorized range query --
    the Win_SeqFFAT_GPU pipeline (flatfat_gpu.hpp kernels) in one jitted
    chain."""
    from .flatfat_jax import _programs
    jax, jnp = _jax()
    build, _update, query = _programs(combine, neutral, t_pad)

    @jax.jit
    def run(values, se):
        starts, ends = se[0], se[1]
        valid = ends > starts
        tree = build(values)
        out = query(tree, starts, ends, valid)
        return jnp.where(valid, out, 0)

    return run


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
    near-free; entered before, it waits for the computation too."""

    __slots__ = ("_dev", "_n")

    def __init__(self, dev_array, n_valid: int):
        self._dev = dev_array
        self._n = n_valid
        dev_array.copy_to_host_async()

    def ready(self) -> bool:
        """True when the device computation has finished (block() will
        not stall)."""
        return bool(self._dev.is_ready())

    def wait(self) -> None:
        """Sleep until ``ready()`` would read true."""
        self._dev.block_until_ready()

    def block(self) -> np.ndarray:
        return np.asarray(self._dev)[: self._n]


class WindowComputeEngine:
    """Executes batches of window extents against a flat value buffer.

    ``kind`` is a builtin combine name or a JAX callable
    ``fn(gwid, cols: dict[str, f32[W]], mask: bool[W]) -> f32``
    (the TPU twin of the GPU functor signature, API:104/118).
    """

    def __init__(self, kind: Any = "sum", value_col: str = "value",
                 dtype=np.float32):
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
        self.dtype = dtype
        # one in-flight dispatch per ENGINE: farm replicas overlap
        # their launches, one engine's launches stay ordered
        self._lock = threading.Lock()

    def compute(self, cols: Dict[str, np.ndarray], starts: np.ndarray,
                ends: np.ndarray, gwids: np.ndarray) -> DeviceBatchHandle:
        """Launch one batch; returns an async handle."""
        with self._lock:
            return self._compute(cols, starts, ends, gwids)

    def _compute(self, cols: Dict[str, np.ndarray], starts: np.ndarray,
                 ends: np.ndarray, gwids: np.ndarray) -> DeviceBatchHandle:
        _, jnp = _jax()
        B = len(starts)
        T = len(next(iter(cols.values())))
        # floor the shape buckets: padding a small launch to 2048 costs
        # ~16-32 KB of transfer (noise next to a launch's fixed cost)
        # and collapses the set of distinct compiled programs to a
        # handful, so steady-state launches never hit a mid-stream XLA
        # compile
        T_pad = next_pow2(max(T, 2048))
        B_pad = next_pow2(max(B, 2048))
        # starts/ends ride in ONE packed int32 array: every device_put
        # has a fixed cost, so the builtin paths ship exactly two
        # buffers (values + extents) per launch
        se = np.zeros((2, B_pad), dtype=np.int32)
        se[0, :B] = starts
        se[1, :B] = ends

        def pad_col(v, fill=0):
            out = np.full(T_pad, fill, dtype=self.dtype)
            out[:T] = v
            return out

        if self.is_ffat:
            _, comb, neutral = self.kind
            vals_dev = jnp.asarray(pad_col(cols[self.value_col], neutral))
            se_dev = jnp.asarray(se)
            prog = (_ffat_pallas_program(comb, neutral, T_pad, B_pad)
                    if _use_pallas("WINDFLOW_PALLAS_FFAT", T_pad, B_pad)
                    else _ffat_program(comb, neutral, T_pad))
            dev = prog(vals_dev, se_dev)
        elif callable(self.kind):
            valid = np.zeros(B_pad, dtype=bool)
            valid[:B] = True
            gwids_p = np.zeros(B_pad, dtype=np.int64)
            gwids_p[:B] = gwids
            w_pad = next_pow2(int((ends - starts).max()) if B else 1)
            names = tuple(sorted(c for c in cols))
            padded = [pad_col(cols[c]) for c in names]
            prog = _custom_program(self.kind, w_pad, names)
            dev = prog(jnp.asarray(gwids_p), jnp.asarray(se[0]),
                       jnp.asarray(se[1]), jnp.asarray(valid), *padded)
        elif self.kind == "mean_panes":
            wp = next_pow2(max(int((ends - starts).max()) if B else 1, 2))
            prog = (_tile_mean_program(wp) if wp <= _TILE_MAX_W
                    else _scan_pair_program())
            dev = prog(jnp.asarray(pad_col(cols[self.value_col])),
                       jnp.asarray(pad_col(cols["count"])),
                       jnp.asarray(se))
        elif self.kind in ("max", "min"):
            fill = -np.inf if self.kind == "max" else np.inf
            n_levels = max(1, int(np.log2(T_pad)) + 1)
            prog = _sparse_table_program(self.kind, n_levels)
            dev = prog(jnp.asarray(pad_col(cols[self.value_col], fill)),
                       jnp.asarray(se))
        elif (self.kind == "sum"
              and _use_pallas("WINDFLOW_PALLAS_WINSUM", T_pad, B_pad)):
            # hand-scheduled Pallas alternative to the XLA sum paths
            # (the ComputeBatch_Kernel twin).  T_pad/B_pad are powers
            # of two >= 2048, so the lane/row alignment holds by
            # construction.
            from .pallas.window_sum import window_sums_device
            dev = window_sums_device(
                pad_col(cols[self.value_col]), se[0], se[1])[:, 0]
        else:
            wp = next_pow2(max(int((ends - starts).max()) if B else 1, 2))
            prog = (_tile_sum_program(wp)
                    if self.kind == "sum" and wp <= _TILE_MAX_W
                    else _scan_program(self.kind))
            dev = prog(jnp.asarray(pad_col(cols[self.value_col])),
                       jnp.asarray(se))
        return DeviceBatchHandle(dev, B)
