"""Device-resident FlatFAT: the XLA twin of the GPU aggregator tree.

Re-design of reference ``wf/flatfat_gpu.hpp`` (461 LoC, CUDA): the tree
lives in device memory (HBM here); its three kernels map to three jitted
programs:

* ``InitTreeLevel_Kernel``/host ``build`` (:53-64, :275-333)  -> `build`
  (level-wise strided combine, lax-unrolled over log2(n) levels);
* ``UpdateTreeLevel_Kernel`` (:68-82) -> `update` (scatter new leaves,
  recompute each level vectorized);
* ``ComputeResults_Kernel`` (:92-135, per-window bit-trick range
  decomposition) -> `query_ranges` (vectorized segment-tree fold over
  all windows at once, preserving left-to-right combine order for
  non-commutative functions).

The tree is a flat [2n] array in heap layout (root at 1, leaves at
[n, 2n)), functional-in/functional-out as XLA wants; the host engine
keeps the current tree array between batches (the device-resident state
of the reference).
"""
from __future__ import annotations

import functools
from typing import Callable

import numpy as np

from .backend import jax_modules


@functools.lru_cache(maxsize=None)
def _programs(combine: Callable, neutral: float, n: int):
    jax, jnp = jax_modules()

    levels = int(np.log2(n))
    assert 1 << levels == n, "FlatFAT capacity must be a power of two"

    @jax.jit
    def build(leaves):  # leaves: [n]
        tree = jnp.full((2 * n,), neutral, leaves.dtype)
        tree = tree.at[n:].set(leaves)
        for j in range(levels - 1, -1, -1):  # level j holds 2^j nodes
            lo, hi = 1 << j, 1 << (j + 1)
            children = tree[2 * lo: 2 * hi]
            combined = combine(children[0::2], children[1::2])
            tree = jax.lax.dynamic_update_slice(tree, combined, (lo,))
        return tree

    @jax.jit
    def update(tree, positions, values, valid):
        """Scatter new leaf values then recompute every level (the
        reference updates only touched subtrees per level; recomputing
        whole levels is the vectorized TPU-shaped equivalent)."""
        safe_pos = jnp.where(valid, positions + n, 0)
        tree = tree.at[safe_pos].set(
            jnp.where(valid, values, tree[safe_pos]))
        for j in range(levels - 1, -1, -1):
            lo, hi = 1 << j, 1 << (j + 1)
            children = tree[2 * lo: 2 * hi]
            combined = combine(children[0::2], children[1::2])
            tree = jax.lax.dynamic_update_slice(tree, combined, (lo,))
        return tree

    @jax.jit
    def query_ranges(tree, starts, ends, valid):
        """Fold leaves [start, end) per window, O(log n) steps for all
        windows at once; left/right partial accumulators keep the
        combine order oldest->newest."""
        lo = starts + n
        hi = ends + n
        left = jnp.full(starts.shape, neutral, tree.dtype)
        right = jnp.full(starts.shape, neutral, tree.dtype)
        for _ in range(levels + 1):
            take_l = (lo < hi) & (lo & 1).astype(bool)
            left = jnp.where(take_l, combine(left, tree[lo]), left)
            lo = jnp.where(take_l, lo + 1, lo)
            take_r = (lo < hi) & (hi & 1).astype(bool)
            hi_idx = jnp.where(take_r, hi - 1, hi)
            right = jnp.where(take_r, combine(tree[hi_idx], right), right)
            hi = hi_idx
            lo = lo >> 1
            hi = hi >> 1
        out = combine(left, right)
        return jnp.where(valid, out, neutral)

    return build, update, query_ranges


@functools.lru_cache(maxsize=None)
def _batched_programs(combine: Callable, neutral: float, n: int):
    """Key-batched device-resident trees [K, 2n]: the incremental
    (rebuild=false) mode of the reference, where the aggregator tree
    stays on the device between batches and only touched paths are
    recomputed (UpdateTreeLevel_Kernel, flatfat_gpu.hpp:68-82) --
    vectorized here as log n scatter rounds over the update batch."""
    jax, jnp = jax_modules()

    levels = int(np.log2(n))
    assert 1 << levels == n, "FlatFAT capacity must be a power of two"

    # The resident tree is DONATED (donate_argnums): the forest lives
    # in HBM across the stream's lifetime, every update returns its
    # successor, and donation lets XLA reuse the buffer in place --
    # the double-buffered carry of the reference's rebuild=false mode
    # (win_seqffat_gpu.hpp:150) without a second tree's footprint.
    # The CPU backend deletes a donated argument just as the TPU does,
    # so tests run the aliasing the chip will.

    # the level sweeps are lax.fori_loop, not Python-unrolled: every
    # iteration carries fixed shapes, and unrolling 2 x levels rounds
    # of gather/scatter made the fused program's XLA compile scale
    # with log(capacity) (tens of seconds on the CPU test backend for
    # a 2^13-leaf forest); the rolled loop compiles in O(1)

    def _update_body(tree, keys, positions, values, valid):
        """Scatter new leaves at (key, pos) then recompute ONLY the
        touched root paths: O(B log n) work independent of K and n.
        Duplicate parents scatter identical recomputed values, so
        in-batch collisions are benign."""
        safe_k = jnp.where(valid, keys, 0)
        # invalid lanes write heap slot 0 -- never read (root lives at
        # 1) and never a valid target, so duplicate-index scatters
        # cannot clobber a real update with a stale value
        idx = jnp.where(valid, positions + n, 0)
        tree = tree.at[safe_k, idx].set(
            jnp.where(valid, values, tree[safe_k, idx]))

        def level(_j, carry):
            tree, idx = carry
            parent = idx >> 1
            left = tree[safe_k, 2 * parent]
            right = tree[safe_k, 2 * parent + 1]
            tree = tree.at[safe_k, parent].set(
                jnp.where(valid, combine(left, right),
                          tree[safe_k, parent]))
            return tree, parent

        tree, _ = jax.lax.fori_loop(0, levels, level, (tree, idx))
        return tree

    update_sparse = functools.partial(jax.jit, donate_argnums=(0,))(
        _update_body)

    def _query_body(tree, keys, starts, ends, valid):
        """Per-window fold over leaf ring positions [start, end) of each
        window's key tree; same bit-walk as the single-tree query."""
        safe_k = jnp.where(valid, keys, 0)
        neutral_col = jnp.full(starts.shape, neutral, tree.dtype)

        def step(_j, carry):
            lo, hi, left, right = carry
            take_l = (lo < hi) & (lo & 1).astype(bool)
            left = jnp.where(take_l, combine(left, tree[safe_k, lo]),
                             left)
            lo = jnp.where(take_l, lo + 1, lo)
            take_r = (lo < hi) & (hi & 1).astype(bool)
            hi_idx = jnp.where(take_r, hi - 1, hi)
            right = jnp.where(take_r,
                              combine(tree[safe_k, hi_idx], right),
                              right)
            return lo >> 1, hi_idx >> 1, left, right

        _lo, _hi, left, right = jax.lax.fori_loop(
            0, levels + 1, step,
            (starts + n, ends + n, neutral_col, neutral_col))
        out = combine(left, right)
        return jnp.where(valid, out, neutral)

    query_ranges = jax.jit(_query_body)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def update_and_query(tree, keys, positions, values, valid,
                         q_keys, q_starts, q_ends, q_valid):
        """The fused per-launch program of the resident lane: scatter
        the chunk's new leaves, recompute their root paths, then answer
        every due window against the POST-update tree -- decode ->
        fold -> trigger in ONE launch, so a launch ships only new
        values in and fired results out, never the resident state."""
        tree = _update_body(tree, keys, positions, values, valid)
        out = _query_body(tree, q_keys, q_starts, q_ends, q_valid)
        return tree, out

    @functools.partial(jax.jit, donate_argnums=(0,))
    def update_runs_and_query(tree, run_rows, run_starts, run_lens,
                              values, q_keys, q_starts, q_ends,
                              q_valid):
        """Run-descriptor form of the fused program: new leaves always
        land at CONSECUTIVE ring positions per key (arrival order /
        pane order), so a launch ships only the values plus
        (row, start, len) triples -- positions are expanded ON DEVICE
        (12 bytes per run instead of 8 per leaf)."""
        cum = jnp.cumsum(run_lens)
        v = jnp.arange(values.shape[0], dtype=jnp.int32)
        r = jnp.minimum(jnp.searchsorted(cum, v, side="right"),
                        run_lens.shape[0] - 1)
        base = cum[r] - run_lens[r]
        pos = (run_starts[r] + (v - base)) % n
        keys = run_rows[r]
        valid = v < cum[-1]
        tree = _update_body(tree, keys, pos, values, valid)
        out = _query_body(tree, q_keys, q_starts, q_ends, q_valid)
        return tree, out

    return (update_sparse, query_ranges, update_and_query,
            update_runs_and_query)


class BatchedFlatFAT:
    """Device-resident per-key FlatFAT forest (the ``rebuild=false``
    incremental mode of Win_SeqFFAT_GPU).

    One [K, 2n] array holds every key's aggregator tree in HBM across
    batches; leaves form a circular buffer over each key's series
    (leaf position = id % n, the reference's circular level update),
    so capacity ``n_leaves`` must cover the window span.  Updates touch
    only the modified root paths; range queries that wrap the ring are
    answered in two ordered pieces to preserve non-commutative combine
    order (oldest -> newest)."""

    def __init__(self, combine: Callable, neutral: float, n_keys: int,
                 n_leaves: int, dtype=np.float32):
        n = 1
        while n < max(2, n_leaves):
            n <<= 1
        self.n = n
        self.n_keys = n_keys
        self.neutral = neutral
        self.combine = combine
        (self._update, self._query, self._update_query,
         self._update_runs_query) = _batched_programs(combine, neutral,
                                                      n)
        _, jnp = jax_modules()
        self.tree = jnp.full((n_keys, 2 * n), neutral, dtype)
        # leaves [n, 2n) start as neutral; internal nodes of a
        # neutral-filled tree are neutral (monoid identity), so no
        # build pass is needed

    @property
    def state_bytes(self) -> int:
        """Resident footprint of the forest in device memory (the
        ``Device_state_bytes_resident`` gauge)."""
        try:
            return int(self.tree.nbytes)
        except Exception:
            return 0

    def update(self, keys, ids, values) -> None:
        """Insert values at ring positions ids % n for their keys."""
        _, jnp = jax_modules()
        keys = np.asarray(keys)
        b = 1
        while b < max(512, len(keys)):  # floored bucket (see above)
            b <<= 1
        k = np.zeros(b, np.int32)
        p = np.zeros(b, np.int32)
        v = np.full(b, self.neutral, np.float32)
        ok = np.zeros(b, bool)
        k[: len(keys)] = keys
        p[: len(keys)] = np.asarray(ids) % self.n
        v[: len(keys)] = values
        ok[: len(keys)] = True
        self.tree = self._update(self.tree, jnp.asarray(k), jnp.asarray(p),
                                 jnp.asarray(v), jnp.asarray(ok))

    def _pack_queries(self, keys, starts, ends):
        """Pad query extents to a pow2 bucket with ring-wrap handling:
        a wrapping range [s, e) is answered as two ordered pieces
        ([s, n) then [0, e mod n)) so non-commutative combines keep
        oldest -> newest order.  Returns (k2, s2, e2, ok, wraps, B)."""
        keys = np.asarray(keys, np.int64)
        starts = np.asarray(starts, np.int64)
        ends = np.asarray(ends, np.int64)
        if np.any(ends - starts > self.n):
            raise ValueError("window extent exceeds tree capacity")
        s = starts % self.n
        e_raw = ends % self.n
        wraps = (ends > starts) & (e_raw <= s)
        B = len(keys)
        b = 1
        while b < max(256, 2 * B):  # floored bucket: few compiles
            b <<= 1
        k2 = np.zeros(b, np.int32)
        s2 = np.zeros(b, np.int32)
        e2 = np.zeros(b, np.int32)
        ok = np.zeros(b, bool)
        # piece 1: [s, wrap ? n : e_raw)
        k2[:B] = keys
        s2[:B] = s
        e2[:B] = np.where(wraps, self.n, e_raw)
        ok[:B] = ends > starts
        # piece 2 (wrapping only): [0, e_raw)
        k2[B:2 * B] = keys
        s2[B:2 * B] = 0
        e2[B:2 * B] = np.where(wraps, e_raw, 0)
        ok[B:2 * B] = wraps
        return k2, s2, e2, ok, wraps, B

    def _combine_pieces(self, out: np.ndarray, wraps: np.ndarray,
                        B: int) -> np.ndarray:
        _, jnp = jax_modules()
        head, tail = out[:B], out[B:2 * B]
        if not wraps.any():
            return head
        combined = np.asarray(self.combine(jnp.asarray(head),
                                           jnp.asarray(tail)))
        return np.where(wraps, combined, head)

    def update_query_launch(self, keys, ids, values, q_keys, q_starts,
                            q_ends):
        """Fused scatter + root-path recompute + range query in ONE
        jitted launch against the donated resident tree (the
        decode -> fold -> trigger program of the resident lane).
        Returns ``(dev_out, wraps, B)``: the un-blocked device result
        (2B wrap pieces) for async dispatch plus what
        :meth:`finish_query` needs to resolve it on host."""
        _, jnp = jax_modules()
        keys = np.asarray(keys)
        # floor the update bucket: padding is cheap device work, and
        # collapsing the distinct pad shapes to a handful means
        # steady-state launches never hit a mid-stream XLA compile
        b = 1
        while b < max(512, len(keys)):
            b <<= 1
        k = np.zeros(b, np.int32)
        p = np.zeros(b, np.int32)
        v = np.full(b, self.neutral, np.float32)
        ok = np.zeros(b, bool)
        k[: len(keys)] = keys
        p[: len(keys)] = np.asarray(ids) % self.n
        v[: len(keys)] = values
        ok[: len(keys)] = True
        k2, s2, e2, qok, wraps, B = self._pack_queries(q_keys, q_starts,
                                                       q_ends)
        self.tree, out = self._update_query(
            self.tree, jnp.asarray(k), jnp.asarray(p), jnp.asarray(v),
            jnp.asarray(ok), jnp.asarray(k2), jnp.asarray(s2),
            jnp.asarray(e2), jnp.asarray(qok))
        return out, wraps, B

    def update_runs_query_launch(self, rows, starts, lens, values,
                                 q_keys, q_starts, q_ends):
        """Run-descriptor form of :meth:`update_query_launch`: each
        (rows[i], starts[i], lens[i]) names a CONSECUTIVE run of new
        leaves for one key; positions expand on device, so the launch
        ships values + 12 bytes per run instead of 8 bytes per leaf.
        ``starts`` may be absolute ids (pre-reduced mod n on host, so
        int32 device arithmetic can never overflow)."""
        _, jnp = jax_modules()
        rows = np.asarray(rows, np.int64)
        lens = np.asarray(lens, np.int64)
        total = int(lens.sum())
        R = len(rows)
        rb = 1
        while rb < max(8, R):  # floored run bucket
            rb <<= 1
        rr = np.zeros(rb, np.int32)
        rs = np.zeros(rb, np.int32)
        rl = np.zeros(rb, np.int32)
        rr[:R] = rows
        rs[:R] = np.asarray(starts, np.int64) % self.n
        rl[:R] = lens
        vb = 1
        while vb < max(512, total):  # floored value bucket
            vb <<= 1
        v = np.full(vb, self.neutral, np.float32)
        v[:total] = values
        k2, s2, e2, qok, wraps, B = self._pack_queries(q_keys, q_starts,
                                                       q_ends)
        self.tree, out = self._update_runs_query(
            self.tree, jnp.asarray(rr), jnp.asarray(rs),
            jnp.asarray(rl), jnp.asarray(v), jnp.asarray(k2),
            jnp.asarray(s2), jnp.asarray(e2), jnp.asarray(qok))
        return out, wraps, B

    def update_runs_query(self, rows, starts, lens, values, q_keys,
                          q_starts, q_ends) -> np.ndarray:
        """Blocking form of :meth:`update_runs_query_launch`."""
        dev, wraps, B = self.update_runs_query_launch(
            rows, starts, lens, values, q_keys, q_starts, q_ends)
        return self.finish_query(dev, wraps, B)

    def finish_query(self, dev_out, wraps, B) -> np.ndarray:
        """Materialize one launch's query results on host (ring-wrap
        pieces combined in time order)."""
        return self._combine_pieces(np.asarray(dev_out), wraps, B)

    def update_query(self, keys, ids, values, q_keys, q_starts,
                     q_ends) -> np.ndarray:
        """Blocking form of :meth:`update_query_launch`."""
        dev, wraps, B = self.update_query_launch(keys, ids, values,
                                                 q_keys, q_starts, q_ends)
        return self.finish_query(dev, wraps, B)

    def query(self, keys, starts, ends) -> np.ndarray:
        """Window results for extents [starts, ends) in id space (end -
        start <= n); wrapping ranges are combined as (tail, head) to
        keep time order."""
        _, jnp = jax_modules()
        k2, s2, e2, ok, wraps, B = self._pack_queries(keys, starts, ends)
        out = np.asarray(self._query(self.tree, jnp.asarray(k2),
                                     jnp.asarray(s2), jnp.asarray(e2),
                                     jnp.asarray(ok)))
        return self._combine_pieces(out, wraps, B)


class FlatFATJax:
    """Stateful host wrapper owning the device tree array.

    ``combine`` must form a monoid with identity ``neutral`` (the
    query seeds its left/right accumulators with ``neutral``); it need
    not be commutative -- fold order is preserved oldest->newest."""

    def __init__(self, combine: Callable, neutral: float, n_leaves: int,
                 dtype=np.float32):
        n = 1
        while n < max(2, n_leaves):
            n <<= 1
        self.n = n
        self.neutral = neutral
        self.dtype = dtype
        self._build, self._update, self._query = _programs(
            combine, neutral, n)
        _, jnp = jax_modules()
        self.tree = self._build(jnp.full((n,), neutral, dtype))

    def build(self, leaves: np.ndarray) -> None:
        _, jnp = jax_modules()
        padded = np.full(self.n, self.neutral, self.dtype)
        padded[: len(leaves)] = leaves
        self.tree = self._build(jnp.asarray(padded))

    def update(self, positions: np.ndarray, values: np.ndarray) -> None:
        _, jnp = jax_modules()
        b = next_pow2 = 1
        while next_pow2 < max(1, len(positions)):
            next_pow2 <<= 1
        pos = np.zeros(next_pow2, np.int32)
        val = np.full(next_pow2, self.neutral, self.dtype)
        ok = np.zeros(next_pow2, bool)
        pos[: len(positions)] = positions
        val[: len(values)] = values
        ok[: len(positions)] = True
        self.tree = self._update(self.tree, jnp.asarray(pos),
                                 jnp.asarray(val), jnp.asarray(ok))

    def query_ranges(self, starts: np.ndarray,
                     ends: np.ndarray) -> np.ndarray:
        _, jnp = jax_modules()
        b = 1
        while b < max(1, len(starts)):
            b <<= 1
        s = np.zeros(b, np.int32)
        e = np.zeros(b, np.int32)
        ok = np.zeros(b, bool)
        s[: len(starts)] = starts
        e[: len(ends)] = ends
        ok[: len(starts)] = True
        out = self._query(self.tree, jnp.asarray(s), jnp.asarray(e),
                          jnp.asarray(ok))
        return np.asarray(out)[: len(starts)]
