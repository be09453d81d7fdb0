"""Multi-chip sharded window aggregation: the distributed execution plane.

This is the TPU-native replacement for scaling strategies the reference
implements as thread farms (SURVEY.md §2.4), mapped onto a
('key', 'win') mesh:

* **Key_Farm / Key_FFAT across chips** (BASELINE config #4): per-key
  series and window state are sharded over the 'key' axis; each shard
  runs the same batched window program locally; no cross-chip traffic
  in steady state (keys are independent) -- like data parallelism.
* **Win_MapReduce across chips** (BASELINE config #5): each window's
  tuples are striped over the 'win' axis; every chip computes a stripe
  partial and the window result is a ``psum`` over 'win' riding ICI --
  like tensor/sequence parallelism.
* **Pane_Farm across chips** (BASELINE config #3): chips hold
  consecutive time-chunks; pane partials are computed locally and
  window combines read neighbour panes via ``all_gather`` over 'win' --
  the two-level blockwise reduction.

Everything is expressed with ``shard_map`` over a Mesh so XLA lowers the
collectives; the host runtime feeds per-shard batches (one WinSeqTPU
replica per shard keeps the batching protocol unchanged).
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import numpy as np

from ..ops.backend import jax_modules


@functools.lru_cache(maxsize=None)
def _sharded_programs(mesh_id: int, win_len: int, slide_len: int):
    """Build the jitted multi-chip streaming step for a given mesh.

    Returns ``step(values, starts, ends, stripe_values, pane_values)``
    computing, in one compiled program:
      1. key-sharded sliding-window sums     [K_shards, B]    (KF path)
      2. psum-combined striped window sums   [B2]             (WMR path)
      3. pane partials + gathered window combine              (PF path)
    """
    jax, jnp = jax_modules()
    from jax.sharding import PartitionSpec as P

    def shard_map(f, mesh, in_specs, out_specs):
        # check_vma off: outputs replicated via collectives (all_gather/
        # psum) that the static replication checker cannot always infer
        return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    mesh = _MESHES[mesh_id]

    def kf_shard(values, starts, ends):
        # [1, T] values, [1, B] extents on this shard
        c = jnp.concatenate([jnp.zeros((1, 1), values.dtype),
                             jnp.cumsum(values, axis=1)], axis=1)
        return jnp.take_along_axis(c, ends, axis=1) - \
            jnp.take_along_axis(c, starts, axis=1)

    def wmr_shard(stripe):
        # [K_loc, 1, B2, W_stripe]: sum own stripe then psum over 'win'
        partial = jnp.sum(stripe, axis=-1)
        return jax.lax.psum(partial, "win")

    def pf_shard(pane_vals):
        # [K_loc, 1, P_loc, pane]: local pane partials (PLQ), then the
        # full pane timeline via all_gather over 'win' (WLQ input)
        partials = jnp.sum(pane_vals, axis=-1)          # [K_loc, 1, P_loc]
        allp = jax.lax.all_gather(partials, "win", axis=1, tiled=True)
        return allp.reshape(allp.shape[0], -1)           # [K_loc, P_tot]

    kf = shard_map(kf_shard, mesh=mesh,
                   in_specs=(P("key", None), P("key", None), P("key", None)),
                   out_specs=P("key", None))

    wmr = shard_map(wmr_shard, mesh=mesh,
                    in_specs=(P("key", "win", None, None),),
                    out_specs=P("key", None, None))

    pf = shard_map(pf_shard, mesh=mesh,
                   in_specs=(P("key", "win", None, None),),
                   out_specs=P("key", None))

    @jax.jit
    def step(values, starts, ends, stripe_values, pane_values):
        kf_out = kf(values, starts, ends)
        wmr_out = wmr(stripe_values)
        pane_partials = pf(pane_values)
        # WLQ: combine panes into sliding windows on the gathered axis
        pane_len = pane_values.shape[-1]
        wpp = max(1, win_len // pane_len)   # panes per window
        spp = max(1, slide_len // pane_len)  # panes per slide
        n_windows = max(1, (pane_partials.shape[1] - wpp) // spp + 1)
        idx = (jnp.arange(n_windows)[:, None] * spp
               + jnp.arange(wpp)[None, :])
        pf_out = jnp.sum(pane_partials[:, idx], axis=-1)
        return kf_out, wmr_out, pf_out

    return step


_MESHES: Dict[int, Any] = {}


def pairwise_fold(x, combine, neutral, xp):
    """Log-depth pairwise combine tree along the LAST axis (associative
    by the FFAT contract).  ``xp`` is numpy for the host PLQ or
    jax.numpy inside a traced program -- one implementation serves both
    halves of the __host__ __device__ combine contract."""
    while x.shape[-1] > 1:
        if x.shape[-1] % 2:
            pad = xp.full(x.shape[:-1] + (1,), neutral, x.dtype)
            x = xp.concatenate([x, pad], axis=-1)
        x = xp.asarray(combine(x[..., 0::2], x[..., 1::2]))
    return x[..., 0]


def _resolve_kind(kind):
    """Normalize a mesh combine spec to (name, combine, neutral, lift).

    ``kind`` is a builtin name ('sum'/'count'/'mean'/'max'/'min') or an
    FFAT spec -- either the single-chip 3-tuple ('ffat', combine,
    neutral) that farms_tpu._ffat_kind produces (lift rides separately
    there) or the mesh 4-tuple ('ffat', lift, combine, neutral) with a
    columnar lift.  The combine must work on numpy scalars AND jnp
    arrays -- the mesh twin of the reference's __host__ __device__
    combine contract (flatfat_gpu.hpp:68-82)."""
    if isinstance(kind, tuple) and kind and kind[0] == "ffat":
        if len(kind) == 4:
            _, lift, combine, neutral = kind
        elif len(kind) == 3:
            lift, (_, combine, neutral) = None, kind
        else:
            raise ValueError(
                "FFAT mesh kind must be ('ffat', combine, neutral) or "
                "('ffat', lift, combine, neutral)")
        return "ffat", combine, float(neutral), lift
    if kind == "max":
        _, jnp = jax_modules()
        return "max", jnp.maximum, float("-inf"), None
    if kind == "min":
        _, jnp = jax_modules()
        return "min", jnp.minimum, float("inf"), None
    if kind in ("sum", "count", "mean"):
        return kind, None, 0.0, None
    raise ValueError(f"unknown mesh window kind: {kind!r}")


class ShardedWindowEngine:
    """Key-sharded multi-chip window engine (the distributed twin of
    WindowComputeEngine).  Holds the mesh; each call runs the full
    sharded step (KF + WMR + PF paths) as one XLA program with
    collectives over ICI.

    ``kind`` selects the combine (see _resolve_kind): invertible kinds
    run prefix-scan differencing per shard; max/min and FFAT
    lift+combine build a per-shard device FlatFAT and answer every
    extent with a range query (the key_farm_gpu.hpp arbitrary-functor
    surface at mesh scale)."""

    def __init__(self, mesh, win_len: int, slide_len: int, kind="sum"):
        self.mesh = mesh
        self.win_len = win_len
        self.slide_len = slide_len
        self.kind, self.combine, self.neutral, self.lift = \
            _resolve_kind(kind)
        mesh_id = id(mesh)
        _MESHES[mesh_id] = mesh
        self._step = _sharded_programs(mesh_id, win_len, slide_len)

    @property
    def n_key_shards(self) -> int:
        return self.mesh.shape["key"]

    @property
    def n_win_shards(self) -> int:
        return self.mesh.shape["win"]

    def step(self, values, starts, ends, stripe_values, pane_values):
        """One sharded streaming step; see _sharded_programs."""
        return self._step(values, starts, ends, stripe_values, pane_values)

    def compute_pf_ring(self, pane_values, pane_len: int):
        """Ring sequence-parallel pane combine: the ppermute alternative
        to the all_gather PF path for long timelines.

        The pane timeline is sharded in consecutive chunks over 'win'
        (chip w holds panes [w*P_loc, (w+1)*P_loc)).  Sliding windows
        starting in a chip's chunk need at most ``wpp - 1`` panes from
        its right neighbours, fetched with ``hops`` one-step neighbour
        ``ppermute``s -- O(hops * P_loc) ICI traffic per chip instead of
        the all_gather's O(P_total), the ring-attention communication
        pattern applied to the window axis.  Windows overrunning the
        global timeline end are masked to the combine's neutral (0).

        pane_values: [K, W_shards * P_loc, pane_len] sharded
        ('key', 'win') on axis 0/1.  Returns [K, W_shards * P_loc // spp]
        window sums, 'key'-sharded, windows in global time order.
        """
        jax, jnp = jax_modules()
        from jax.sharding import NamedSharding, PartitionSpec as P

        wpp = max(1, self.win_len // pane_len)    # panes per window
        spp = max(1, self.slide_len // pane_len)  # panes per slide
        W = self.n_win_shards
        p_total = pane_values.shape[1]
        p_loc = p_total // W
        if p_loc % spp:
            raise ValueError(
                f"panes per shard ({p_loc}) must be a multiple of the "
                f"slide ({spp} panes) for the ring layout")
        hops = min(W - 1, -(-(wpp - 1) // p_loc))  # ceil, capped at ring
        n_loc_wins = p_loc // spp

        if self.kind == "mean":
            raise ValueError("PaneFarmMesh does not support 'mean' "
                             "(pane partials are not mean-decomposable "
                             "without a count channel)")
        key = (id(self.mesh), wpp, spp, W, p_loc, pane_len, self.kind)
        if getattr(self, "_ring_key", None) != key:
            perm = [(i, (i - 1) % W) for i in range(W)]
            kind, comb = self.kind, self.combine

            neutral = self.neutral

            def fold(x, axis):
                # combine-fold along one axis: one-op reductions for the
                # builtins; a log-depth pairwise tree for a custom FFAT
                # combine (associative by contract) so a wide window
                # extent costs O(log w) HLO ops, not a serial chain
                if kind in ("sum", "count"):
                    return jnp.sum(x, axis=axis)
                if kind == "max":
                    return jnp.max(x, axis=axis)
                if kind == "min":
                    return jnp.min(x, axis=axis)
                return pairwise_fold(jnp.moveaxis(x, axis, -1), comb,
                                     neutral, jnp)

            def ring_shard(pane_vals):
                # [K, P_loc, pane_len] per shard
                partials = fold(pane_vals, -1)             # [K, P_loc]
                blocks = [partials]
                cur = partials
                for _ in range(hops):
                    # chip w receives chip (w+1)'s block: one ring hop
                    cur = jax.lax.ppermute(cur, "win", perm)
                    blocks.append(cur)
                ext = jnp.concatenate(blocks, axis=-1)
                starts_l = jnp.arange(n_loc_wins) * spp
                idx = starts_l[:, None] + jnp.arange(wpp)[None, :]
                # clamp only protects windows masked below (for every
                # valid window g_start + wpp <= p_total implies the
                # extent fits inside ext)
                idx = jnp.minimum(idx, ext.shape[-1] - 1)
                wins = fold(ext[:, idx], -1)               # [K, n_loc]
                # mask windows whose extent passes the global end (their
                # ring reads wrapped around to chip 0)
                w_id = jax.lax.axis_index("win")
                g_start = w_id * p_loc + starts_l
                ok = g_start + wpp <= p_total
                return jnp.where(ok[None, :], wins, 0.0)

            self._ring = jax.jit(jax.shard_map(
                ring_shard, mesh=self.mesh,
                in_specs=(P("key", "win", None),),
                out_specs=P("key", "win"), check_vma=False))
            self._ring_key = key
        sh = NamedSharding(self.mesh, P("key", "win", None))
        return self._ring(jax.device_put(pane_values, sh))

    def compute_wmr(self, stripes):
        """Striped window combines over 'win' (the Win_MapReduce
        distribution as a standalone program, used by
        operators.tpu.wmr_mesh.WinMapReduceMesh).

        ``stripes``: [K_rows, W_shards, B, stripe_len] — window b of row
        k holds its tuples round-robin striped over the 'win' axis
        (WinMap_Emitter's per-key round robin, wm_nodes.hpp:62), padded
        with the combine's neutral.  Each chip folds its stripe locally
        (the MAP stage); the cross-stripe REDUCE rides ICI as a psum /
        pmax / pmin for the builtins, or an all_gather + log-depth
        pairwise combine for a custom FFAT fold.  Returns [K_rows, B]
        full window results."""
        jax, jnp = jax_modules()
        from jax.sharding import NamedSharding, PartitionSpec as P

        if self.kind == "mean":
            raise ValueError("WinMapReduceMesh does not support 'mean' "
                             "(stripe partials carry no count channel)")
        if not hasattr(self, "_wmr_only"):
            kind, comb, neutral = self.kind, self.combine, self.neutral

            def wmr_shard(stripe):
                # [K_loc, 1, B, stripe_len] on this chip
                if kind in ("sum", "count"):
                    return jax.lax.psum(jnp.sum(stripe, axis=-1), "win")
                if kind == "max":
                    return jax.lax.pmax(jnp.max(stripe, axis=-1), "win")
                if kind == "min":
                    return jax.lax.pmin(jnp.min(stripe, axis=-1), "win")
                partial = pairwise_fold(stripe, comb, neutral, jnp)
                allp = jax.lax.all_gather(partial, "win", axis=1,
                                          tiled=True)     # [K_loc, W, B]
                out = pairwise_fold(jnp.moveaxis(allp, 1, -1), comb,
                                    neutral, jnp)          # [K_loc, B]
                return out[:, None, :]

            self._wmr_only = jax.jit(jax.shard_map(
                wmr_shard, mesh=self.mesh,
                in_specs=(P("key", "win", None, None),),
                out_specs=P("key", None, None), check_vma=False))
        sh = NamedSharding(self.mesh, P("key", "win", None, None))
        out = self._wmr_only(jax.device_put(stripes, sh))
        return out[:, 0, :]

    def compute_kf(self, values, starts, ends):
        """Key-sharded window combines (the Key_Farm-across-chips path
        used by operators.tpu.mesh_farm).  ``values`` is [K_shards, T]
        (T a power of two), extents are [K_shards, B]; everything
        sharded over 'key'."""
        jax, jnp = jax_modules()
        from jax.sharding import NamedSharding, PartitionSpec as P

        if not hasattr(self, "_kf_only"):
            kind, comb, neutral = self.kind, self.combine, self.neutral

            def kf_shard(v, s, e):
                if kind == "count":
                    return (e - s).astype(v.dtype)
                if kind in ("sum", "mean"):
                    c = jnp.concatenate([jnp.zeros((1, 1), v.dtype),
                                         jnp.cumsum(v, axis=1)], axis=1)
                    out = jnp.take_along_axis(c, e, axis=1) - \
                        jnp.take_along_axis(c, s, axis=1)
                    if kind == "mean":
                        out = out / jnp.maximum(e - s, 1)
                    return out
                # max/min/ffat: per-row device FlatFAT + range queries
                from ..ops.flatfat_jax import _programs
                build, _upd, query = _programs(comb, neutral, v.shape[1])

                def one(row, ss, ee):
                    return query(build(row), ss, ee, ee > ss)

                out = jax.vmap(one)(v, s, e)
                return jnp.where(e > s, out, 0)

            self._kf_only = jax.jit(jax.shard_map(
                kf_shard, mesh=self.mesh,
                in_specs=(P("key", None), P("key", None), P("key", None)),
                out_specs=P("key", None), check_vma=False))
        sh = NamedSharding(self.mesh, P("key", None))
        return self._kf_only(jax.device_put(values, sh),
                             jax.device_put(starts, sh),
                             jax.device_put(ends, sh))

    def example_inputs(self, T: int = 64, B: int = 8, keys_per_shard: int = 2,
                       stripe_w: int = 8, panes_per_shard: int = 4,
                       pane_len: int = 4):
        """Tiny correctly-sharded inputs for compile checks/dry runs."""
        jax, _ = jax_modules()
        from jax.sharding import NamedSharding, PartitionSpec as P

        K = self.n_key_shards
        W = self.n_win_shards
        rng = np.random.default_rng(0)
        values = rng.normal(size=(K, T)).astype(np.float32)
        starts = np.tile(np.arange(B, dtype=np.int32) * 4, (K, 1))
        ends = starts + np.int32(self.win_len)
        stripe = rng.normal(
            size=(K * keys_per_shard, W, B, stripe_w)).astype(np.float32)
        pane = rng.normal(
            size=(K * keys_per_shard, W, panes_per_shard,
                  pane_len)).astype(np.float32)
        dev = lambda x, spec: jax.device_put(
            x, NamedSharding(self.mesh, spec))
        return (dev(values, P("key", None)),
                dev(starts, P("key", None)),
                dev(ends, P("key", None)),
                dev(stripe, P("key", "win", None, None)),
                dev(pane, P("key", "win", None, None)))
