"""Device-mesh helpers for multi-chip execution.

The reference has no network backend at all -- its fabric is FastFlow
queues in one process (SURVEY.md §5 last bullet).  windflow_tpu scales
past one chip the TPU way: a ``jax.sharding.Mesh`` with named axes,
shardings annotated per array, and XLA inserting the collectives over
ICI/DCN.  Axis conventions used throughout:

* ``key``  -- key-shard axis: per-key window state is sharded by key
  hash (the Key_Farm / Key_FFAT distribution, ≈ data parallelism);
* ``win``  -- intra-window axis: one window's tuples are striped and
  partials psum-combined (the Win_MapReduce distribution, ≈
  tensor/sequence parallelism).
"""
from __future__ import annotations

from typing import Optional, Tuple


def make_mesh(n_devices: Optional[int] = None,
              axis_names: Tuple[str, str] = ("key", "win"),
              win_axis: int = 1):
    """Build a 2-D ('key', 'win') mesh over the available devices.

    ``win_axis`` chips cooperate on each window (psum over 'win'); the
    remaining devices shard the key space.
    """
    import numpy as np
    from ..ops.backend import jax_modules
    jax, _ = jax_modules()
    from jax.sharding import Mesh

    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    if n % win_axis != 0:
        raise ValueError(f"{n} devices not divisible by win_axis={win_axis}")
    arr = np.array(devices).reshape(n // win_axis, win_axis)
    return Mesh(arr, axis_names)


def make_multihost_mesh(win_axis: int = 1,
                        axis_names: Tuple[str, str] = ("key", "win")):
    """Multi-host ('key', 'win') mesh with DCN/ICI-aware layout.

    Keys are independent sub-streams (no steady-state cross-key
    traffic), so the 'key' axis is laid across hosts -- its rare
    collectives may ride DCN.  The 'win' axis carries the psum /
    all_gather / ppermute combines of WMR / PF / ring paths, so it is
    kept inside one host's slice where the collectives ride ICI
    (the scaling-book rule: bandwidth-hungry axes on ICI, between-host
    axes on DCN).

    Single-process runs fall back to ``make_mesh`` over local devices.
    Multi-host runs require ``jax.distributed.initialize()`` first (one
    process per host, standard JAX multi-host bootstrap).
    """
    from ..ops.backend import jax_modules
    jax, _ = jax_modules()

    n_procs = jax.process_count()
    if n_procs == 1:
        return make_mesh(win_axis=win_axis, axis_names=axis_names)
    import numpy as np
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    local = jax.local_device_count()
    if local % win_axis != 0:
        raise ValueError(
            f"{local} local devices not divisible by win_axis={win_axis}")
    n_slices = len({getattr(d, "slice_index", None)
                    for d in jax.devices()})
    if n_slices == n_procs:
        # hybrid mesh: first axis split across hosts (DCN), second
        # within (ICI); axis order matches (key, win).  Genuine
        # topology errors propagate -- only the no-slice-topology case
        # below uses the process-grouped layout.
        dev_mesh = mesh_utils.create_hybrid_device_mesh(
            mesh_shape=(local // win_axis, win_axis),
            dcn_mesh_shape=(n_procs, 1),
        )
    else:
        # no per-process slice topology exposed (e.g. the forced-host-
        # platform CPU backend of the 2-process DCN exercise): group
        # devices by process so every 'win' row stays inside one
        # process -- the same locality the hybrid mesh provides
        devs = sorted(jax.devices(),
                      key=lambda d: (d.process_index, d.id))
        dev_mesh = np.array(devs).reshape(-1, win_axis)
    return Mesh(dev_mesh, axis_names)


def key_sharding(mesh, rank: int = 1):
    """NamedSharding placing axis 0 on 'key' (per-key state layout)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = P("key", *([None] * (rank - 1)))
    return NamedSharding(mesh, spec)
