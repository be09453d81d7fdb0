"""ops/pallas/row_gather.py: a block of window rows gathered by DMA.

The kernel runs interpreted here (the CPU backend) and must give the
vmapped ``lax.dynamic_slice`` tile bit for bit, beyond each window's end
too; the engine takes it on the TPU alone, at widths of whole lane-rows;
a user's function over the kernel's tile gives what it gives over the
slices; and the kernel compiles for a TPU v5e at the widths the engine
hands it, with no chip (a described topology)."""
import functools
import os

import numpy as np
import pytest

from windflow_tpu.ops import window_compute
from windflow_tpu.ops.backend import jax_modules
from windflow_tpu.ops.pallas import row_gather
from windflow_tpu.ops.window_compute import WindowComputeEngine

LANES = row_gather.LANES


def column(t_pad, seed=0):
    """float32 of random bits (NaNs and subnormals among them), one
    lane-row beyond ``t_pad`` as the program's view of a column has."""
    bits = np.random.default_rng(seed).integers(
        0, 2 ** 32, t_pad + LANES, dtype=np.uint64).astype(np.uint32)
    return bits.view(np.float32)


def starts_for(case, t_pad, w_pad, n, seed=1):
    rng = np.random.default_rng(seed)
    last = t_pad - w_pad
    if case == "zero":
        s = np.zeros(n)
    elif case == "odd":
        s = np.sort(rng.integers(0, last // 2, n)) * 2 + 1
    elif case == "end":
        s = np.sort(rng.integers(0, last + 1, n))
        s[-n // 4:] = last
    elif case == "overlapping":
        # a plug's windows slide one element; some repeat a start
        s = np.concatenate([p * (w_pad // 3) + np.arange(n // 4)
                            for p in range(4)])
        s[1::5] = s[::5][:len(s[1::5])]
    else:   # partly filled: the rows behind the launch start at 0
        s = np.zeros(n)
        s[:n // 3] = np.sort(rng.integers(0, last + 1, n // 3))
    assert s.max() <= last
    return s.astype(np.int32)


def sliced(col, starts, w_pad, t_pad):
    jax, jnp = jax_modules()
    c = jnp.asarray(col[:t_pad])
    return np.asarray(jax.vmap(
        lambda s: jax.lax.dynamic_slice(c, (s,), (w_pad,)))(
            jnp.asarray(starts)))


@functools.lru_cache(maxsize=None)
def interpreted(w_pad):
    """The kernel jitted once a width, the cases of a width sharing it."""
    jax, _ = jax_modules()
    return jax.jit(lambda s, c: row_gather.row_tiles(c, s, w_pad,
                                                     interpret=True))


@pytest.mark.parametrize("case", ["zero", "odd", "end", "overlapping",
                                  "partly_filled"])
@pytest.mark.parametrize("w_pad", [128, 512, 4096])
def test_the_tile_is_the_slices_bit_for_bit(w_pad, case):
    _, jnp = jax_modules()
    t_pad = 4 * w_pad
    n = 2 * row_gather.rows_a_step(w_pad)
    col, starts = column(t_pad), starts_for(case, t_pad, w_pad, n)
    got = np.asarray(interpreted(w_pad)(jnp.asarray(starts),
                                        jnp.asarray(col)))
    assert got.shape == (n, w_pad) and got.dtype == np.float32
    assert (got.view(np.uint32)
            == sliced(col, starts, w_pad, t_pad).view(np.uint32)).all()


@pytest.mark.parametrize("backend,w_pad,engages", [
    ("tpu", 128, True), ("tpu", 4096, True), ("tpu", row_gather.MAX_W, True),
    ("tpu", 64, False), ("tpu", 2 * row_gather.MAX_W, False),
    ("cpu", 4096, False), ("gpu", 4096, False)])
def test_the_kernel_runs_on_the_tpu_at_whole_lane_rows(backend, w_pad,
                                                       engages):
    assert row_gather.engages(backend, w_pad) is engages


@pytest.mark.parametrize("w_pad", [128, 4096, row_gather.MAX_W])
def test_a_step_of_rows_fits_its_vmem(w_pad):
    R = row_gather.rows_a_step(w_pad)
    assert R % 8 == 0 and 8 <= R <= 64
    # the output block twice over and the fetched lane-rows once
    assert 2 * R * w_pad * 4 + R * (w_pad + 8 * LANES) * 4 <= 4 << 20


# -- through the engine ------------------------------------------------------

def median(gwid, cols, mask):
    """A user's function of two columns: the median of the window's
    loads, less that of its second column."""
    _, jnp = jax_modules()
    n = mask.sum()

    def mid(v):
        v = jnp.sort(jnp.where(mask, v, jnp.inf))
        return (v[(n - 1) // 2] + v[n // 2]) / 2
    return (mid(cols["value"]) - mid(cols["other"])).astype(jnp.float32)


def median_launch(n_keys=9, per_key=33, width=300, seed=3):
    """A launch as a plug's series give it: each key's windows slide one
    element over its own series, ascending by key."""
    rng = np.random.default_rng(seed)
    T = n_keys * (width + per_key)
    cols = {"value": rng.integers(0, 3000, T).astype(np.float64),
            "other": rng.integers(0, 50, T).astype(np.float64)}
    starts = np.concatenate([k * (width + per_key) + np.arange(per_key)
                             for k in range(n_keys)])
    ends = starts + width - rng.integers(0, 40, len(starts))
    return cols, starts, ends


@pytest.mark.parametrize("dma", [False, True])
def test_a_users_function_reads_the_same_tile_either_way(monkeypatch, dma):
    cols, starts, ends = median_launch()
    want = np.array([np.median(cols["value"][s:e])
                     - np.median(cols["other"][s:e])
                     for s, e in zip(starts, ends)], np.float32)
    # blocks of 128 rows of 512: the launch's 297 rows fill two and a
    # part of a third
    monkeypatch.setattr(window_compute, "_FN_BLOCK_ELEMS", 128 * 512)
    monkeypatch.setattr(row_gather, "engages", lambda backend, w: dma)
    handle = WindowComputeEngine(median).compute(
        cols, starts, ends, np.arange(len(starts)))
    assert handle.fn_rows_dma == (len(starts) if dma else 0)
    assert (handle.block() == want).all()


def test_the_cpu_backend_slices_every_row():
    cols, starts, ends = median_launch(n_keys=3)
    handle = WindowComputeEngine(median).compute(
        cols, starts, ends, np.arange(len(starts)))
    assert handle.fn_rows_dma == 0 and handle.fn_elems > 0


# -- compiled for the chip, with no chip -------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("w_pad,t_pad", [
    (4096, 1 << 23),     # the median cell's: 3,600 readings a window
    (128, 1 << 20),      # the narrowest, a block of 131,072 rows
    (row_gather.MAX_W, 1 << 23)])
def test_the_kernel_compiles_for_a_v5e(one_chip, w_pad, t_pad):
    jax, jnp = jax_modules()
    _, block = window_compute.fn_rows(1 << 18, w_pad)

    def gather(starts, col):
        return row_gather.row_tiles(col, starts, w_pad, interpret=False)
    compiled = jax.jit(gather).lower(
        jax.ShapeDtypeStruct((block,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((t_pad + LANES,), jnp.float32,
                             sharding=one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_the_operator_carries_the_count_to_its_launch_records(monkeypatch):
    """A stream through the window operator: the same rows whichever path
    gathers the tile, and each launch record (and its stats row) counts
    the rows the kernel gathered."""
    from windflow_tpu.core.basic import WinType
    from windflow_tpu.core.tuples import TupleBatch
    from windflow_tpu.operators.tpu.win_seq_tpu import WinSeqTPULogic

    _, jnp = jax_modules()

    def one_column(gwid, cols, mask):
        return median(gwid, {"value": cols["value"],
                             "other": jnp.zeros_like(cols["value"])}, mask)

    rng = np.random.default_rng(5)
    n, keys = 1800, 3
    batch = TupleBatch({"key": np.arange(n) % keys, "id": np.arange(n),
                        "ts": np.arange(n) // keys,
                        "value": rng.integers(0, 3000, n).astype(float)})
    rows, records = {}, {}
    for dma in (False, True):
        monkeypatch.setattr(row_gather, "engages", lambda backend, w: dma)
        logic = WinSeqTPULogic(one_column, 150, 1, WinType.TB,
                               batch_len=256, emit_batches=True,
                               async_dispatch=False, max_batch_delay_ms=1e9)
        out = []
        logic.svc(batch, 0, out.append)
        logic.eos_flush(out.append)
        rows[dma] = sorted(zip(*(np.concatenate([getattr(b, c) for b in out])
                                 .tolist() for c in ("key", "id")),
                               np.concatenate([b["value"] for b in out])
                               .tolist()))
        records[dma] = list(logic._launches.records)
    assert rows[True] == rows[False] and len(rows[True]) > 1000
    assert sum(r.fn_rows_dma for r in records[True]) \
        == sum(r.rows for r in records[True]) == len(rows[True])
    assert all(r.fn_rows_dma == 0 for r in records[False])
    emitted = [r for r in records[True] if r.t_emitted is not None]
    assert emitted and [r.as_row()["Fn_rows_dma"] for r in emitted] == [
        r.rows for r in emitted]
