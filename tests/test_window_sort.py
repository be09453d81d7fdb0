"""ops/pallas/window_sort.py: a user's sort lowered to a sorting network.

The kernel runs interpreted here (the CPU backend) and must give
``lax.sort``'s rows bit for bit at every width the engine hands it:
heavy ties, +-inf, rows padded with +inf at every count, int32; a block
that holds -0.0, a NaN or a subnormal takes ``lax.sort`` itself.  The
rewrite engages on a TPU for the sorts it takes (the cell's median,
``jnp.median``, ``jnp.quantile``) and on nothing else, where the program
is the one the commit before the network built (its lowered text's hash
in ``tests/golden/programs_without_network.json``, the builtins' too).  A stream
through the operator carries the network's count to its launch records;
and the kernel compiles for a TPU v5e at the widest rows, with no chip.

``python tests/test_window_sort.py`` prints the programs' hashes as the
golden file holds them: run it from a checkout of a commit to be
trusted."""
import functools
import hashlib
import json
import os

import numpy as np
import pytest

from windflow_tpu.ops import window_compute
from windflow_tpu.ops.backend import jax_modules
from windflow_tpu.ops.pallas import window_sort
from windflow_tpu.ops.window_compute import WindowComputeEngine

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden",
                      "programs_without_network.json")
WIDTHS = (128, 1024, 4096, 8192)


@functools.lru_cache(maxsize=None)
def network(rows, w):
    """The kernel jitted once a shape, interpreted."""
    jax, _ = jax_modules()
    return jax.jit(lambda x: window_sort.sort_rows(x, interpret=True))


@functools.lru_cache(maxsize=None)
def guarded(w):
    """``sort_block`` with ``jnp.sort`` behind it, jitted once a width."""
    jax, jnp = jax_modules()
    return jax.jit(lambda x: window_sort.sort_block(
        x, lambda u: jnp.sort(u, axis=1), interpret=True))


def xla_sorted(x):
    jax, jnp = jax_modules()
    return np.asarray(jax.jit(lambda v: jnp.sort(v, axis=1))(x))


def same_bits(a, b):
    return a.dtype == b.dtype and a.shape == b.shape and (
        a.view(np.uint32) == b.view(np.uint32)).all()


def padded_rows(w, seed=0):
    """A row for every count of +inf padding from 0 to w (the rows
    beyond, to a whole 128, pad at random): heavy ties (eleven loads),
    +-inf among them, in random order before the padding."""
    rng = np.random.default_rng(seed)
    rows = -(-(w + 1) // 128) * 128
    x = rng.integers(0, 11, (rows, w)).astype(np.float32)
    x[rng.random((rows, w)) < 0.01] = np.inf
    x[rng.random((rows, w)) < 0.01] = -np.inf
    pad = np.concatenate([np.arange(w + 1),
                          rng.integers(0, w + 1, rows - w - 1)])
    x[np.arange(w)[None, :] >= (w - pad)[:, None]] = np.inf
    return x


@pytest.mark.parametrize("w", WIDTHS)
def test_the_network_is_lax_sort_bit_for_bit(w):
    x = padded_rows(w)
    # a block of 1,024 rows at a time: one program a width
    step = min(len(x), 1024)
    rows = [x[i:i + step] for i in range(0, len(x), step)]
    if len(rows[-1]) < step:
        rows[-1] = np.concatenate([rows[-1], x[:step - len(rows[-1])]])
    for block in rows:
        got = np.asarray(network(step, w)(block))
        assert same_bits(got, xla_sorted(block))


@pytest.mark.parametrize("w", WIDTHS)
def test_int32_rows(w):
    rng = np.random.default_rng(w)
    x = rng.integers(-2 ** 31, 2 ** 31, (128, w)).astype(np.int32)
    x[:64] %= 7                                  # and heavy ties
    got = np.asarray(network(128, w)(x))
    assert got.dtype == np.int32 and (got == np.sort(x, axis=1)).all()


@pytest.mark.parametrize("w", [128, 4096])
@pytest.mark.parametrize("odd", [-0.0, np.nan, 1e-40, -1e-42])
def test_a_block_the_network_would_misorder_takes_lax_sort(w, odd):
    """-0.0 sorts equal to +0.0 and keeps its place; a NaN sorts above
    +inf; a subnormal the VPU would flush: the block takes ``lax.sort``,
    chosen on the device, and reads not taken."""
    clean = padded_rows(w)[:128]
    clean[:, :w // 4] = 0.0
    x = clean.copy()
    x[5, 7] = odd
    for block, kernel in ((x, False), (clean, True)):
        got, taken = guarded(w)(block)
        assert bool(taken) is kernel
        assert same_bits(np.asarray(got), xla_sorted(block))


@pytest.mark.parametrize("backend,shape,dtype,engages", [
    ("tpu", (4096, 4096), np.float32, True),
    ("tpu", (128, 128), np.int32, True),
    ("tpu", (2048, 8192), np.float32, True),
    ("tpu", (4096, 64), np.float32, False),       # narrower than a vreg
    ("tpu", (4096, 16384), np.float32, False),    # wider than the kernel
    ("tpu", (4096, 3000), np.float32, False),     # not a power of two
    ("tpu", (100, 4096), np.float32, False),      # rows not whole lanes
    ("tpu", (4096, 4096), np.float64, False),
    ("tpu", (4096, 4096), np.int16, False),
    ("tpu", (16, 128, 128), np.float32, False),   # not 2-D
    ("cpu", (4096, 4096), np.float32, False),
    ("gpu", (4096, 4096), np.float32, False)])
def test_where_the_network_engages(backend, shape, dtype, engages):
    assert window_sort.engages(backend, shape, dtype) is engages


def test_the_schedule_has_every_stage_of_the_network():
    for w in WIDTHS:
        n = w.bit_length() - 1
        stages = 0
        for p in window_sort.schedule(w):
            # a sublane pass: the mirror and the stages below it there
            stages += len(p[3]) if p[0] == "slabs" else p[1] - (n - 3)
        assert stages == n * (n + 1) // 2


# -- the rewrite ------------------------------------------------------------

def median(gwid, cols, mask):
    """The cell's: the mean of the two middle loads."""
    _, jnp = jax_modules()
    n = mask.sum()
    v = jnp.sort(jnp.where(mask, cols["value"], jnp.inf))
    return ((v[(n - 1) // 2] + v[n // 2]) / 2).astype(jnp.float32)


def jnp_median(gwid, cols, mask):
    _, jnp = jax_modules()
    return jnp.median(jnp.where(mask, cols["value"], cols["other"]))


def jnp_quantile(gwid, cols, mask):
    _, jnp = jax_modules()
    return jnp.quantile(jnp.where(mask, cols["value"], 0.0), 0.9)


def by_argsort(gwid, cols, mask):
    _, jnp = jax_modules()
    v = jnp.where(mask, cols["value"], jnp.inf)
    return v[jnp.argsort(v)[mask.sum() // 2]]


def along_axis0(gwid, cols, mask):
    """Each place's smaller of the two columns, by a sort along axis 0."""
    _, jnp = jax_modules()
    pair = jnp.sort(jnp.stack([cols["value"], cols["other"]]), axis=0)
    return jnp.where(mask, pair[0], 0).sum()


def no_sort(gwid, cols, mask):
    _, jnp = jax_modules()
    return jnp.where(mask, cols["value"] - cols["other"], 0).max()


SORTING = {"median": median, "jnp_median": jnp_median,
           "jnp_quantile": jnp_quantile}
OTHERS = {"argsort": by_argsort, "axis0": along_axis0, "no_sort": no_sort}

# a launch of two columns, rows of up to 128, blocks of 4,096 rows
W_PAD, T_PAD, B_PAD, BLOCK = 128, 2048, 4096, 4096
NAMES = ("other", "value")


def custom_program(fn, backend):
    """``_custom_program`` at the shapes above, as on ``backend``."""
    return window_compute._custom_program(fn, W_PAD, NAMES, T_PAD, B_PAD,
                                          BLOCK, False, backend)


def lowered_text(prog, n):
    jax, jnp = jax_modules()
    return prog.lower(jax.ShapeDtypeStruct((n,), jnp.int32)).as_text()


def program_hashes():
    """sha256 of the lowered text of each program the network must leave
    as it was: the builtins' and a user's function that does not sort
    in the network's way, as the TPU backend would build them."""
    wc = window_compute
    n_fn = wc.packed_len(2, T_PAD, B_PAD, 3) + 1
    progs = {
        "count": (wc._count_program(2048), wc.packed_len(0, 0, 2048)),
        "tile_sum": (wc._tile_sum_program(16, 2048, 2048),
                     wc.packed_len(1, 2048, 2048)),
        "tile_mean": (wc._tile_mean_program(16, 2048, 2048),
                      wc.packed_len(2, 2048, 2048)),
        "block_sum": (wc._block_sum_program("sum", 2, 4096, 2048),
                      wc.packed_len(1, 4096, 2048)),
        "block_mean": (wc._block_sum_program("mean", 2, 4096, 2048),
                       wc.packed_len(1, 4096, 2048)),
        "block_mean_panes": (wc._block_sum_program("mean_panes", 2, 4096,
                                                   2048),
                             wc.packed_len(2, 4096, 2048)),
        "max": (wc._sparse_table_program("max", 2048, 2048),
                wc.packed_len(1, 2048, 2048)),
        "min": (wc._sparse_table_program("min", 2048, 2048),
                wc.packed_len(1, 2048, 2048)),
        "argmax": (wc._argmax_program(2048, 2048),
                   wc.packed_len(1, 2048, 2048)),
        **{f"fn_{name}": (custom_program(fn, "tpu"), n_fn)
           for name, fn in OTHERS.items()}}
    return {name: hashlib.sha256(
        lowered_text(p, n).encode()).hexdigest()
        for name, (p, n) in progs.items()}


def test_the_programs_it_does_not_take_are_the_parents():
    with open(GOLDEN) as f:
        want = json.load(f)
    assert program_hashes() == want


@pytest.mark.parametrize("name", sorted(OTHERS))
def test_a_sort_it_does_not_take_leaves_the_program(name):
    fn = OTHERS[name]
    assert window_compute._fn_sorts(fn, W_PAD, NAMES, BLOCK, "tpu") is None
    n = window_compute.packed_len(2, T_PAD, B_PAD, 3) + 1
    assert lowered_text(custom_program(fn, "tpu"), n) == lowered_text(
        custom_program(fn, "cpu"), n)


def launch(seed=3, n_keys=6, per_key=40, width=120):
    """Each key's windows slide one element over its own series; whole
    loads with ties, some windows short."""
    rng = np.random.default_rng(seed)
    T = n_keys * (width + per_key)
    cols = {"value": rng.integers(0, 300, T).astype(np.float64),
            "other": rng.integers(0, 30, T).astype(np.float64)}
    starts = np.concatenate([k * (width + per_key) + np.arange(per_key)
                             for k in range(n_keys)])
    ends = starts + width - rng.integers(0, 60, len(starts))
    return cols, starts, ends


@pytest.fixture
def network_on(monkeypatch):
    """``network_on(True)``: the engine's programs built as on the TPU,
    where the network takes a sort, and run here (the kernel
    interpreted); ``network_on(False)``: as on the CPU.  The programs
    built meanwhile are dropped after the test."""
    real = window_sort.engages

    def switch(on):
        monkeypatch.setattr(window_sort, "engages",
                            lambda backend, shape, dtype:
                            on and real("tpu", shape, dtype))
        window_compute._fn_sorts.cache_clear()
        window_compute._custom_program.cache_clear()
    yield switch
    window_compute._fn_sorts.cache_clear()
    window_compute._custom_program.cache_clear()


@pytest.mark.parametrize("name", sorted(SORTING))
def test_a_sorting_function_engages_and_reads_the_same(monkeypatch,
                                                       network_on, name):
    fn = SORTING[name]
    assert window_compute._fn_sorts(fn, W_PAD, NAMES, BLOCK,
                                    "tpu") is not None
    cols, starts, ends = launch()
    # blocks of 128 rows: the launch's 240 fill one and part of two
    monkeypatch.setattr(window_compute, "_FN_BLOCK_ELEMS", 128 * 128)
    got = {}
    for on in (False, True):
        network_on(on)
        h = WindowComputeEngine(fn).compute(cols, starts, ends,
                                            np.arange(len(starts)))
        got[on] = (h.block(), h.fn_rows_sorted)
    assert got[False][1] == 0 and got[True][1] == len(starts)
    assert same_bits(got[True][0], got[False][0])


def test_a_block_with_a_nan_is_counted_out(monkeypatch, network_on):
    """The launch's second block holds a NaN: its rows are sorted by
    ``lax.sort`` and left out of the count, and every row reads as the
    program without the network reads it."""
    cols, starts, ends = launch()
    cols["value"][starts[200] + 3] = np.nan
    monkeypatch.setattr(window_compute, "_FN_BLOCK_ELEMS", 128 * 128)
    got = {}
    for on in (False, True):
        network_on(on)
        h = WindowComputeEngine(median).compute(cols, starts, ends,
                                                np.arange(len(starts)))
        got[on] = (h.block(), h.fn_rows_sorted)
    assert got[True][1] == 128
    assert same_bits(got[True][0], got[False][0])


def test_the_operator_carries_the_count_to_its_launch_records(network_on):
    """A stream through the window operator: the same rows whichever way
    the sort is lowered, and each launch record (and its stats row)
    counts the rows the network sorted."""
    from windflow_tpu.core.basic import WinType
    from windflow_tpu.core.tuples import TupleBatch
    from windflow_tpu.operators.tpu.win_seq_tpu import WinSeqTPULogic

    rng = np.random.default_rng(5)
    n, keys = 1800, 3
    batch = TupleBatch({"key": np.arange(n) % keys, "id": np.arange(n),
                        "ts": np.arange(n) // keys,
                        "value": rng.integers(0, 3000, n).astype(float)})
    rows, records = {}, {}
    for on in (False, True):
        network_on(on)
        logic = WinSeqTPULogic(median, 150, 1, WinType.TB,
                               batch_len=256, emit_batches=True,
                               async_dispatch=False, max_batch_delay_ms=1e9)
        out = []
        logic.svc(batch, 0, out.append)
        logic.eos_flush(out.append)
        rows[on] = sorted(zip(
            *(np.concatenate([getattr(b, c) for b in out]).tolist()
              for c in ("key", "id")),
            np.concatenate([b["value"] for b in out]).tolist()))
        records[on] = list(logic._launches.records)
    assert rows[True] == rows[False] and len(rows[True]) > 1000
    assert sum(r.fn_rows_sorted for r in records[True]) \
        == sum(r.rows for r in records[True]) == len(rows[True])
    assert all(r.fn_rows_sorted == 0 for r in records[False])
    emitted = [r for r in records[True] if r.t_emitted is not None]
    assert emitted and [r.as_row()["Fn_rows_sorted"] for r in emitted] == [
        r.rows for r in emitted]


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


@pytest.mark.parametrize("w", [4096, 8192])
def test_the_kernel_compiles_for_a_v5e(one_chip, w):
    """A block as the engine hands it (``fn_rows``: 2**24 elements),
    within the VMEM the kernel asks for."""
    jax, jnp = jax_modules()
    _, block = window_compute.fn_rows(1 << 18, w)
    compiled = jax.jit(
        lambda x: window_sort.sort_rows(x, interpret=False)).lower(
        jax.ShapeDtypeStruct((block, w), jnp.float32,
                             sharding=one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


if __name__ == "__main__":
    print(json.dumps(program_hashes(), indent=1, sort_keys=True))
