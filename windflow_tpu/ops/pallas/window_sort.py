"""Pallas TPU kernel: the rows of a block sorted by a bitonic network, and
the rewrite that hands it a user's sort.

A user's window function runs vmapped over a [rows, w] tile
(ops/window_compute._custom_program).  Where it sorts a window, the
traced program holds one ``sort`` over the tile's last axis, and XLA's
TPU sort is what sets the pace: 9.0 ms of device time a block of 4,096
rows of 4,096 in the median cell (PERF.md section 6).  A sort is not the
user's choice of algorithm to keep but a lowering to choose, so
:func:`lower_sorts` traces the vmapped function once and evaluates its
jaxpr with each ``sort`` that :func:`engages` replaced by this kernel;
every other equation is bound as traced.

**Layout.**  A grid step takes 128 rows and transposes them in VMEM,
128 elements at a time, into a scratch of ``w / 8`` slabs of one vreg
each (2 MiB at w = 4,096): the rows across the lanes, each row's
elements down the slabs and their sublanes.  The network sorts so that
rank ``s * w / 8 + a`` of a row lies at slab ``a``, sublane ``s``; the
ranks go back out by strided loads of one sublane across 128 slabs and
a transpose.  (XLA's transposes around the kernel took 0.3 ms more a
block of 4,096 x 4,096 on a TPU v5e; PERF.md section 6.)

**The network** is bitonic in its all-ascending form: merge ``k`` first
compares each element of a run of ``2**k`` with its mirror image in the
run (index bits 0..k-1 flipped), then with its partner across bit
``k - 2``, ..., bit 0 (:func:`schedule`).  A stage across a bit of the
slab index is a whole-vreg ``minimum`` / ``maximum`` between slabs; only
the stages across the three sublane bits, the top bits of a row, which
come in the last three merges, exchange sublanes (a roll and a select).
Stages go by passes: each loads the slabs a few consecutive bits apart
into registers, runs its stages there and stores them back, so a pass
costs one load and one store a slab whatever its stages.  No pair is
unrolled in Python: a pass is a loop over its groups of slabs.

**Exactness.**  ``jnp.sort`` is a stable ``lax.sort`` whose comparator
takes -0.0 equal to +0.0 and every NaN equal and above +inf.  Where a
float32 block holds none of those and no subnormal (the VPU flushes
them to zero), equal keys have equal bits, and a min/max network gives
``lax.sort``'s output bit for bit; :func:`sort_block` tests each block
on the device and sorts one that holds any of them with ``lax.sort``
itself.  int32 needs no test.  Off the TPU the kernel runs in interpret
mode (the tests).
"""
from __future__ import annotations

import functools

import numpy as np

from ..backend import jax_modules
from .window_sum import LANES, interpret_off_tpu

SUBLANES = 8
# the rows the kernel takes: whole [128, 128] tiles to transpose, and a
# grid step's 128 rows five times over in VMEM (in and out twice each,
# the scratch once: 10 MiB at 4,096)
MIN_W, MAX_W = LANES, 1 << 13
# the first pass sorts runs of 2**_RUN_BITS slabs in registers; every
# later pass crosses at most _PASS_BITS bits of the slab index, with
# _CHUNK neighbouring slabs of each group in flight (the fastest of the
# shapes tried on a TPU v5e: PERF.md section 6)
_RUN_BITS = 4
_PASS_BITS = 3
_CHUNK = 4


def engages(backend: str, shape, dtype) -> bool:
    """Whether a sort over the last axis of a ``shape`` operand is the
    kernel's: on the TPU backend, for a [rows, w] operand of float32 or
    int32 with ``rows`` a multiple of 128 and ``w`` a power of two from
    128 to 8,192 (what ``window_compute._compute_fn``'s ``w_pad`` is at
    those widths).  Everywhere else the program keeps ``lax.sort``."""
    if backend != "tpu" or len(shape) != 2:
        return False
    rows, w = shape
    return (MIN_W <= w <= MAX_W and w & (w - 1) == 0 and rows % LANES == 0
            and np.dtype(dtype) in (np.float32, np.int32))


def schedule(w: int):
    """The network over rows of ``w`` as passes, in order.  A pass
    ``("slabs", hi, lo, ops)`` crosses bits ``lo..hi`` of the slab
    index; each op is ``("mirror", b)``, each element against the one
    with bits 0..b flipped, or ``("half", b)``, against the one with bit
    ``b`` flipped, the lower index taking the minimum.  A mirror below
    ``hi`` comes only where ``lo`` is 0.  A pass ``("sublanes", k)`` is
    merge ``k``'s mirror, which flips every slab bit and the sublane
    bits below ``k``, and its stages across the sublane bits.  Merge
    ``k`` has ``k`` stages; rows of ``2**n`` have ``n (n + 1) / 2``."""
    n = w.bit_length() - 1
    m = n - 3                                  # the slab index's bits
    k0 = min(_RUN_BITS, m)

    def halves(hi, lo):
        return tuple(("half", b) for b in range(hi, lo - 1, -1))

    runs = ()
    for k in range(1, k0 + 1):
        runs += (("mirror", k - 1),) + halves(k - 2, 0)
    passes = [("slabs", k0 - 1, 0, runs)]
    for k in range(k0 + 1, n + 1):
        if k <= m:
            lo = max(k - _PASS_BITS, k0)
            passes.append(("slabs", k - 1, lo,
                           (("mirror", k - 1),) + halves(k - 2, lo)))
            hi = lo - 1
        else:
            passes.append(("sublanes", k))
            hi = m - 1
        while hi >= k0:
            lo = max(hi - _PASS_BITS + 1, k0)
            passes.append(("slabs", hi, lo, halves(hi, lo)))
            hi = lo - 1
        passes.append(("slabs", k0 - 1, 0, halves(k0 - 1, 0)))
    return passes


@functools.lru_cache(maxsize=None)
def _build(rows: int, w: int, dtype: str, interpret: bool):
    jax, jnp = jax_modules()
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    A = w // SUBLANES
    m = A.bit_length() - 1
    lax = jax.lax

    # every index and mask below is built from lax's primitives: jnp's
    # operators trace through jit, which multiplies the kernel's
    # lowering time (on the dispatcher's thread, in the warm-up)
    def exchange(x, y):
        return lax.min(x, y), lax.max(x, y)

    def slabs(first, n):
        """``n`` slabs of the scratch from row ``first`` (traced)."""
        return pl.ds(pl.multiple_of(first, SUBLANES), n * SUBLANES)

    def reverse(x):
        """A chunk's slabs in reverse order."""
        n = x.shape[0] // SUBLANES
        return x if n == 1 else lax.concatenate(
            [lax.slice_in_dim(x, SUBLANES * c, SUBLANES * (c + 1))
             for c in range(n - 1, -1, -1)], 0)

    def log2(n):
        return n.bit_length() - 1

    def slabs_pass(ref, hi, lo, ops):
        r, L = hi - lo + 1, 1 << lo
        C = min(L, _CHUNK)
        # a mirror across the top bit pairs a lower chunk of C slabs
        # with the upper one the mirror maps it to, whose slabs then lie
        # in reverse order; a mirror below it comes only where C is 1
        mirrored = L > 1 and ops[0] == ("mirror", hi)
        assert all(k == "half" for k, _ in ops[mirrored:]) or L == 1
        per_group = L // C
        half = 1 << (r - 1)

        def body(i, carry):
            # iteration i: group i // per_group, chunk i % per_group
            group = lax.shift_left(lax.shift_right_logical(
                i, log2(per_group)), hi + 1 + log2(SUBLANES))
            off = lax.shift_left(lax.bitwise_and(i, per_group - 1),
                                 log2(C * SUBLANES))
            lower, upper = lax.add(group, off), lax.sub(group, off)
            at = [lax.add(upper, SUBLANES * (t * L + L - C))
                  if mirrored and t >= half
                  else lax.add(lower, SUBLANES * t * L)
                  for t in range(1 << r)]
            v = [ref[slabs(a, C)] for a in at]
            for kind, b in ops:
                d = 1 << (b - lo)
                for t in range(1 << r):
                    if t & d:
                        continue
                    if kind == "half":
                        v[t], v[t | d] = exchange(v[t], v[t | d])
                    else:
                        u = t ^ (2 * d - 1)
                        v[t], top = exchange(v[t], reverse(v[u]))
                        v[u] = reverse(top)
            for a, x in zip(at, v):
                ref[slabs(a, C)] = x
            return carry

        lax.fori_loop(0, A // ((1 << r) * C), body, 0)

    def sublanes_pass(ref, k):
        e = k - m                     # the merge's sublane bits
        C = _CHUNK

        def body(i, carry):
            row = lax.broadcasted_iota(jnp.int32, (C * SUBLANES, LANES), 0)

            def swap(x, d):
                """``x`` with row ``r`` holding what row ``r ^ d`` held,
                the same slab's other sublane."""
                up = pltpu.roll(x, d, 0)
                down = pltpu.roll(x, C * SUBLANES - d, 0)
                # which way a roll turns is read off the rolled iota
                return lax.select(lax.eq(pltpu.roll(row, d, 0),
                                         lax.bitwise_xor(row, d)), up, down)

            def step(x, y, bit):
                return lax.select(lax.eq(lax.bitwise_and(row, 1 << bit), 0),
                                  lax.min(x, y), lax.max(x, y))

            lo_at = lax.shift_left(i, log2(C * SUBLANES))
            hi_at = lax.sub((A - C) * SUBLANES, lo_at)
            x = ref[slabs(lo_at, C)]
            y = reverse(ref[slabs(hi_at, C)])    # x's slabs' mirrors
            fx, fy = x, y
            for j in range(e):
                fx, fy = swap(fx, 1 << j), swap(fy, 1 << j)
            x, y = step(x, fy, e - 1), step(y, fx, e - 1)
            for j in range(e - 2, -1, -1):
                x, y = step(x, swap(x, 1 << j), j), step(y, swap(y, 1 << j), j)
            ref[slabs(lo_at, C)] = x
            ref[slabs(hi_at, C)] = reverse(y)
            return carry

        lax.fori_loop(0, A // (2 * C), body, 0)

    def kernel(x_ref, o_ref, buf):
        # in: element 128 c + r of a row to row 128 c + r of the scratch
        # (the network may take its input in any order)
        for c in range(w // LANES):
            buf[c * LANES:(c + 1) * LANES] = lax.transpose(
                x_ref[:, c * LANES:(c + 1) * LANES], (1, 0))
        for p in schedule(w):
            if p[0] == "slabs":
                slabs_pass(buf, *p[1:])
            else:
                sublanes_pass(buf, p[1])
        # out: rank e lies at slab e % A, sublane e // A, so the ranks
        # of one sublane are rows 8 apart; ranks 128 c ... 128 c + 127
        # are one such run, or several where a sublane holds fewer
        n = min(A, LANES)
        for c in range(w // LANES):
            tile = lax.concatenate([
                buf[pl.ds(SUBLANES * (e % A) + e // A, n, stride=SUBLANES)]
                for e in range(c * LANES, (c + 1) * LANES, n)], 0)
            o_ref[:, c * LANES:(c + 1) * LANES] = lax.transpose(tile, (1, 0))

    nbytes = LANES * w * np.dtype(dtype).itemsize
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, w), dtype),
        grid=(rows // LANES,),
        in_specs=[pl.BlockSpec((LANES, w), lambda g: (g, 0))],
        out_specs=pl.BlockSpec((LANES, w), lambda g: (g, 0)),
        scratch_shapes=[pltpu.VMEM((w, LANES), np.dtype(dtype))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=max(16 << 20, 5 * nbytes + (4 << 20))),
        interpret=interpret,
        name="window_sort",
    )


def sort_rows(x, interpret: bool = None):
    """``x`` [rows, w] sorted along its last axis, ascending, by the
    network.  Traceable; :func:`engages` says which shapes and dtypes it
    takes.  Bit for bit ``lax.sort`` where a float32 ``x`` holds no
    -0.0, NaN or subnormal (:func:`sort_block` makes sure)."""
    if interpret is None:
        interpret = interpret_off_tpu()
    rows, w = x.shape
    return _build(rows, w, np.dtype(x.dtype).name, bool(interpret))(x)


def sort_block(x, fallback, interpret: bool = None):
    """``(x sorted along its last axis, whether the kernel sorted it)``.
    A float32 block that holds -0.0, a NaN or a subnormal is sorted by
    ``fallback`` (the traced ``lax.sort``) instead, chosen on the device
    by ``lax.cond``; int32 always goes to the kernel."""
    jax, jnp = jax_modules()

    def kernel(v):
        return sort_rows(v, interpret)

    if x.dtype == jnp.int32:
        return kernel(x), jnp.bool_(True)
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    mag = bits & 0x7FFFFFFF
    odd = jnp.any((mag > 0x7F800000) | ((mag < 0x00800000) & (bits != 0)))
    return jax.lax.cond(odd, fallback, kernel, x), ~odd


# -- the rewrite --------------------------------------------------------------

def _sub_jaxpr(eqn):
    """The closed jaxpr a ``jit`` / ``closed_call`` equation calls, or
    None (``jnp.sort`` is itself jitted)."""
    from jax.extend.core import primitives as P
    if eqn.primitive is P.jit_p:
        return eqn.params["jaxpr"]
    if eqn.primitive is P.closed_call_p:
        return eqn.params["call_jaxpr"]
    return None


def _kernel_takes(eqn, backend: str) -> bool:
    from jax.extend.core import primitives as P
    if eqn.primitive is not P.sort_p or len(eqn.invars) != 1 \
            or eqn.params["num_keys"] != 1:
        return False
    aval = eqn.invars[0].aval
    return (eqn.params["dimension"] == len(aval.shape) - 1
            and engages(backend, aval.shape, aval.dtype))


def _engages_in(jaxpr, backend: str) -> bool:
    for eqn in jaxpr.eqns:
        sub = _sub_jaxpr(eqn)
        if _kernel_takes(eqn, backend) or (
                sub is not None and _engages_in(sub.jaxpr, backend)):
            return True
    return False


def _eval(jaxpr, consts, args, backend: str, taken: list):
    """``jaxpr`` evaluated on ``args``, each sort the kernel takes
    replaced by it (its flag appended to ``taken``), every other
    equation bound as traced."""
    from jax.extend.core import Literal
    env = {}

    def read(v):
        return v.val if isinstance(v, Literal) else env[v]

    env.update(zip(jaxpr.constvars, consts))
    env.update(zip(jaxpr.invars, args))
    for eqn in jaxpr.eqns:
        ins = [read(v) for v in eqn.invars]
        sub = _sub_jaxpr(eqn)
        if _kernel_takes(eqn, backend):
            out, ok = sort_block(
                ins[0], lambda x, eqn=eqn: eqn.primitive.bind(
                    x, **eqn.params)[0])
            taken.append(ok)
            outs = [out]
        elif sub is not None and _engages_in(sub.jaxpr, backend):
            outs = _eval(sub.jaxpr, sub.consts, ins, backend, taken)
        else:
            subfuns, params = eqn.primitive.get_bind_params(eqn.params)
            outs = eqn.primitive.bind(*subfuns, *ins, **params)
            if not eqn.primitive.multiple_results:
                outs = [outs]
        env.update(zip(eqn.outvars, outs))
    return [read(v) for v in jaxpr.outvars]


def lower_sorts(fn, args, backend: str):
    """``fn`` traced once on ``args`` (shapes will do).  None where no
    sort of it is the kernel's (:func:`engages`, over the last axis, one
    operand and one key, inside ``jit`` / ``closed_call`` too): the
    caller keeps ``fn`` as it is.  Else a function of the same arguments
    that gives ``(fn's result, whether the kernel sorted every block it
    was handed)``."""
    jax, jnp = jax_modules()
    closed, shape = jax.make_jaxpr(fn, return_shape=True)(*args)
    if not _engages_in(closed.jaxpr, backend):
        return None
    out_tree = jax.tree_util.tree_structure(shape)

    def run(*a):
        taken = []
        outs = _eval(closed.jaxpr, closed.consts,
                     jax.tree_util.tree_leaves(a), backend, taken)
        return (jax.tree_util.tree_unflatten(out_tree, outs),
                functools.reduce(jnp.logical_and, taken))

    return run
