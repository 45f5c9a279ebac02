"""Grouped matrix products over rows sorted by group, as Pallas TPU
kernels: the expert layer's products and their two transposes.

lhs [M, K] holds the rows of G groups one after another, ``rows[g]`` of
group g from ``offsets[g]`` (``offsets`` the cumulative sum of ``rows``
from 0); the rows past ``sum(rows)`` belong to no group.  Three products:

* :func:`grouped_matmul`: ``out[r] = lhs[r] @ rhs[g(r)]``, rhs [G, K, N]
  (kernel ``ragged-dot-gmm``);
* :func:`grouped_matmul_t`: ``out[r] = ct[r] @ rhs[g(r)]^T``, the
  cotangent of lhs (``ragged-dot-gmm-t``: the same kernel against rhs
  transposed, read in its own layout);
* :func:`grouped_matmul_rhs_grad`: ``out[g] = lhs[rows of g]^T @ ct[rows
  of g]``, [G, K, N], the cotangent of rhs, zero for a group with no row
  (``ragged-dot-tgmm``).

The grid walks tiles of ``tm`` rows.  A tile that holds rows of several
groups is visited once for each, its rows of other groups masked; a
group's visits follow one another and a tile's too, so an output block
stays in VMEM until its last visit.  Which tile and group a visit takes
is metadata read from the row counts on the device (:func:`_visits`: the
offsets [G+1], a group and a tile id for each of the ``M / tm + G - 1``
visits there can be), handed to the kernel as scalar prefetch, and the
number of visits run is read there too: the work follows ``sum(rows)``,
not M.  The scheme is megablox's (``jax.experimental.pallas.ops.tpu.
megablox``); the same names are in the ``ragged-dot`` family that XLA's
own grouped kernel (``ragged-dot-none``) has.

**Tiles follow the shapes** (:func:`tiles`): ``tk`` and ``tn`` the whole
widths K and N, or their largest divisors in whole 128-lane tiles that
fit ``TILE_BYTES``, so a grid step holds a group's whole weight and does a
GFLOP of matrix work for a fixed cost of a fraction of a microsecond; XLA's
kernel drops to 512 x 256 x 128 steps at widths that 256 does not divide
(2304 x 896).  ``tm`` from the rows a group holds.

Operands stay in their dtype (bf16 under AMP), accumulation float32,
results in the operands' dtype.  Off the TPU, and for shapes no tile fits,
``jax.lax.ragged_dot`` and its transposes do the same work (``_xla_*``);
``PADDLE_TPU_PALLAS=interpret`` runs the kernels in the interpreter (CPU
tests).  Both paths hold the same contract: the rows past ``sum(rows)`` of
:func:`grouped_matmul` and :func:`grouped_matmul_t` are zero, and so is
:func:`grouped_matmul_rhs_grad` of a group with no row.

Counting: while :func:`noting_sites` is open, each product that is traced
adds one under ``(mode, path, shape, tiles)``: mode ``forward``,
``lhs_grad`` or ``rhs_grad``, path ``kernel`` or ``xla``
(``observability.runtime.record_grouped_dot_sites``).
"""

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import use_pallas

LANES = 128
# what a grid step holds in VMEM: its blocks twice (the pipeline fetches
# the next step's while this one runs) and the float32 accumulator
TILE_BYTES = 40 * 2**20
VMEM_LIMIT = 64 * 2**20
# rows a tile: at most TM_MAX, and a group's share of the rows spans at
# least TILES_A_GROUP of them (a group's edge inside a tile costs a visit)
TM_MAX = 512
TM_MIN = 16
TILES_A_GROUP = 8

_noting = threading.local()


@contextlib.contextmanager
def noting_sites(counts):
    """While active on this thread, every grouped product that is traced
    adds one to ``counts[(mode, path, shape, tiles)]``."""
    was = getattr(_noting, "counts", None)
    _noting.counts = counts
    try:
        yield counts
    finally:
        _noting.counts = was


def _note_site(mode, path, shape, tiling):
    counts = getattr(_noting, "counts", None)
    if counts is not None:
        counts[(mode, path, "x".join(map(str, shape)),
                tiling and "x".join(map(str, tiling)))] += 1


# ---------------------------------------------------------------------------
# Tiles and the walk
# ---------------------------------------------------------------------------

def _footprint(tm, tk, tn, itemsize, acc):
    """VMEM bytes of a step: three blocks twice, and the accumulator
    (``acc`` its two dims)."""
    blocks = tm * tk + tk * tn + tm * tn
    return 2 * blocks * itemsize + 4 * acc[0] * acc[1]


def _divisors(width):
    """The block widths a dim of ``width`` takes, largest first: the whole
    width, then its divisors in whole lane tiles."""
    return [width] + [d for d in range(width - LANES, 0, -LANES)
                      if width % d == 0 and d % LANES == 0]


def tiles(m, k, n, groups, dtype, mode="forward"):
    """``(tm, tk, tn)`` of a grouped product of lhs [m, k] over ``groups``
    groups into [m, n] (``mode`` ``rhs_grad``: lhs [m, k] and ct [m, n]
    into [groups, k, n]), or None where no tile fits: a pure function of
    the shapes.  ``tm`` the largest power of two up to ``TM_MAX`` that
    divides m with a group's share of the rows (``m / groups``) over
    ``TILES_A_GROUP`` of it; ``tk`` and ``tn`` the widest blocks (the
    whole width, else whole lane tiles dividing it) that fit
    ``TILE_BYTES``, the wider of the two narrowed first."""
    tm = TM_MAX
    while tm > TM_MIN and (tm * TILES_A_GROUP > m // groups or m % tm):
        tm //= 2
    if m % tm:
        return None
    itemsize = jnp.dtype(dtype).itemsize
    ks, ns = _divisors(k), _divisors(n)
    while True:
        tk, tn = ks[0], ns[0]
        acc = (tk, tn) if mode == "rhs_grad" else (tm, tn)
        if _footprint(tm, tk, tn, itemsize, acc) <= TILE_BYTES:
            return tm, tk, tn
        wider = ks if (tk >= tn and len(ks) > 1) or len(ns) == 1 else ns
        if len(wider) == 1:
            return None
        wider.pop(0)


def _visits(rows, m, tm, visit_empty):
    """The grid's walk over tiles of ``tm`` rows: ``offsets`` [G+1],
    ``group`` and ``tile`` [m / tm + G - 1] (what visit v takes), and the
    number of visits to run.  A group visits each tile its rows touch, in
    order, and where ``visit_empty`` a group with no row visits one tile
    (to write its zeros)."""
    g = rows.shape[0]
    ends = jnp.cumsum(rows)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = offsets[:-1] // tm
    span = jnp.where(rows > 0, (ends - 1) // tm - first + 1,
                     int(visit_empty))
    until = jnp.cumsum(span)
    v = jax.lax.broadcasted_iota(jnp.int32, (m // tm + g - 1, 1), 0)
    group = jnp.minimum(jnp.sum(until[None, :] <= v, axis=1, dtype=jnp.int32),
                        g - 1)
    tile = jnp.minimum(first[group] + v[:, 0] - (until - span)[group],
                       m // tm - 1)
    return offsets, group, tile, until[-1]


def _row_mask(offsets, group, tile, v, tm, width):
    """``[tm, width]``: the rows of visit v's tile that its group holds."""
    g = group[v]
    row = jax.lax.broadcasted_iota(jnp.int32, (tm, width), 0) + tile[v] * tm
    return jnp.logical_and(row >= offsets[g], row < offsets[g + 1])


def _whole(offsets, group, tile, v, tm):
    """Does visit v's group hold every row of its tile?"""
    g, lo = group[v], tile[v] * tm
    return jnp.logical_and(offsets[g] <= lo, offsets[g + 1] >= lo + tm)


def _operands(dtype, interpret):
    """The products' operand dtype: the operands' own, float32 in the
    interpreter, whose CPU dots take no bf16 operands into a float32
    result (the same numbers: bf16 casts up exactly)."""
    return jnp.float32 if interpret else dtype


# ---------------------------------------------------------------------------
# The kernels
# ---------------------------------------------------------------------------

def _gmm_kernel(offsets, group, tile, lhs_ref, rhs_ref, out_ref, *acc_ref,
                tm, tiles_k, contract, dtype):
    """Visit ``program_id(1)``: its tile's rows times its group's block of
    rhs (``contract``: rhs's dim the product takes, 0, or 1 where rhs is
    read transposed), added over the k blocks; the rows of its group
    stored, the tile's other rows left to their own visits."""
    v, kk = pl.program_id(1), pl.program_id(2)
    prod = jax.lax.dot_general(
        lhs_ref[...].astype(dtype), rhs_ref[...].astype(dtype),
        (((1,), (contract,)), ((), ())), preferred_element_type=jnp.float32)

    def store(acc):
        whole = _whole(offsets, group, tile, v, tm)

        @pl.when(whole)
        def _():
            out_ref[...] = acc.astype(out_ref.dtype)

        @pl.when(jnp.logical_not(whole))
        def _():
            keep = _row_mask(offsets, group, tile, v, tm, acc.shape[1])
            out_ref[...] = jax.lax.select(
                keep, acc, out_ref[...].astype(jnp.float32)
            ).astype(out_ref.dtype)

    if tiles_k == 1:
        store(prod)
        return
    acc_ref, = acc_ref

    @pl.when(kk == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    acc_ref[...] += prod

    @pl.when(kk == tiles_k - 1)
    def _():
        store(acc_ref[...])


def _tgmm_kernel(offsets, group, tile, lhs_ref, ct_ref, out_ref, acc_ref, *,
                 tm, dtype):
    """Visit ``program_id(2)``: its group's rows of the tile, lhs^T @ ct,
    added to the group's accumulator; stored at the group's last visit
    (zero for a group with no row, which visits once)."""
    v, last = pl.program_id(2), pl.num_programs(2) - 1
    g = group[v]

    @pl.when(jnp.logical_or(v == 0, group[jnp.maximum(v - 1, 0)] != g))
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(offsets[g + 1] > offsets[g])
    def _():
        lhs, ct = lhs_ref[...], ct_ref[...]
        whole = _whole(offsets, group, tile, v, tm)

        def masked(x):
            keep = _row_mask(offsets, group, tile, v, tm, x.shape[1])
            return jnp.where(jnp.logical_or(whole, keep), x, 0)

        acc_ref[...] += jax.lax.dot_general(
            masked(lhs).astype(dtype), masked(ct).astype(dtype),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_or(v == last, group[jnp.minimum(v + 1, last)] != g))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


# ---------------------------------------------------------------------------
# The calls
# ---------------------------------------------------------------------------

def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


# Each call is a jit of its own: jax traces and lowers the kernel once a
# shape and not once a site (160 sites in the mellum2 step, six shapes)
@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _gmm(lhs, rhs, rows, tiling, transpose, interpret):
    """lhs [M, K] by rhs [G, K, N] (``transpose``: [G, N, K]) into
    [M, N]; the rows past ``sum(rows)`` left unwritten."""
    tm, tk, tn = tiling
    m, k = lhs.shape
    n = rhs.shape[1] if transpose else rhs.shape[2]
    tiles_k = k // tk
    offsets, group, tile, run = _visits(rows, m, tm, visit_empty=False)
    if transpose:
        rhs_spec = pl.BlockSpec((None, tn, tk),
                                lambda j, v, kk, o, g, t: (g[v], j, kk))
    else:
        rhs_spec = pl.BlockSpec((None, tk, tn),
                                lambda j, v, kk, o, g, t: (g[v], kk, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tiles_k=tiles_k,
                          contract=int(transpose),
                          dtype=_operands(lhs.dtype, interpret)),
        name="ragged-dot-gmm-t" if transpose else "ragged-dot-gmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, run, tiles_k),
            in_specs=[pl.BlockSpec((tm, tk),
                                   lambda j, v, kk, o, g, t: (t[v], kk)),
                      rhs_spec],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, kk, o, g, t: (t[v], j)),
            scratch_shapes=([pltpu.VMEM((tm, tn), jnp.float32)]
                            if tiles_k > 1 else [])),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
    )(offsets, group, tile, lhs, rhs)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _tgmm(lhs, ct, rows, tiling, interpret):
    """lhs [M, K] and ct [M, N] into [G, K, N]."""
    tm, tk, tn = tiling
    m, k = lhs.shape
    n = ct.shape[1]
    offsets, group, tile, run = _visits(rows, m, tm, visit_empty=True)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm,
                          dtype=_operands(lhs.dtype, interpret)),
        name="ragged-dot-tgmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, k // tk, run),
            in_specs=[pl.BlockSpec((tm, tk),
                                   lambda j, i, v, o, g, t: (t[v], i)),
                      pl.BlockSpec((tm, tn),
                                   lambda j, i, v, o, g, t: (t[v], j))],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda j, i, v, o, g, t: (g[v], i, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((rows.shape[0], k, n), lhs.dtype),
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(offsets, group, tile, lhs, ct)


# ---------------------------------------------------------------------------
# The XLA twins (off the TPU)
# ---------------------------------------------------------------------------

def _xla_gmm(lhs, rhs, rows):
    return jax.lax.ragged_dot(lhs, rhs, rows)


def _xla_gmm_t(ct, rhs, rows):
    """The pullback of ``ragged_dot`` in its lhs, at ct (linear: the
    point it is taken at does not matter, and XLA drops the product
    there)."""
    lhs = jnp.zeros((ct.shape[0], rhs.shape[1]), ct.dtype)
    return jax.vjp(lambda x: jax.lax.ragged_dot(x, rhs, rows), lhs)[1](ct)[0]


def _xla_tgmm(lhs, ct, rows):
    """The pullback of ``ragged_dot`` in its rhs, at ct."""
    rhs = jnp.zeros((rows.shape[0], lhs.shape[1], ct.shape[1]), lhs.dtype)
    return jax.vjp(lambda w: jax.lax.ragged_dot(lhs, w, rows), rhs)[1](ct)[0]


# ---------------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------------

def _route(mode, m, k, n, rows, *operands):
    """``(tiles, interpret)`` where the kernel runs this product, else
    ``(None, False)``; the site noted either way."""
    use, interpret = use_pallas()
    dtypes = {x.dtype for x in operands}
    tiling = None
    if use and len(dtypes) == 1 and dtypes <= {jnp.dtype(jnp.bfloat16),
                                               jnp.dtype(jnp.float32)}:
        tiling = tiles(m, k, n, rows.shape[0], dtypes.pop(), mode)
    _note_site(mode, "xla" if tiling is None else "kernel", (m, k, n),
               tiling)
    return tiling, interpret


def _rows_covered(out, rows):
    """out with its rows past ``sum(rows)`` zero."""
    covered = jax.lax.broadcasted_iota(jnp.int32, (out.shape[0], 1), 0) \
        < jnp.sum(rows)
    return jnp.where(covered, out, 0)


def grouped_matmul(lhs, rhs, rows):
    """``out[r] = lhs[r] @ rhs[g(r)]``: lhs [M, K], its first ``sum(rows)``
    rows sorted by group; rhs [G, K, N]; rows [G] int32.  [M, N] in lhs's
    dtype, the rows past ``sum(rows)`` zero."""
    (m, k), n = lhs.shape, rhs.shape[2]
    tiling, interpret = _route("forward", m, k, n, rows, lhs, rhs)
    out = (_xla_gmm(lhs, rhs, rows) if tiling is None
           else _gmm(lhs, rhs, rows, tiling, False, interpret))
    return _rows_covered(out, rows)


def grouped_matmul_t(ct, rhs, rows):
    """``out[r] = ct[r] @ rhs[g(r)]^T``: ct [M, N], rhs [G, K, N]; the
    cotangent of :func:`grouped_matmul`'s lhs, [M, K], the rows past
    ``sum(rows)`` zero."""
    (m, n), k = ct.shape, rhs.shape[1]
    tiling, interpret = _route("lhs_grad", m, n, k, rows, ct, rhs)
    out = (_xla_gmm_t(ct, rhs, rows) if tiling is None
           else _gmm(ct, rhs, rows, tiling, True, interpret))
    return _rows_covered(out, rows)


def grouped_matmul_rhs_grad(lhs, ct, rows):
    """``out[g] = lhs[rows of g]^T @ ct[rows of g]``: lhs [M, K], ct
    [M, N]; the cotangent of :func:`grouped_matmul`'s rhs, [G, K, N],
    zero for a group with no row."""
    (m, k), n = lhs.shape, ct.shape[1]
    tiling, interpret = _route("rhs_grad", m, k, n, rows, lhs, ct)
    if tiling is not None:
        return _tgmm(lhs, ct, rows, tiling, interpret)
    return jnp.where((rows > 0)[:, None, None], _xla_tgmm(lhs, ct, rows), 0)
