"""Flash attention (fwd + bwd) as Pallas TPU kernels.

Reference analogue: the fused attention kernels under
``paddle/fluid/operators/fused/`` (fusion_* ops) — hand-fused native kernels
for the hot path.  On TPU the hot path is attention; this module implements
the FlashAttention-2 blocked online-softmax algorithm so the [B,H,T,T]
score matrix never touches HBM:

* forward: grid (B*H, Tq/bq, spans of K); a grid step holds one Q block and
  a span of K and V (the whole sequence up to 4 MiB: ``_span``) and sweeps
  the span's K chunks of ``bk`` rows in a loop inside the kernel; running
  (m, l, acc) live in VMEM scratch across the sweep; output + m and l
  written when the last span ends.
* backward: two kernels — dK/dV (a K block a grid step, sweeping the Q
  chunks of a span of Q and dO) and dQ (a Q block, sweeping K chunks) —
  using the saved m and l and the precomputed delta = rowsum(dO * O), the
  standard FA2 recomputation split.  Each is ONE ``pallas_call`` a site
  and one small body (a loop, never a body unrolled over blocks): every
  site's kernels are traced when a Program is built and traced and lowered
  again at its first step, so a kernel's trace cost is paid twice a site in
  set-up (``tests/test_flash_attention.py::test_one_small_kernel_a_site``).

Supported bias: an additive key-padding bias of shape [B, Tk] (the common
[B,1,1,Tk] mask squeezed), broadcast over heads and query positions; it is
treated as constant (no gradient — padding masks are data, not parameters).
Causal masking is a flag, and with it ``window``: key j is visible to
query i iff ``j <= i`` and ``i - j < window`` (a band of ``window`` keys,
the query's own among them).  A sweep's loop bounds come from where the
diagonal and the window's edge lie: chunks wholly inside the band run
without a mask, chunks the diagonal or the edge crosses run the same step
with the mask (loops over one body; the dK/dV kernel computes a square
block on the diagonal in two parts that leave out the quarter above it:
``_tiles``), chunks above the diagonal or wholly behind the edge are not
visited at all; where a sequence needs several spans, a grid step whose
span lies wholly outside the band runs no chunk and its block index is
clamped to the nearest one inside, so nothing is fetched for it either.

Head groups: K and V may have fewer heads than Q (``[B, Hkv, T, d]``, ``H
% Hkv == 0``); query head h reads key-value head ``h // (H // Hkv)``.
The block specs of K and V (and of dK and dV) index the key-value head,
so nothing ``[B, H, T, d]`` is ever made from K or V; the dK/dV kernel's
sweep runs over the group's query heads as well as their Q chunks and
writes a K block's dK and dV once, summed over the group.

Q and K share one head width ``d`` (the contraction of the scores); V has
its own, ``dv``, which is also the output's: latent attention carries a
rotary part on Q and K only (192 against 128).  The forward's PV product
and accumulator, dO, dV and delta are ``dv`` wide, the scores, dQ and dK
``d`` wide; nothing is padded to the wider of the two.

Attention-probability dropout IS supported in-kernel (``dropout_rate``):
the FA2 formulation — the softmax denominator l comes from the UNdropped
probabilities, dropout scales the numerator entries feeding the PV matmul
— so the [B,H,T,T] mask never materializes in HBM.  Mask bits come from
the TPU hardware PRNG (``pltpu.prng_seed``/``prng_random_bits``), seeded
per (batch·head, q-block, k-block) so the backward recomputation draws
the IDENTICAL mask.  ``pltpu`` PRNG has no CPU lowering, so interpret-
mode tests set ``PADDLE_TPU_FLASH_DROPOUT_DEBUG=iota``: mask bits then
come from a position hash (same formula exposed as
:func:`debug_keep_mask`) letting CPU tests verify the dropout MATH
against the XLA reference; the hardware PRNG path is validated on-chip.

Per-row stats (m, l) live in (block_q, 128) VMEM scratch with the value
replicated across lanes, and the arithmetic on them stays in that form
(``_lanes`` widens it to a score block by repeating whole vregs): the only
reductions of a chunk are the row max and row sum of its scores.  The
backward kernels take the saved rows as they are: dK/dV keeps the keys
down the rows of its score blocks, so m, l and delta are lane vectors and
P^T dO, dS^T Q are plain products (no transpose of a score block); dQ turns
the three rows of its Q block to columns once a sweep.  1/l is an exact
division a row, and ``sm_scale`` meets dK and dQ once, at the end.

Off-TPU, and for shapes the kernel does not cover, the entry point routes
to a pure-XLA implementation; set ``PADDLE_TPU_PALLAS=interpret`` to force
the Pallas kernels in interpreter mode (CPU correctness tests), or ``=off``
to force the XLA path (see :func:`paddle_tpu.ops.pallas.use_pallas`).
"""

import contextlib
import functools
import math
import operator
import os
import threading

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import use_pallas

NEG_INF = -1e30



def _rows(x, y):
    """The ``(1, n)`` rows of two ``(n, 128)`` statistics that hold a
    row's number in every lane.  One transpose on the XLU where Mosaic
    has it (whole 128 x 128 tiles), x in the lower lanes and y in the
    upper: a transposed vreg costs two issue slots, a column turned by
    rotations ten."""
    n = x.shape[0]
    if n % 128:
        return x[:, :1].reshape(1, n), y[:, :1].reshape(1, n)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    both = jax.lax.select(lane < 64, x, y).T
    return both[:1], both[64:65]


def _dropout_debug():
    return os.environ.get("PADDLE_TPU_FLASH_DROPOUT_DEBUG") == "iota"


def _rate_threshold(rate):
    """uint32 threshold: keep a cell iff its random bits >= threshold."""
    return jnp.uint32(min(int(rate * 4294967296.0), 4294967295))


def _hash_bits(b, r, c, seed):
    """Position-hash mask bits (debug/CPU path) — uint32 wraparound
    arithmetic, identical inside the kernel and in debug_keep_mask."""
    h = (r * jnp.uint32(2654435761)
         ^ (c * jnp.uint32(97559) + b * jnp.uint32(31)))
    h = h ^ seed.astype(jnp.uint32)
    return h * jnp.uint32(2246822519)


def _keep_mask(shape, rate, seed_ref, bh, block, debug, keys_down=False):
    """In-kernel Bernoulli keep mask of batch·head bh for the block
    ``block`` = (Q block, K block, its first query, its first key),
    ``shape`` = (block_q, block_k); transposed, the same draws, if
    ``keys_down``.  Hardware path: per-block counter seeding of the TPU
    PRNG (fwd and bwd seed identically, so the draw reproduces)."""
    qi, kj, q_pos, k_pos = block
    if debug:
        if keys_down:
            shape = shape[::-1]
        r = (jax.lax.broadcasted_iota(jnp.uint32, shape, int(keys_down))
             + jnp.asarray(q_pos, jnp.uint32))
        c = (jax.lax.broadcasted_iota(jnp.uint32, shape, int(not keys_down))
             + jnp.asarray(k_pos, jnp.uint32))
        bits = _hash_bits(bh.astype(jnp.uint32), r, c, seed_ref[0])
    else:
        # v5e Mosaic caps prng_seed at 2 words ("Setting seed with more
        # than 2 values is not supported") — use BOTH words: batch·head
        # XORs into the user seed (word 0) so distinct bh never collide,
        # and only (qi, kj) share the mixing word.  Deterministic in
        # (bh, qi, kj), so the bwd recompute draws the identical mask;
        # int32 wraparound is well-defined in Mosaic.
        mix = qi * jnp.int32(7919) + kj * jnp.int32(104729)
        pltpu.prng_seed(seed_ref[0] ^ bh, mix)
        bits = pltpu.prng_random_bits(shape)
        bits = pltpu.bitcast(bits.T if keys_down else bits, jnp.uint32)
    return bits >= _rate_threshold(rate)


def debug_keep_mask(bh, tq, tk, rate, seed):
    """Full-matrix keep mask for the debug hash — the OUT-of-kernel twin
    of the kernel's debug path, used by CPU tests and the XLA fallback
    under PADDLE_TPU_FLASH_DROPOUT_DEBUG=iota."""
    b = jnp.arange(bh, dtype=jnp.uint32)[:, None, None]
    r = jnp.arange(tq, dtype=jnp.uint32)[None, :, None]
    c = jnp.arange(tk, dtype=jnp.uint32)[None, None, :]
    bits = _hash_bits(b, r, c, jnp.uint32(seed))
    return bits >= _rate_threshold(rate)


# ---------------------------------------------------------------------------
# What a kernel call visits: chunks of a grid step's span, up to the diagonal
# ---------------------------------------------------------------------------

_noting = threading.local()


@contextlib.contextmanager
def noting_blocks(counts):
    """While active on this thread, every flash kernel call that is
    traced adds its (block_q, block_k) blocks to ``counts[(kernel,
    kind)]``, kind ``possible`` (the whole rectangle), ``visited`` (those
    a sweep computes) and ``masked`` (visited blocks the diagonal
    crosses), each times batch·head.  The Executor holds it open while
    it traces a step (``observability.runtime.record_flash_blocks``)."""
    was = getattr(_noting, "counts", None)
    _noting.counts = counts
    try:
        yield counts
    finally:
        _noting.counts = was


def _note_blocks(kernel, bh, nq, nk, block_q, block_k, causal, window=None):
    counts = getattr(_noting, "counts", None)
    if counts is None:
        return
    band = math.inf if window is None else window

    def crossed(i, j):  # by the diagonal, or by the window's edge
        return (j * block_k + block_k - 1 > i * block_q
                or i * block_q + block_q - 1 - j * block_k >= band)

    seen = [crossed(i, j) for i in range(nq) for j in range(nk)
            if not causal or (j * block_k <= i * block_q + block_q - 1
                              and i * block_q - (j * block_k + block_k - 1)
                              < band)]
    for kind, n in (("possible", nq * nk), ("visited", len(seen)),
                    ("masked", sum(seen) if causal else 0)):
        counts[kernel, kind] += bh * n
        if window is not None:  # the sites with a window, again
            counts[kernel, "window_" + kind] += bh * n


def _span(n, rows, row_bytes):
    """How many of the ``n`` blocks of ``rows`` rows that a sweep walks
    one grid step holds in VMEM (a divisor of ``n``): as many as 3 MiB
    take (twice that with the pipeline's second buffer, beside 4 to 6 MiB
    of score blocks, under Mosaic's 16 MiB), so that a sweep is a loop
    inside the kernel and not a grid step a block (0.13-0.23 us each on
    a v5e, the blocks above the diagonal among them)."""
    span = n
    while span > 1 and (span * rows * row_bytes > 3 << 20 or n % span):
        span -= 1
    return span


def _sweep(ranges, step):
    """``step(c, masked)`` for the chunks ``lo <= c < hi`` of each
    ``(masked, lo, hi)``: a loop in the kernel, its bounds static (not
    causal) or computed from where the diagonal and the window's edge
    lie.  ``(masked, lo, hi, lo2, hi2)``: the chunks ``lo2 <= c < hi2``
    too, in the same loop (the edge's chunks and the diagonal's are one
    masked loop over one body, with the chunks between them left out)."""
    for masked, lo, hi, *then in ranges:
        if then:
            first = _isub(hi, lo)
            skip = _isub(then[0], hi)
            jax.lax.fori_loop(
                0, _iadd(first, _isub(then[1], then[0])),
                lambda t, _, masked=masked, lo=lo, first=first, skip=skip:
                step(_iadd(_iadd(lo, t), jax.lax.select(
                    jax.lax.lt(t, first), 0, skip)), masked), None)
        elif isinstance(hi, int) and isinstance(lo, int) and hi - lo == 1:
            step(lo, masked)  # not causal, one block: no loop
        else:
            jax.lax.fori_loop(
                lo, hi, lambda c, _, masked=masked: step(c, masked), None)


def _ints(python, lax):
    """Index arithmetic: Python's own on two ints, else one lax call (a
    jax.numpy operator on a traced scalar costs five to trace)."""
    def op(x, y):
        both = isinstance(x, int) and isinstance(y, int)
        return python(x, y) if both else lax(x, y)
    return op


_iadd = _ints(operator.add, jax.lax.add)
_isub = _ints(operator.sub, jax.lax.sub)
_imul = _ints(operator.mul, jax.lax.mul)


def _at(first, n):
    """``n`` rows (or lanes) from ``first``, a multiple of ``n``."""
    return pl.ds(first if isinstance(first, int)
                 else pl.multiple_of(first, n), n)


def _tiles(masked, block_q, block_k):
    """The parts of a ``block_q x block_k`` block that the dK/dV kernel
    computes, each ``(q0, queries, k0, keys)``: the whole block, or, of a
    square block the diagonal crosses (corner to corner then), the three
    quarters at and under it in two parts: the early queries against the
    early keys, the late queries against all.  The quarter above the
    diagonal costs no product.  (Only dK/dV, whose step holds four
    products: its second copy of the step is traced once a site, the
    forward's would be twice, for a third less to save.)"""
    half = block_q // 2
    if masked is not True or block_q != block_k or half % 128:
        return [(0, block_q, 0, block_k)]  # EDGE: no line corner to corner
    return [(0, half, 0, half), (half, half, 0, block_k)]


def _div(x, n):
    """``x // n`` for a traced ``x >= 0``: one equation, where the
    floor division of jax.numpy lowers a dozen."""
    return jax.lax.div(x, jnp.int32(n))


def _within(x, span):
    return jax.lax.min(jax.lax.max(x, 0), span)


# The arithmetic of the kernel bodies is written with jax.lax functions,
# operands of one shape: a jax.numpy operator on a tracer costs five times
# a lax call to trace, and set-up traces every site's kernels twice
_add, _sub, _mul, _exp = jax.lax.add, jax.lax.sub, jax.lax.mul, jax.lax.exp


def _select(keep, x, other):
    """``jnp.where(keep, x, other)`` for a scalar ``other``, inline."""
    return jax.lax.select(keep, x, jax.lax.full_like(x, other))


def _cast(x, like):
    return jax.lax.convert_element_type(x, like.dtype)


def _over_keys(reduce, s):
    """A row reduction of the scores ``(rows, keys)`` as ``(rows, 128)``,
    every lane the row's number."""
    return jax.lax.broadcast_in_dim(reduce(s, (1,)), (s.shape[0], 128), (0,))


def _down(row, n):
    """A ``(1, q)`` row of statistics against ``(n, q)`` scores."""
    return jax.lax.broadcast_in_dim(row, (n, row.shape[1]), (0, 1))


def _major(axis, n):
    """The grid's index along the swept axis, a Python 0 where one step
    holds the whole sweep (then ``pl.when`` sees plain booleans)."""
    return pl.program_id(axis) if n > 1 else 0


def _lanes(x, n):
    """``(rows, n)`` from ``(rows, 128)`` whose lanes all hold the row's
    number: whole vregs repeated, or a prefix of the lanes, so that no
    value crosses lanes."""
    if n % 128 == 0:
        return x if n == 128 else pltpu.repeat(x, n // 128, axis=1)
    if n < 128:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _column(row):
    """A ``(1, n)`` row of statistics as ``(n, 128)``, its number in
    every lane: the inverse of :func:`_row`."""
    n = row.shape[-1]
    if n % 128 == 0:
        return jnp.broadcast_to(row, (128, n)).T
    return jnp.broadcast_to(row.reshape(n, 1), (n, 128))


def _scores(x, y, sm_scale, bias, offset, keys_down, window=None):
    """``x @ y.T * sm_scale (+ bias)`` in float32 from input-dtype
    operands.  ``offset`` (None: no mask) is the first key's position
    less the first query's: the score of query r and key c of the block
    is visible iff ``r - c >= offset`` and, under a ``window``, ``r - c <
    offset + window``; ``keys_down`` says the keys run down the rows (the
    dK/dV kernel's orientation)."""
    # matmuls run at the INPUT dtype with f32 accumulation: under bf16
    # AMP the MXU's bf16 rate is ~4x its f32 rate, and bf16xbf16->f32 is
    # bit-identical to upcast-then-f32 (bf16 casts are exact;
    # 8-bit-mantissa products fit f32's 24); f32 inputs are unchanged
    s = _mul(jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ), sm_scale)
    if bias is not None:
        s = s + bias  # a row or a column against the block
    if offset is not None:
        r = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        c = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ahead = _sub(c, r) if keys_down else _sub(r, c)
        seen = jax.lax.ge(ahead, offset)
        if window is not None:
            seen = jax.lax.bitwise_and(
                seen, jax.lax.lt(ahead, _iadd(offset, window)))
        s = _select(seen, s, NEG_INF)
    return s


EDGE = "edge"  # masked, by a window's edge too: truthy, and not True


def _k_sweep(causal, window, i, J, span, block_q, block_k):
    """The K chunks of grid step ``J``'s span that Q block ``i`` sees:
    those wholly under the diagonal unmasked, then those it crosses;
    under a ``window``, those wholly inside the band, then those its
    edge crosses with those the diagonal crosses, and none behind the
    edge."""
    if not causal:
        return [(False, 0, span)]
    first, base = _imul(i, block_q), _imul(J, span)
    whole = _within(_isub(_div(_iadd(first, 1), block_k), base), span)
    seen = _within(_isub(_iadd(_div(_iadd(first, block_q - 1), block_k), 1),
                         base), span)
    if window is None:
        return [(False, 0, whole), (True, whole, seen)]
    # keys before ``first - window + 1`` lie behind the edge for every
    # query of the block; from ``first + block_q - window`` on, for none
    edge = _within(_isub(_div(jax.lax.max(_isub(first, window - 1), 0),
                              block_k), base), span)
    inside = jax.lax.min(whole, _within(_isub(_div(jax.lax.max(
        _iadd(first, block_q - window + block_k - 1), 0), block_k), base),
        span))
    return [(False, inside, whole), (EDGE, edge, inside, whole, seen)]


def _q_sweep(causal, window, j, I, span, block_q, block_k):
    """The Q chunks of grid step ``I``'s span that see K block ``j``:
    those the diagonal crosses, then those under it; under a ``window``
    the latter end where its edge begins to cross, the chunks it crosses
    share the diagonal's loop (whole blocks then: ``_tiles``), and none
    follow them."""
    if not causal:
        return [(False, 0, span)]
    first, base = _imul(j, block_k), _imul(I, span)
    seen = _within(_isub(_div(first, block_q), base), span)
    whole = _within(_isub(
        _div(_iadd(first, block_k + block_q - 2), block_q), base), span)
    if window is None:
        return [(True, seen, whole), (False, whole, span)]
    # a query from ``first + window`` on has lost the block's first key,
    # one from ``first + block_k - 1 + window`` on its last
    inside = _within(_isub(_div(_iadd(first, window), block_q), base), span)
    last = _within(_isub(_iadd(_div(
        _iadd(first, window + block_k - 2), block_q), 1), base), span)
    crossed = jax.lax.min(whole, inside)
    return [(EDGE, seen, crossed, inside, last), (False, crossed, inside)]


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, *rest, n_major, sm_scale, causal,
                window, has_bias, block_q, block_k, dropout_rate,
                dropout_debug):
    bias_ref = rest[0] if has_bias else None
    seed_ref, o_ref, m_out_ref, l_out_ref, acc_ref, m_ref, l_ref = \
        rest[has_bias:]
    b, i, J = pl.program_id(0), pl.program_id(1), _major(2, n_major)
    span, dv = k_ref.shape[1] // block_k, acc_ref.shape[-1]

    @pl.when(J == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _step(c, masked):
        j = _iadd(_imul(J, span), c)  # this K chunk among the sequence's
        q_pos, k_pos = _imul(i, block_q), _imul(j, block_k)
        keys = _at(_imul(c, block_k), block_k)
        v = v_ref[0, keys, :]  # [bk, dv]
        s = _scores(
            q_ref[0], k_ref[0, keys, :], sm_scale,
            bias_ref[0, :, keys].astype(jnp.float32) if has_bias else None,
            _isub(k_pos, q_pos) if masked else None, False, window)
        # m and l stay in their (bq, 128) form, every lane the row's
        # number: the only reductions of a step are the scores' own
        m_prev = m_ref[:]
        m_new = jax.lax.max(m_prev, _over_keys(jax.lax.reduce_max, s))
        alpha = _exp(_sub(m_prev, m_new))
        p = _exp(_sub(s, _lanes(m_new, block_k)))
        # FA2 dropout: l accumulates the UNdropped p (true softmax
        # denominator); only the numerator entries feeding PV are masked
        l_ref[:] = _add(_mul(alpha, l_ref[:]),
                        _over_keys(jax.lax.reduce_sum, p))
        m_ref[:] = m_new
        if dropout_rate > 0.0:
            keep = _keep_mask(p.shape, dropout_rate, seed_ref, b,
                              (i, j, q_pos, k_pos), dropout_debug)
            p = _select(keep, _mul(p, 1.0 / (1.0 - dropout_rate)), 0.0)
        # PV at input dtype (p downcast under AMP): the MXU-rate
        # tradeoff mha_reference makes identically; acc stays f32
        acc_ref[:] = _add(
            _mul(acc_ref[:], _lanes(alpha, dv)),
            jax.lax.dot_general(
                _cast(p, v), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))

    _sweep(_k_sweep(causal, window, i, J, span, block_q, block_k), _step)

    @pl.when(J == n_major - 1)
    def _finalize():
        l = l_ref[:]
        l = _select(l != 0.0, l, 1.0)  # a row no chunk reached: zeros
        o_ref[0] = (acc_ref[:] / _lanes(l, dv)).astype(o_ref.dtype)
        # m and l are saved SEPARATELY (not lse = m + log l): when |m| is
        # large (e.g. -1e4 padding bias on every visible key) the f32 sum
        # m + log(l) loses all bits of log(l); exp(s - m) * (1/l) in the
        # backward reproduces the forward's p instead
        m_out_ref[0], l_out_ref[0] = _rows(m_ref[:], l)


def _operands(q, k, v, bias, q_rows, k_rows, blocks_of):
    """``spec`` and the specs and arguments every kernel starts with, q,
    k, v (, bias), in blocks of ``q_rows`` and ``k_rows`` rows.
    ``blocks_of(*grid indices) -> (b, i, j)`` says which blocks a grid
    step holds, ``b`` the query's batch·head; ``spec(shape, index)`` is
    the BlockSpec at ``index(b, i, j)``.  K and V are indexed by their
    own head, ``b // group``."""
    group = q.shape[0] // k.shape[0]

    def spec(shape, index):
        return pl.BlockSpec(shape, lambda *g: index(*blocks_of(*g)))

    def kv(b, i, j):
        return (b if group == 1 else _div(b, group), j, 0)

    specs = [spec((1, q_rows, q.shape[2]), lambda b, i, j: (b, i, 0)),
             spec((1, k_rows, k.shape[2]), kv),
             spec((1, k_rows, v.shape[2]), kv)]
    args = [q, k, v]
    if bias is not None:
        nheads = q.shape[0] // bias.shape[0]
        specs.append(spec((1, 1, k_rows),
                          lambda b, i, j: (b // nheads, 0, j)))
        args.append(bias.reshape(bias.shape[0], 1, k.shape[1]))
    return spec, specs, args


def _clamped(J, lo=None, hi=None):
    """Span ``J`` of a sweep, or the nearest that the band of its block
    touches (``lo .. hi``, None: the sequence's end): a step outside the
    band runs no chunk, and a block index that repeats is not fetched
    again."""
    if hi is not None:
        J = jnp.minimum(J, hi)
    return J if lo is None else jnp.maximum(J, lo)


def _q_major(causal, window, block_q, k_rows, n_major):
    """``blocks_of`` for a grid (b, Q block i, span J of K): a causal
    step whose span lies wholly above the diagonal is given the last
    span under it, one wholly behind the window's edge the first span
    the band touches."""
    if not causal or n_major == 1:
        return lambda b, i, J: (b, i, J)
    return lambda b, i, J: (b, i, _clamped(
        J, None if window is None
        else _div(jnp.maximum(i * block_q - (window - 1), 0), k_rows),
        _div(i * block_q + block_q - 1, k_rows)))


def _flash_fwd(q, k, v, bias, seed, causal, sm_scale, block_q, block_k,
               interpret, dropout_rate, dropout_debug, window=None):
    bh, tq, d = q.shape
    _, tk, dv = v.shape
    nq, nk = tq // block_q, tk // block_k
    _note_blocks("fwd", bh, nq, nk, block_q, block_k, causal, window)
    span = _span(nk, block_k, (d + dv) * k.dtype.itemsize)
    k_rows = span * block_k
    _, in_specs, args = _operands(
        q, k, v, bias, block_q, k_rows,
        _q_major(causal, window, block_q, k_rows, nk // span))
    stat = pl.BlockSpec((1, 1, block_q), lambda b, i, J: (b, 0, i))

    o, m_out, l_out = pl.pallas_call(
        functools.partial(
            _fwd_kernel, n_major=nk // span, sm_scale=sm_scale, causal=causal,
            window=window, has_bias=bias is not None, block_q=block_q,
            block_k=block_k, dropout_rate=dropout_rate,
            dropout_debug=dropout_debug),
        name="flash_attention_fwd",
        grid=(bh, nq, nk // span),
        in_specs=in_specs + [pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i, J: (b, i, 0)),
            stat, stat,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, tq), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, tq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
    )(*args, seed)
    return o, m_out, l_out


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------

def _bwd_dkv_kernel(q_ref, k_ref, v_ref, *rest, n_major, group, sm_scale,
                    causal, window, has_bias, block_q, block_k, dropout_rate,
                    dropout_debug):
    """dK and dV of one K block over its sweep of Q chunks, with the keys
    down the rows of every score-shaped value (the transpose of the
    forward's): the rows' statistics m, l, delta are then lane vectors
    used as saved, and P^T dO and dS^T Q are plain products.  The grid's
    last axis walks the ``group`` query heads that read this key-value
    head, and each one's ``n_major`` spans of Q and dO: the sums of all
    of them are written once."""
    bias_ref = rest[0] if has_bias else None
    seed_ref, do_ref, m_ref, l_ref, dl_ref, dk_ref, dv_ref, dk_acc, \
        dv_acc, *bias_col = rest[has_bias:]
    b, j, G = pl.program_id(0), pl.program_id(1), _major(2, group * n_major)
    I = G
    if group > 1:  # b: the key-value head's; the query head's for dropout
        b = _iadd(_imul(b, group), _div(G, n_major))
        I = jax.lax.rem(G, n_major) if n_major > 1 else 0
    span = q_ref.shape[1] // block_q

    @pl.when(G == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)
        if has_bias:  # the K block's bias, turned to a column once
            bias_col[0][:] = _column(bias_ref[0].astype(jnp.float32))

    def _step(c, masked):
        i = _iadd(_imul(I, span), c)  # this Q chunk among the sequence's
        q_pos, k_pos = _imul(i, block_q), _imul(j, block_k)
        if dropout_rate > 0.0:
            # the SAME seeding as the forward reproduces the mask;
            # O = P_drop V, so dV uses P_drop and the softmax-jacobian
            # input is the mask-scaled dP (sum P·dP = delta still holds
            # because delta = rowsum(dO·O))
            keep = _keep_mask((block_q, block_k), dropout_rate, seed_ref,
                              b, (i, j, q_pos, k_pos), dropout_debug,
                              keys_down=True)
            inv_keep = 1.0 / (1.0 - dropout_rate)
        for q0, qn, k0, kn in _tiles(masked, block_q, block_k):
            rows = _at(_iadd(_imul(c, block_q), q0), qn)
            keys = slice(k0, k0 + kn)
            q = q_ref[0, rows, :]
            do = do_ref[0, rows, :]
            s = _scores(
                k_ref[0, keys, :], q, sm_scale,
                _lanes(bias_col[0][keys, :], qn) if has_bias else None,
                _isub(_iadd(k_pos, k0), _iadd(q_pos, q0)) if masked
                else None, True, window)
            p = _mul(_exp(_sub(s, _down(m_ref[0, :, rows], kn))),
                     _down(jax.lax.div(1.0, l_ref[0, :, rows]), kn))
            # dP^T = V @ dO^T
            dp = jax.lax.dot_general(
                v_ref[0, keys, :], do, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if dropout_rate > 0.0:
                kept = keep[keys, q0:q0 + qn]
                p_v = _select(kept, _mul(p, inv_keep), 0.0)
                dp = _select(kept, _mul(dp, inv_keep), 0.0)
            else:
                p_v = p
            # dV += P_drop^T @ dO
            dv_acc[keys, :] = _add(dv_acc[keys, :], jax.lax.dot_general(
                _cast(p_v, do), do, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ))
            # dS = P * (dP_masked - delta); dK += dS^T @ Q, scaled at
            # the end
            ds = _mul(p, _sub(dp, _down(dl_ref[0, :, rows], kn)))
            dk_acc[keys, :] = _add(dk_acc[keys, :], jax.lax.dot_general(
                _cast(ds, q), q, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ))

    _sweep(_q_sweep(causal, window, j, I, span, block_q, block_k), _step)

    @pl.when(G == group * n_major - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[:] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, *rest, n_major, sm_scale, causal,
                   window, has_bias, block_q, block_k, dropout_rate,
                   dropout_debug):
    bias_ref = rest[0] if has_bias else None
    seed_ref, do_ref, m_ref, l_ref, dl_ref, dq_ref, dq_acc, m_col, \
        linv_col, dl_col = rest[has_bias:]
    b, i, J = pl.program_id(0), pl.program_id(1), _major(2, n_major)
    span = k_ref.shape[1] // block_k

    @pl.when(J == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        # the Q block's statistics, turned to columns once a sweep
        m_col[:] = _column(m_ref[0])
        linv_col[:] = _column(1.0 / l_ref[0])
        dl_col[:] = _column(dl_ref[0])

    def _step(c, masked):
        j = _iadd(_imul(J, span), c)
        q_pos, k_pos = _imul(i, block_q), _imul(j, block_k)
        keys = _at(_imul(c, block_k), block_k)
        k = k_ref[0, keys, :]
        s = _scores(
            q_ref[0], k, sm_scale,
            bias_ref[0, :, keys].astype(jnp.float32) if has_bias else None,
            _isub(k_pos, q_pos) if masked else None, False, window)
        p = _mul(_exp(_sub(s, _lanes(m_col[:], block_k))),
                 _lanes(linv_col[:], block_k))
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0, keys, :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if dropout_rate > 0.0:
            keep = _keep_mask(p.shape, dropout_rate, seed_ref, b,
                              (i, j, q_pos, k_pos), dropout_debug)
            dp = _select(keep, _mul(dp, 1.0 / (1.0 - dropout_rate)), 0.0)
        ds = _mul(p, _sub(dp, _lanes(dl_col[:], block_k)))
        dq_acc[:] = _add(dq_acc[:], jax.lax.dot_general(
            _cast(ds, k), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ))

    _sweep(_k_sweep(causal, window, i, J, span, block_q, block_k), _step)

    @pl.when(J == n_major - 1)
    def _finalize():
        dq_ref[0] = (dq_acc[:] * sm_scale).astype(dq_ref.dtype)


def _flash_bwd(q, k, v, bias, seed, o, m, l, do, causal, sm_scale,
               block_q, block_k, interpret, dropout_rate, dropout_debug,
               window=None):
    bh, tq, d = q.shape
    bkv, tk, d_v = v.shape
    group = bh // bkv
    nq, nk = tq // block_q, tk // block_k
    item = q.dtype.itemsize

    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )[:, None, :]  # [bh, 1, tq], matching the saved m/l row layout
    kw = dict(sm_scale=sm_scale, causal=causal, window=window,
              has_bias=bias is not None, block_q=block_q, block_k=block_k,
              dropout_rate=dropout_rate, dropout_debug=dropout_debug)

    def operands(q_rows, k_rows, blocks_of):
        """... then seed, dO, m, l, delta: the rest of both kernels'."""
        spec, specs, args = _operands(q, k, v, bias, q_rows, k_rows,
                                      blocks_of)
        stat = spec((1, 1, q_rows), lambda b, i, j: (b, 0, i))
        specs += [pl.BlockSpec(memory_space=pltpu.SMEM),
                  spec((1, q_rows, d_v), lambda b, i, j: (b, i, 0)),
                  stat, stat, stat]
        return specs, args + [seed, do, m, l, delta]

    # --- dK/dV: a K block's sweep of the group's heads and their Q chunks ---
    _note_blocks("dkv", bh, nq, nk, block_q, block_k, causal, window)
    span = _span(nq, block_q, (d + d_v) * item + 12)
    q_rows, n_major = span * block_q, nq // span

    def blocks_of(b, j, G):
        """Grid (key-value head b, K block j, G = query head of the group
        x span I of Q): a span outside the band, see _q_major."""
        I = G
        if group > 1:
            b, I = b * group + _div(G, n_major), jax.lax.rem(G, n_major)
        if causal and n_major > 1:
            I = _clamped(I, _div(j * block_k, q_rows),
                         None if window is None else _div(
                             j * block_k + block_k + window - 2, q_rows))
        return b, I, j

    specs, args = operands(q_rows, block_k, blocks_of)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, n_major=n_major, group=group,
                          **kw),
        name="flash_attention_dkv",
        grid=(bkv, nk, group * n_major),
        in_specs=specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, G: (b, j, 0)),
            pl.BlockSpec((1, block_k, d_v), lambda b, j, G: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bkv, tk, d), k.dtype),
            jax.ShapeDtypeStruct((bkv, tk, d_v), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d_v), jnp.float32),
        ] + ([pltpu.VMEM((block_k, 128), jnp.float32)]
             if bias is not None else []),
        interpret=interpret,
    )(*args)

    # --- dQ: a Q block's sweep of K chunks ---
    _note_blocks("dq", bh, nq, nk, block_q, block_k, causal, window)
    span = _span(nk, block_k, (d + d_v) * item)
    k_rows = span * block_k
    specs, args = operands(block_q, k_rows, _q_major(
        causal, window, block_q, k_rows, nk // span))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, n_major=nk // span, **kw),
        name="flash_attention_dq",
        grid=(bh, nq, nk // span),
        in_specs=specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, J: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)]
        + [pltpu.VMEM((block_q, 128), jnp.float32)] * 3,
        interpret=interpret,
    )(*args)

    return dq, dk, dv


# ---------------------------------------------------------------------------
# XLA fallback (also the numerical reference in tests)
# ---------------------------------------------------------------------------

def mha_reference(q, k, v, bias=None, causal=False, sm_scale=None,
                  dropout_rate=0.0, seed=None, debug=False, window=None):
    """Plain-XLA multi-head attention. q: [B,H,T,D]; k: [B,Hkv,Tk,D]; v:
    [B,Hkv,Tk,Dv], query head h reading head ``h // (H // Hkv)`` of k
    and v; bias: [B,Tk]; ``window`` (with ``causal``): only the last
    ``window`` keys up to a query's own are visible to it.
    With dropout: upscale-in-train on the probabilities; the mask comes
    from the debug position hash (bit-matching the kernel's debug mode)
    or jax.random (statistically matching the kernel's hardware PRNG)."""
    d = q.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    heads, group = q.shape[1], q.shape[1] // k.shape[1]
    if group > 1:  # the group's heads as rows of their key-value head's
        q = q.reshape(q.shape[0], k.shape[1], group * q.shape[2], d)
    # matmuls run in the INPUT dtype (bf16 under AMP → full-rate MXU;
    # upcasting the operands to f32 would quarter the matmul rate) with
    # f32 accumulation; softmax statistics stay f32 either way
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * sm_scale
    if group > 1:
        s = s.reshape(s.shape[0], heads, -1, s.shape[-1])
    if bias is not None:
        s = s + bias[:, None, None, :].astype(jnp.float32)
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq - window)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_rate and dropout_rate > 0.0:
        b, h, tq, tk = p.shape
        sd = jnp.reshape(jnp.asarray(0 if seed is None else seed,
                                     jnp.int32), (1,))
        if debug:
            keep = debug_keep_mask(b * h, tq, tk, dropout_rate,
                                   sd[0]).reshape(b, h, tq, tk)
        else:
            keep = jax.random.bernoulli(
                jax.random.PRNGKey(sd[0]), 1.0 - dropout_rate, p.shape)
        keep = jax.lax.stop_gradient(keep)
        p = jnp.where(keep, p, 0.0) / (1.0 - dropout_rate)
    if group > 1:
        p = p.reshape(p.shape[0], v.shape[1], -1, p.shape[-1])
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v,
                   preferred_element_type=jnp.float32)
    if group > 1:
        o = o.reshape(o.shape[0], heads, -1, o.shape[-1])
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# Public entry: custom_vjp'd flash attention
# ---------------------------------------------------------------------------

def _pick_blocks(tq, tk):
    """Block shapes: env caps win (manual override for on-chip sweeps,
    tools/bench_flash.py --blocks), else the autotune cache's measured
    winner for this (tq, tk) on this backend, else the hand-set 512
    defaults; divisibility/alignment still enforced here."""
    cap_q = cap_k = None
    env_q = os.environ.get("PADDLE_TPU_FLASH_BLOCK_Q", "").strip()
    env_k = os.environ.get("PADDLE_TPU_FLASH_BLOCK_K", "").strip()
    if env_q:
        cap_q = int(env_q)
    if env_k:
        cap_k = int(env_k)
    if cap_q is None or cap_k is None:
        try:
            from ...autotune import cached_params

            won = cached_params("flash_blocks",
                                {"block_q": 512, "block_k": 512},
                                tq=tq, tk=tk)
            cap_q = cap_q if cap_q is not None else int(won["block_q"])
            cap_k = cap_k if cap_k is not None else int(won["block_k"])
        except Exception:  # pragma: no cover - autotune unavailable
            cap_q = cap_q if cap_q is not None else 512
            cap_k = cap_k if cap_k is not None else 512
    bq = max(8, min(cap_q, tq))
    while tq % bq:
        bq //= 2
    bk = max(128, min(cap_k, tk))
    while tk % bk:
        bk //= 2
    return bq, bk


def flash_min_t():
    """The sequence length at which the blocked Pallas kernel starts
    beating XLA's fused unblocked attention.  Round-5 v5e sweep
    (tools/bench_flash.py; the capture predates PR 1): XLA wins at T=128 (model-level
    +26%) and still edges the kernel at T=256 (attention-level 7-16%,
    both dropout regimes); the kernel wins at T=512 (+15% model-level,
    2.1x over XLA / 4.8x over the upstream jax kernel at T=2048) — so
    the boundary sits at 512.  Model builders (models/bert.py
    fuse_attn="auto") route by the same value.

    Resolution order: ``PADDLE_TPU_FLASH_MIN_T`` (manual override) →
    the autotune cache's recorded decision for this backend
    (``tools/decide_flash_min_t.py --write-cache``, or
    ``paddle_tpu.autotune.record_flash_min_t`` from an on-chip sweep)
    → the hand-set 512 default.  ``PADDLE_TPU_AUTOTUNE=0`` restores
    the pure env/default behavior bit-exactly."""
    env = os.environ.get("PADDLE_TPU_FLASH_MIN_T", "").strip()
    if env:
        return int(env)
    try:
        from ...autotune import flash_min_t_decision

        t = flash_min_t_decision()
        if t is not None:
            return int(t)
    except Exception:  # pragma: no cover - autotune unavailable
        pass
    return 512


def _kernel_applicable(q, k, bias, dv=None):
    bh, tq, d = q.shape
    _, tk, _ = k.shape
    if max(d, dv or d) > 512:
        return False
    # Perf heuristic (measured on v5e): the blocked kernel wins once the
    # score matrix per head exceeds ~256x256 (2.0-2.4x at T=2048); at
    # T=128 XLA's fused unblocked attention is faster, so let it have it.
    # The boundary is env-tunable (PADDLE_TPU_FLASH_MIN_T) so on-chip
    # sweeps (tools/bench_flash.py) can re-decide it — with in-kernel
    # dropout the break-even may sit lower, since the XLA path then pays
    # a materialized [B,H,T,T] mask the kernel never writes.
    min_t = flash_min_t()
    if max(tq, tk) < min_t and not use_pallas()[1]:
        return False
    bq, bk = _pick_blocks(tq, tk)
    if tq % bq or tk % bk or bq < 8 or bq % 8 or bk < 128 or bk % 128:
        return False
    if bias is not None and (bias.shape[0] == 0 or bh % bias.shape[0] != 0
                             or bias.shape[1] != tk):
        return False
    return True


def routes_to_kernel(q, k, bias=None, v=None):
    """Does :func:`flash_attention` run the Pallas kernels for these
    shapes (q, k: [B, H, T, D]; v: [B, H, Tk, Dv], taken to be as wide
    as k where left out; bias as the entry point takes it; anything
    with ``.shape``), or XLA attention?  The entry point's one routing
    decision."""
    b, h, tq, d = q.shape
    tk = k.shape[2]
    if bias is not None:
        bias = jax.ShapeDtypeStruct((bias.shape[0], bias.shape[-1]),
                                    jnp.float32)
    return use_pallas()[0] and _kernel_applicable(
        jax.ShapeDtypeStruct((b * h, tq, d), jnp.float32),
        jax.ShapeDtypeStruct((b * h, tk, d), jnp.float32), bias,
        dv=None if v is None else v.shape[-1])


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12))
def _flash(q, k, v, bias, seed, causal, sm_scale, block_q, block_k,
           interpret, dropout_rate, dropout_debug, window=None):
    o, _, _ = _flash_fwd(q, k, v, bias, seed, causal, sm_scale, block_q,
                         block_k, interpret, dropout_rate, dropout_debug,
                         window)
    return o


def _flash_fwd_rule(q, k, v, bias, seed, *static):
    o, m, l = _flash_fwd(q, k, v, bias, seed, *static)
    return o, (q, k, v, bias, seed, o, m, l)


def _flash_bwd_rule(*static_res_do):
    *static, res, do = static_res_do
    q, k, v, bias, seed, o, m, l = res
    dq, dk, dv = _flash_bwd(q, k, v, bias, seed, o, m, l, do, *static)
    dbias = None if bias is None else jnp.zeros_like(bias)
    return (dq, dk, dv, dbias, None)  # int seed: no cotangent


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(8, 16)))
def _flash_kept(q, k, v, bias, seed, o, m, l, *static):
    """:func:`_flash` where the forward kernel has run already and its
    ``(o, m, l)`` were kept: traces no kernel forward, and backward the
    two backward kernels over the same residuals."""
    return o


def _flash_kept_fwd_rule(q, k, v, bias, seed, o, m, l, *static):
    return o, (q, k, v, bias, seed, o, m, l)


def _flash_kept_bwd_rule(*static_res_do):
    return _flash_bwd_rule(*static_res_do) + (None, None, None)


_flash_kept.defvjp(_flash_kept_fwd_rule, _flash_kept_bwd_rule)


def flash_attention(q, k, v, bias=None, causal=False, sm_scale=None,
                    dropout_rate=0.0, dropout_seed=None, window=None,
                    residuals=None, return_residuals=False):
    """Multi-head attention: Pallas flash kernel on TPU, XLA elsewhere.

    q: [B, H, T, D]; k: [B, Hkv, Tk, D]; v: [B, Hkv, Tk, Dv] (Dv may
    differ from D; ``H % Hkv == 0``: query head h reads key-value head
    ``h // (H // Hkv)``, and K and V are never expanded to H heads);
    bias: additive key bias [B, Tk] or [B,1,1,Tk] (no gradient flows to
    bias); returns [B, H, Tq, Dv].  ``sm_scale`` defaults to 1/sqrt(D).
    ``window`` (an int >= 1, with ``causal``): key j is visible to query
    i iff ``j <= i`` and ``i - j < window``.

    dropout_rate > 0 applies attention-probability dropout IN-KERNEL
    (upscale-in-train semantics); ``dropout_seed`` is an int32 scalar or
    [1] array that must change per step.  On the XLA fallback the same
    rate is applied with jax.random (debug hash under
    PADDLE_TPU_FLASH_DROPOUT_DEBUG=iota, where both paths draw the
    identical mask for cross-checking).

    ``return_residuals``: return ``(out, residuals)``, where residuals
    is what the forward kernel computed and the backward kernels read,
    ``(o, m, l)`` as the kernels shape them, or None where XLA attention
    ran.  ``residuals``: such a triple from an earlier call on the same
    inputs; the forward kernel is not run again, and the gradient is the
    backward kernels' over these (a recompute region's re-run:
    ``ops/control_flow.py``).
    """
    if bias is not None:
        # constant on BOTH paths: the Pallas custom_vjp returns zero bias
        # cotangents, so the XLA fallback must not leak real ones either
        bias = jax.lax.stop_gradient(bias)
        if bias.ndim == 4:
            bias = bias.reshape(bias.shape[0], bias.shape[-1])
    d = q.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    dropout_rate = float(dropout_rate or 0.0)
    if not 0.0 <= dropout_rate < 1.0:
        raise ValueError(
            "dropout_rate must be in [0, 1), got %r (rate 1 would "
            "upscale by 1/0)" % dropout_rate)
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed")
    if window is not None:
        window = int(window)
        if not causal or window < 1:
            raise ValueError("window=%r wants causal=True and window >= 1"
                             % window)
        if window >= k.shape[2]:
            window = None  # every key up to the query's own: causal
    interpret = use_pallas()[1]
    debug = _dropout_debug()
    b, h, tq, _ = q.shape
    hkv, tk = k.shape[1], k.shape[2]
    if h % hkv or v.shape[1] != hkv:
        raise ValueError("%d query heads do not divide over k's %d and "
                         "v's %d" % (h, hkv, v.shape[1]))
    qf = q.reshape(b * h, tq, d)
    kf = k.reshape(b * hkv, tk, d)
    dv = v.shape[-1]
    vf = v.reshape(b * hkv, tk, dv)
    seed = jnp.reshape(
        jnp.asarray(0 if dropout_seed is None else dropout_seed,
                    jnp.int32), (1,))
    if not routes_to_kernel(q, k, bias, v):
        out = mha_reference(q, k, v, bias=bias, causal=causal,
                            sm_scale=sm_scale,
                            dropout_rate=dropout_rate, seed=seed,
                            debug=debug, window=window)
        return (out, None) if return_residuals else out
    if interpret and dropout_rate > 0.0 and not debug:
        # the pltpu hardware PRNG has no CPU/interpret lowering — without
        # the debug hash the kernel would die deep in Pallas with an
        # opaque 'prng_seed not found for platform cpu'
        raise ValueError(
            "in-kernel dropout cannot run under PADDLE_TPU_PALLAS="
            "interpret: the pltpu PRNG has no CPU lowering. Set "
            "PADDLE_TPU_FLASH_DROPOUT_DEBUG=iota (deterministic debug "
            "hash, identical masks on kernel and XLA paths) or unset "
            "PADDLE_TPU_PALLAS to use the XLA fallback")
    bq, bk = _pick_blocks(tq, tk)
    static = (causal, sm_scale, bq, bk, interpret, dropout_rate, debug,
              window)
    if residuals is None:
        if not return_residuals:
            return _flash(qf, kf, vf, bias, seed,
                          *static).reshape(b, h, tq, dv)
        # the kernel once, outside any differentiation; the gradient goes
        # through _flash_kept, over what the kernel gave
        residuals = _flash_fwd(*map(jax.lax.stop_gradient, (qf, kf, vf)),
                               bias, seed, *static)
    out = _flash_kept(qf, kf, vf, bias, seed, *residuals,
                      *static).reshape(b, h, tq, dv)
    return (out, residuals) if return_residuals else out
