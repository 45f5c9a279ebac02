"""Paged flash-decode Pallas kernel: single-query attention over a
block-table-indirect KV pool.

The paged cache is ``[num_blocks, H, block_len, Dh]`` — a request's K/V
rows live in the (non-contiguous) blocks its table names, so the ring
kernel's contiguous ``[BH, Tmax, D]`` streaming BlockSpec cannot see
them.  The indirection is Pallas's scalar-prefetch row-DMA idiom
(``pltpu.PrefetchScalarGridSpec``): the flattened per-(sequence, head)
block table rides in as a scalar-prefetch argument, the grid is
``(rows, max_blocks)``, and the K/V BlockSpec index maps read
``table[row, j]`` to DMA exactly the j-th OWNED block HBM→VMEM — blocks
never transit as a dense gather, and Mosaic double-buffers the block
DMAs across grid steps because the whole table is known before the
kernel body runs.  Online softmax across the non-contiguous blocks is
the ring kernel's lanes-replicated m/l accumulation, with the same
``block_start < length`` skip (a request 40 tokens into a 16-block
table touches 3 blocks, not 16).

Factoring note (arXiv 2104.05755): the kernel is a schedule over the
same block-level primitive as the ring kernel — one
``(1, block_len, d)`` tile of scores + online-softmax accumulate — so
the autotune ``decode`` family covers both; the paged layout adds the
``block_len`` knob (``PADDLE_TPU_PAGED_BLOCK_LEN`` → measured winner →
hand-set default).

Like every kernel in the tree it ships with an XLA composite
(:func:`paged_decode_reference`) that is the CPU/GPU fallback AND the
numerical oracle: gather the table's blocks into the contiguous layout,
then defer to :func:`~paddle_tpu.ops.pallas.flash_decode.decode_reference`
(≤1e-5 documented tolerance, bit-identical masked-softmax math — the
paged-vs-ring greedy-token equivalence in bench rides on this).
"""

import functools
import math

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import use_pallas
from .flash_attention import NEG_INF
from .flash_decode import decode_min_t, decode_reference, _norm_lengths

__all__ = [
    "paged_flash_decode", "paged_decode_reference", "paged_block_len",
    "gather_paged_cache", "DEFAULT_BLOCK_LEN",
]

# hand-set default block length (cache rows per block).  16 keeps the
# pool granular enough that a 30-token generation wastes at most 15
# rows, while a (1, 16, d) f32 tile still fills TPU sublanes.
DEFAULT_BLOCK_LEN = 16


def paged_block_len(d, max_len=None):
    """Pool block length: env cap (``PADDLE_TPU_PAGED_BLOCK_LEN``) →
    the autotune ``decode`` family's measured ``block_len`` for this
    head_dim on this backend → the hand-set default; forced to divide
    ``max_len`` (when given) so a full table gathers to exactly the
    ring cache's depth — the shape identity the bit-exact paged-vs-ring
    A/B rides on."""
    try:
        from ...autotune import cached_block_cap

        cap = cached_block_cap("decode", "PADDLE_TPU_PAGED_BLOCK_LEN",
                               "block_len", DEFAULT_BLOCK_LEN, d=d)
    except Exception:  # pragma: no cover - autotune unavailable
        cap = DEFAULT_BLOCK_LEN
    bl = max(1, int(cap))
    if max_len:
        bl = min(bl, int(max_len))
        while int(max_len) % bl:
            bl //= 2
    return max(bl, 1)


def gather_paged_cache(cache, table):
    """Materialize table-owned blocks contiguously:
    cache ``[N, H, BL, D]`` + table ``[S, MB]`` → ``[S, H, MB*BL, D]``.
    Unmapped (``-1``) entries clamp to block 0 — their columns sit past
    every request's valid length, so the attention mask never reads
    them (and the zero-fill init keeps them finite)."""
    n, h, bl, d = cache.shape
    s, mb = table.shape
    safe = jnp.clip(jnp.asarray(table, jnp.int32), 0, n - 1)
    g = cache[safe]                              # [S, MB, H, BL, D]
    g = jnp.transpose(g, (0, 2, 1, 3, 4))        # [S, H, MB, BL, D]
    return g.reshape(s, h, mb * bl, d)


def paged_decode_reference(q, k_cache, v_cache, lengths, table,
                           sm_scale=None):
    """XLA composite (fallback + oracle): gather the owned blocks into
    the ring layout, then the exact ring-oracle masked softmax.  With a
    full-depth table (``MB*BL == Tmax``) this is the SAME einsum shape
    and mask as the ring path — bit-identical greedy tokens."""
    table = jnp.asarray(table, jnp.int32)
    if table.ndim == 1:
        table = table[None, :]
    k = gather_paged_cache(k_cache, table)
    v = gather_paged_cache(v_cache, table)
    return decode_reference(q, k, v, lengths, sm_scale=sm_scale)


def _paged_decode_kernel(len_ref, tab_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, sm_scale, block_len):
    r = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    length = len_ref[r]

    # block j covers cache positions [j*BL, (j+1)*BL) of THIS row's
    # logical sequence — whichever pool block the table routed it to
    @pl.when(j * block_len < length)
    def _compute():
        q = q_ref[0]  # [1, d]
        k = k_ref[0]  # [bl, d]
        v = v_ref[0]  # [bl, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # [1, bl] f32
        cols = j * block_len + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_len), 1
        )
        s = jnp.where(cols < length, s, NEG_INF)

        m_prev = jnp.max(m_ref[:], axis=1, keepdims=True)
        l_prev = jnp.max(l_ref[:], axis=1, keepdims=True)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nb - 1)
    def _finalize():
        l = jnp.max(l_ref[:], axis=1, keepdims=True)
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


def _paged_flash_decode_call(q, k, v, lengths, table, sm_scale,
                             block_len, interpret):
    """q [R, 1, D]; k/v [N*H flattened blocks, BL, D]; table [R, MB]
    (already head-flattened); lengths [R]."""
    rows, _, d = q.shape
    mb = table.shape[1]
    n = k.shape[0]
    kernel = functools.partial(_paged_decode_kernel, sm_scale=sm_scale,
                               block_len=block_len)
    # unmapped (-1) table entries: route the DMA at block 0 — the
    # compute guard (block start >= length) never reads it
    safe_tab = jnp.clip(jnp.asarray(table, jnp.int32), 0, n - 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(rows, mb),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda r, j, lens, tab: (r, 0, 0)),
            pl.BlockSpec((1, block_len, d),
                         lambda r, j, lens, tab: (tab[r, j], 0, 0)),
            pl.BlockSpec((1, block_len, d),
                         lambda r, j, lens, tab: (tab[r, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d),
                               lambda r, j, lens, tab: (r, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1, 128), jnp.float32),
            pltpu.VMEM((1, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        name="paged_flash_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, 1, d), q.dtype),
        interpret=interpret,
    )(lengths, safe_tab, q, k, v)


def paged_flash_decode(q, k_cache, v_cache, lengths, table,
                       sm_scale=None):
    """Single-step decode attention through a block table.

    q ``[S, H, D]``; caches ``[N, H, BL, D]`` (the shared pool); table
    ``[S, MB]`` int32 (``-1`` = unmapped); lengths scalar or ``[S]``
    (valid cache rows per sequence).  Pallas kernel on TPU when the
    table depth ``MB*BL`` is at/above the ``decode`` family's measured
    engagement threshold; gather + ring-oracle composite otherwise.
    """
    s, h, d = q.shape
    n, _, bl, _ = k_cache.shape
    table = jnp.asarray(table, jnp.int32)
    if table.ndim == 1:
        table = table[None, :]
    mb = table.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    use, interpret = use_pallas()
    if not use or mb * bl < decode_min_t() or bl < 1:
        return paged_decode_reference(q, k_cache, v_cache, lengths,
                                      table, sm_scale=sm_scale)
    lens = _norm_lengths(lengths, s)
    lens_rh = jnp.repeat(lens, h)  # [S*H], row-major like the reshape
    # flatten heads into the block axis: pool block n, head hh lives at
    # flat row n*H + hh, so each (sequence, head) row gets its own table
    flat_tab = (table[:, None, :] * h
                + jnp.arange(h, dtype=jnp.int32)[None, :, None])
    flat_tab = jnp.where(table[:, None, :] < 0, -1,
                         flat_tab).reshape(s * h, mb)
    o = _paged_flash_decode_call(
        q.reshape(s * h, 1, d),
        k_cache.reshape(n * h, bl, d),
        v_cache.reshape(n * h, bl, d),
        lens_rh, flat_tab, float(sm_scale), bl, interpret,
    )
    return o.reshape(s, h, d)
