"""Flash-*decode* Pallas kernel: one query row per sequence against the
device-resident KV cache, masked to the write cursor.

The autoregressive inner loop's attention shape is degenerate — q is
``[BH, 1, D]`` while K/V are the full ``[BH, Tmax, D]`` cache — so the
prefill flash kernel's q-blocking buys nothing; what matters is streaming
the cache through VMEM in ``block_k`` chunks with online softmax and
skipping the chunks past the cursor entirely (a request 40 tokens into a
4096-slot cache touches one block, not 32).  Reference shape analysis:
"Tensor Processing Primitives" (arXiv 2104.05755) — the single-pass
shape-stable primitive — applied to the flash-decoding decomposition.

Like ``flash_attention.py`` the kernel ships with an XLA composite
(:func:`decode_reference`) that is both the off-TPU route and the
numerical oracle (documented tolerance: ≤1e-5 relative); the Pallas path
engages on TPU (or under ``PADDLE_TPU_PALLAS=interpret`` for CPU tests).

Autotune: block size and the engagement threshold are a new ``decode``
family in the PR-6 measure-and-learn cache — ``PADDLE_TPU_DECODE_BLOCK_K``
/ ``PADDLE_TPU_DECODE_MIN_T`` env caps win, then the cache's measured
winner, then the hand-set defaults (512 / 256).  ``PADDLE_TPU_AUTOTUNE=0``
restores the hand-set defaults bit-exactly.
"""

import functools
import math
import os

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import use_pallas
from .flash_attention import NEG_INF

__all__ = [
    "flash_decode", "decode_reference", "decode_block_k", "decode_min_t",
]

# hand-set defaults: the pre-autotune behavior PADDLE_TPU_AUTOTUNE=0
# must restore bit-exactly
DEFAULT_BLOCK_K = 512
DEFAULT_MIN_T = 256


def decode_block_k(t, d):
    """KV block size: env cap (``PADDLE_TPU_DECODE_BLOCK_K``) → autotune
    cache winner for this (t, d) on this backend (``decode`` family) →
    hand-set 512; divisibility against the cache length enforced here."""
    try:
        from ...autotune import cached_block_cap

        cap = cached_block_cap("decode", "PADDLE_TPU_DECODE_BLOCK_K",
                               "block_k", DEFAULT_BLOCK_K, t=t, d=d)
    except Exception:  # pragma: no cover - autotune unavailable
        cap = DEFAULT_BLOCK_K
    bk = max(128, min(int(cap), t))
    while t % bk:
        bk //= 2
    return max(bk, 1)


def decode_min_t():
    """Cache length below which the XLA composite beats the blocked
    kernel (launch overhead dominates a one-block cache).  Resolution:
    ``PADDLE_TPU_DECODE_MIN_T`` → the autotune cache's recorded decision
    for this backend (``decode_min_t`` family, written by the bench
    sweep) → the hand-set 256."""
    env = os.environ.get("PADDLE_TPU_DECODE_MIN_T", "").strip()
    if env:
        try:
            return int(env)
        except ValueError:
            return DEFAULT_MIN_T
    try:
        from ...autotune import decode_min_t_decision

        t = decode_min_t_decision()
        if t is not None:
            return int(t)
    except Exception:  # pragma: no cover - autotune unavailable
        pass
    return DEFAULT_MIN_T


def _norm_lengths(lengths, b):
    """Per-sequence valid-entry counts as an int32 [B] vector (a scalar
    cursor broadcasts: every row shares the write position)."""
    lengths = jnp.asarray(lengths, jnp.int32)
    if lengths.ndim == 0:
        lengths = jnp.broadcast_to(lengths, (b,))
    return lengths.reshape(b)


def decode_reference(q, k, v, lengths, sm_scale=None):
    """XLA composite single-query attention (fallback + oracle).

    q [B, H, D]; k/v [B, H, T, D] (ring cache, positions >= length are
    garbage); lengths scalar or [B].  Returns [B, H, D].  f32 softmax
    with input-dtype matmuls, matching the kernel's accumulation."""
    b, h, d = q.shape
    t = k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    lengths = _norm_lengths(lengths, b)
    s = jnp.einsum("bhd,bhtd->bht", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    mask = jnp.arange(t, dtype=jnp.int32)[None, None, :] < \
        lengths[:, None, None]
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l = jnp.where(l == 0.0, 1.0, l)  # empty cache → zeros, not NaN
    p = (p / l).astype(v.dtype)
    return jnp.einsum("bht,bhtd->bhd", p, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, sm_scale, block_k):
    bh = pl.program_id(0)
    j = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    length = len_ref[bh]

    @pl.when(j * block_k < length)
    def _compute():
        q = q_ref[0]  # [1, d]
        k = k_ref[0]  # [bk, d]
        v = v_ref[0]  # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # [1, bk] f32
        cols = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1
        )
        s = jnp.where(cols < length, s, NEG_INF)

        # lanes of m_ref/l_ref all hold the same value (flash_attention's
        # lanes-replicated per-row stats, degenerate single-row case)
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

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.max(l_ref[:], axis=1, keepdims=True)
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


def _flash_decode_call(q, k, v, lengths, sm_scale, block_k, interpret):
    bh, _, d = q.shape
    t = k.shape[1]
    grid = (bh, t // block_k)
    kernel = functools.partial(_decode_kernel, sm_scale=sm_scale,
                               block_k=block_k)
    return pl.pallas_call(
        kernel,
        name="flash_decode",
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, 1, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1, 128), jnp.float32),
            pltpu.VMEM((1, 128), jnp.float32),
        ],
        interpret=interpret,
    )(lengths, q, k, v)


def _kernel_applicable(t, d, block_k):
    return t >= 1 and d >= 1 and t % block_k == 0


def flash_decode(q, k, v, lengths, sm_scale=None):
    """Single-step decode attention with automatic path selection.

    q [B, H, D] (this step's query), k/v [B, H, Tmax, D] (the ring
    cache), lengths scalar or [B] (the cursor — number of valid cache
    entries).  Pallas kernel on TPU when Tmax is at/above the measured
    :func:`decode_min_t` engagement threshold, XLA composite otherwise.
    """
    b, h, d = q.shape
    t = k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    use, interpret = use_pallas()
    block_k = decode_block_k(t, d)
    if (not use or t < decode_min_t()
            or not _kernel_applicable(t, d, block_k)):
        return decode_reference(q, k, v, lengths, sm_scale=sm_scale)
    lens = _norm_lengths(lengths, b)
    lens_bh = jnp.repeat(lens, h)  # [B*H], row-major like the reshape
    o = _flash_decode_call(
        q.reshape(b * h, 1, d),
        k.reshape(b * h, t, d),
        v.reshape(b * h, t, d),
        lens_bh, float(sm_scale), block_k, interpret,
    )
    return o.reshape(b, h, d)
