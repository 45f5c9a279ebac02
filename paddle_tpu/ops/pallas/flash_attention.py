"""Flash attention (fwd + bwd) as Pallas TPU kernels.

Reference analogue: the fused attention kernels under
``paddle/fluid/operators/fused/`` (fusion_* ops) — hand-fused native kernels
for the hot path.  On TPU the hot path is attention; this module implements
the FlashAttention-2 blocked online-softmax algorithm so the [B,H,T,T]
score matrix never touches HBM:

* forward: grid (B*H, Tq/bq, Tk/bk), KV innermost; running (m, l, acc) live
  in VMEM scratch across the KV sweep; output + logsumexp written on the
  last KV block.
* backward: two kernels — dK/dV (grid over KV blocks, sweeping Q) and dQ
  (grid over Q blocks, sweeping KV) — using the saved logsumexp and the
  precomputed delta = rowsum(dO * O), the standard FA2 recomputation split.

Supported bias: an additive key-padding bias of shape [B, Tk] (the common
[B,1,1,Tk] mask squeezed), broadcast over heads and query positions; it is
treated as constant (no gradient — padding masks are data, not parameters).
Causal masking is a flag; above-diagonal blocks are skipped entirely.

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
replicated across lanes; rows are recovered with a lanes-reduce and moved
between row/column orientation with 2-D reshapes (both verified supported
by Mosaic on v5e).

Off-TPU, and for shapes the kernel does not cover, the entry point routes
to a pure-XLA implementation; set ``PADDLE_TPU_PALLAS=interpret`` to force
the Pallas kernels in interpreter mode (CPU correctness tests), or ``=off``
to force the XLA path (see :func:`paddle_tpu.ops.pallas.use_pallas`).
"""

import functools
import math
import os

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import use_pallas

NEG_INF = -1e30


def _row(x2d):
    """(1, n) row from a (n, 1) column value."""
    return x2d.reshape(1, -1)


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


def _keep_mask(shape, rate, seed_ref, bh, qi, kj, block_q, block_k, debug):
    """In-kernel Bernoulli keep mask for the (qi, kj) block of
    batch·head bh.  Hardware path: per-block counter seeding of the TPU
    PRNG (fwd and bwd seed identically, so the draw reproduces)."""
    if debug:
        r = (jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
             + (qi * block_q).astype(jnp.uint32))
        c = (jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
             + (kj * block_k).astype(jnp.uint32))
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
        bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
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
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, seed_ref, o_ref, m_out_ref,
                l_out_ref, acc_ref, m_ref, l_ref, *, sm_scale, causal,
                block_q, block_k, dropout_rate, dropout_debug):
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _compute():
        # matmuls run at the INPUT dtype with f32 accumulation: under
        # bf16 AMP the MXU's bf16 rate is ~4x its f32 rate, and
        # bf16xbf16->f32 QK^T is bit-identical to upcast-then-f32 (bf16
        # casts are exact; 8-bit-mantissa products fit f32's 24).  Same
        # fix as the r04 XLA-fallback change; f32 inputs are unchanged.
        q = q_ref[0]  # [bq, d]
        k = k_ref[0]  # [bk, d]
        v = v_ref[0]  # [bk, dv]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # [bq, bk] f32
        if bias_ref is not None:
            s = s + bias_ref[0].astype(jnp.float32)  # (1, bk) broadcasts
        if causal:
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(rows >= cols, s, NEG_INF)

        # lanes of m_ref/l_ref all hold the same value; a lanes-max recovers
        # the (bq, 1) column without lane slicing
        m_prev = jnp.max(m_ref[:], axis=1, keepdims=True)
        l_prev = jnp.max(l_ref[:], axis=1, keepdims=True)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        # FA2 dropout: l accumulates the UNdropped p (true softmax
        # denominator); only the numerator entries feeding PV are masked
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        if dropout_rate > 0.0:
            keep = _keep_mask(p.shape, dropout_rate, seed_ref, b, i, j,
                              block_q, block_k, dropout_debug)
            p = jnp.where(keep, p, 0.0) / (1.0 - dropout_rate)
        # PV at input dtype (p downcast under AMP): the MXU-rate
        # tradeoff mha_reference makes identically; acc stays f32
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        @pl.when(j * block_k <= i * block_q + (block_q - 1))
        def _():
            _compute()
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _finalize():
        m = jnp.max(m_ref[:], axis=1, keepdims=True)
        l = jnp.max(l_ref[:], axis=1, keepdims=True)
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows → zeros, not NaN
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # m and l are saved SEPARATELY (not lse = m + log l): when |m| is
        # large (e.g. -1e4 padding bias on every visible key) the f32 sum
        # m + log(l) loses all bits of log(l); exp(s - m)/l in the backward
        # reproduces the forward's p bit-for-bit instead
        m_out_ref[0] = _row(m)
        l_out_ref[0] = _row(l)


def _flash_fwd(q, k, v, bias, seed, causal, sm_scale, block_q, block_k,
               interpret, dropout_rate, dropout_debug):
    bh, tq, d = q.shape
    _, tk, dv = v.shape
    nq, nk = tq // block_q, tk // block_k
    grid = (bh, nq, nk)

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b, j, 0)),
    ]
    args = [q, k, v]
    kw = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
              block_k=block_k, dropout_rate=dropout_rate,
              dropout_debug=dropout_debug)
    if bias is not None:
        nheads = bh // bias.shape[0]
        in_specs.append(
            pl.BlockSpec((1, 1, block_k),
                         lambda b, i, j: (b // nheads, 0, j))
        )
        args.append(bias.reshape(bias.shape[0], 1, tk))
        kernel = functools.partial(_fwd_kernel, **kw)
    else:
        def kernel(qr, kr, vr, sr, o, mo, lo, acc, m, l):
            return _fwd_kernel(qr, kr, vr, None, sr, o, mo, lo, acc, m, l,
                               **kw)
    in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    args.append(seed)

    o, m_out, l_out = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
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
    )(*args)
    return o, m_out, l_out


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------

def _recompute_p(q, k, bias_ref, m_col, l_col, sm_scale, causal, i, j,
                 block_q, block_k):
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale
    if bias_ref is not None:
        s = s + bias_ref[0].astype(jnp.float32)
    if causal:
        rows = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        cols = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(rows >= cols, s, NEG_INF)
    return jnp.exp(s - m_col) / l_col


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, bias_ref, seed_ref, do_ref, m_ref,
                    l_ref, dl_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                    sm_scale, causal, block_q, block_k, dropout_rate,
                    dropout_debug):
    b = pl.program_id(0)
    j = pl.program_id(1)  # kv block
    i = pl.program_id(2)  # q block (innermost sweep)
    nq = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute():
        # input-dtype matmuls, f32 accumulation (see _fwd_kernel note)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        m_col = m_ref[0].reshape(block_q, 1)
        l_col = l_ref[0].reshape(block_q, 1)
        delta_col = dl_ref[0].reshape(block_q, 1)
        p = _recompute_p(q, k, bias_ref, m_col, l_col, sm_scale, causal,
                         i, j, block_q, block_k)
        # dP = dO @ V^T
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if dropout_rate > 0.0:
            # the SAME (b, i, j) seeding as the forward reproduces the
            # mask; O = P_drop V, so dV uses P_drop and the softmax-
            # jacobian input is the mask-scaled dP (sum P·dP = delta
            # still holds because delta = rowsum(dO·O))
            keep = _keep_mask(p.shape, dropout_rate, seed_ref, b, i, j,
                              block_q, block_k, dropout_debug)
            p_v = jnp.where(keep, p, 0.0) / (1.0 - dropout_rate)
            dp = jnp.where(keep, dp, 0.0) / (1.0 - dropout_rate)
        else:
            p_v = p
        # dV += P_drop^T @ dO
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p_v.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dS = P * (dP_masked - delta)
        ds = p * (dp - delta_col)
        # dK += dS^T @ Q * scale
        dk_acc[:] = dk_acc[:] + sm_scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        @pl.when(i * block_q + (block_q - 1) >= j * block_k)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, bias_ref, seed_ref, do_ref, m_ref,
                   l_ref, dl_ref, dq_ref, dq_acc, *, sm_scale, causal,
                   block_q, block_k, dropout_rate, dropout_debug):
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _compute():
        # input-dtype matmuls, f32 accumulation (see _fwd_kernel note)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        m_col = m_ref[0].reshape(block_q, 1)
        l_col = l_ref[0].reshape(block_q, 1)
        delta_col = dl_ref[0].reshape(block_q, 1)
        p = _recompute_p(q, k, bias_ref, m_col, l_col, sm_scale, causal,
                         i, j, block_q, block_k)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if dropout_rate > 0.0:
            keep = _keep_mask(p.shape, dropout_rate, seed_ref, b, i, j,
                              block_q, block_k, dropout_debug)
            dp = jnp.where(keep, dp, 0.0) / (1.0 - dropout_rate)
        ds = p * (dp - delta_col)
        dq_acc[:] = dq_acc[:] + sm_scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        @pl.when(j * block_k <= i * block_q + (block_q - 1))
        def _():
            _compute()
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd(q, k, v, bias, seed, o, m, l, do, causal, sm_scale,
               block_q, block_k, interpret, dropout_rate, dropout_debug):
    bh, tq, d = q.shape
    _, tk, d_v = v.shape
    nq, nk = tq // block_q, tk // block_k

    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )[:, None, :]  # [bh, 1, tq], matching the saved m/l row layout
    bias3 = None if bias is None else bias.reshape(bias.shape[0], 1, tk)
    kw = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
              block_k=block_k, dropout_rate=dropout_rate,
              dropout_debug=dropout_debug)

    # --- dK/dV: grid (bh, kv-block, q-sweep) ---
    dkv_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),   # q
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),   # k
        pl.BlockSpec((1, block_k, d_v), lambda b, j, i: (b, j, 0)),  # v
    ]
    dkv_args = [q, k, v]
    if bias is not None:
        nheads = bh // bias.shape[0]
        dkv_specs.append(
            pl.BlockSpec((1, 1, block_k),
                         lambda b, j, i: (b // nheads, 0, j))
        )
        dkv_args.append(bias3)
        dkv_kernel = functools.partial(_bwd_dkv_kernel, **kw)
    else:
        def dkv_kernel(qr, kr, vr, sr, dor, mr, lr, dlr, dkr, dvr, dka,
                       dva):
            return _bwd_dkv_kernel(
                qr, kr, vr, None, sr, dor, mr, lr, dlr, dkr, dvr, dka,
                dva, **kw)
    dkv_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))   # seed
    dkv_args.append(seed)
    dkv_specs += [
        pl.BlockSpec((1, block_q, d_v), lambda b, j, i: (b, i, 0)),    # do
        pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i)),     # m
        pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i)),     # l
        pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i)),     # delta
    ]
    dkv_args += [do, m, l, delta]

    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_attention_dkv",
        grid=(bh, nk, nq),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d_v), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, tk, d_v), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d_v), jnp.float32),
        ],
        interpret=interpret,
    )(*dkv_args)

    # --- dQ: grid (bh, q-block, kv-sweep) ---
    dq_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, d_v), lambda b, i, j: (b, j, 0)),
    ]
    dq_args = [q, k, v]
    if bias is not None:
        nheads = bh // bias.shape[0]
        dq_specs.append(
            pl.BlockSpec((1, 1, block_k),
                         lambda b, i, j: (b // nheads, 0, j))
        )
        dq_args.append(bias3)
        dq_kernel = functools.partial(_bwd_dq_kernel, **kw)
    else:
        def dq_kernel(qr, kr, vr, sr, dor, mr, lr, dlr, dqr, dqa):
            return _bwd_dq_kernel(
                qr, kr, vr, None, sr, dor, mr, lr, dlr, dqr, dqa, **kw)
    dq_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))   # seed
    dq_args.append(seed)
    dq_specs += [
        pl.BlockSpec((1, block_q, d_v), lambda b, i, j: (b, i, 0)),    # do
        pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),     # m
        pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),     # l
        pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),     # delta
    ]
    dq_args += [do, m, l, delta]

    dq = pl.pallas_call(
        dq_kernel,
        name="flash_attention_dq",
        grid=(bh, nq, nk),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(*dq_args)

    return dq, dk, dv


# ---------------------------------------------------------------------------
# XLA fallback (also the numerical reference in tests)
# ---------------------------------------------------------------------------

def mha_reference(q, k, v, bias=None, causal=False, sm_scale=None,
                  dropout_rate=0.0, seed=None, debug=False):
    """Plain-XLA multi-head attention. q,k: [B,H,T,D]; v: [B,H,Tk,Dv];
    bias: [B,Tk].
    With dropout: upscale-in-train on the probabilities; the mask comes
    from the debug position hash (bit-matching the kernel's debug mode)
    or jax.random (statistically matching the kernel's hardware PRNG)."""
    d = q.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    # matmuls run in the INPUT dtype (bf16 under AMP → full-rate MXU;
    # upcasting the operands to f32 would quarter the matmul rate) with
    # f32 accumulation; softmax statistics stay f32 either way
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * sm_scale
    if bias is not None:
        s = s + bias[:, None, None, :].astype(jnp.float32)
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
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
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v,
                   preferred_element_type=jnp.float32)
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, bias, seed, causal, sm_scale, block_q, block_k,
           interpret, dropout_rate, dropout_debug):
    o, _, _ = _flash_fwd(q, k, v, bias, seed, causal, sm_scale, block_q,
                         block_k, interpret, dropout_rate, dropout_debug)
    return o


def _flash_fwd_rule(q, k, v, bias, seed, causal, sm_scale, block_q,
                    block_k, interpret, dropout_rate, dropout_debug):
    o, m, l = _flash_fwd(q, k, v, bias, seed, causal, sm_scale, block_q,
                         block_k, interpret, dropout_rate, dropout_debug)
    return o, (q, k, v, bias, seed, o, m, l)


def _flash_bwd_rule(causal, sm_scale, block_q, block_k, interpret,
                    dropout_rate, dropout_debug, res, do):
    q, k, v, bias, seed, o, m, l = res
    dq, dk, dv = _flash_bwd(q, k, v, bias, seed, o, m, l, do, causal,
                            sm_scale, block_q, block_k, interpret,
                            dropout_rate, dropout_debug)
    dbias = None if bias is None else jnp.zeros_like(bias)
    return (dq, dk, dv, dbias, None)  # int seed: no cotangent


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, bias=None, causal=False, sm_scale=None,
                    dropout_rate=0.0, dropout_seed=None):
    """Multi-head attention: Pallas flash kernel on TPU, XLA elsewhere.

    q,k: [B, H, T, D]; v: [B, H, Tk, Dv] (Dv may differ from D); bias:
    additive key bias [B, Tk] or [B,1,1,Tk] (no gradient flows to bias);
    returns [B, H, Tq, Dv].  ``sm_scale`` defaults to 1/sqrt(D).

    dropout_rate > 0 applies attention-probability dropout IN-KERNEL
    (upscale-in-train semantics); ``dropout_seed`` is an int32 scalar or
    [1] array that must change per step.  On the XLA fallback the same
    rate is applied with jax.random (debug hash under
    PADDLE_TPU_FLASH_DROPOUT_DEBUG=iota, where both paths draw the
    identical mask for cross-checking).
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
    interpret = use_pallas()[1]
    debug = _dropout_debug()
    b, h, tq, _ = q.shape
    tk = k.shape[2]
    qf = q.reshape(b * h, tq, d)
    kf = k.reshape(b * h, tk, d)
    dv = v.shape[-1]
    vf = v.reshape(b * h, tk, dv)
    seed = jnp.reshape(
        jnp.asarray(0 if dropout_seed is None else dropout_seed,
                    jnp.int32), (1,))
    if not routes_to_kernel(q, k, bias, v):
        return mha_reference(q, k, v, bias=bias, causal=causal,
                             sm_scale=sm_scale,
                             dropout_rate=dropout_rate, seed=seed,
                             debug=debug)
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
    o = _flash(qf, kf, vf, bias, seed, causal, sm_scale, bq, bk,
               interpret, dropout_rate, debug)
    return o.reshape(b, h, tq, dv)
