"""Fused dropout + residual-add + layer_norm as a Pallas TPU kernel.

Reference analogue: the fused elementwise/normalization kernels the
reference keeps as native code — ``paddle/fluid/operators/fused/
fused_elemwise_activation_op.cc`` (chained elementwise fusion) and the
layer_norm JIT kernel under ``paddle/fluid/operators/jit/`` — hand-fused
hot-path kernels around the big GEMMs.

The transformer encoder's inter-GEMM glue is
``layer_norm(x + dropout(sublayer(x)))``: three HBM-bound ops whose
intermediates (the dropped activations and the residual sum) each cost a
full [N, D] round-trip.  XLA fuses the elementwise chain INTO the LN
reduction only partially (the r05 BERT profile bills dropout+norm ~4.6ms
of a 58ms step across 24 sites).  This kernel does the whole pattern in
one VMEM pass: mask bits from the TPU hardware PRNG (same per-block
counter-seeding discipline as the flash kernel, so the backward
recomputation draws the identical mask), the residual sum ``y`` saved
for backward, and the row stats written as [1, N] f32 so forward and
backward normalize identically.

Backward is the standard LN gradient with dgamma/dbeta accumulated as
per-block partials (summed outside the kernel), plus the dropout mask
re-applied to the dx branch.

Off-TPU and for ineligible shapes the entry point routes to a pure-XLA
expression of the same math; ``PADDLE_TPU_PALLAS=interpret`` forces the
kernel in interpreter mode (CPU tests use the same
``PADDLE_TPU_FLASH_DROPOUT_DEBUG=iota`` hash-mask escape as the flash
kernel — ``pltpu`` PRNG has no CPU lowering).
"""

import functools
import os

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import use_pallas
from .flash_attention import _hash_bits, _rate_threshold

_BN = 256  # rows per grid step; D stays whole in the lane dimension


def _debug_mask():
    return os.environ.get("PADDLE_TPU_FLASH_DROPOUT_DEBUG") == "iota"


def _block_rows(n, d=None):
    """Rows per grid step: env cap → autotune-cached winner for this
    (n, d) → the hand-set default; always a divisor of n."""
    if d is None:
        cap = _BN
    else:
        try:
            from ...autotune import cached_block_cap

            cap = cached_block_cap(
                "fused_ln", "PADDLE_TPU_FUSED_LN_BLOCK_ROWS",
                "block_rows", _BN, rows=n, d=d)
        except Exception:  # pragma: no cover - autotune unavailable
            cap = _BN
    bn = min(max(cap, 1), n)
    while n % bn:
        bn //= 2
    return max(bn, 1)


def _row_keep_mask(shape, rate, seed_ref, i, bn, debug):
    """Bernoulli keep mask for rows [i*bn, (i+1)*bn); deterministic in
    (seed, i) so forward and backward draw identically."""
    if debug:
        r = (jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
             + (i * bn).astype(jnp.uint32))
        c = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
        bits = _hash_bits(jnp.uint32(0), r, c, seed_ref[0])
    else:
        pltpu.prng_seed(seed_ref[0], i)
        bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    return bits >= _rate_threshold(rate)


def _fwd_kernel(x_ref, res_ref, g_ref, b_ref, seed_ref,
                out_ref, y_ref, mean_ref, rstd_ref,
                *, rate, eps, bn, debug):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    res = res_ref[...].astype(jnp.float32)
    if rate > 0.0:
        keep = _row_keep_mask(x.shape, rate, seed_ref, i, bn, debug)
        x = jnp.where(keep, x * (1.0 / (1.0 - rate)), 0.0)
    y = x + res
    mean = jnp.mean(y, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(y - mean), axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (y - mean) * rstd
    out = xhat * g_ref[...].astype(jnp.float32) \
        + b_ref[...].astype(jnp.float32)
    out_ref[...] = out.astype(out_ref.dtype)
    y_ref[...] = y.astype(y_ref.dtype)
    mean_ref[...] = mean.reshape(1, -1)
    rstd_ref[...] = rstd.reshape(1, -1)


def _bwd_kernel(dout_ref, y_ref, g_ref, mean_ref, rstd_ref, seed_ref,
                dx_ref, dres_ref, dg_ref, db_ref,
                *, rate, bn, debug):
    i = pl.program_id(0)
    dout = dout_ref[...].astype(jnp.float32)
    y = y_ref[...].astype(jnp.float32)
    mean = mean_ref[...].reshape(-1, 1)
    rstd = rstd_ref[...].reshape(-1, 1)
    xhat = (y - mean) * rstd

    # dgamma/dbeta: TPU grid steps run sequentially and revisit the
    # same [1, D] output block (index_map pins (0, 0)), so accumulate
    # across row blocks in-kernel — a [grid, D] partials array would
    # need a block first-dim of 1, which Mosaic's (8, 128) tiling
    # rejects (this exact lowering error cost the first hardware
    # attempt of the A/B)
    @pl.when(i == 0)
    def _init():
        dg_ref[...] = jnp.zeros(dg_ref.shape, dg_ref.dtype)
        db_ref[...] = jnp.zeros(db_ref.shape, db_ref.dtype)

    dg_ref[...] += jnp.sum(dout * xhat, axis=0, keepdims=True)
    db_ref[...] += jnp.sum(dout, axis=0, keepdims=True)
    dxhat = dout * g_ref[...].astype(jnp.float32)
    m1 = jnp.mean(dxhat, axis=1, keepdims=True)
    m2 = jnp.mean(dxhat * xhat, axis=1, keepdims=True)
    dy = rstd * (dxhat - m1 - xhat * m2)
    dres_ref[...] = dy.astype(dres_ref.dtype)
    dx = dy
    if rate > 0.0:
        keep = _row_keep_mask(dx.shape, rate, seed_ref, i, bn, debug)
        dx = jnp.where(keep, dx * (1.0 / (1.0 - rate)), 0.0)
    dx_ref[...] = dx.astype(dx_ref.dtype)


def _eligible(x):
    n, d = x.shape
    if d % 128 or d > 4096 or n % 8:
        return False
    # the per-row statistics ride in (1, bn) lane tiles of a (1, n) array
    bn = _block_rows(n, d)
    if bn % 128 and bn != n:
        return False
    return use_pallas()[0]


def _xla_reference(x, residual, gamma, beta, rate, eps, seed, debug):
    """The same math as one jax expression (autodiff provides backward);
    the off-TPU / ineligible-shape fallback."""
    xf = x.astype(jnp.float32)
    if rate > 0.0:
        if debug:
            n, d = x.shape
            r = jnp.arange(n, dtype=jnp.uint32)[:, None]
            c = jnp.arange(d, dtype=jnp.uint32)[None, :]
            keep = _hash_bits(jnp.uint32(0), r, c,
                              seed[0].astype(jnp.uint32)) \
                >= _rate_threshold(rate)
        else:
            keep = jax.random.bernoulli(
                jax.random.PRNGKey(seed[0].astype(jnp.uint32)),
                1.0 - rate, x.shape)
        xf = jnp.where(keep, xf * (1.0 / (1.0 - rate)), 0.0)
    y = xf + residual.astype(jnp.float32)
    mean = jnp.mean(y, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(y - mean), axis=1, keepdims=True)
    xhat = (y - mean) * jax.lax.rsqrt(var + eps)
    out = xhat * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
    return out.astype(x.dtype)


def _fwd_call(x, residual, gamma, beta, rate, eps, seed):
    n, d = x.shape
    bn = _block_rows(n, d)
    grid = (n // bn,)
    debug = _debug_mask()
    interpret = use_pallas()[1]
    kernel = functools.partial(_fwd_kernel, rate=rate, eps=eps, bn=bn,
                               debug=debug)
    out, y, mean, rstd = pl.pallas_call(
        kernel,
        name="fused_ln_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x.dtype),
            jax.ShapeDtypeStruct((n, d), x.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        interpret=interpret,
    )(x, residual, gamma.reshape(1, d), beta.reshape(1, d), seed)
    return out, y, mean, rstd


def _bwd_call(dout, y, gamma, mean, rstd, rate, seed, dtypes):
    n, d = y.shape
    bn = _block_rows(n, d)
    grid = (n // bn,)
    debug = _debug_mask()
    interpret = use_pallas()[1]
    kernel = functools.partial(_bwd_kernel, rate=rate, bn=bn, debug=debug)
    dx, dres, dg_part, db_part = pl.pallas_call(
        kernel,
        name="fused_ln_bwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), dtypes[0]),
            jax.ShapeDtypeStruct((n, d), dtypes[1]),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ],
        interpret=interpret,
    )(dout, y, gamma.reshape(1, d), mean, rstd, seed)
    return dx, dres, dg_part, db_part


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused_core(x, residual, gamma, beta, rate, eps, seed):
    out, _, _, _ = _fwd_call(x, residual, gamma, beta, rate, eps, seed)
    return out


def _fused_core_fwd(x, residual, gamma, beta, rate, eps, seed):
    out, y, mean, rstd = _fwd_call(x, residual, gamma, beta, rate, eps,
                                   seed)
    return out, (y, gamma, mean, rstd, seed)


def _fused_core_bwd(rate, eps, saved, dout):
    # y was stored in x's dtype and residual/beta share the model's
    # compute dtypes (y / gamma respectively) — cotangent dtypes follow
    y, gamma, mean, rstd, seed = saved
    dx, dres, dg, db = _bwd_call(
        dout, y, gamma, mean, rstd, rate, seed, (y.dtype, y.dtype))
    return (dx, dres, dg.reshape(-1).astype(gamma.dtype),
            db.reshape(-1).astype(gamma.dtype), None)


_fused_core.defvjp(_fused_core_fwd, _fused_core_bwd)


def fused_dropout_add_ln(x, residual, gamma, beta, dropout_rate=0.0,
                         eps=1e-5, seed=None):
    """``layer_norm(residual + dropout(x)) * gamma + beta`` in one pass.

    x, residual: [N, D] (callers flatten leading dims); gamma/beta: [D].
    dropout is inverted-scale (``upscale_in_train``); rate 0 skips the
    mask entirely (eval / no-dropout configs still save the fused
    HBM round-trips).  seed: int32 array shape [1] (required when
    dropout_rate > 0)."""
    rate = float(dropout_rate or 0.0)
    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)
    if not routes_to_kernel(x, rate):
        return _xla_reference(x, residual, gamma, beta, rate, eps, seed,
                              _debug_mask())
    return _fused_core(x, residual, gamma, beta, rate, eps, seed)


def routes_to_kernel(x, dropout_rate=0.0):
    """Does :func:`fused_dropout_add_ln` run the Pallas kernels for an
    ``x`` of this shape ([N, D]; anything with ``.shape``) at this rate,
    or the XLA composite?  The entry point's one routing decision."""
    if use_pallas()[1] and dropout_rate > 0.0 and not _debug_mask():
        # the pltpu hardware PRNG has no CPU/interpret lowering — the
        # kernel would die deep in Pallas with an opaque 'prng_seed not
        # found for platform cpu'.  Unlike the flash entry (whose caller
        # explicitly opted into the kernel) this op is routinely
        # INTRODUCED by the fusion pass rewrite, so degrade to the XLA
        # composite instead of raising; set
        # PADDLE_TPU_FLASH_DROPOUT_DEBUG=iota to run the kernel with the
        # deterministic debug hash instead.
        return False
    return _eligible(x)
