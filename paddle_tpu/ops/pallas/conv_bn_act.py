"""Fused BatchNorm + activation epilogue for conv outputs, as a Pallas
TPU kernel.

Reference analogue: the conv+BN+act fusion the reference keeps as
native passes and kernels (``fuse_bn_act_ops`` build-strategy pass and
the inference-time conv+bn fold).  On TPU the conv itself belongs to
XLA — ``lax.conv_general_dilated`` drives the MXU at full rate and a
hand-blocked Pallas conv would re-derive exactly the pipelining Mosaic
already emits — but the r05 ResNet-50 profile shows the EPILOGUE is
what XLA leaves on the floor (MFU 0.250 measured vs 0.381 by XLA's own
accounting): the batch-norm normalize/affine and the relu each cost a
full HBM round-trip of the conv output, and training-mode BN splits
into stats + normalize XLA does not always fuse back into one sweep.

This module is that epilogue as ONE VMEM pass over the conv output in
its channels-last 2-D view ``[R, C]`` (R = N·H·W): normalize with
precomputed per-channel ``mean``/``rstd``, affine with ``gamma``/
``beta``, activation, one read + one write.  The TPP decomposition
argument (arXiv:2104.05755): express the composite as one micro-kernel
over a 2-D tile and let the framework loop over tiles — here the Pallas
grid over row blocks, whose size is an autotunable knob
(``PADDLE_TPU_CONV_BN_BLOCK_ROWS`` caps it; the autotune cache can
re-decide it per (R, C, dtype)).

Backward is the matching one-pass kernel: activation mask, per-channel
``dgamma``/``dbeta``/``dmean``/``drstd`` partials accumulated across
sequential grid steps (the fused-LN discipline — TPU grid steps revisit
the pinned [1, C] output block), and the elementwise ``dy``.  The chain
through the batch statistics to the conv output is OUTSIDE the custom
vjp (plain jnp reductions), so jax composes the full BN-train gradient
correctly.

Eligibility: channels-last 2-D view with ``C % 128 == 0`` (the lane
dimension), ``R % 8 == 0``, relu/identity activation.  Everything else
— NCHW without a profitable transpose, odd channel counts, exotic
activations — takes the pure-XLA composite in ``ops/nn.py``, which is
bit-exact with the unfused conv→batch_norm→act chain by construction.
``PADDLE_TPU_PALLAS=interpret`` forces the kernel on CPU (tests);
``=off`` forces the XLA path.
"""

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from . import use_pallas

_DEFAULT_BLOCK_ROWS = 256

# activations the kernel implements in-VMEM; everything else falls back
# to the XLA composite (which supports any registered activation)
KERNEL_ACTS = ("identity", "relu")


def _block_rows(n, c, dtype):
    """Rows per grid step: env cap → autotune-cached winner per
    (R, C, dtype) → the hand-set default; always a divisor of n."""
    try:
        from ...autotune import cached_block_cap

        cap = cached_block_cap(
            "conv_bn_act", "PADDLE_TPU_CONV_BN_BLOCK_ROWS",
            "block_rows", _DEFAULT_BLOCK_ROWS,
            rows=n, channels=c, dtype=str(dtype))
    except Exception:  # noqa: BLE001 - autotune unavailable
        cap = _DEFAULT_BLOCK_ROWS
    bn = min(max(cap, 1), n)
    while n % bn:
        bn //= 2
    return max(bn, 1)


def epilogue_eligible(rows, channels, act):
    """Whether the Pallas epilogue kernel can take this site (the caller
    already arranged a channels-last 2-D view)."""
    if act not in KERNEL_ACTS:
        return False
    if channels % 128 or channels > 4096 or rows % 8:
        return False
    return use_pallas()[0]


def _apply_act(x, act):
    if act == "relu":
        return jnp.maximum(x, 0)
    return x


def _fwd_kernel(y_ref, g_ref, b_ref, m_ref, r_ref, out_ref, *, act):
    y = y_ref[...].astype(jnp.float32)
    # the same float sequence as the unfused batch_norm lowering:
    # (x - mean) * rstd, then * gamma + beta, then cast, then act —
    # elementwise, so the kernel output is bit-identical per element
    h = (y - m_ref[...].astype(jnp.float32)) * r_ref[...]
    h = h * g_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    out_ref[...] = _apply_act(h.astype(out_ref.dtype), act)


def _bwd_kernel(dout_ref, y_ref, g_ref, b_ref, m_ref, r_ref,
                dy_ref, dg_ref, db_ref, dm_ref, dr_ref, *, act):
    i = pl.program_id(0)
    dout = dout_ref[...].astype(jnp.float32)
    y = y_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    r = r_ref[...]
    centered = y - m
    xhat = centered * r
    if act == "relu":
        # recompute the pre-cast activation input; the mask at exactly 0
        # matches jnp.maximum's vjp convention (grad flows iff s > 0)
        s = xhat * g + b_ref[...].astype(jnp.float32)
        dout = jnp.where(s > 0, dout, 0.0)

    # per-channel partials accumulate across sequential grid steps into
    # the pinned [1, C] output blocks (index_map (0, 0)) — the fused-LN
    # discipline; a [grid, C] partials array would need a block first
    # dim of 1, which Mosaic's (8, 128) tiling rejects
    @pl.when(i == 0)
    def _init():
        dg_ref[...] = jnp.zeros(dg_ref.shape, dg_ref.dtype)
        db_ref[...] = jnp.zeros(db_ref.shape, db_ref.dtype)
        dm_ref[...] = jnp.zeros(dm_ref.shape, dm_ref.dtype)
        dr_ref[...] = jnp.zeros(dr_ref.shape, dr_ref.dtype)

    dg_ref[...] += jnp.sum(dout * xhat, axis=0, keepdims=True)
    db_ref[...] += jnp.sum(dout, axis=0, keepdims=True)
    gr = g * r
    dy = dout * gr
    dm_ref[...] += -jnp.sum(dy, axis=0, keepdims=True)
    dr_ref[...] += jnp.sum(dout * g * centered, axis=0, keepdims=True)
    dy_ref[...] = dy.astype(dy_ref.dtype)


def _fwd_call(y, gamma, beta, mean, rstd, act):
    n, d = y.shape
    bn = _block_rows(n, d, y.dtype)
    interpret = use_pallas()[1]
    kernel = functools.partial(_fwd_kernel, act=act)
    return pl.pallas_call(
        kernel,
        name="conv_bn_act_fwd",
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), y.dtype),
        interpret=interpret,
    )(y, gamma.reshape(1, d), beta.reshape(1, d), mean.reshape(1, d),
      rstd.reshape(1, d))


def _bwd_call(dout, y, gamma, beta, mean, rstd, act):
    n, d = y.shape
    bn = _block_rows(n, d, y.dtype)
    interpret = use_pallas()[1]
    kernel = functools.partial(_bwd_kernel, act=act)
    dy, dg, db, dm, dr = pl.pallas_call(
        kernel,
        name="conv_bn_act_bwd",
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), y.dtype),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ],
        interpret=interpret,
    )(dout, y, gamma.reshape(1, d), beta.reshape(1, d),
      mean.reshape(1, d), rstd.reshape(1, d))
    return dy, dg, db, dm, dr


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _epilogue_core(y, gamma, beta, mean, rstd, act):
    return _fwd_call(y, gamma, beta, mean, rstd, act)


def _epilogue_core_fwd(y, gamma, beta, mean, rstd, act):
    out = _fwd_call(y, gamma, beta, mean, rstd, act)
    return out, (y, gamma, beta, mean, rstd)


def _epilogue_core_bwd(act, saved, dout):
    y, gamma, beta, mean, rstd = saved
    dy, dg, db, dm, dr = _bwd_call(dout, y, gamma, beta, mean, rstd, act)
    return (dy,
            dg.reshape(-1).astype(gamma.dtype),
            db.reshape(-1).astype(beta.dtype),
            dm.reshape(-1).astype(mean.dtype),
            dr.reshape(-1).astype(rstd.dtype))


_epilogue_core.defvjp(_epilogue_core_fwd, _epilogue_core_bwd)


def bn_act_epilogue(y2d, gamma, beta, mean, rstd, act="identity"):
    """``act((y - mean) * rstd * gamma + beta)`` over a channels-last
    2-D view in one VMEM pass.

    y2d: [R, C]; gamma/beta/mean/rstd: [C] (rstd precomputed as
    ``rsqrt(var + eps)`` — the caller owns the statistics so train/eval
    and running-stat updates stay with the op lowering).  The caller
    must have checked :func:`epilogue_eligible`.  Differentiable in
    every tensor argument; the chain through mean/rstd to the batch
    statistics composes outside via jax.
    """
    return _epilogue_core(y2d, gamma, beta,
                          mean.astype(jnp.float32),
                          rstd.astype(jnp.float32), act)
