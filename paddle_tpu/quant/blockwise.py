"""bf16/f32 → int8 block quantization with per-block scales.

The wire format: a tensor is flattened, zero-padded to a multiple of the
block size B (``PADDLE_TPU_QUANT_BLOCK``, default 256), and each block
carries ``q = clip(round(x / s), -127, 127)`` as int8 plus one f32 scale
``s = absmax / 127``.  Dequant is exactly ``q * s`` — the round trip is a
pure function of the input bits, so replay is bit-exact and the forward
op needs no saved state.

Error model (documented, consumed by the drift monitor's ``quant_error``
gauge): within a block of absmax ``m`` the quantization step is
``Δ = m/127``; rounding gives per-element absolute error ≤ ``Δ/2 =
m/254`` and, for the usual dense-gradient case of values spread across
the step, RMS error ≈ ``Δ/√12 = m/(127·√12) ≈ m/440``.  Relative error
is bounded by the block's dynamic range — elements much smaller than the
block absmax see proportionally larger relative error, which is why B is
a knob: smaller blocks localize the scale (better dynamic range) at the
cost of a larger scale sidecar (4/B bytes per element; B=256 → 1.6%
overhead on the int8 payload).

Zero/denormal guard: an all-zero block would give scale 0 and
``x / s = NaN``; the scale is therefore ``where(absmax > 0, absmax/127,
1)`` so zero blocks quantize to zeros with a harmless unit scale.

Kernels: the quantize direction fuses absmax-reduce + scale + round +
cast in one VMEM pass (the XLA composite materializes the [N] absmax and
re-reads x); autotune family ``quant`` caches the rows-per-grid-step
winner.  Off-TPU and for ineligible shapes the entry points route to the
identical-math XLA composite; ``PADDLE_TPU_PALLAS=interpret`` forces
the kernel in interpreter mode (CPU tests).
"""

import functools
import os

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from ..ops.pallas import use_pallas

__all__ = ["quant_enabled", "quant_block", "block_quantize",
           "block_dequantize", "predicted_rms_error", "quantization_error"]

_DEFAULT_BLOCK = 256
_QMAX = 127.0
_BN = 256  # blocks per grid step (rows of the [nblocks, B] view)


def quant_enabled():
    """Global kill switch: ``PADDLE_TPU_QUANT=0`` disables quantized
    collectives everywhere (planner, fusion rewrite, runtime) and
    restores the bf16 paths bit-exactly."""
    return os.environ.get("PADDLE_TPU_QUANT", "").strip() != "0"


def quant_block(default=_DEFAULT_BLOCK):
    """Quantization block size: ``PADDLE_TPU_QUANT_BLOCK`` → default."""
    env = os.environ.get("PADDLE_TPU_QUANT_BLOCK", "").strip()
    if env:
        try:
            v = int(env)
            if v > 0:
                return v
        except ValueError:
            pass
    return default


def padded_size(numel, block):
    """numel rounded up to a whole number of blocks."""
    return -(-int(numel) // int(block)) * int(block)


def _block_rows(nblocks, block):
    """Grid-step row count for the [nblocks, block] view: env cap →
    autotune-cached winner (family ``quant``) → default; a divisor of
    nblocks."""
    try:
        from ..autotune import cached_block_cap

        cap = cached_block_cap("quant", "PADDLE_TPU_QUANT_BLOCK_ROWS",
                               "block_rows", _BN, nblocks=nblocks,
                               block=block)
    except Exception:  # pragma: no cover - autotune unavailable
        cap = _BN
    bn = min(max(cap, 1), nblocks)
    while nblocks % bn:
        bn //= 2
    return max(bn, 1)


def _eligible(nblocks, block):
    if block % 128 or nblocks % 8:
        return False
    # the scales ride in (1, bn) lane tiles of a (1, nblocks) array
    bn = _block_rows(nblocks, block)
    if bn % 128 and bn != nblocks:
        return False
    return use_pallas()[0]


def _scale_of(absmax):
    # zero/denormal blocks: unit scale, so q = round(0/1) = 0 — no NaN
    return jnp.where(absmax > 0.0, absmax / _QMAX, 1.0)


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = _scale_of(absmax)
    q = jnp.clip(jnp.round(x / scale), -_QMAX, _QMAX)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale.reshape(1, -1)


def _dequant_kernel(q_ref, s_ref, out_ref):
    q = q_ref[...].astype(jnp.float32)
    s = s_ref[...].reshape(-1, 1)
    out_ref[...] = (q * s).astype(out_ref.dtype)


def _quantize_xla(blocks):
    absmax = jnp.max(jnp.abs(blocks), axis=1)
    scale = _scale_of(absmax)
    q = jnp.clip(jnp.round(blocks / scale[:, None]), -_QMAX, _QMAX)
    return q.astype(jnp.int8), scale


def _quantize_call(blocks, kernel=True):
    nblocks, block = blocks.shape
    if not kernel or not _eligible(nblocks, block):
        return _quantize_xla(blocks)
    bn = _block_rows(nblocks, block)
    q, s = pl.pallas_call(
        _quant_kernel,
        name="block_quantize",
        grid=(nblocks // bn,),
        in_specs=[pl.BlockSpec((bn, block), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bn, block), lambda i: (i, 0)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nblocks, block), jnp.int8),
            jax.ShapeDtypeStruct((1, nblocks), jnp.float32),
        ],
        interpret=use_pallas()[1],
    )(blocks)
    return q, s.reshape(-1)


def _dequantize_call(q, scales, dtype, kernel=True):
    nblocks, block = q.shape
    if not kernel or not _eligible(nblocks, block):
        return (q.astype(jnp.float32) * scales[:, None]).astype(dtype)
    bn = _block_rows(nblocks, block)
    out = pl.pallas_call(
        _dequant_kernel,
        name="block_dequantize",
        grid=(nblocks // bn,),
        in_specs=[
            pl.BlockSpec((bn, block), lambda i: (i, 0)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((bn, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nblocks, block), dtype),
        interpret=use_pallas()[1],
    )(q, scales.reshape(1, -1))
    return out


def block_quantize(x, block=None, kernel=True):
    """Quantize ``x`` (any shape, float dtype) to int8 blocks.

    Returns ``(q, scales)``: q int8 of shape [npad] (flat, zero-padded to
    a block multiple), scales f32 of shape [npad // block].  Odd-sized
    tails are zero-padded — the pad elements quantize to 0 under the
    tail block's real absmax, so dequant + trim is exact about them.

    ``kernel=False`` pins the XLA composite: pallas_call has no
    shard_map replication rule, so callers tracing under a mesh axis
    (the quantized collective) must take the composite — same math,
    same bits."""
    b = int(block) if block else quant_block()
    flat = x.reshape(-1).astype(jnp.float32)
    npad = padded_size(flat.size, b)
    if npad != flat.size:
        flat = jnp.pad(flat, (0, npad - flat.size))
    q, scales = _quantize_call(flat.reshape(npad // b, b), kernel=kernel)
    return q.reshape(-1), scales


def block_dequantize(q, scales, size=None, shape=None, dtype=jnp.float32,
                     kernel=True):
    """Exact dequant ``q * scale``; trims the pad back to ``size`` (or
    ``shape``'s numel) and reshapes when asked.  ``kernel=False`` as in
    :func:`block_quantize`."""
    nblocks = scales.shape[0]
    block = q.size // nblocks
    out = _dequantize_call(q.reshape(nblocks, block), scales,
                           jnp.dtype(dtype), kernel=kernel).reshape(-1)
    if shape is not None:
        size = 1
        for d in shape:
            size *= int(d)
    if size is not None and size != out.size:
        out = out[:size]
    if shape is not None:
        out = out.reshape(shape)
    return out


def predicted_rms_error(scales):
    """The error model's predicted RMS quantization error for a tensor
    with the given per-block scales: per-block RMS ≈ Δ/√12 with Δ = the
    block scale, averaged over blocks in quadrature."""
    s = jnp.asarray(scales, jnp.float32)
    return jnp.sqrt(jnp.mean(jnp.square(s)) / 12.0)


def quantization_error(x, block=None):
    """Measured vs predicted round-trip error (drift-gauge feed).

    Returns dict(measured_rms, predicted_rms, rel_error) — rel_error is
    measured RMS over the tensor's own RMS (0 for an all-zero input)."""
    xf = jnp.asarray(x).reshape(-1).astype(jnp.float32)
    q, scales = block_quantize(xf, block=block)
    back = block_dequantize(q, scales, size=xf.size)
    err = back - xf
    measured = jnp.sqrt(jnp.mean(jnp.square(err)))
    x_rms = jnp.sqrt(jnp.mean(jnp.square(xf)))
    rel = jnp.where(x_rms > 0.0, measured / x_rms, 0.0)
    return {"measured_rms": measured,
            "predicted_rms": predicted_rms_error(scales),
            "rel_error": rel}
