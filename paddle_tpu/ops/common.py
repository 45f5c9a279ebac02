"""Shared helpers for op lowerings."""

import numpy as np
import jax.numpy as jnp

from ..core import VarDesc, convert_np_dtype_to_dtype_


def resolve_dtype(attr_dtype):
    """Resolve a dtype attr (str / numpy / VarType enum int) to a jnp dtype,
    canonicalized for TPU: 64-bit types map to their 32-bit versions (jax
    default x64-disabled semantics; the graph-level dtype metadata retains
    the declared width)."""
    if isinstance(attr_dtype, (int, VarDesc.VarType)) and not isinstance(
        attr_dtype, bool
    ):
        name = convert_np_dtype_to_dtype_(VarDesc.VarType(int(attr_dtype)))
    else:
        name = convert_np_dtype_to_dtype_(attr_dtype)
    if name == "bfloat16":
        return jnp.bfloat16
    canon = {"int64": "int32", "float64": "float32", "uint64": "uint32"}
    return np.dtype(canon.get(name, name))


def fluid_broadcast(x, y, axis):
    """Fluid elementwise broadcast semantics (reference
    ``operators/elementwise/elementwise_op_function.h``): align y's dims to
    x's starting at `axis` (default -1 = trailing alignment, i.e. numpy)."""
    xnd, ynd = jnp.ndim(x), jnp.ndim(y)
    if xnd == ynd or ynd == 0:
        return x, y
    if xnd > ynd:
        if axis is None or axis == -1:
            axis = xnd - ynd
        new_shape = (1,) * axis + tuple(jnp.shape(y)) + (1,) * (xnd - axis - ynd)
        return x, jnp.reshape(y, new_shape)
    else:
        if axis is None or axis == -1:
            axis = ynd - xnd
        new_shape = (1,) * axis + tuple(jnp.shape(x)) + (1,) * (ynd - axis - xnd)
        return jnp.reshape(x, new_shape), y


def normalize_axis(axis, ndim):
    if axis < 0:
        axis += ndim
    return axis


def flatten_concat(xs, dtype=None):
    """Pack a list of arrays into one flat stream (the
    bucketed-collective layout), optionally casting each segment."""
    return jnp.concatenate([
        x.reshape(-1).astype(dtype) if dtype is not None else x.reshape(-1)
        for x in xs
    ])


def split_like(flat, refs, cast=True):
    """Unpack a flat stream into segments shaped (and, with ``cast``,
    dtyped) like ``refs`` — the inverse of :func:`flatten_concat`.
    Segment sizes are static (taken from the refs' shapes), so the
    slices stay jit-friendly."""
    outs = []
    off = 0
    for r in refs:
        shape = jnp.shape(r)
        n = 1
        for d in shape:
            n *= int(d)
        seg = flat[off:off + n].reshape(shape)
        outs.append(seg.astype(r.dtype) if cast else seg)
        off += n
    return outs
