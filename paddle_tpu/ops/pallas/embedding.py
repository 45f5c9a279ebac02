"""Device-side embedding gather as a Pallas TPU kernel (scalar-prefetch
row DMA), with the scatter-add backward.

Reference analogue: the distributed lookup-table path
(``operators/distributed/parameter_prefetch.cc``) whose TPU host-side
redesign is :mod:`paddle_tpu.host_table` — the table lives in host RAM
and every step pays a host gather + H2D of the slab plus a D2H of the
slab gradient.  That round-trip caps DeepFM at its baseline (2720
ex/s/chip flat).  When the table FITS device memory (or a row shard of
it does, ``_is_distributed`` row sharding), the lookups belong on the
chip: this module is that device-side gather.

Kernel: ``pltpu.PrefetchScalarGridSpec`` with the flat id vector as the
scalar-prefetch argument.  The TPU lowering only accepts blocks whose
second-minor dimension is a multiple of the 8-row tile, so a single
``(1, D)`` row cannot be a block: each grid step produces one 8-row
output tile, and the table is passed 8 times, once per output row, each
with its own index map that DMAs the aligned 8-row table tile holding
``ids[8*i + j]`` HBM→VMEM; the kernel body picks row ``id % 8`` out of
each tile.  Rows never transit as a dense [V, D] read (only the touched
tiles move, 8 rows per id), and the id stream is known before the body
runs, so Mosaic double-buffers the tile DMAs across grid steps.

Backward: the standard sparse-embedding gradient — a scatter-add of the
slab gradient into a zero [V, D] buffer (``.at[ids].add``), XLA's
native SelectedRows-equivalent form on TPU, attached via custom_vjp so
both the Pallas and XLA forwards share it.

Routing: ``jnp.take`` (the exact ``lookup_table`` lowering semantics:
negative ids clamp to row 0, overflowing ids clamp to the last row,
``padding_idx`` rows read zeros) off-TPU or for ineligible shapes;
``PADDLE_TPU_PALLAS=interpret`` forces the kernel on CPU for tests.
"""

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import use_pallas

_TILE_ROWS = 8  # second-minor tile of the TPU memory layout


def gather_eligible(rows, dim, dtype=jnp.float32):
    """Whether the Pallas gather kernel takes a [rows, dim] table."""
    if dim % 128 or dim > 8192 or rows < 1:
        return False
    if not jnp.issubdtype(dtype, jnp.floating):
        return False
    return use_pallas()[0]


def _gather_kernel(ids_ref, *refs):
    # the index maps already routed the tile holding ids[8*i + j] into
    # tiles[j]; pick the row inside it
    *tiles, out_ref = refs
    i = pl.program_id(0)
    rows = []
    for j, tile in enumerate(tiles):
        r = ids_ref[_TILE_ROWS * i + j] % _TILE_ROWS
        if out_ref.dtype.itemsize == 4:
            rows.append(tile[pl.ds(r, 1), :])
        else:
            # packed dtypes: Mosaic cannot load at a dynamic sublane
            # offset, so select the row by mask (exact: x + 0)
            blk = tile[...].astype(jnp.float32)
            sel = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0) == r
            rows.append(jnp.sum(jnp.where(sel, blk, 0.0), axis=0,
                                keepdims=True))
    out_ref[...] = jnp.concatenate(rows, axis=0).astype(out_ref.dtype)


def _pallas_gather(table, flat_ids):
    n = flat_ids.shape[0]
    _, d = table.shape
    steps = -(-n // _TILE_ROWS)
    # pad the id stream to whole output tiles (row 0 is always valid)
    ids = jnp.pad(flat_ids, (0, steps * _TILE_ROWS - n))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec(
                (_TILE_ROWS, d),
                lambda i, ids, j=j: (ids[_TILE_ROWS * i + j] // _TILE_ROWS,
                                     0))
            for j in range(_TILE_ROWS)
        ],
        out_specs=pl.BlockSpec((_TILE_ROWS, d), lambda i, ids: (i, 0)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        name="embedding_gather",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((steps * _TILE_ROWS, d),
                                       table.dtype),
        interpret=use_pallas()[1],
    )(ids, *([table] * _TILE_ROWS))
    return out[:n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _gather_core(table, flat_ids, meta):
    """Row gather with clamped int32 ids; Pallas when eligible.
    ``meta`` = (rows, dim, dtype_str) — static, so the backward knows
    the table struct without hauling the table through the residuals."""
    if gather_eligible(*table.shape, table.dtype):
        return _pallas_gather(table, flat_ids)
    return jnp.take(table, flat_ids, axis=0)


def _gather_core_fwd(table, flat_ids, meta):
    return _gather_core(table, flat_ids, meta), flat_ids


def _gather_core_bwd(meta, flat_ids, dout):
    rows, dim, dtype = meta
    # scatter-add: duplicate ids accumulate, exactly the vjp of take
    # (and the reference's SelectedRows sparse-grad merge-add)
    dtab = jnp.zeros((rows, dim), dout.dtype).at[flat_ids].add(dout)
    return dtab.astype(dtype), None


_gather_core.defvjp(_gather_core_fwd, _gather_core_bwd)


def embedding_gather(W, Ids, padding_idx=-1):
    """``W[ids]`` with the framework ``lookup_table`` semantics, Pallas
    row-DMA gather on TPU (XLA take elsewhere).

    W: [V, D]; Ids: any int shape, a trailing dim of 1 is squeezed
    (the reference's ``[..., 1]`` id layout); returns ids.shape + (D,).
    Negative ids clamp to row 0 and ids >= V NaN-fill with no gradient
    (``jnp.take``'s default fill mode — identical to the unfused
    lowering, so the rewrite is value-preserving even on corrupt id
    streams); ``padding_idx`` rows come back zero with no gradient.
    """
    ids = Ids
    squeeze_last = ids.ndim > 1 and ids.shape[-1] == 1
    if squeeze_last:
        ids = ids[..., 0]
    ids = ids.astype(jnp.int32)
    v, dim = W.shape
    flat = jnp.clip(ids, 0, v - 1).reshape(-1)
    meta = (int(v), int(dim), str(W.dtype))
    out = _gather_core(W, flat, meta).reshape(ids.shape + (dim,))
    if jnp.issubdtype(out.dtype, jnp.floating):
        # jnp.take's default fill mode NaN-fills ids >= V (and the vjp
        # sends them no gradient) — replicate exactly, so the fused op
        # is value-preserving vs the lookup_table lowering even on
        # corrupt id streams
        out = jnp.where((ids >= v)[..., None],
                        jnp.full_like(out, jnp.nan), out)
    if padding_idx is not None and padding_idx != -1:
        out = jnp.where(
            (ids == padding_idx)[..., None], jnp.zeros_like(out), out)
    return out
