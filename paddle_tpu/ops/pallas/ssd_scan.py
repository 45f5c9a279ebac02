"""Mamba-2's state-space-dual (SSD) scan, forward and backward, as Pallas
TPU kernels.

Per head h, with a scalar decay rate ``A_h < 0``, a step ``dt_t > 0`` (Δ),
``x_t`` of ``P`` features and ``B_t``, ``C_t`` of ``N`` of the head's group
(``G`` groups of ``H / G`` heads, head h reading group ``h // (H / G)``;
with one group they are shared by every head)::

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T      (S: P x N, S_0 = 0)
    y_t = S_t C_t + D_h x_t

Chunked (Dao and Gu 2024, "Transformers are SSMs", the SSD algorithm), a
chunk of ``L`` positions at a time: with ``cum_t`` the sum of ``dt A``
over the chunk up to t (inclusive) and ``u_s = dt_s x_s``::

    y_t = sum_{s <= t} exp(cum_t - cum_s) (C_t . B_s) u_s    (within)
          + exp(cum_t) S_prev C_t                          (carried in)
    S   = exp(cum_last) S_prev + sum_s exp(cum_last - cum_s) u_s B_s^T

* forward (``ssd_scan_fwd``): grid (batch, blocks of ``hb`` heads,
  chunks), the chunk axis sequential; a block lies inside one group,
  whose B and C its block specs pick; the carried state of the block's
  heads, ``S^T`` as ``[N, hb P]`` float32, lives in VMEM scratch from
  chunk to chunk.  A grid step computes ``C B^T`` once for its heads,
  each head's decay matrix from its ``cum`` row, the within-chunk product
  and the carried-in term, writes y and the state the chunk began with
  (the backward's residual), and moves the state on.
* backward (``ssd_scan_bwd``): the same grid with the chunks walked from
  the last, the state's gradient carried in scratch the same way; score-
  shaped values keep the source position ``s`` down the rows (the
  transpose of the forward's), so every product is a plain one or an
  ``a @ b^T``; it writes dx, the rows of dΔ and d(cum), and dB and dC of
  its heads (summed outside over the head blocks of each group).

Heads lie ``P`` lanes apart in ``[.., H P]`` (the layout ``in_proj``
gives): a 128-lane tile holds ``128 / P`` heads, and a head's product is
taken over the whole tile and kept on its own lanes, which costs the
matrix unit nothing at ``P`` 64 (its width is 128).  The decays, ``cum``
and the carried state are float32; the products take operands of x's
dtype (bf16 under AMP) with float32 accumulation.

``cum`` (a cumulative sum inside each chunk) and ``D x`` are XLA
around the kernels, so ``jax`` differentiates them: the kernels' custom
VJP hands back d(cum) and dΔ, and dA, dΔ and dD follow.  Off the TPU, or
for shapes the kernels do not cover, the same chunked algorithm runs as
XLA ops (``_chunked_xla``); ``PADDLE_TPU_PALLAS=interpret`` runs the
kernels in the interpreter (CPU tests).

Counting: while :func:`noting_chunks` is open, each kernel that is traced
adds its chunks (times batch and heads) under its pass, ``forward``,
``region_rerun`` (a forward traced inside :func:`rerunning`: a recompute
region's re-run) or ``backward``
(``observability.runtime.record_ssd_chunks``).
"""

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import use_pallas

CHUNK = 256
HEADS_A_BLOCK = 16

_noting = threading.local()


@contextlib.contextmanager
def noting_chunks(counts):
    """While active on this thread, every scan kernel that is traced adds
    its chunks (times batch and heads) to ``counts[pass]``."""
    was = getattr(_noting, "counts", None)
    _noting.counts = counts
    try:
        yield counts
    finally:
        _noting.counts = was


@contextlib.contextmanager
def rerunning():
    """Forward kernels traced inside count as ``region_rerun``."""
    was = getattr(_noting, "rerun", False)
    _noting.rerun = True
    try:
        yield
    finally:
        _noting.rerun = was


def _note_chunks(kind, chunks):
    counts = getattr(_noting, "counts", None)
    if counts is None:
        return
    if kind == "forward" and getattr(_noting, "rerun", False):
        kind = "region_rerun"
    counts[kind] += chunks


# ---------------------------------------------------------------------------
# The kernels
# ---------------------------------------------------------------------------

def _column(row, n):
    """A ``(1, L)`` row as ``(L, n)``, ``n`` a multiple of 128: row t
    holds ``row[t]`` in every lane (a transpose on the XLU, then whole
    vregs repeated)."""
    col = jnp.broadcast_to(row, (128, row.shape[1])).T
    return col if n == 128 else pltpu.repeat(col, n // 128, axis=1)


def _per_lane(rows, lane_head):
    """``(L, 128)`` from the ``(1, L)`` rows of a tile's heads: each lane
    the value of its own head."""
    out = _column(rows[0], 128)
    for j, row in enumerate(rows[1:], 1):
        out = jnp.where(lane_head == j, _column(row, 128), out)
    return out


def _decay(row, causal, later_down):
    """``exp(cum_later - cum_earlier)`` under the causal mask, ``(L, L)``
    from one head's ``(1, L)`` row of ``cum``; ``later_down``: the later
    position runs down the rows (the forward's orientation)."""
    n = row.shape[1]
    across = jnp.broadcast_to(row, (n, n))
    down = _column(row, n)
    seg = down - across if later_down else across - down
    return jnp.exp(jnp.where(causal, seg, -jnp.inf))


def _dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_t(a, b):
    """``a @ b.T``: contraction over the lanes of both."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _fwd_kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, bt_ref, y_ref, s_ref,
                state, *, head_dim, dtype):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        state[:] = jnp.zeros_like(state)

    s_ref[0, 0] = state[:]  # the state this chunk begins with
    length, width = x_ref.shape[1], x_ref.shape[2]
    per = 128 // head_dim
    cm = c_ref[0].astype(dtype)
    g = _dot_t(cm, b_ref[0].astype(dtype))       # C_t . B_s: [t, s]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (length, length), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (length, length), 1))
    carried = _dot(cm, state[:].astype(dtype))    # C_t S_prev: [t, H P]
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (length, 128), 1) \
        // head_dim
    for k in range(width // 128):
        lanes = slice(128 * k, 128 * k + 128)
        heads = range(per * k, per * k + per)
        rows = [cum_ref[0, h:h + 1, :] for h in heads]
        cum = _per_lane(rows, lane_head)
        u = x_ref[0, :, lanes].astype(jnp.float32) * _per_lane(
            [dt_ref[0, h:h + 1, :] for h in heads], lane_head)
        ub = u.astype(dtype)
        y = jnp.exp(cum) * carried[:, lanes]
        for j, row in enumerate(rows):
            m = (g * _decay(row, causal, True)).astype(dtype)
            y = y + jnp.where(lane_head == j, _dot(m, ub), 0.0)
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        last = cum[length - 1:length, :]
        w = jnp.exp(last - cum)
        state[:, lanes] = state[:, lanes] * jnp.exp(last) + _dot(
            bt_ref[0].astype(dtype), (w * u).astype(dtype))


def _head_sums(q, per, head_dim):
    """The ``(1, L)`` rows of per-head sums over the lanes of an
    ``(L, 128)`` tile: one transpose, then sums down the rows."""
    qt = q.T
    return [jnp.sum(qt[j * head_dim:(j + 1) * head_dim], axis=0,
                    keepdims=True) for j in range(per)]


def _bwd_kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, bt_ref, ct_ref, s_ref,
                dy_ref, dx_ref, ddt_ref, dcum_ref, db_ref, dc_ref, dstate,
                *, head_dim, dtype):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        dstate[:] = jnp.zeros_like(dstate)

    length, width = x_ref.shape[1], x_ref.shape[2]
    per = 128 // head_dim
    bm, cm = b_ref[0].astype(dtype), c_ref[0].astype(dtype)
    gt = _dot_t(bm, cm)                           # B_s . C_t: [s, t]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (length, length), 1)
              >= jax.lax.broadcasted_iota(jnp.int32, (length, length), 0))
    s0, dse = s_ref[0, 0], dstate[:]
    carried = _dot(cm, s0.astype(dtype))          # C_t S_prev
    into_end = _dot(bm, dse.astype(dtype))        # B_s dS_end
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (length, 128), 1) \
        // head_dim
    at_last = jax.lax.broadcasted_iota(jnp.int32, (1, length), 1) \
        == length - 1
    lane_row = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1) // head_dim
    dgt = jnp.zeros((length, length), jnp.float32)
    db = jnp.zeros(bm.shape, jnp.float32)
    dc = jnp.zeros(cm.shape, jnp.float32)
    for k in range(width // 128):
        lanes = slice(128 * k, 128 * k + 128)
        heads = range(per * k, per * k + per)
        rows = [cum_ref[0, h:h + 1, :] for h in heads]
        cum = _per_lane(rows, lane_head)
        dt = _per_lane([dt_ref[0, h:h + 1, :] for h in heads], lane_head)
        x = x_ref[0, :, lanes].astype(jnp.float32)
        u = x * dt
        ub = u.astype(dtype)
        dy = dy_ref[0, :, lanes].astype(jnp.float32)
        dyb = dy.astype(dtype)
        ey = jnp.exp(cum) * dy
        last = cum[length - 1:length, :]
        w = jnp.exp(last - cum)
        wu = w * u
        to_end = w * into_end[:, lanes]
        du_within = jnp.zeros_like(u)
        within_rows = []
        for j, row in enumerate(rows):
            dct = _decay(row, causal, False)      # [s, t]
            mt = (gt * dct).astype(dtype)
            du_within = du_within + jnp.where(
                lane_head == j, _dot(mt, dyb), 0.0)
            dmt = _dot_t(jnp.where(lane_head == j, ub, 0).astype(dtype),
                         dyb)                     # u_s . dy_t, head j
            dgt = dgt + dmt * dct
            # the later end's share of d(cum) from the very products the
            # earlier end's takes (``ub * du_within``): the diagonal,
            # the largest, cancels between them
            within_rows.append(jnp.sum(dmt * mt.astype(jnp.float32),
                                       axis=0, keepdims=True))
        du = du_within + to_end
        dx_ref[0, :, lanes] = (dt * du).astype(dx_ref.dtype)
        # d(cum_s): the carried-in term, the within term's earlier end,
        # what S_end took from u_s; d(cum_last) gets the latter's sum and
        # what S_end took from S_prev
        ends = wu * into_end[:, lanes]
        moved = _head_sums(ey * carried[:, lanes]
                           - ub.astype(jnp.float32) * du_within - ends,
                           per, head_dim)
        ended = _head_sums(ends, per, head_dim)
        took = _head_sums(x * du, per, head_dim)
        kept = jnp.sum(dse[:, lanes] * s0[:, lanes], axis=0, keepdims=True)
        for j, h in enumerate(heads):
            last_j = jnp.sum(jnp.where(at_last, rows[j], 0.0), axis=1,
                             keepdims=True)
            total = jnp.sum(ended[j], axis=1, keepdims=True) + jnp.exp(
                last_j) * jnp.sum(jnp.where(lane_row == j, kept, 0.0),
                                  axis=1, keepdims=True)
            dcum_ref[0, h:h + 1, :] = (within_rows[j] + moved[j]
                                       + jnp.where(at_last, total, 0.0))
            ddt_ref[0, h:h + 1, :] = took[j]
        db = db + _dot_t(wu.astype(dtype), dse[:, lanes].astype(dtype))
        dc = dc + _dot_t(ey.astype(dtype), s0[:, lanes].astype(dtype))
        dstate[:, lanes] = dse[:, lanes] * jnp.exp(last) + _dot(
            ct_ref[0].astype(dtype), ey.astype(dtype))
    db_ref[0, 0] = db + _dot(dgt.astype(dtype), cm)
    dc_ref[0, 0] = dc + _dot(bt_ref[0].astype(dtype),
                             dgt.astype(dtype)).T


# ---------------------------------------------------------------------------
# The calls
# ---------------------------------------------------------------------------

def _specs(hb, head_dim, chunk, n, reverse, chunks, blocks_a_group=None):
    """Block specs over (batch, head block, chunk): ``reverse`` walks
    the chunks from the last; ``blocks_a_group``: B and C hold a group
    of ``n`` lanes for each run of that many head blocks (None: one
    group, the same for every block)."""
    def at(c):
        return chunks - 1 - c if reverse else c

    lanes = pl.BlockSpec((1, chunk, hb * head_dim),
                         lambda b, h, c: (b, at(c), h))
    rows = pl.BlockSpec((1, hb, chunk), lambda b, h, c: (b, h, at(c)))
    if blocks_a_group is None:
        shared = pl.BlockSpec((1, chunk, n), lambda b, h, c: (b, at(c), 0))
        turned = pl.BlockSpec((1, n, chunk), lambda b, h, c: (b, 0, at(c)))
    else:
        shared = pl.BlockSpec((1, chunk, n), lambda b, h, c: (
            b, at(c), h // blocks_a_group))
        turned = pl.BlockSpec((1, n, chunk), lambda b, h, c: (
            b, h // blocks_a_group, at(c)))
    state = pl.BlockSpec((1, 1, n, hb * head_dim),
                         lambda b, h, c: (b, at(c), 0, h))
    per_block = pl.BlockSpec((1, 1, chunk, n),
                             lambda b, h, c: (b, h, at(c), 0))
    return lanes, rows, shared, turned, state, per_block


def _operands(x, interpret):
    """The products' operand dtype: x's (bf16 under AMP, float32
    accumulation), float32 in the interpreter, whose CPU dots take no
    bf16 operands into a float32 result (the same numbers: bf16 casts up
    exactly and its products fit float32)."""
    return jnp.float32 if interpret else x.dtype


def _grouped(b, c, width, hb, head_dim):
    """B and C as the kernels take them, ``[Bt, T, G N]``, and the head
    blocks a group spans (None for one group: ``[Bt, T, N]`` as given)."""
    if b.ndim == 3:
        return b, c, None
    bt, t, groups, n = b.shape
    return (b.reshape(bt, t, groups * n), c.reshape(bt, t, groups * n),
            width // (hb * head_dim * groups))


def _ssd_fwd(x, dtT, cumT, b, c, static):
    """``(y, the state each chunk begins with [Bt, chunks, N, H P])``."""
    head_dim, chunk, hb, interpret = static
    bt, t, width = x.shape
    n, chunks = b.shape[-1], t // chunk
    _note_chunks("forward", bt * chunks * (width // head_dim))
    b, c, per_group = _grouped(b, c, width, hb, head_dim)
    lanes, rows, shared, turned, state, _ = _specs(
        hb, head_dim, chunk, n, False, chunks, per_group)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, head_dim=head_dim,
                          dtype=_operands(x, interpret)),
        name="ssd_scan_fwd",
        grid=(bt, width // (hb * head_dim), chunks),
        in_specs=[lanes, rows, rows, shared, shared, turned],
        out_specs=[lanes, state],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((bt, chunks, n, width),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, hb * head_dim), jnp.float32)],
        interpret=interpret,
    )(x, dtT, cumT, b, c, jnp.swapaxes(b, 1, 2))


def _ssd_bwd(x, dtT, cumT, b, c, states, dy, static):
    head_dim, chunk, hb, interpret = static
    bt, t, width = x.shape
    n, chunks, blocks = b.shape[-1], t // chunk, width // (hb * head_dim)
    _note_chunks("backward", bt * chunks * (width // head_dim))
    groups = _groups(b)
    b, c, per_group = _grouped(b, c, width, hb, head_dim)
    lanes, rows, shared, turned, state, per_block = _specs(
        hb, head_dim, chunk, n, True, chunks, per_group)
    dx, ddt, dcum, db, dc = pl.pallas_call(
        functools.partial(_bwd_kernel, head_dim=head_dim,
                          dtype=_operands(x, interpret)),
        name="ssd_scan_bwd",
        grid=(bt, blocks, chunks),
        in_specs=[lanes, rows, rows, shared, shared, turned, turned, state,
                  lanes],
        out_specs=[lanes, rows, rows, per_block, per_block],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(dtT.shape, jnp.float32),
                   jax.ShapeDtypeStruct(cumT.shape, jnp.float32),
                   jax.ShapeDtypeStruct((bt, blocks, t, n), jnp.float32),
                   jax.ShapeDtypeStruct((bt, blocks, t, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, hb * head_dim), jnp.float32)],
        interpret=interpret,
    )(x, dtT, cumT, b, c, jnp.swapaxes(b, 1, 2), jnp.swapaxes(c, 1, 2),
      states, dy)

    def over_blocks(v):
        """[Bt, blocks, T, N] summed over each group's head blocks."""
        if per_group is None:
            return v.sum(1)
        return jnp.swapaxes(v.reshape(bt, groups, per_group, t, n).sum(2),
                            1, 2)

    return (dx, ddt.astype(dtT.dtype), dcum,
            over_blocks(db).astype(b.dtype), over_blocks(dc).astype(c.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(x, dtT, cumT, b, c, static):
    return _ssd_fwd(x, dtT, cumT, b, c, static)[0]


def _scan_fwd_rule(x, dtT, cumT, b, c, static):
    y, states = _ssd_fwd(x, dtT, cumT, b, c, static)
    return y, (x, dtT, cumT, b, c, states)


def _scan_bwd_rule(static, res, dy):
    return _ssd_bwd(*res, dy, static)


_scan.defvjp(_scan_fwd_rule, _scan_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _scan_kept(x, dtT, cumT, b, c, y, states, static):
    """:func:`_scan` where the forward kernel has run already and its
    ``(y, states)`` were kept: traces no forward kernel, and backward the
    backward kernel over the same residuals."""
    return y


def _scan_kept_fwd_rule(x, dtT, cumT, b, c, y, states, static):
    return y, (x, dtT, cumT, b, c, states)


def _scan_kept_bwd_rule(static, res, dy):
    return _ssd_bwd(*res, dy, static) + (None, None)


_scan_kept.defvjp(_scan_kept_fwd_rule, _scan_kept_bwd_rule)


# ---------------------------------------------------------------------------
# The same algorithm in XLA ops, where the kernels do not run
# ---------------------------------------------------------------------------

def _chunked_xla(x, dt, cum, b, c, chunk):
    """``y`` without ``D x``: x [Bt, T, H, P], dt and cum [Bt, T, H], b
    and c [Bt, T, G, N] (head h reads group ``h // (H / G)``), T a
    multiple of ``chunk``; float32."""
    bt, t, h, p = x.shape
    g, k = b.shape[2], t // chunk
    xc = x.reshape(bt, k, chunk, g, h // g, p)
    dtc, cc = (v.reshape(bt, k, chunk, g, h // g) for v in (dt, cum))
    bc, ccm = (v.reshape(bt, k, chunk, g, -1) for v in (b, c))
    u = xc * dtc[..., None]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :,
                                                       None, None]
    seg = cc[:, :, :, None] - cc[:, :, None]         # [.., t, s, G, H/G]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    gm = jnp.einsum("bktgn,bksgn->bktsg", ccm, bc)
    y = jnp.einsum("bktsg,bktsgr,bksgrp->bktgrp", gm, decay, u)
    w = jnp.exp(cc[:, :, -1:] - cc)
    into = jnp.einsum("bksgr,bksgrp,bksgn->bkgrpn", w, u, bc)

    def carry(s, step):
        add, fall = step
        return s * fall[..., None, None] + add, s

    _, begins = jax.lax.scan(
        carry, jnp.zeros((bt, g, h // g, p, bc.shape[-1]), x.dtype),
        (jnp.moveaxis(into, 1, 0), jnp.moveaxis(jnp.exp(cc[:, :, -1]), 1, 0)))
    y = y + jnp.einsum("bktgn,kbgrpn->bktgrp", ccm, begins) \
        * jnp.exp(cc)[..., None]
    return y.reshape(bt, t, h, p)


# ---------------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------------

def _groups(b):
    return 1 if len(b.shape) == 3 else b.shape[2]


def routes_to_kernel(x, b, chunk=CHUNK):
    """Does :func:`ssd_scan` run the Pallas kernels for x [Bt, T, H, P]
    and b [Bt, T, N] or [Bt, T, G, N] (anything with ``.shape``), or XLA
    ops?"""
    _, _, h, p = x.shape
    return (use_pallas()[0] and 128 % p == 0 and h * p % 128 == 0
            and b.shape[-1] % 16 == 0 and chunk % 128 == 0
            and _heads_a_block(h, p, _groups(b)) is not None)


def _heads_a_block(h, p, groups=1):
    """Heads a grid step: about ``HEADS_A_BLOCK``, dividing the ``h /
    groups`` heads of a group (a block reads one group's B and C), whole
    128-lane tiles, and a multiple of 8 rows of ``cum`` (or all of them);
    None where no such block exists."""
    if h % groups:
        return None
    per = h // groups
    hb = min(HEADS_A_BLOCK, per)
    while per % hb or hb * p % 128 or (hb % 8 and hb != h):
        hb += 1
        if hb > per:
            return None
    return hb


def ssd_scan(x, dt, a, b, c, d=None, chunk=CHUNK, residuals=None,
             return_residuals=False):
    """Mamba-2's scan.  x: [Bt, T, H, P]; dt (Δ, after its softplus):
    [Bt, T, H]; a (A, negative): [H]; b, c: [Bt, T, N], one group shared
    by every head, or [Bt, T, G, N], head h reading group ``h // (H /
    G)``; d: [H] or None.  Returns y [Bt, T, H, P] in x's dtype.
    dt, a and d are taken in float32.  T need not be a multiple of
    ``chunk``: the tail is padded with Δ = 0, which moves no state.

    ``return_residuals``: return ``(y, residuals)``, where residuals is
    what the forward kernel computed and the backward kernel reads (y
    without ``D x``, and the state each chunk began with), or None where
    XLA ops ran.  ``residuals``: such a pair from an earlier call on the
    same inputs; the forward kernel is not run again (a recompute
    region's re-run: ``ops/nn.py``)."""
    bt, t, h, p = x.shape
    dt = dt.astype(jnp.float32)
    pad = -t % chunk
    if pad:
        def grow(v):
            return jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
        x, dt, b, c = grow(x), grow(dt), grow(b), grow(c)
    tp = t + pad
    steps = dt * a.astype(jnp.float32)
    cum = jnp.cumsum(steps.reshape(bt, tp // chunk, chunk, h),
                     axis=2).reshape(bt, tp, h)
    kept = None
    if not routes_to_kernel(x, b, chunk):
        def grouped(v):
            v = v.astype(jnp.float32)
            return v[:, :, None] if v.ndim == 3 else v
        y = _chunked_xla(x.astype(jnp.float32), dt, cum, grouped(b),
                         grouped(c), chunk).astype(x.dtype)
    else:
        static = (p, chunk, _heads_a_block(h, p, _groups(b)),
                  use_pallas()[1])
        args = (x.reshape(bt, tp, h * p), jnp.swapaxes(dt, 1, 2),
                jnp.swapaxes(cum, 1, 2), b.astype(x.dtype),
                c.astype(x.dtype))
        if residuals is None and not return_residuals:
            y = _scan(*args, static)
        else:
            if residuals is None:
                # the kernel once, outside any differentiation; the
                # gradient goes through _scan_kept, over what it gave
                residuals = _ssd_fwd(*map(jax.lax.stop_gradient, args),
                                     static)
            kept = residuals
            y = _scan_kept(*args, *residuals, static)
        y = y.reshape(bt, tp, h, p)
    if d is not None:
        y = (y.astype(jnp.float32) + d.astype(jnp.float32)[:, None]
             * x.astype(jnp.float32)).astype(x.dtype)
    y = y[:, :t]
    return (y, kept) if return_residuals else y

