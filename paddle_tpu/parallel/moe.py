"""Mixture-of-Experts FFN with expert parallelism over a mesh axis.

The reference (2019) has no MoE; this is net-new capability the build
brief requires (the dp/tp/pp/sp/EP sharding roster).  Switch-Transformer
construction, TPU-native:

* top-1 gating with a capacity limit per expert (static shapes: XLA
  needs fixed [E, C, D] dispatch buffers; over-capacity tokens pass
  through the residual unrouted — standard Switch behavior);
* experts are SHARDED over the ``expert`` mesh axis (each device holds
  E/n experts' weights);
* dispatch/combine are each ONE ``all_to_all`` over ICI: tokens move to
  the device holding their expert, the expert FFN runs as a batched
  einsum over the local experts, results return to their source device;
* the Switch auxiliary load-balancing loss (mean fraction x mean gate
  probability per expert, scaled by E) is returned alongside.

Entry points mirror the other parallel primitives:
* :func:`moe_ffn_local` — call INSIDE shard_map (token shard per device);
* :func:`moe_ffn` — global [B, T, D] + mesh wrapper (batch sharded over
  the ``expert`` axis, experts sharded over the same axis — the usual
  dp=ep co-located layout).

**The dropless share** (:func:`sigmoid_topk_route`, :func:`plan_held_rows`,
:func:`held_experts_ffn`) is the expert layer that expert parallelism over
many small experts needs, and the lowering of the Program ops
``moe_route`` and ``moe_experts``: the device is told which experts it
holds (``first``, ``held`` of ``E``), routes over all ``E`` (sigmoid
scores, the ``top_k`` largest of score + correction bias, gates
normalised over the chosen and scaled) and computes the part of the
layer's result that its own experts give.  Nothing is dropped whatever
the imbalance: every choice has its place in an order of ``tokens *
top_k`` choices (every choice of every token may land here), those of
the held experts first and sorted by expert, and one loop walks that
order in blocks (:func:`block_rows`) as far as the rows routed here reach,
its trip count read from the row counts on the device.  A block gathers
its tokens' rows, runs the three products as :func:`grouped_dot`
(``jax.lax.ragged_dot``) over the held experts' row counts inside the
block (on the TPU XLA lowers it to one grouped Mosaic kernel,
``ragged-dot-none``, that visits only the tiles the counts cover) and
scatter-adds the results to their tokens: dispatch, products and
combine all follow the rows routed here, not ``tokens * top_k`` and not
``rows * experts``, and no buffer of every choice exists.  On one device
it runs without an exchange; under expert parallelism the exchange moves
rows between devices before and after it.
The Switch path above is as it was.
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["moe_ffn", "moe_ffn_local", "init_moe_params",
           "moe_dispatch", "moe_combine", "MOE_RING_ID",
           "sigmoid_topk_route", "held_rows", "plan_held_rows",
           "grouped_dot", "share_level", "BLOCK_ROWS", "block_rows",
           "blocks_run", "held_experts_ffn"]

# ring-id convention (see parallel/pipeline.py / README "Analyzer")
MOE_RING_ID = 2


def _append_all_to_all(x, ring_id, tag, split_axis, concat_axis):
    """Append an ``all_to_all`` IR op re-sharding ``x`` (global view:
    shape-preserving; under shard_map it is the real lax collective).
    The ring_id stamp is what the ``collective-ring`` lint check and the
    cross-worker schedule prover key on."""
    from .. import unique_name

    block = x.block
    out = block.create_var(
        name=unique_name.generate(x.name + "." + tag),
        shape=x.shape, dtype=x.dtype)
    block.append_op(
        type="all_to_all", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"ring_id": int(ring_id), "split_axis": int(split_axis),
               "concat_axis": int(concat_axis), "comm_tag": tag})
    return out


def moe_dispatch(x, ring_id=MOE_RING_ID, split_axis=0, concat_axis=0):
    """Program-IR twin of the dispatch ``all_to_all`` in
    :func:`moe_ffn_local`: tokens move to the device holding their
    expert.  Emits one ring-stamped ``all_to_all`` op so expert-parallel
    programs carry their communication schedule in the IR the static
    analyzer walks."""
    return _append_all_to_all(x, ring_id, "moe_dispatch",
                              split_axis, concat_axis)


def moe_combine(x, ring_id=MOE_RING_ID, split_axis=0, concat_axis=0):
    """Program-IR twin of the combine ``all_to_all``: expert outputs
    return to their source device.  Must mirror :func:`moe_dispatch` on
    every worker, in the same order — the schedule prover checks it."""
    return _append_all_to_all(x, ring_id, "moe_combine",
                              split_axis, concat_axis)


def init_moe_params(rng, d_model, d_ff, n_experts, dtype=jnp.float32):
    """(gate_w, w1, b1, w2, b2) with expert-major stacking."""
    k1, k2, k3 = jax.random.split(rng, 3)
    scale_in = 1.0 / jnp.sqrt(d_model)
    return (
        jax.random.normal(k1, (d_model, n_experts), dtype) * scale_in,
        jax.random.normal(k2, (n_experts, d_model, d_ff), dtype) * scale_in,
        jnp.zeros((n_experts, d_ff), dtype),
        jax.random.normal(k3, (n_experts, d_ff, d_model), dtype)
        * (1.0 / jnp.sqrt(d_ff)),
        jnp.zeros((n_experts, d_model), dtype),
    )


def _dispatch_tensors(x, gates, n_experts, capacity):
    """Build the [E, C, D] dispatch buffer + combine weights.

    x: [T, D] local tokens; gates: [T, E] softmax probs.
    Returns (dispatched [E, C, D], combine weights [T], expert_idx [T],
    slot_idx [T], kept [T] bool, onehot [T, E] int32)."""
    expert_idx = jnp.argmax(gates, axis=-1)                      # [T]
    gate_val = jnp.take_along_axis(
        gates, expert_idx[:, None], axis=-1)[:, 0]               # [T]
    onehot = jax.nn.one_hot(expert_idx, n_experts, dtype=jnp.int32)
    
    # position of each token within its expert's queue
    slot_idx = (jnp.cumsum(onehot, axis=0) - 1)                  # [T, E]
    slot_idx = jnp.take_along_axis(
        slot_idx, expert_idx[:, None], axis=-1)[:, 0]            # [T]
    kept = slot_idx < capacity
    # scatter tokens into [E, C, D]; dropped tokens target (0, C) → OOB
    e_t = jnp.where(kept, expert_idx, 0)
    s_t = jnp.where(kept, slot_idx, capacity)
    # dropped tokens target slot index `capacity` → out of bounds →
    # mode="drop" discards the whole update; no value masking needed
    dispatched = jnp.zeros(
        (n_experts, capacity, x.shape[-1]), x.dtype
    ).at[e_t, s_t].set(x, mode="drop")
    return dispatched, gate_val, e_t, s_t, kept, onehot


def moe_ffn_local(x, params, axis_name, axis_size, capacity_factor=1.25,
                  activation=jax.nn.gelu):
    """Per-shard Switch MoE FFN.  x: [T, D] local tokens; params from
    :func:`init_moe_params` with weights expert-SHARDED on dim 0 (each
    device holds E/n experts).  Returns (y [T, D], aux_loss scalar)."""
    gate_w, w1, b1, w2, b2 = params
    n = axis_size
    t, d = x.shape
    el = w1.shape[0]           # local experts
    e = el * n                 # global experts
    x32 = x.astype(jnp.float32)
    logits = x32 @ gate_w.astype(jnp.float32)                    # [T, E]
    gates = jax.nn.softmax(logits, axis=-1)

    cap = max(1, int(capacity_factor * t / e))
    dispatched, gate_val, e_t, s_t, kept, onehot = _dispatch_tensors(
        x, gates, e, cap)

    # Switch aux loss: E * mean_e(fraction_e * mean_prob_e), averaged
    # over the axis so every device computes the same value (reuses the
    # dispatch one-hot rather than rebuilding a [T, E] buffer)
    frac = jnp.mean(onehot.astype(jnp.float32), 0)
    prob = jnp.mean(gates, axis=0)
    aux = e * jnp.sum(frac * prob)
    aux = jax.lax.pmean(aux, axis_name)

    # dispatch all_to_all: [E=n·el, C, D] → each device keeps its own
    # el experts' queues from every source device: [el, n·C, D]
    dd = dispatched.reshape(n, el, cap, d)
    dd = jax.lax.all_to_all(dd, axis_name, split_axis=0, concat_axis=2,
                            tiled=True)
    # tiled: dim0 n→1, dim2 cap→n·cap
    dd = dd.reshape(el, n * cap, d)

    # expert FFN over local experts (batched on the expert dim — one
    # MXU einsum per layer, all experts at once)
    h = activation(
        jnp.einsum("ecd,edf->ecf", dd.astype(jnp.float32),
                   w1.astype(jnp.float32)) + b1[:, None, :])
    y = jnp.einsum("ecf,efd->ecd", h, w2.astype(jnp.float32)) \
        + b2[:, None, :]

    # combine all_to_all: route results back to the source devices
    y = y.reshape(el, n, cap, d)
    y = jax.lax.all_to_all(y, axis_name, split_axis=1, concat_axis=0,
                           tiled=True)
    # [n·el, 1, C, D] source-major on dim0 = global expert order
    y = y.reshape(e, cap, d)

    # gather each token's result from its (expert, slot); dropped tokens
    # contribute zero (pure residual pass-through)
    out = y[e_t, s_t]                                            # [T, D]
    out = jnp.where(kept[:, None], out * gate_val[:, None], 0.0)
    return out.astype(x.dtype), aux


def moe_ffn(x, params, mesh, axis_name, capacity_factor=1.25,
            activation=jax.nn.gelu):
    """Global entry: x [B, T, D] batch-sharded over ``axis_name``,
    expert weights sharded on their expert dim.  Returns (y, aux)."""
    from ..jax_compat import shard_map

    n = mesh.shape[axis_name]
    b, t, d = x.shape
    if b % n:
        raise ValueError("batch %d not divisible by axis %r size %d"
                         % (b, axis_name, n))
    gate_w, w1, b1, w2, b2 = params
    if w1.shape[0] % n:
        raise ValueError("n_experts %d not divisible by axis size %d"
                         % (w1.shape[0], n))

    pspec = (P(), P(axis_name), P(axis_name), P(axis_name), P(axis_name))

    def local(xl, prms):
        xf = xl.reshape(-1, d)
        y, aux = moe_ffn_local(xf, prms, axis_name, n,
                               capacity_factor, activation)
        return y.reshape(xl.shape), aux

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(axis_name), pspec),
        out_specs=(P(axis_name), P()),
        check_vma=False,
    )(x, params)


# ---------------------------------------------------------------------------
# The dropless share of a top-k expert layer (module docstring)
# ---------------------------------------------------------------------------

SCORE_FUNCS = ("sigmoid", "softmax")


def share_level(scores, share, rounds=24):
    """scores: [T, E].  For each column the level that ``share`` of its T
    values lie above, to ``2 ** -rounds`` of the column's range: halved
    in, ``rounds`` passes of compare and count (a sort of T x E values
    costs more, and nothing here needs their order)."""
    want = share * scores.shape[0]

    def halve(_, bounds):
        low, high = bounds
        mid = 0.5 * (low + high)
        over = jnp.sum(scores > mid, axis=0) > want
        return jnp.where(over, mid, low), jnp.where(over, high, mid)

    bounds = jnp.min(scores, axis=0), jnp.max(scores, axis=0)
    return jax.lax.fori_loop(0, rounds, halve, bounds)[1]


def sigmoid_topk_route(x, w_router, bias, top_k, scale=1.0, norm=True,
                       center=False, score_func="sigmoid"):
    """x: [T, D]; w_router: [D, E]; bias: [E] (the choice's correction
    bias, no gradient).  Scores ``s = sigmoid(x W)`` (``score_func``
    ``"softmax"``: the softmax over all E) in float32 at the
    highest matmul precision (a near-tie decides which expert runs); the
    ``top_k`` largest of ``s + bias`` are chosen (under ``"softmax"`` of
    ``log s + bias``: the same experts where the bias is zero, and a bias
    that can move a choice among scores that lie decades apart), the
    gates are the chosen ``s`` (over their sum where ``norm``) times
    ``scale``.

    ``center``: the bias is not the one given but minus each expert's
    mean score over these T tokens, so that an expert is chosen by how
    much more it scores a token than it scores the tokens on average.
    It is what balances the load of tokens that differ little among
    themselves, as a model's do before it is trained: there a few
    experts' mean scores lie above all the others' whatever the token,
    and a bias kept from one batch does not fit the next.  Under
    ``"softmax"`` it is minus the log-score that ``top_k / E`` of the
    tokens give the expert more than (:func:`share_level`): log-scores
    spread and lean differently from expert to expert, and counted from
    their means the experts' loads still lay 0.6 to 1.5 times the even
    share, by the weights.  (Under data parallelism either would be
    taken over all the step's tokens.)

    Returns ``(idx [T, top_k] int32, gates [T, top_k] float32, bias [E]
    as used)``."""
    if score_func not in SCORE_FUNCS:
        raise ValueError("score_func %r is none of %s"
                         % (score_func, SCORE_FUNCS))
    z = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
    if score_func == "softmax":
        s, chosen_by = jax.nn.softmax(z, axis=-1), jax.nn.log_softmax(z, -1)
    else:
        s = chosen_by = jax.nn.sigmoid(z)
    chosen_by = jax.lax.stop_gradient(chosen_by)
    if not center:
        bias = bias.astype(jnp.float32)
    elif score_func == "softmax":
        bias = -share_level(chosen_by, top_k / chosen_by.shape[1])
    else:
        bias = -jnp.mean(chosen_by, axis=0)
    bias = jax.lax.stop_gradient(bias)
    _, idx = jax.lax.top_k(chosen_by + bias, top_k)
    gates = jnp.take_along_axis(s, idx, axis=-1)
    if norm:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), gates * scale, bias


def held_rows(idx, first, held):
    """idx: [T, K] experts chosen over all E.  Returns ``key`` ([T*K]: a
    choice's held expert counted from ``first``, or ``held`` where it
    names an expert that is not here) and ``rows`` ([held] int32: the
    rows each held expert is given)."""
    local = idx.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    rows = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                   dtype=jnp.int32)
    return key, rows


def plan_held_rows(idx, first, held):
    """Where each choice goes: the choices of the held experts, sorted by
    expert (stable, so by token inside an expert), come first.  Returns
    ``order`` ([T*K]: the flat choice ``t * K + k`` that row r of the
    buffer takes) and ``rows``."""
    key, rows = held_rows(idx, first, held)
    return jnp.argsort(key, stable=True).astype(jnp.int32), rows


@jax.custom_vjp
def grouped_dot(lhs, rhs, rows):
    """``out[r] = lhs[r] @ rhs[g(r)]``: lhs [R, K] with its first
    ``sum(rows)`` rows sorted by group, rhs [G, K, N], rows [G] the rows
    of each group.  ``jax.lax.ragged_dot``, whose work follows
    ``sum(rows)`` and not R or G (on the TPU one grouped Mosaic kernel,
    ``ragged-dot-none``), with what it leaves unwritten made zero at the
    source, forward and backward: the rows past ``sum(rows)`` of the
    result and of lhs's cotangent, and the cotangent of a group with no
    row.  Left as they come they are whatever the buffer held, and a
    product rule downstream multiplies by them."""
    covered = (jnp.arange(lhs.shape[0]) < jnp.sum(rows))[:, None]
    return jnp.where(covered, jax.lax.ragged_dot(lhs, rhs, rows), 0)


def _grouped_dot_fwd(lhs, rhs, rows):
    return grouped_dot(lhs, rhs, rows), (lhs, rhs, rows)


def _grouped_dot_bwd(res, ct):
    lhs, rhs, rows = res
    _, pullback = jax.vjp(
        lambda lhs, rhs: jax.lax.ragged_dot(lhs, rhs, rows), lhs, rhs)
    d_lhs, d_rhs = pullback(ct)
    covered = (jnp.arange(lhs.shape[0]) < jnp.sum(rows))[:, None]
    return (jnp.where(covered, d_lhs, 0),
            jnp.where((rows > 0)[:, None, None], d_rhs, 0), None)


grouped_dot.defvjp(_grouped_dot_fwd, _grouped_dot_bwd)


# Choices a trip of ``held_experts_ffn``'s loop takes.  A trip's cost is
# mostly its scatter-adds, the same for every row of the block whether
# routed here or past the count, and what it does once a block (the
# weights' cotangents added, their layouts copied): on the v5e larger
# blocks won (PERF.md 6, PR 30), and the layer's temporaries grow with it.
BLOCK_ROWS = 8192


def block_rows(choices, held, total=None):
    """Rows a block of :func:`held_experts_ffn`'s loop holds, for a layer
    that holds ``held`` of ``total`` experts and routes ``choices``
    (tokens x top_k) choices a step: one and a half times the rows an
    even spread gives it, in whole 1,024s, so that one trip holds a
    step's rows unless the layer is given half as much again (a trip
    more or less is 6 to 16 ms on the v5e, and with the even share at a
    block's end the number of trips turned on the seed: PERF.md 6,
    PRs 35 and 36).  ``BLOCK_ROWS`` where ``total`` is not known."""
    if not total:
        return BLOCK_ROWS
    return max(1024, -(-3 * choices * held // (2 * total * 1024)) * 1024)


def blocks_run(rows, block=BLOCK_ROWS):
    """Trips the loop of :func:`held_experts_ffn` makes for ``rows`` (the
    rows given to each held expert): the blocks of ``block`` buffer rows
    that hold a routed row.  Read on the device."""
    return (jnp.sum(rows) + (block - 1)) // block


def _block_ffn(scope, xs, g, w_gate, w_up, w_down, sizes):
    """One block of the buffer: xs [B, D] the rows' tokens, g [B] their
    gates, sizes [held] the rows of each held expert inside the block.
    Returns [B, D] float32, what each row adds to its token."""
    with scope("products"):
        h = grouped_dot(xs, w_gate, sizes)
        u = grouped_dot(xs, w_up, sizes)
        a = (jax.nn.silu(h.astype(jnp.float32))
             * u.astype(jnp.float32)).astype(xs.dtype)
        y = grouped_dot(a, w_down, sizes)
    with scope("combine"):
        return y.astype(jnp.float32) * g[:, None]


def _block_inputs(scope, size, i, x, gates, order, rows):
    """Block i of the buffer (``size`` rows): the flat choices it takes,
    their tokens, those tokens' rows of x, the choices' gates, and the
    held experts' cumulative row counts cut to the block's range."""
    with scope("dispatch"):
        lo = i * size
        choice = jax.lax.dynamic_slice(order, (lo,), (size,))
        token = choice // gates.shape[1]    # in [0, T): no bounds to check
        xs = x.at[token].get(mode="promise_in_bounds")
        g = gates.reshape(-1).at[choice].get(mode="promise_in_bounds")
        ends = jnp.cumsum(rows)
        sizes = (jnp.clip(ends - lo, 0, size)
                 - jnp.clip(ends - rows - lo, 0, size))
    return choice, token, xs, g, sizes


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _walk_blocks(scope, size, x, gates, w_gate, w_up, w_down, order, rows):
    """``held_experts_ffn`` behind its plan: order [a multiple of the
    block's ``size``] and rows [held] from :func:`plan_held_rows`."""
    def block(i, out):
        _, token, xs, g, sizes = _block_inputs(scope, size, i, x, gates,
                                               order, rows)
        y = _block_ffn(scope, xs, g, w_gate, w_up, w_down, sizes)
        with scope("combine"):
            return out.at[token].add(y, mode="promise_in_bounds")

    out = jax.lax.fori_loop(0, blocks_run(rows, size), block,
                            jnp.zeros(x.shape, jnp.float32))
    return out.astype(x.dtype)


def _walk_blocks_fwd(scope, size, *args):
    return _walk_blocks(scope, size, *args), args


def _walk_blocks_bwd(scope, size, args, ct):
    """The same loop again: a block is computed anew and differentiated
    (``jax.vjp`` of the one block function), nothing is kept between
    blocks, and the cotangents of x (in x's dtype, as the transpose of a
    gather from x adds them), the gates and the weights are carried."""
    x, gates, w_gate, w_up, w_down, order, rows = args

    def block(i, carry):
        d_x, d_gates, d_weights = carry
        choice, token, xs, g, sizes = _block_inputs(scope, size, i, x,
                                                    gates, order, rows)
        _, pullback = jax.vjp(
            lambda *a: _block_ffn(scope, *a, sizes), xs, g, w_gate, w_up,
            w_down)
        d_xs, d_g, *d_w = pullback(
            ct.at[token].get(mode="promise_in_bounds").astype(jnp.float32))
        return (d_x.at[token].add(d_xs, mode="promise_in_bounds"),
                d_gates.at[choice].add(d_g, mode="promise_in_bounds"),
                [a + b for a, b in zip(d_weights, d_w)])

    d_x, d_gates, d_weights = jax.lax.fori_loop(
        0, blocks_run(rows, size), block,
        (jnp.zeros_like(x), jnp.zeros(gates.size, gates.dtype),
         [jnp.zeros_like(w) for w in (w_gate, w_up, w_down)]))
    return d_x, d_gates.reshape(gates.shape), *d_weights, None, None


_walk_blocks.defvjp(_walk_blocks_fwd, _walk_blocks_bwd)


def held_experts_ffn(x, idx, gates, w_gate, w_up, w_down, first=0,
                     scope=jax.named_scope, total=None):
    """The held experts' part of ``sum_k gates[t,k] * E_idx[t,k](x[t])``
    with ``E(x) = W_down (silu(W_gate x) * W_up x)``.

    x: [T, D]; idx, gates: [T, K] from :func:`sigmoid_topk_route`;
    w_gate, w_up: [held, D, F]; w_down: [held, F, D]: the experts
    ``first .. first + held - 1`` of ``total`` (sizes the loop's block:
    :func:`block_rows`).  Returns ``(y [T, D], rows [held]
    int32)``; ``rows`` are the rows each held expert was given, summing
    to the work done.

    One path whatever the load: every choice of every token has its
    place in an order of ``T * K`` choices, those of the held experts
    first and sorted by expert (:func:`plan_held_rows`), and one loop
    walks that order in blocks of :func:`block_rows` as far as the routed
    rows reach (:func:`blocks_run`, read from ``rows`` on the device).  A
    block gathers its choices' tokens from x, runs the three products
    grouped by the held experts' row counts cut to the block's range,
    and scatter-adds the results to their tokens, times their gates, in
    float32, into the ``[T, D]`` result the loop carries: no buffer of
    ``T * K`` rows exists, and with every choice routed here the loop
    runs every block.  The last block's rows past the routed ones take
    tokens that chose an expert elsewhere; their products are zero, so
    they add nothing and take no gradient to a weight.  A loop with a
    traced trip count has no reverse mode, so the backward pass is the
    same loop again (``_walk_blocks_bwd``).  ``scope(name)`` names the
    three parts of a block for a device trace: ``dispatch``, ``products``
    and ``combine``."""
    held = w_gate.shape[0]
    size = block_rows(idx.size, held, total)
    with scope("dispatch"):
        order, rows = plan_held_rows(idx, first, held)
        # padded places lie past every routed row: choice 0, which adds 0
        order = jnp.pad(order, (0, -order.size % size))
    out = _walk_blocks(scope, size, x, gates, w_gate.astype(x.dtype),
                       w_up.astype(x.dtype), w_down.astype(x.dtype),
                       order, rows)
    return out, rows
