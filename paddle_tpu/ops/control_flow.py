"""Control-flow op lowerings: sub-block ops → lax control flow.

Reference: ``paddle/fluid/operators/controlflow/while_op.cc`` (interprets the
sub-block per iteration against step scopes) and
``conditional_block_op.cc``; ``recurrent_op.cc`` for StaticRNN.

TPU-native: the sub-block is lowered ONCE into a pure jax function and run
under ``lax.while_loop`` / ``lax.cond`` / ``lax.scan`` — no per-iteration
host dispatch, fully compiled, fixed shapes.  Loop state (the carry) is the
set of sub-block-written vars that are loop-carried (read before written, or
live-out); everything else is a per-iteration temporary.

LoDTensorArray (beam-search/RNN collectors) is a fixed-capacity device
buffer + length scalar — `array_write` is a dynamic_update_slice, the
TPU-static analogue of the reference's growable vector<LoDTensor>.
"""

import contextlib

import numpy as np

from .registry import register_op, EMPTY_VAR_NAME, RegionKept

SUB_BLOCK_OPS = ("while", "conditional_block", "recurrent",
                 "recurrent_grad", "conditional_block_grad", "while_grad",
                 "recompute_block", "recompute_block_grad")

ARRAY_CAPACITY_ATTR = "tensor_array_capacity"
DEFAULT_ARRAY_CAPACITY = 128


def _gather_inputs(op, env):
    ins = {}
    for slot, names in op.inputs.items():
        ins[slot] = [
            None if (not n or n == EMPTY_VAR_NAME) else env.get(n)
            for n in names
        ]
    return ins


def _carry_analysis(sub_block, outer_env):
    """Split sub-block-written vars into loop-carried vs temporaries.

    carried := written vars that are (a) read within the body before their
    first write (previous-iteration value used), or (b) present in the
    outer env (live-in/live-out state).
    """
    written_order = []
    written = set()
    read_before_write = set()
    for op in sub_block.ops:
        for n in op.input_arg_names:
            if n and n != EMPTY_VAR_NAME and n not in written:
                read_before_write.add(n)
        for n in op.output_arg_names:
            if n and n != EMPTY_VAR_NAME and n not in written:
                written.add(n)
                written_order.append(n)
    carried = [
        n for n in written_order
        if n in read_before_write or n in outer_env
    ]
    return carried, written_order


def sub_block_external_reads(sub_block, exclude=()):
    """Names read by the sub-block before any write (closure captures)."""
    written = set(exclude)
    reads = []
    for op in sub_block.ops:
        for n in op.input_arg_names:
            if n and n != EMPTY_VAR_NAME and n not in written and n not in reads:
                reads.append(n)
        written.update(op.output_arg_names)
    return reads


def _nonzero_cotangent(g, primal):
    import jax
    import jax.numpy as jnp

    if g is None:
        return jnp.zeros_like(primal)
    return g


def _clean_grad(g, primal):
    import jax
    import jax.numpy as jnp

    if g is None or g.dtype == jax.dtypes.float0:
        return jnp.zeros(jnp.shape(primal), jnp.float32)
    return g


@contextlib.contextmanager
def _lowering_region(ctx, region):
    """``ctx.region`` is ``region`` while a sub-block is lowered."""
    was, ctx.region = ctx.region, region
    try:
        yield
    finally:
        ctx.region = was


def run_sub_block_op(op, block, env, ctx, run_block_fn, region=None):
    """Lower one op of ``SUB_BLOCK_OPS`` into ``env``.  ``region``
    (``recompute_block`` and its grad): the ``registry.RegionKept`` the
    two share where one call lowers both, else None."""
    if op.type == "recompute_block":
        # no twin in this call: the enclosing region's store, if any
        region = region or ctx.region
    elif op.type != "recompute_block_grad":
        region = None  # a loop's or a branch's body is a trace of its own
    with _lowering_region(ctx, region):
        _run_sub_block_op(op, block, env, ctx, run_block_fn)


def _run_sub_block_op(op, block, env, ctx, run_block_fn):
    import jax
    import jax.numpy as jnp

    program = block.program
    sub_block = program.block(op.attrs["sub_block"])

    if op.type == "recurrent_grad":
        _run_recurrent_grad(op, sub_block, env, ctx, run_block_fn)
        return
    if op.type == "conditional_block_grad":
        _run_conditional_grad(op, sub_block, env, ctx, run_block_fn)
        return
    if op.type == "while_grad":
        _run_while_grad(op, sub_block, env, ctx, run_block_fn)
        return

    if op.type == "while":
        cond_name = op.inputs["Condition"][0]
        carried, written = _carry_analysis(sub_block, env)
        if cond_name not in carried:
            carried = carried + [cond_name]
        missing = [n for n in carried if n not in env]
        if missing:
            raise RuntimeError(
                "while op: loop-carried vars %s have no initial value "
                "before the loop" % missing
            )
        carry0 = {n: env[n] for n in carried}
        outer = dict(env)

        def body(carry):
            e = dict(outer)
            e.update(carry)
            run_block_fn(sub_block, e, ctx)
            return {n: e[n] for n in carried}

        def cond(carry):
            return jnp.reshape(carry[cond_name], ()).astype(bool)

        if ctx.probing and not op.attrs.get("max_trip_count"):
            # two-pass unbounded-while-grad support: concrete host loop
            # that counts trips (max over re-entries for nested loops).
            # Bounded whiles keep the lax path and are NOT recorded —
            # their counts would join the jit-cache key and trigger
            # spurious recompiles when the data-dependent count varies
            carry = carry0
            trips = 0
            while bool(cond(carry)):
                carry = body(carry)
                trips += 1
            idx = int(op.attrs["sub_block"])
            ctx.trip_counts[idx] = max(ctx.trip_counts.get(idx, 0), trips)
            env.update(carry)
            return

        final = jax.lax.while_loop(cond, body, carry0)
        env.update(final)
        return

    if op.type == "recompute_block":
        # forward of the remat region: a PLAIN run of the sub-block (this
        # call is never differentiated by jax — grads are explicit ops),
        # emitting every written name into env.  Unconsumed entries are
        # ordinary unbarriered values, so XLA DCEs them; the remat effect
        # lives entirely in the GRAD op's barriered re-forward.  Only
        # what a kernel site puts into ``ctx.region`` outlives the region.
        out_names = list(op.outputs.get("Out", []))
        cap = [n for n in op.inputs.get("Captured", [])
               or sub_block_external_reads(sub_block) if n in env]
        outer = dict(env)

        def region(cap_vals):
            e = dict(outer)
            e.update(dict(zip(cap, cap_vals)))
            run_block_fn(sub_block, e, ctx)
            return tuple(e[n] for n in out_names)

        # plain run: this call is never differentiated by jax (grads are
        # explicit ops), so the region's unexported intermediates die
        # here; the grad op recomputes them behind a barrier
        outs = region(tuple(env[n] for n in cap))
        env.update(dict(zip(out_names, outs)))
        return

    if op.type == "recompute_block_grad":
        _run_recompute_grad(op, sub_block, env, ctx, run_block_fn)
        return

    if op.type == "conditional_block":
        cond_val = env[op.inputs["Cond"][0]]
        carried, written = _carry_analysis(sub_block, env)
        outer = dict(env)
        branch_outs = [n for n in written if n in env] or carried
        branch_outs = list(dict.fromkeys(branch_outs))

        def true_fn(carry):
            e = dict(outer)
            e.update(carry)
            run_block_fn(sub_block, e, ctx)
            return {n: e[n] for n in branch_outs}

        def false_fn(carry):
            return dict(carry)

        carry0 = {n: env[n] for n in branch_outs}
        pred = jnp.reshape(cond_val, ()).astype(bool)
        result = jax.lax.cond(pred, true_fn, false_fn, carry0)
        env.update(result)
        return

    if op.type == "recurrent":
        _run_recurrent(op, sub_block, env, ctx, run_block_fn)
        return

    raise NotImplementedError(op.type)


def _block_carry_sets(sub_block):
    """Env-independent carry analysis: (written-in-order, read-before-write).

    The grad pass must reproduce the forward loop's math without depending on
    the runtime env contents, so it uses only block structure + the
    pre-loop snapshots recorded by the While layer."""
    written_order = []
    written = set()
    read_before_write = set()
    for op in sub_block.ops:
        for n in op.input_arg_names:
            if n and n != EMPTY_VAR_NAME and n not in written:
                read_before_write.add(n)
        for n in op.output_arg_names:
            if n and n != EMPTY_VAR_NAME and n not in written:
                written.add(n)
                written_order.append(n)
    return written_order, read_before_write


def _run_while_grad(op, sub_block, env, ctx, run_block_fn):
    """Reverse-mode through a bounded `while`: re-run the loop as a
    lax.scan over ``max_trip_count`` steps with an active mask (the standard
    XLA answer to differentiating data-dependent loops — scan is
    transposable, while_loop is not), then jax.vjp w.r.t. the pre-loop
    carry values and the captured outer vars.

    Reference: ``paddle/fluid/operators/controlflow/while_op.cc``
    (WhileGradOp interprets the block in reverse per step scope); here the
    whole masked loop is one differentiable scan."""
    import jax
    import jax.numpy as jnp

    out_names = op.inputs.get("Out", [])
    gout_names = op.inputs.get("Out@GRAD", [])
    cap_names = op.inputs.get("Captured", [])
    cond_name = op.inputs["Condition"][0]
    snap_vars = op.attrs.get("snapshot_vars", [])
    snap_pres = op.attrs.get("snapshot_pres", [])
    pre_of = dict(zip(snap_vars, snap_pres))
    max_trip = int(op.attrs.get("max_trip_count") or 0)
    if not max_trip:
        # unbounded while: the executor's probe pass ran the loop on
        # concrete values and recorded the trip count; use it as the
        # static scan length (masking keeps extra steps inert; a
        # legitimately zero-trip loop scans 0 steps → zero grads)
        idx = int(op.attrs["sub_block"])
        if idx not in (ctx.trip_counts or {}):
            raise NotImplementedError(
                "gradients through an unbounded `while` need the "
                "executor's trip-count probe (Executor.run does this "
                "automatically); in this context pass "
                "While(cond, max_trip_count=N) or use StaticRNN"
            )
        max_trip = int(ctx.trip_counts[idx])

    written_order, read_before_write = _block_carry_sets(sub_block)
    carried = [
        n for n in written_order
        if n in read_before_write or n in pre_of
    ]
    if cond_name not in carried:
        carried.append(cond_name)

    init_vals = []
    for n in carried:
        pre = pre_of.get(n)
        if pre is not None and pre in env:
            init_vals.append(env[pre])
        elif n in env:
            # not written before the loop in the parent block: current env
            # value IS the pre-loop value (never snapshotted)
            init_vals.append(env[n])
        else:
            raise RuntimeError(
                "while_grad: no pre-loop value for carried var %r" % n
            )
    cap_vals = tuple(env[n] for n in cap_names)
    active0 = jnp.reshape(init_vals[carried.index(cond_name)], ()).astype(bool)
    outer = dict(env)

    def f(init_vals, cap_vals):
        caps = dict(zip(cap_names, cap_vals))

        def step(state, _):
            carry, active = state
            e = dict(outer)
            e.update(caps)
            e.update(dict(zip(carried, carry)))
            run_block_fn(sub_block, e, ctx)
            new_carry = tuple(
                jnp.where(active, e[n], old)
                for n, old in zip(carried, carry)
            )
            new_cond = jnp.reshape(
                new_carry[carried.index(cond_name)], ()
            ).astype(bool)
            return (new_carry, jnp.logical_and(active, new_cond)), None

        (final, _), _ = jax.lax.scan(
            step, (tuple(init_vals), active0), None, length=max_trip
        )
        # only float-dtype finals need cotangents
        return tuple(
            final[i] for i in range(len(carried))
            if jnp.issubdtype(final[i].dtype, jnp.inexact)
        )

    float_idx = [
        i for i, v in enumerate(init_vals)
        if jnp.issubdtype(jnp.asarray(v).dtype, jnp.inexact)
    ]
    primal, vjp_fn = jax.vjp(f, tuple(init_vals), cap_vals)
    grad_of_out = dict(zip(out_names, gout_names))
    cots = []
    for k, i in enumerate(float_idx):
        n = carried[i]
        gname = grad_of_out.get(n)
        g = env.get(gname) if gname and gname != EMPTY_VAR_NAME else None
        if g is not None:
            cots.append(g.astype(primal[k].dtype))
        else:
            cots.append(jnp.zeros_like(primal[k]))
    ginit, gcap = vjp_fn(tuple(cots))
    gi_of = dict(zip(carried, ginit))
    names = op.outputs.get("StateIn@GRAD", [])
    for n, gn in zip(out_names, names):
        if gn and gn != EMPTY_VAR_NAME and n in gi_of:
            pre = pre_of.get(n)
            p = env[pre] if pre is not None and pre in env else env[n]
            env[gn] = _clean_grad(gi_of[n], p)
    names = op.outputs.get("Captured@GRAD", [])
    for n, g, p in zip(names, gcap, cap_vals):
        if n and n != EMPTY_VAR_NAME:
            env[n] = _clean_grad(g, p)


def _seq_lengths(env, op):
    """[B] int32 lengths from the optional sequence_length input (DynamicRNN
    masked-scan path); None for the StaticRNN full-length path."""
    import jax.numpy as jnp

    names = op.inputs.get("sequence_length", [])
    if not names or not names[0] or names[0] == EMPTY_VAR_NAME:
        return None
    lengths = jnp.reshape(env[names[0]], (-1,)).astype(jnp.int32)  # [B]
    return lengths


def _make_step(outer, sub_block, ctx, run_block_fn, op, masked):
    """Shared scan-step closure for recurrent fwd + grad lowerings."""
    import jax.numpy as jnp

    step_inputs = op.attrs["step_input_names"]
    state_names = op.attrs["state_names"]
    state_out_names = op.attrs["state_out_names"]
    step_output_names = op.attrs["step_output_names"]

    def step(caps, carry, xt, mt):
        e = dict(outer)
        e.update(caps)
        for name, val in zip(state_names, carry):
            e[name] = val
        for name, val in zip(step_inputs, xt):
            e[name] = val
        run_block_fn(sub_block, e, ctx)
        new_carry = tuple(e[n] for n in state_out_names)
        ys = tuple(e[n] for n in step_output_names)
        if masked:
            def bmask(v):
                return jnp.reshape(mt, (-1,) + (1,) * (v.ndim - 1))

            # inactive (t >= length) rows keep their previous state; padded
            # step outputs are zeroed (the padded-batch representation of
            # "no output at this step")
            new_carry = tuple(
                jnp.where(bmask(nv), nv, ov)
                for nv, ov in zip(new_carry, carry)
            )
            ys = tuple(jnp.where(bmask(y), y, jnp.zeros_like(y)) for y in ys)
        return new_carry, ys

    return step


def _run_recurrent(op, sub_block, env, ctx, run_block_fn):
    """StaticRNN (reference recurrent_op.cc): scan the sub-block over the
    time axis of the sequence inputs.  With attr time_major=False +
    a sequence_length input this is the DynamicRNN lowering: batch-major
    padded [B,T,...] sequences, state updates masked by t < length
    (the TPU-static replacement for the reference's lod_rank_table
    shrinking-batch reordering, control_flow.py:1700)."""
    import jax
    import jax.numpy as jnp

    seq_inputs = op.inputs.get("inputs", [])
    init_states = op.inputs.get("initial_states", [])  # [B, ...] outer vars
    outputs = op.outputs.get("outputs", [])          # stacked outs
    time_major = op.attrs.get("time_major", True)

    outer = dict(env)
    xs = [env[n] for n in seq_inputs]
    if not time_major:
        xs = [jnp.moveaxis(x, 1, 0) for x in xs]  # [B,T,...] -> [T,B,...]
    carry0 = tuple(env[n] for n in init_states)
    lengths = _seq_lengths(env, op)
    T = jnp.shape(xs[0])[0] if xs else int(op.attrs.get("max_len", 0))
    if lengths is not None:
        mask = jnp.arange(T)[:, None] < lengths[None, :]  # [T, B]
    else:
        mask = None

    step_fn = _make_step(outer, sub_block, ctx, run_block_fn, op,
                         masked=mask is not None)

    def step(carry, inp):
        xt, mt = inp
        return step_fn({}, carry, xt, mt)

    final_carry, stacked = jax.lax.scan(
        step, carry0, (tuple(xs), mask), length=None if xs else T
    )
    for name, val in zip(outputs, stacked):
        if not time_major:
            val = jnp.moveaxis(val, 0, 1)  # [T,B,...] -> [B,T,...]
        env[name] = val
    for name, val in zip(op.outputs.get("final_states", []), final_carry):
        env[name] = val


def _run_recurrent_grad(op, sub_block, env, ctx, run_block_fn):
    """Grad of the StaticRNN scan: jax.vjp over the SAME scan closure,
    differentiating w.r.t. sequence inputs, initial states, AND captured
    outer vars (the parameters used inside the step block) — the role of
    the reference's recurrent_grad op (recurrent_op.cc RecurrentGradOp)."""
    import jax
    import jax.numpy as jnp

    seq_names = op.inputs.get("inputs", [])
    init_names = op.inputs.get("initial_states", [])
    cap_names = op.inputs.get("Captured", [])
    out_names = op.inputs.get("outputs", [])
    gout_names = op.inputs.get("outputs@GRAD", [])
    time_major = op.attrs.get("time_major", True)
    outer = dict(env)
    lengths = _seq_lengths(env, op)

    def f(seq_vals, init_vals, cap_vals):
        caps = dict(zip(cap_names, cap_vals))
        xs = list(seq_vals)
        if not time_major:
            xs = [jnp.moveaxis(x, 1, 0) for x in xs]
        T = jnp.shape(xs[0])[0]
        mask = (jnp.arange(T)[:, None] < lengths[None, :]
                if lengths is not None else None)
        step_fn = _make_step(outer, sub_block, ctx, run_block_fn, op,
                             masked=mask is not None)

        def step(carry, inp):
            xt, mt = inp
            return step_fn(caps, carry, xt, mt)

        _, ys = jax.lax.scan(step, tuple(init_vals), (tuple(xs), mask))
        if not time_major:
            ys = tuple(jnp.moveaxis(y, 0, 1) for y in ys)
        return ys

    seq_vals = tuple(env[n] for n in seq_names)
    init_vals = tuple(env[n] for n in init_names)
    cap_vals = tuple(env[n] for n in cap_names)
    primal, vjp_fn = jax.vjp(f, seq_vals, init_vals, cap_vals)
    cots = []
    for i, p in enumerate(primal):
        gname = gout_names[i] if i < len(gout_names) else EMPTY_VAR_NAME
        g = env.get(gname) if gname and gname != EMPTY_VAR_NAME else None
        cots.append(_nonzero_cotangent(g, p))
    gseq, ginit, gcap = vjp_fn(tuple(cots))
    for slot, gvals, primals in (
        ("inputs@GRAD", gseq, seq_vals),
        ("initial_states@GRAD", ginit, init_vals),
        ("Captured@GRAD", gcap, cap_vals),
    ):
        names = op.outputs.get(slot, [])
        for n, g, p in zip(names, gvals, primals):
            if n and n != EMPTY_VAR_NAME:
                env[n] = _clean_grad(g, p)


def _run_recompute_grad(op, sub_block, env, ctx, run_block_fn):
    """Grad of recompute_block: jax.vjp over the region re-run from
    BARRIERED inputs.  The optimization_barrier on the captured values
    (jax.checkpoint's own mechanism) makes the recompute a distinct
    subgraph XLA cannot CSE with the forward op's chain — without it the
    'recompute' would alias the original activations and their liveness
    would span fwd→bwd again, defeating the remat.  The barrier ties the
    captured values to the region's incoming gradients, so a re-run
    cannot start before the backward pass reaches its region; what the
    region's kernel sites kept of its forward run (``ctx.region``, where
    the forward op was lowered by the same call) goes through the same
    barrier, and the re-run's lowering of such a site takes it in place
    of a second forward kernel."""
    import jax

    cap_names = op.inputs.get("Captured", [])
    out_names = op.inputs.get("Out", [])
    gout_names = op.inputs.get("Out@GRAD", [])
    outer = dict(env)

    def f(cap_vals):
        e = dict(outer)
        e.update(dict(zip(cap_names, cap_vals)))
        run_block_fn(sub_block, e, ctx)
        return tuple(e[n] for n in out_names)

    cap_vals = tuple(env[n] for n in cap_names)
    gouts = {n: env[n] for n in gout_names
             if n and n != EMPTY_VAR_NAME and env.get(n) is not None}
    kept = {} if ctx.region is None else ctx.region.values
    if cap_vals:
        cap_vals, gouts, kept = jax.lax.optimization_barrier(
            (cap_vals, gouts, kept))
    with _lowering_region(ctx, RegionKept(kept)):
        primal, vjp_fn = jax.vjp(f, cap_vals)
    cots = []
    for i, p in enumerate(primal):
        gname = gout_names[i] if i < len(gout_names) else EMPTY_VAR_NAME
        cots.append(_nonzero_cotangent(gouts.get(gname), p))
    (gcap,) = vjp_fn(tuple(cots))
    names = op.outputs.get("Captured@GRAD", [])
    for n, g, p in zip(names, gcap, cap_vals):
        if n and n != EMPTY_VAR_NAME:
            env[n] = _clean_grad(g, p)


def _run_conditional_grad(op, sub_block, env, ctx, run_block_fn):
    """Grad of conditional_block via vjp over lax.cond, w.r.t. captured
    outer vars.  Note: grads w.r.t. the PRE-values of vars overwritten by
    the block (the false-branch passthrough) are not propagated — those
    pre-values are no longer live in the SSA env; typical conditional
    blocks (lr bands, metric branches) have no grad flow through them."""
    import jax
    import jax.numpy as jnp

    cond_name = op.inputs["Cond"][0]
    cap_names = op.inputs.get("Captured", [])
    out_names = op.inputs.get("Out", [])
    gout_names = op.inputs.get("Out@GRAD", [])
    outer = dict(env)
    pred = jnp.reshape(env[cond_name], ()).astype(bool)

    def f(cap_vals):
        caps = dict(zip(cap_names, cap_vals))

        def true_fn(cap):
            e = dict(outer)
            e.update(dict(zip(cap_names, cap)))
            run_block_fn(sub_block, e, ctx)
            return tuple(e[n] for n in out_names)

        def false_fn(cap):
            return tuple(outer[n] for n in out_names)

        return jax.lax.cond(pred, true_fn, false_fn, cap_vals)

    cap_vals = tuple(env[n] for n in cap_names)
    primal, vjp_fn = jax.vjp(f, cap_vals)
    cots = []
    for i, p in enumerate(primal):
        gname = gout_names[i] if i < len(gout_names) else EMPTY_VAR_NAME
        g = env.get(gname) if gname and gname != EMPTY_VAR_NAME else None
        cots.append(_nonzero_cotangent(g, p))
    (gcap,) = vjp_fn(tuple(cots))
    names = op.outputs.get("Captured@GRAD", [])
    for n, g, p in zip(names, gcap, cap_vals):
        if n and n != EMPTY_VAR_NAME:
            env[n] = _clean_grad(g, p)


# ---------------------------------------------------------------------------
# LoDTensorArray ops (reference: lod_tensor_array ops + lod_array_length_op)
# ---------------------------------------------------------------------------

def _no_infer(op, block):
    pass


@register_op("write_to_array", inputs=["X", "I", "Array"], outputs=["Out"],
             no_grad=True, infer_shape=_no_infer)
def write_to_array(ctx, attrs, X, I, Array):
    import jax
    import jax.numpy as jnp

    idx = jnp.reshape(I, ()).astype(jnp.int32)
    cap = int(attrs.get(ARRAY_CAPACITY_ATTR, DEFAULT_ARRAY_CAPACITY))
    if Array is None:
        buf = jnp.zeros((cap,) + tuple(jnp.shape(X)), X.dtype)
        length = jnp.asarray(0, jnp.int32)
    else:
        buf, length = Array["buffer"], Array["length"]
    buf = jax.lax.dynamic_update_index_in_dim(buf, X, idx, 0)
    return {"Out": {"buffer": buf, "length": jnp.maximum(length, idx + 1)}}


@register_op("read_from_array", inputs=["X", "I"], outputs=["Out"],
             no_grad=True, infer_shape=_no_infer)
def read_from_array(ctx, attrs, X, I):
    import jax
    import jax.numpy as jnp

    idx = jnp.reshape(I, ()).astype(jnp.int32)
    return jax.lax.dynamic_index_in_dim(X["buffer"], idx, 0, keepdims=False)


@register_op("lod_array_length", inputs=["X"], outputs=["Out"], no_grad=True,
             infer_shape=_no_infer)
def lod_array_length(ctx, attrs, X):
    import jax.numpy as jnp

    return jnp.reshape(X["length"], (1,)).astype(jnp.int32)


@register_op("split_lod_tensor", inputs=["X", "Mask"],
             outputs=["OutTrue", "OutFalse"])
def split_lod_tensor(ctx, attrs, X, Mask):
    """Reference split_lod_tensor_op.cc partitions rows by mask into two
    ragged tensors.  Under XLA static shapes both 'halves' keep the full
    batch (masked-execution semantics): the row selection happens at
    merge_lod_tensor, so each branch computes on all rows and inactive
    rows are discarded by the final select — the TPU-standard way to run
    data-dependent per-row branches."""
    return {"OutTrue": X, "OutFalse": X}


@register_op("merge_lod_tensor", inputs=["InTrue", "InFalse", "Mask", "X"],
             outputs=["Out"])
def merge_lod_tensor(ctx, attrs, InTrue, InFalse, Mask, X):
    """Row-wise select by mask (merge_lod_tensor_op.cc re-interleaving,
    expressed as a where select over the full batch)."""
    import jax.numpy as jnp

    m = Mask
    if m.ndim < InTrue.ndim:
        m = m.reshape(m.shape + (1,) * (InTrue.ndim - m.ndim))
    elif m.ndim > InTrue.ndim:
        m = m.reshape(m.shape[: InTrue.ndim])
    return jnp.where(m.astype(bool), InTrue, InFalse)


@register_op("lod_rank_table", inputs=["X"], outputs=["Out"], no_grad=True,
             infer_shape=_no_infer)
def lod_rank_table(ctx, attrs, X):
    """Reference lod_rank_table_op.cc sorts sequences by length for the
    shrinking-batch DynamicRNN.  Padded batches need no reorder: the
    'rank table' is the lengths tensor itself (descending sort indices
    attached for parity consumers)."""
    import jax.numpy as jnp

    lengths = jnp.reshape(X, (-1,)) if X.ndim <= 1 else \
        jnp.full((X.shape[0],), X.shape[1], jnp.int32)
    order = jnp.argsort(-lengths.astype(jnp.int32))
    return {"Out": {"lengths": lengths, "order": order}}


@register_op("max_sequence_len2", inputs=["RankTable"], outputs=["Out"],
             no_grad=True, infer_shape=_no_infer)
def max_sequence_len2(ctx, attrs, RankTable):
    import jax.numpy as jnp

    return jnp.max(RankTable["lengths"]).reshape(1).astype(jnp.int64)


@register_op("lod_tensor_to_array", inputs=["X", "RankTable"],
             outputs=["Out"], infer_shape=_no_infer)
def lod_tensor_to_array(ctx, attrs, X, RankTable):
    """Reference lod_tensor_to_array_op.cc slices a ragged batch into
    per-timestep tensors.  Padded [B, T, ...] form: the 'array' is the
    time-major view in a fixed-capacity buffer."""
    import jax.numpy as jnp

    tm = jnp.moveaxis(X, 1, 0)  # [T, B, ...]
    return {"Out": {"buffer": tm,
                    "length": jnp.asarray(tm.shape[0], jnp.int32)}}


@register_op("array_to_lod_tensor", inputs=["X", "RankTable"],
             outputs=["Out"], infer_shape=_no_infer)
def array_to_lod_tensor(ctx, attrs, X, RankTable):
    """Inverse of lod_tensor_to_array: stack the time-major buffer back
    to batch-major (array_to_lod_tensor_op.cc)."""
    import jax.numpy as jnp

    return jnp.moveaxis(X["buffer"], 0, 1)


@register_op("shrink_rnn_memory", inputs=["X", "RankTable", "I"],
             outputs=["Out"], infer_shape=_no_infer)
def shrink_rnn_memory(ctx, attrs, X, RankTable, I):
    """Reference shrink_rnn_memory_op.cc drops finished sequences from
    the RNN state as t grows; with masked-scan recurrence the state is
    full-width and masking handles completion — identity passthrough."""
    return X


@register_op("reorder_lod_tensor_by_rank", inputs=["X", "RankTable"],
             outputs=["Out"], infer_shape=_no_infer)
def reorder_lod_tensor_by_rank(ctx, attrs, X, RankTable):
    """Row reorder by the rank table's descending-length order
    (reorder_lod_tensor_by_rank_op.cc)."""
    return X[RankTable["order"]]


@register_op("tensor_array_to_tensor", inputs=["X"],
             outputs=["Out", "OutIndex"], infer_shape=_no_infer,
             stateful_outputs=("OutIndex",))
def tensor_array_to_tensor(ctx, attrs, X):
    """Concatenate the tensor-array buffer along `axis` with the leading
    array dim folded in (tensor_array_to_tensor_op.cc)."""
    import jax.numpy as jnp

    axis = int(attrs.get("axis", 1))
    buf = X["buffer"]  # [K, ...]
    k = buf.shape[0]
    parts = [buf[i] for i in range(k)]
    out = jnp.concatenate(parts, axis=axis) if axis != 0 else jnp.stack(
        parts, axis=0).reshape((-1,) + buf.shape[2:])
    sizes = jnp.full((k,), parts[0].shape[axis] if parts else 0, jnp.int32)
    return {"Out": out, "OutIndex": sizes}
